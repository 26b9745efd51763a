"""The one traffic generator: turns a traffic file (``bench/traffic/<mix>.json``)
into requests, from the seed.

Three kinds of mix:

* ``open``: independent users.  Arrivals follow ``arrivals.process``:
  ``poisson`` at ``rate_rps``, or ``mmpp2``, a two-state modulated Poisson
  process whose base state lasts ``base_mean_s`` and whose burst state, at
  ``burst_factor`` times the base rate, lasts ``burst_mean_s`` on average;
  ``mean_rps`` is the long-run rate.
* ``closed``: ``clients`` callers, each sending its next request when the
  last one returned.
* ``sessions``: multi-turn chats.  Sessions arrive as ``poisson``; each picks
  one of ``system_prompts.count`` seeded system prompts by Zipf
  (``zipf_s``), has ``turns.min``..``turns.max`` turns, and each turn arrives
  an exponential gap of mean ``turn_gap_s`` after the previous turn's
  arrival.  A turn's prompt is the system prompt, the earlier turns (user
  text, then seeded assistant text) and the new user text, capped at
  ``max_prompt`` tokens; a turn that cannot add ``min_user`` tokens under
  the cap ends the session.

Lengths are drawn from ``lognormal`` (``median``, ``sigma``) or ``uniform``
distributions, clipped to ``[min, max]``.

Every seed gets the same work in another order (a closed loop: the same
distribution).  A mix whose file sets ``order_seed`` gets it in one fixed
order too: its arrivals, sizes and session structure come from that seed,
and the run's seed draws only the token ids.  A distribution of ``n``
values is its ``n`` quantiles at ``(i + 0.5) / n``, permuted by the seed, and
an open schedule's gaps are the quantiles of the exponential, permuted and
scaled so that the arrivals fill ``[0, seconds)``.  The number of requests,
their sizes and the spread of their gaps are fixed by the file and the
window; the seed chooses the order and the token ids.  So runs with
different seeds differ by the order of the work, not by how much there is.
"""
from __future__ import annotations

import dataclasses
from statistics import NormalDist
from typing import Dict, List

import numpy as np

OPEN_KINDS = ("open", "sessions")


@dataclasses.dataclass
class Request:
    rid: int
    t_s: float                  # scheduled arrival, seconds after the window opens
    prompt: np.ndarray          # int32 token ids
    n_out: int                  # tokens to generate
    slo: str


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """A generator per purpose, so adding one stream never shifts another."""
    return np.random.default_rng([int(seed) % 2**64, stream])


def order_seed(spec: Dict, seed: int) -> int:
    """The seed of a mix's structure: its ``order_seed`` where it sets one."""
    return spec.get("order_seed", seed)


# --- distributions -----------------------------------------------------------
def quantiles(dist: Dict, n: int) -> np.ndarray:
    """The ``n`` quantiles of a length distribution, clipped, as ints."""
    q = (np.arange(n) + 0.5) / n
    kind = dist["dist"]
    if kind == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(p)) for p in q])
        vals = dist["median"] * np.exp(dist["sigma"] * z)
    elif kind == "uniform":
        vals = dist["min"] + q * (dist["max"] - dist["min"] + 1) - 0.5
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return np.clip(np.rint(vals), dist["min"], dist["max"]).astype(np.int64)


def draw(dist: Dict, n: int, rng: np.random.Generator) -> np.ndarray:
    return rng.permutation(quantiles(dist, n))


def exp_gaps(n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` unit-mean exponential gaps: the quantiles, permuted."""
    q = (np.arange(n) + 0.5) / n
    return rng.permutation(-np.log1p(-q))


def fill(gaps: np.ndarray, t0: float, span: float) -> np.ndarray:
    """Arrival times from ``gaps``, scaled so the first lands at ``t0`` and
    the next after the last would land at ``t0 + span``."""
    c = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    return t0 + span * c / gaps.sum()


def zipf_quantiles(count: int, s: float, n: int) -> np.ndarray:
    """``n`` quantiles of Zipf(``s``) over ``count`` ranks (0-based)."""
    p = 1.0 / np.arange(1, count + 1) ** s
    cdf = np.cumsum(p / p.sum())
    q = (np.arange(n) + 0.5) / n
    return np.minimum(np.searchsorted(cdf, q), count - 1)


# --- open loop -----------------------------------------------------------------
def mmpp2_segments(arr: Dict, seconds: float,
                   rng: np.random.Generator) -> List[tuple]:
    """The two-state process's timeline as (start, length, rate) segments
    filling ``[0, seconds)``.  The states' lengths are the exponential's
    quantiles, in an order ``rng`` permutes, so every seed gets the same
    bursts, and so the same number of arrivals, at other times."""
    k, mb, mu = arr["burst_factor"], arr["base_mean_s"], arr["burst_mean_s"]
    base = arr["mean_rps"] * (mb + mu) / (mb + k * mu)
    cycles = max(int(round(seconds / (mb + mu))), 1)
    lb, lu = mb * exp_gaps(cycles, rng), mu * exp_gaps(cycles, rng)
    scale = seconds / (lb.sum() + lu.sum())
    out, t = [], 0.0
    for c in range(cycles):
        for d, rate in ((lb[c] * scale, base), (lu[c] * scale, k * base)):
            out.append((t, d, rate))
            t += d
    return out


def arrival_times(arr: Dict, seconds: float, seed: int) -> np.ndarray:
    proc = arr["process"]
    rng = rng_for(seed, 1)
    if proc == "poisson":
        n = max(int(round(arr["rate_rps"] * seconds)), 1)
        return fill(exp_gaps(n, rng), 0.0, seconds)
    if proc == "mmpp2":
        times = [fill(exp_gaps(m, rng), t, d)
                 for t, d, rate in mmpp2_segments(arr, seconds,
                                                  rng_for(seed, 11))
                 for m in [int(round(rate * d))] if m]
        return np.concatenate(times)
    raise ValueError(f"unknown arrival process {proc!r}")


def open_requests(spec: Dict, seed: int, seconds: float,
                  vocab: int) -> List[Request]:
    if spec["kind"] == "sessions":
        return session_requests(spec, seed, seconds, vocab)
    o = order_seed(spec, seed)
    times = arrival_times(spec["arrivals"], seconds, o)
    n = len(times)
    plens = draw(spec["prompt"], n, rng_for(o, 2))
    outs = draw(spec["output"], n, rng_for(o, 3))
    tok = rng_for(seed, 4)
    return [Request(i, float(times[i]),
                    tok.integers(0, vocab, int(plens[i]), dtype=np.int32),
                    int(outs[i]), spec["slo"]) for i in range(n)]


def session_requests(spec: Dict, seed: int, seconds: float,
                     vocab: int) -> List[Request]:
    o = order_seed(spec, seed)
    n_s = max(int(round(spec["arrivals"]["rate_rps"] * seconds)), 1)
    starts = fill(exp_gaps(n_s, rng_for(o, 1)), 0.0, seconds)
    sp = spec["system_prompts"]
    tok = rng_for(seed, 4)
    systems = [tok.integers(0, vocab, sp["tokens"], dtype=np.int32)
               for _ in range(sp["count"])]
    which = rng_for(o, 5).permutation(
        zipf_quantiles(sp["count"], sp["zipf_s"], n_s))
    tr = spec["turns"]
    n_turns = draw({"dist": "uniform", "min": tr["min"], "max": tr["max"]},
                   n_s, rng_for(o, 6))
    slots = int(n_turns.sum())        # one draw of each size per turn
    users = iter(draw(spec["user"], slots, rng_for(o, 7)))
    assists = iter(draw(spec["assistant"], slots, rng_for(o, 8)))
    outs = iter(draw(spec["output"], slots, rng_for(o, 9)))
    gaps = iter(spec["turn_gap_s"] * exp_gaps(slots, rng_for(o, 10)))
    reqs: List[Request] = []
    for s in range(n_s):
        hist = systems[which[s]]
        t = float(starts[s])
        for turn in range(int(n_turns[s])):
            u, a, o, g = next(users), next(assists), next(outs), next(gaps)
            if turn:
                t += float(g)
                hist = np.concatenate(
                    [hist, tok.integers(0, vocab, int(a), dtype=np.int32)])
            room = spec["max_prompt"] - len(hist)
            if t >= seconds or room < spec["min_user"]:
                break
            hist = np.concatenate(
                [hist, tok.integers(0, vocab, int(min(u, room)),
                                    dtype=np.int32)])
            reqs.append(Request(0, t, hist, int(o), spec["slo"]))
    reqs.sort(key=lambda r: r.t_s)
    for i, r in enumerate(reqs):
        r.rid = i
    return reqs


# --- closed loop ---------------------------------------------------------------
class ClosedSource:
    """The closed loop's requests in the order they are sent: the ``k``-th
    request sent is the same for a seed whichever client sends it.  Sizes
    come in blocks of ``block`` quantile draws."""

    def __init__(self, spec: Dict, seed: int, vocab: int, block: int = 1024):
        self.spec, self.seed, self.vocab, self.block = spec, seed, vocab, block
        self.clients = int(spec["clients"])
        self.tok = rng_for(seed, 4)
        self._sizes: List[tuple] = []
        self.sent = 0

    def next(self, t_s: float) -> Request:
        if self.sent >= len(self._sizes):
            b = len(self._sizes) // self.block
            p = draw(self.spec["prompt"], self.block, rng_for(self.seed, 2 + 10 * b))
            o = draw(self.spec["output"], self.block, rng_for(self.seed, 3 + 10 * b))
            self._sizes.extend(zip(p.tolist(), o.tolist()))
        plen, n_out = self._sizes[self.sent]
        r = Request(self.sent, t_s,
                    self.tok.integers(0, self.vocab, plen, dtype=np.int32),
                    n_out, self.spec["slo"])
        self.sent += 1
        return r


def is_open(spec: Dict) -> bool:
    return spec["kind"] in OPEN_KINDS
