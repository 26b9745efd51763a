"""The traffic generator: deterministic by seed, the stated distributions
and clipping, the open-loop schedule and the closed-loop client count."""
import glob
import json
import os
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import traffic_gen as TG  # noqa: E402

VOCAB = 151936
SEED = 2**31 + 12345          # seeds pass 32 signed bits
MIXES = sorted(os.path.basename(p)[:-5]
               for p in glob.glob(os.path.join(BENCH, "traffic", "*.json")))


def mix(name):
    with open(os.path.join(BENCH, "traffic", name + ".json")) as f:
        return json.load(f)


def requests(spec, seed, seconds=40.0, n_closed=300):
    if TG.is_open(spec):
        return TG.open_requests(spec, seed, seconds, VOCAB)
    src = TG.ClosedSource(spec, seed, VOCAB)
    return [src.next(0.0) for _ in range(n_closed)]


def test_every_mix_is_a_known_kind():
    assert MIXES == ["batch-closed", "chat-poisson", "sessions-prefix",
                     "short-burst", "tp2x2-chat"]
    for name in MIXES:
        assert mix(name)["kind"] in ("open", "closed", "sessions")


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_requests(name):
    a, b = requests(mix(name), SEED), requests(mix(name), SEED)
    assert len(a) == len(b) > 0
    for x, y in zip(a, b):
        assert (x.rid, x.t_s, x.n_out, x.slo) == (y.rid, y.t_s, y.n_out, y.slo)
        assert np.array_equal(x.prompt, y.prompt)


@pytest.mark.parametrize("name", MIXES)
def test_other_seed_same_work_in_another_order(name):
    spec = mix(name)
    # the closed loop draws its sizes in blocks of 1024
    a, b = (requests(spec, SEED, n_closed=1024),
            requests(spec, SEED + 1, n_closed=1024))
    if "order_seed" in spec:           # one fixed order: only ids differ
        assert [(r.t_s, r.n_out, len(r.prompt)) for r in a] == \
            [(r.t_s, r.n_out, len(r.prompt)) for r in b]
        assert not np.array_equal(a[0].prompt[:8], b[0].prompt[:8])
        return
    if spec["kind"] == "sessions":     # which turns fit the window may differ
        assert abs(len(a) - len(b)) <= 0.1 * len(a)
        return
    assert len(a) == len(b)
    assert sorted(r.n_out for r in a) == sorted(r.n_out for r in b)
    assert sorted(len(r.prompt) for r in a) == sorted(len(r.prompt) for r in b)
    assert [r.n_out for r in a] != [r.n_out for r in b]
    assert not np.array_equal(a[0].prompt[:8], b[0].prompt[:8])


@pytest.mark.parametrize("name", MIXES)
def test_lengths_follow_the_stated_distribution(name):
    spec = mix(name)
    reqs = requests(spec, SEED, seconds=600.0, n_closed=1024)
    outs = np.array([r.n_out for r in reqs])
    o = spec["output"]
    assert outs.min() >= o["min"] and outs.max() <= o["max"]
    # clipping aside, half the draws lie below the median
    assert abs(np.median(outs) - o["median"]) <= 0.05 * o["median"] + 1
    plens = np.array([len(r.prompt) for r in reqs])
    # every request fits the engines' max_len of 2048
    assert plens.max() + outs.max() <= 2048
    for r in reqs:
        assert r.prompt.dtype == np.int32
        assert 0 <= r.prompt.min() and r.prompt.max() < VOCAB
    if spec["kind"] != "sessions":
        p = spec["prompt"]
        assert plens.min() >= p["min"] and plens.max() <= p["max"]
        assert abs(np.median(plens) - p["median"]) <= 0.05 * p["median"] + 1


def test_lognormal_quantiles_clip_in_the_right_share():
    d = {"dist": "lognormal", "median": 100, "sigma": 1.0, "min": 50,
         "max": 400}
    v = TG.quantiles(d, 10000)
    # rounded to whole tokens: P(X < 50.5) = Phi(ln 0.505) = 0.2472 and
    # P(X >= 399.5) = 1 - Phi(ln 3.995) = 0.0830
    assert abs(np.mean(v == 50) - 0.2472) < 0.001
    assert abs(np.mean(v == 400) - 0.0830) < 0.001
    u = TG.quantiles({"dist": "uniform", "min": 2, "max": 4}, 3000)
    assert sorted(set(u.tolist())) == [2, 3, 4]
    assert all(abs(np.mean(u == k) - 1 / 3) < 0.01 for k in (2, 3, 4))


def test_poisson_schedule_is_honoured():
    spec = mix("chat-poisson")
    rate, T = spec["arrivals"]["rate_rps"], 40.0
    reqs = TG.open_requests(spec, SEED, T, VOCAB)
    t = np.array([r.t_s for r in reqs])
    assert len(t) == round(rate * T)
    assert t[0] == 0.0 and np.all(np.diff(t) > 0) and t[-1] < T
    gaps = np.diff(t)
    # exponential gaps: coefficient of variation 1
    assert abs(gaps.std() / gaps.mean() - 1.0) < 0.15


def test_burst_state_runs_at_the_stated_factor():
    spec = mix("short-burst")
    arr = spec["arrivals"]
    T = 400.0
    t = np.array([r.t_s for r in TG.open_requests(spec, SEED, T, VOCAB)])
    assert abs(len(t) / T - arr["mean_rps"]) < 0.02 * arr["mean_rps"]
    assert t.min() >= 0 and t.max() < T
    # one-second counts: the busiest decile runs near burst_factor x base
    counts = np.bincount(t.astype(int), minlength=int(T))
    base = arr["mean_rps"] * (arr["base_mean_s"] + arr["burst_mean_s"]) / (
        arr["base_mean_s"] + arr["burst_factor"] * arr["burst_mean_s"])
    hi = np.sort(counts)[-int(T) // 10:].mean()
    assert 0.7 * arr["burst_factor"] * base < hi < 1.5 * arr["burst_factor"] * base
    # the timeline fills the window; another seed gets the same bursts, and
    # so the same number of arrivals, at other times
    segs = TG.mmpp2_segments(arr, T, TG.rng_for(SEED, 11))
    assert segs[0][0] == 0 and segs[-1][0] + segs[-1][1] == pytest.approx(T)
    assert segs == TG.mmpp2_segments(arr, T, TG.rng_for(SEED, 11))
    other_segs = TG.mmpp2_segments(arr, T, TG.rng_for(SEED + 1, 11))
    assert other_segs != segs
    assert sorted(d for _, d, _ in other_segs) == pytest.approx(
        sorted(d for _, d, _ in segs))
    other = [r.t_s for r in TG.open_requests(spec, SEED + 1, T, VOCAB)]
    assert len(other) == len(t) and sorted(other) != sorted(t.tolist())


def test_sessions_share_system_prompts_and_grow_turn_by_turn():
    spec = mix("sessions-prefix")
    reqs = TG.open_requests(spec, SEED, 60.0, VOCAB)
    sp = spec["system_prompts"]
    heads = {r.prompt[:sp["tokens"]].tobytes() for r in reqs}
    assert 1 < len(heads) <= sp["count"]
    assert all(len(r.prompt) <= spec["max_prompt"] for r in reqs)
    assert all(len(r.prompt) >= sp["tokens"] + spec["min_user"] for r in reqs)
    # a later turn of a session starts with the whole earlier prompt
    extended = sum(1 for i, a in enumerate(reqs) for b in reqs[i + 1:]
                   if len(b.prompt) > len(a.prompt)
                   and np.array_equal(b.prompt[:len(a.prompt)], a.prompt))
    assert extended >= len(reqs) // 3
    assert np.all(np.diff([r.t_s for r in reqs]) >= 0)
    assert [r.rid for r in reqs] == list(range(len(reqs)))


def test_closed_loop_has_the_stated_clients_and_a_fixed_order():
    spec = mix("batch-closed")
    src = TG.ClosedSource(spec, SEED, VOCAB)
    assert src.clients == spec["clients"] == 64
    first = [src.next(0.0) for _ in range(2000)]   # crosses a size block
    again = TG.ClosedSource(spec, SEED, VOCAB)
    for r in first:
        s = again.next(5.0)
        assert (s.rid, s.n_out, len(s.prompt)) == (r.rid, r.n_out, len(r.prompt))
        assert s.t_s == 5.0 and s.slo == "deferrable"
