#!/usr/bin/env python3
"""Serve qwen3-1.7b at its published widths through the real Clover engine
on a TPU, and check what comes out.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # four chips: sharded and disaggregated

One chip.  ``build_engine_family(get_config("qwen3-1.7b"))`` gives the
28/14/7-layer depth ladder in bf16, with random weights from ``--seed``.
The paged engine runs one instance of each rung and serves eight requests
(prompts of 16-512 tokens, most sharing a prefix, 16-32 new tokens each),
routed by ``quality_hint`` so that every rung answers.  One controller-driven
warm reconfiguration follows (simulated annealing over measured serving, as
``launch/serve.py --mode real`` does), and the workload is served again.  The
slotted engine then answers the same requests on the top rung.  Last, the
engine's jitted chunked-prefill entry is compared with ``registry.forward``
on the same parameters.

Four chips.  The top rung on a (data 1, model 4) mesh, the same requests on
an unsharded engine on the first device, and a prefill/decode disaggregated
engine on the same mesh.

Every check raises, so any failed phase exits non-zero.  Where JAX finds no
TPU the script stops before any phase.  The last line of standard output is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
The times printed are figures of one smoke run, not benchmark results.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import random
import sys
import time
from typing import Dict, Optional, Sequence, Tuple

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import numpy as np  # noqa: E402

from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

ARCH = "qwen3-1.7b"
FRACS = (1.0, 0.5, 0.25)
LOGIT_RID = 3              # request whose prompt the logit checks use

# Logit bound, per vocabulary entry v:
#     |got_v - ref_v| <= 2^-7 * |ref_v| + DRIFT * std(ref).
# Both sides run the same bf16 graph and differ only in the order XLA
# accumulates in: the prompt cut into 32-token chunks against an f32 paged
# arena vs one pass, or heads and vocabulary split over chips vs whole.
# The first term is one bf16 rounding of that logit on each side (the top
# logit of a random tied-embedding model is ~40 std, where one bf16 step
# is 8).  The second is the drift of the hidden state: each bf16 rounding
# moves it by up to 2^-8 of its size, and 28 residual layers add these up.
# At the full widths on the CPU (vocabulary cut to 16,384) the drift came to
# 2.4% of the std at the 99th percentile and 3.6% at the maximum; 10% keeps
# a margin for the chip's own accumulation order and still fails a step run
# at fp8 precision, whose rounding (2^-4) is sixteen times bf16's.
LOGIT_DRIFT = 0.10


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


@dataclasses.dataclass(frozen=True)
class Plan:
    """Workload and engine sizes.  The defaults are what the chip runs;
    tests pass a smaller plan together with a smaller config."""
    prompt_lens: Tuple[int, ...] = (16, 48, 96, 160, 256, 352, 448, 512)
    new_tokens: Tuple[int, ...] = (16, 24, 32, 16, 24, 32, 20, 32)
    shared_prefix: int = 32        # prompts longer than this share it
    block_size: int = 16
    chunk_blocks: int = 2          # 32-token prefill chunks
    n_slots: int = 4               # arena holds n_slots * max_len tokens
    max_seqs: int = 8

    @property
    def max_len(self) -> int:
        need = max(p + n for p, n in zip(self.prompt_lens, self.new_tokens))
        return -(-need // self.block_size) * self.block_size

    def engine_kw(self) -> Dict[str, object]:
        return dict(n_slots=self.n_slots, max_len=self.max_len,
                    kv_layout="paged", block_size=self.block_size,
                    max_seqs=self.max_seqs, chunk_blocks=self.chunk_blocks)


# =============================================================================
# workload and checks
# =============================================================================
def make_requests(cfg, plan: Plan, seed: int,
                  hints: Optional[Sequence[str]] = None):
    """One request per prompt length; prompts longer than the shared prefix
    start with it.  ``hints`` name ladder rungs, assigned round-robin."""
    from repro.serving.api import InferenceRequest
    rng = np.random.default_rng(seed)
    prefix = rng.integers(0, cfg.vocab_size, plan.shared_prefix)
    reqs = []
    for i, (n, k) in enumerate(zip(plan.prompt_lens, plan.new_tokens)):
        if n > plan.shared_prefix:
            prompt = np.concatenate(
                [prefix, rng.integers(0, cfg.vocab_size, n - len(prefix))])
        else:
            prompt = rng.integers(0, cfg.vocab_size, n)
        reqs.append(InferenceRequest(
            rid=i, prompt=prompt.astype(np.int32), max_new_tokens=k,
            quality_hint=hints[i % len(hints)] if hints else None))
    return reqs


def check_served(label: str, resps, reqs, stats, vocab: int) -> None:
    """Every request answered with exactly ``max_new_tokens`` in-vocabulary
    tokens, and no jit trace after warmup."""
    by_rid = {r.rid: r for r in resps}
    check(sorted(by_rid) == [q.rid for q in reqs],
          f"{label}: answered {sorted(by_rid)} of {[q.rid for q in reqs]}")
    for q in reqs:
        toks = np.asarray(by_rid[q.rid].tokens)
        check(toks.shape == (q.max_new_tokens,),
              f"{label}: rid {q.rid} returned {toks.shape[0]} tokens, "
              f"asked {q.max_new_tokens}")
        check(bool(((toks >= 0) & (toks < vocab)).all()),
              f"{label}: rid {q.rid} has tokens outside [0, {vocab})")
    check(stats["compile_retraces"] == 0,
          f"{label}: {stats['compile_retraces']} jit traces after warmup")
    disp = max(stats["decode_dispatches"], 1)
    log(f"{label}: served {stats['served']} requests, {stats['tokens']} "
        f"tokens, steps_per_dispatch {stats['decode_steps'] / disp:.2f}, "
        f"host_syncs {stats['host_syncs']}, prefix_hit_tokens "
        f"{stats['prefix_hit_tokens']}, compile_retraces 0, wall "
        f"{stats['wall_s']:.3f} s")


def tokens_of(resps) -> Dict[int, np.ndarray]:
    return {r.rid: np.asarray(r.tokens) for r in resps}


def agreement(a: Dict[int, np.ndarray], b: Dict[int, np.ndarray],
              rids: Sequence[int]) -> Tuple[int, int]:
    same = sum(int((a[r] == b[r]).sum()) for r in rids)
    return same, sum(len(a[r]) for r in rids)


def memory_line(dev) -> str:
    st = dev.memory_stats() or {}
    return (f"{dev}: bytes_in_use {st.get('bytes_in_use', 'n/a')}, "
            f"peak_bytes_in_use {st.get('peak_bytes_in_use', 'n/a')}, "
            f"bytes_limit {st.get('bytes_limit', 'n/a')}")


# =============================================================================
# phases
# =============================================================================
def chunked_prefill_logits(ev, plan: Plan, prompt: np.ndarray, arena_dtype,
                           params=None, mesh=None):
    """Last-position logits of ``prompt`` through the engine's jitted
    ``prefill_paged`` entry, chunk by chunk, into a fresh arena."""
    import jax.numpy as jnp
    from repro.models import registry as R
    fn = ev.fns["prefill_paged"]
    bs, C = plan.block_size, plan.chunk_blocks * plan.block_size
    n = len(prompt)
    n_pages = -(-n // bs)
    arena = R.make_block_arena(ev.cfg, n_pages + 1, bs, dtype=arena_dtype,
                               mesh=mesh)
    table = jnp.arange(1, n_pages + 1, dtype=jnp.int32)
    params = ev.params if params is None else params
    for start in range(0, n, C):
        true_c = min(C, n - start)
        chunk = np.zeros((1, C), np.int32)
        chunk[0, :true_c] = prompt[start:start + true_c]
        logits, arena = fn(params, jnp.asarray(chunk), arena, table, start,
                           true_c)
    return np.asarray(logits[0, true_c - 1].astype(jnp.float32))


def compare_logits(label: str, got: np.ndarray, ref: np.ndarray,
                   vocab: int) -> None:
    """Same top-1 token and every logit within the bound above."""
    check(bool(np.isfinite(got).all()), f"{label}: non-finite logits")
    check(bool(np.isfinite(ref).all()), f"{label}: non-finite reference")
    got, ref = got[:vocab], ref[:vocab]
    sigma = float(ref.std())
    diff = np.abs(got - ref)
    allowed = 2.0 ** -7 * np.abs(ref) + LOGIT_DRIFT * sigma
    worst = int((diff / allowed).argmax())
    log(f"{label}: top-1 {int(got.argmax())} vs {int(ref.argmax())}; max abs "
        f"diff {float(diff.max()):.4f} (reference std {sigma:.4f}, max |ref| "
        f"{float(np.abs(ref).max()):.4f}); tightest entry {worst}: diff "
        f"{float(diff[worst]):.4f} of allowed {float(allowed[worst]):.4f}")
    check(int(got.argmax()) == int(ref.argmax()), f"{label}: top-1 differs")
    check(bool((diff <= allowed).all()),
          f"{label}: entry {worst} off by {diff[worst]} > {allowed[worst]}")


def phase_paged_ladder(fam, plan: Plan, reqs):
    """Configure one paged instance per rung (best first, so each configure
    adds one cold rung to the warm ones) and serve the hinted workload."""
    from repro.core import config_graph as CG
    from repro.serving import engine as ENG
    from repro.serving.api import serve_workload
    eng = ENG.RealEngine(fam, quality_selector="static", **plan.engine_kw())
    names = [ev.variant.name for ev in fam][::-1]          # best first
    chips = dict(zip(names, (8, 4, 4)))                    # one 16-chip block
    family = fam[0].variant.family
    weights: Dict[Tuple[str, int], int] = {}
    for name in names:
        weights[(name, chips[name])] = 1
        t_cold = eng.configure(CG.ConfigGraph.from_dict(family, weights))
        inst = next(i for i in eng.instances if i.ev.variant.name == name)
        t0 = time.perf_counter()
        inst.warmup()                    # same shapes again: jit cache hits
        t_rerun = time.perf_counter() - t0
        log(f"rung {name}: {inst.ev.cfg.n_layers} layers, cold configure "
            f"{t_cold:.3f} s (arena + warmup compile), warmup rerun "
            f"{t_rerun:.3f} s")
    graph = CG.ConfigGraph.from_dict(family, weights)
    resps = serve_workload(eng, reqs)
    stats = eng.stats()
    check_served("paged ladder", resps, reqs, stats, fam[0].cfg.vocab_size)
    served_on = {r.variant for r in resps}
    check(served_on == set(names), f"rungs that answered: {served_on}")
    return eng, graph, resps, stats


def phase_warm_reconfigure(eng, fam, graph, reqs, stats0, seed: int) -> None:
    """One controller invocation: simulated annealing whose evaluator
    configures the engine and serves a probe, as ``launch/serve.py --mode
    real`` does; then a warm configure to its choice and a full serve."""
    import jax
    from repro.core import annealing as SA
    from repro.core import carbon as CB
    from repro.core import objective as OBJ
    from repro.serving.api import serve_workload
    probe = reqs[:3]

    def evaluator(g):
        eng.configure(g)
        serve_workload(eng, probe)
        m = eng.stats()
        check(m["compile_retraces"] == 0, f"probe on {g.edges} retraced")
        return OBJ.EvalResult(m["mean_accuracy"], 1.0 / m["p50_s"], 0.5,
                              m["p95_s"], 0.0, m["energy_j"] / m["served"])

    trace = CB.make_trace("CISO-March", hours=1.0)
    obj = OBJ.ObjectiveConfig(
        lam=0.1, a_base=stats0["mean_accuracy"],
        c_base=stats0["energy_j"] / stats0["served"] / 3.6e6 * 380 * 1.5,
        l_tail_s=stats0["p95_s"] * 1.2)
    out = SA.anneal(graph, [ev.variant for ev in fam], evaluator,
                    ci=trace.at(0), obj_cfg=obj,
                    sa_cfg=SA.SAConfig(stale_limit=2, time_limit_s=3.0,
                                       eval_window_s=1.0),
                    rng=random.Random(seed))
    pooled = sum(len(v) for v in eng._pool.values())
    t_warm = eng.configure(out.best)
    log(f"controller: {out.n_evals} evaluations, chose {dict(out.best.edges)}"
        f"; warm reconfigure {t_warm:.4f} s; {pooled + len(eng.instances)} "
        f"paged instances alive, each with its own arena")
    resps = serve_workload(eng, reqs)
    check_served("paged after reconfigure", resps, reqs, eng.stats(),
                 fam[0].cfg.vocab_size)
    log("after reconfigure " + memory_line(jax.devices()[0]))


def phase_slotted(fam, plan: Plan, reqs, paged_resps) -> None:
    """The fleet's default layout on the top rung, and how many tokens it
    shares with the paged engine's top-rung answers (reported, not gated:
    random bf16 weights can flip a greedy token on a near tie)."""
    from repro.core import config_graph as CG
    from repro.serving import engine as ENG
    from repro.serving.api import serve_workload
    top = fam[-1].variant.name
    eng = ENG.RealEngine(fam, n_slots=plan.n_slots, max_len=plan.max_len)
    t_cold = eng.configure(CG.ConfigGraph.from_dict(
        fam[-1].variant.family, {(top, 16): 1}))
    log(f"slotted {top}: cold configure {t_cold:.3f} s")
    resps = serve_workload(eng, reqs)
    check_served("slotted", resps, reqs, eng.stats(), fam[0].cfg.vocab_size)
    rids = [r.rid for r in paged_resps if r.variant == top]
    same, total = agreement(tokens_of(resps), tokens_of(paged_resps), rids)
    log(f"paged vs slotted on {top}: {same}/{total} tokens agree "
        f"(rids {rids})")


def phase_forward_logits(ev, plan: Plan, prompt, arena_dtype) -> None:
    """Chunked paged prefill against ``registry.forward`` (one pass, no
    cache) on the same bf16 parameters."""
    import jax
    from repro.models import registry as R
    got = chunked_prefill_logits(ev, plan, prompt, arena_dtype)
    cfg = ev.cfg
    ref = jax.jit(lambda p, t: R.forward(p, {"tokens": t}, cfg)[0][0, -1])(
        ev.params, prompt[None].astype(np.int32))
    compare_logits(f"prefill_paged vs forward ({cfg.n_layers} layers, "
                   f"{len(prompt)} tokens)", got,
                   np.asarray(ref, np.float32), cfg.vocab_size)


def run_one_chip(cfg, plan: Plan, seed: int) -> None:
    import jax
    from repro.serving import engine as ENG
    t0 = time.perf_counter()
    fam = ENG.build_engine_family(cfg, fracs=FRACS, seed=seed)
    jax.block_until_ready([ev.params for ev in fam])
    log(f"ladder {[ev.cfg.n_layers for ev in fam]} layers, d_model "
        f"{cfg.d_model}, heads {cfg.n_heads}/{cfg.n_kv_heads}, d_head "
        f"{cfg.d_head}, d_ff {cfg.d_ff}, vocab {cfg.vocab_size}, "
        f"{cfg.dtype.__name__}; params "
        f"{[round(ev.variant.params_m) for ev in fam]} M, built in "
        f"{time.perf_counter() - t0:.2f} s")
    hints = [ev.variant.name for ev in fam][::-1]
    reqs = make_requests(cfg, plan, seed, hints)
    eng, graph, paged, stats = phase_paged_ladder(fam, plan, reqs)
    arena_dtype = eng.instances[0].arena["k"].dtype
    phase_warm_reconfigure(eng, fam, graph, reqs, stats, seed)
    del eng
    phase_slotted(fam, plan, reqs, paged)
    phase_forward_logits(fam[-1], plan, reqs[LOGIT_RID].prompt,
                         arena_dtype)


def run_four_chips(cfg, plan: Plan, seed: int, devices) -> None:
    """Mesh-sharded paged serving and prefill/decode disaggregation on
    four chips, against the unsharded engine on the first chip."""
    from repro.core import config_graph as CG
    from repro.launch.mesh import make_mesh_for
    from repro.models import registry as R
    from repro.serving import engine as ENG
    from repro.serving.api import serve_workload
    fam = ENG.build_engine_family(cfg, fracs=(1.0,), seed=seed)
    ev = fam[0]
    graph = CG.ConfigGraph.from_dict(ev.variant.family,
                                     {(ev.variant.name, len(devices)): 1})
    reqs = make_requests(cfg, plan, seed)
    mesh = make_mesh_for(len(devices), model_parallel=len(devices))
    vocab = cfg.vocab_size

    def serve(label, **kw):
        eng = ENG.RealEngine(fam, **plan.engine_kw(), **kw)
        t_cold = eng.configure(graph)
        log(f"{label}: cold configure {t_cold:.3f} s")
        resps = serve_workload(eng, reqs)
        check_served(label, resps, reqs, eng.stats(), vocab)
        return eng, tokens_of(resps)

    sharded, tok_sh = serve("sharded paged", mesh=mesh)
    arena_dtype = sharded.instances[0].arena["k"].dtype
    check(not sharded.instances[0].arena["k"].sharding.is_fully_replicated,
          "sharded arena is replicated")
    _, tok_one = serve("unsharded paged")
    same, total = agreement(tok_sh, tok_one, [q.rid for q in reqs])
    log(f"sharded vs unsharded: {same}/{total} tokens agree")
    _, tok_dis = serve("disaggregated", mesh=mesh,
                       roles={"prefill": 1, "decode": 1})
    for q in reqs:
        check(np.array_equal(tok_dis[q.rid], tok_sh[q.rid]),
              f"disaggregated tokens differ from sharded on rid {q.rid}")
    log("disaggregated vs sharded monolithic: all tokens identical")
    prompt = reqs[LOGIT_RID].prompt
    got = chunked_prefill_logits(ev, plan, prompt, arena_dtype,
                                 params=R.shard_params(ev.params, cfg, mesh),
                                 mesh=mesh)
    ref = chunked_prefill_logits(ev, plan, prompt, arena_dtype)
    compare_logits(f"sharded vs unsharded prefill_paged ({len(prompt)} "
                   f"tokens)", got, ref, vocab)
    for d in devices:
        log(memory_line(d))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    cache = enable_compile_cache()
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found {dev.platform}, not a TPU",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: {len(devices)} chips, {args.chips} asked",
              file=sys.stderr)
        return 2
    devices = devices[:args.chips]
    log(f"device_kind {dev.device_kind}, {len(devices)} chip(s), compile "
        f"cache {cache}")
    from repro.configs import get_config
    cfg = get_config(ARCH)
    plan = Plan()
    if args.chips == 1:
        run_one_chip(cfg, plan, args.seed)
        log(memory_line(dev))
    else:
        run_four_chips(cfg, plan, args.seed, devices)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
