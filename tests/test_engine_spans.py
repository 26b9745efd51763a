"""The engine's phase spans on the device profiler's clock, its named device
programs, and the TTFT split stamped where the work happens.

Only one profiler session runs per process, so the traced run lives in this
one file: a tiny paged engine served under ``jax.profiler``, its
``engine.*`` spans read back from the host plane of the trace."""
import glob
import os
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core import config_graph as CG
from repro.serving import engine as ENG
from repro.serving.api import InferenceRequest

CFG = get_smoke_config("qwen3-1.7b").with_(n_layers=2, dtype=jnp.float32)

# where each span sits: its innermost enclosing engine span
PARENTS = {"engine.step": {None},
           "engine.admit": {"engine.step"},
           "engine.tick": {"engine.step"},
           "engine.prefill_chunk": {"engine.tick"},
           "engine.upload": {"engine.tick"},
           "engine.decode_dispatch": {"engine.tick"},
           "engine.decode_land": {"engine.tick", "engine.upload"},
           "engine.emit": {"engine.tick"},
           "engine.finish": {"engine.tick"}}


@pytest.fixture(scope="module")
def family():
    return ENG.build_engine_family(CFG, fracs=(1.0,))


def _graph():
    return CG.ConfigGraph.from_dict(CFG.name, {("x1", 1): 1})


def _paged(family, n_blocks=48, **kw):
    eng = ENG.RealEngine(family, max_len=96, kv_layout="paged", block_size=8,
                         max_seqs=4, n_blocks=n_blocks, chunk_blocks=2, **kw)
    eng.configure(_graph())
    return eng


def _prompts(n, seed, lo=6, hi=40):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, CFG.vocab_size, size=int(L)).astype(np.int32)
            for L in rng.integers(lo, hi, size=n)]


def _host_spans(trace_dir):
    """``engine.*`` events of the trace's host plane, per line, as
    (start, end, name, stats)."""
    from jax.profiler import ProfileData
    path = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                         "*", "*.xplane.pb")))[-1]
    pd = ProfileData.from_file(path)
    lines = []
    with warnings.catch_warnings():
        # the stats view is a pybind type without __module__
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in pd.planes:
            if not plane.name.startswith("/host:CPU"):
                continue
            for line in plane.lines:
                evs = [(ev.start_ns, ev.end_ns, ev.name, dict(ev.stats))
                       for ev in line.events
                       if ev.name.startswith("engine.")]
                if evs:
                    lines.append(evs)
    return lines


def _parents(spans):
    """The innermost enclosing span of each span, by containment."""
    out, stack = [], []
    for s, e, name, st in sorted(spans, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][1] <= s:
            stack.pop()
        if stack:
            assert e <= stack[-1][1], f"{name} straddles {stack[-1][2]}"
        out.append((name, stack[-1][2] if stack else None, st))
        stack.append((s, e, name))
    return out


@pytest.fixture(scope="module")
def traced(family, tmp_path_factory):
    eng = _paged(family)
    trace_dir = str(tmp_path_factory.mktemp("trace"))
    prompts = _prompts(6, seed=3)
    jax.profiler.start_trace(trace_dir)
    try:
        m = eng._serve_prompts(prompts, n_new=14)
    finally:
        jax.profiler.stop_trace()
    lines = _host_spans(trace_dir)
    assert len(lines) == 1, "engine spans on more than one host thread"
    return m, _parents(lines[0]), len(prompts)


def test_engine_spans_nest_on_the_profiler_clock(traced):
    m, spans, _ = traced
    names = {n for n, _, _ in spans}
    assert set(PARENTS) <= names
    for name, parent, _ in spans:
        assert parent in PARENTS[name], (name, parent)


def test_span_counts_match_the_engine_counters(traced):
    m, spans, n_requests = traced

    def of(name):
        return [st for n, _, st in spans if n == name]
    assert len(of("engine.prefill_chunk")) == m["prefill_chunks"]
    assert all("rid" in st for st in of("engine.prefill_chunk"))
    dispatches = of("engine.decode_dispatch")
    assert len(dispatches) == m["decode_dispatches"]
    assert sum(st["k"] for st in dispatches) == m["decode_steps"]
    assert max(st["k"] for st in dispatches) > 1      # fusion engaged
    assert all(st["rows"] >= 1 for st in dispatches)
    # a landing per dispatch and per first token: never one per row or
    # per token (every request decodes past its first token here)
    lands = of("engine.decode_land")
    assert len(lands) == len(dispatches) + n_requests
    assert all("sync" in st for st in lands)
    assert len(of("engine.emit")) <= len(of("engine.tick"))
    assert m["tokens"] > len(lands)


def test_device_programs_carry_their_names(family):
    eng = _paged(family)
    inst = eng.instances[0]
    fns, B = inst._fns, 4
    lg, inst.arena = fns["prefill_paged"](
        inst.params, jnp.zeros((1, inst.chunk_tokens), jnp.int32),
        inst.arena, jnp.zeros((2,), jnp.int32), 0, 0)
    rows = (jnp.asarray(inst._next[:B]), jnp.asarray(inst.tables[:B]),
            jnp.asarray(inst.lengths[:B]), jnp.zeros((B,), bool))
    idx = jnp.zeros((2,), jnp.int32)
    page = inst.arena["k"][:, :2]
    lowered = {
        "prefill_paged": (inst.params, jnp.zeros((1, inst.chunk_tokens),
                                                 jnp.int32), inst.arena,
                          jnp.zeros((2,), jnp.int32), 0, 0),
        "decode_paged": (inst.params, inst.arena) + rows,
        "restore_paged": (inst.arena, idx, page, page),
        "gather_pages": (inst.arena, idx),
        "first_token": (lg, 0),
        "set_next": (rows[0], 0, jnp.int32(1)),
    }
    for name, args in lowered.items():
        text = fns[name].lower(*args).as_text()
        assert f"module @jit_{name}" in text, name
    text = fns["decode_multi"].lower(inst.params, inst.arena, *rows,
                                     k=2).as_text()
    assert "module @jit_decode_multi" in text


def _check_split(responses):
    assert responses
    for r in responses:
        assert r.prefill_wait_s >= 0.0 and r.first_lag_s >= 0.0, r.rid
        assert r.ttft_s == pytest.approx(
            r.queue_delay_s + r.prefill_wait_s + r.first_lag_s,
            rel=1e-9, abs=1e-9), r.rid
        # never below the old stamp, taken at the last chunk's dispatch
        assert r.ttft_s >= r.queue_delay_s + r.prefill_wait_s - 1e-12


@pytest.mark.parametrize("layout", ["paged", "slotted", "paged-preempt"])
def test_ttft_splits_into_queue_prefill_and_first_lag(family, layout):
    if layout == "slotted":
        eng = ENG.RealEngine(family, n_slots=2, max_len=64)
        eng.configure(_graph())
    elif layout == "paged":
        eng = _paged(family)
    else:
        eng = _paged(family, n_blocks=14, preemption=True)
    firsts = {}

    def on_token(rid, tok):
        firsts.setdefault(rid, tok)
    prompts = _prompts(5, seed=7, lo=6, hi=22)
    # staggered arrivals give each request its own queue delay; the
    # preempting engine gets them all at once, so that the arena overflows
    # however fast the host runs its steps
    gap = 0.0 if layout == "paged-preempt" else 0.002
    for i, p in enumerate(prompts):
        eng.submit(InferenceRequest(rid=i, prompt=p, max_new_tokens=10,
                                    arrival_s=gap * i, on_token=on_token))
    resps = eng.drain()
    if layout == "paged-preempt":
        assert eng.stats()["preemptions"] >= 1
    _check_split(resps)
    st = eng.stats()
    assert st["prefill_wait_p90_s"] == ENG.latency_percentile(
        [r.prefill_wait_s for r in resps], 90.0)
    assert st["first_lag_p90_s"] == ENG.latency_percentile(
        [r.first_lag_s for r in resps], 90.0)
    # the stamped first token is the one the client received first
    assert all(firsts[r.rid] == int(r.tokens[0]) for r in resps)
