"""Device-resident decode hot path: greedy parity of the pipelined loop
(device-fed fused dispatch, async readback, event-bound uploads) against the
synchronous reference loop across admission/release, preemption, and partial
swap-in; fused-step bit-exactness at the model level; compile-once retrace
accounting; and the steady-state host-traffic regression gates."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core import config_graph as CG
from repro.models import registry as R
from repro.serving import engine as ENG

CFG = get_smoke_config("qwen3-1.7b").with_(n_layers=2, dtype=jnp.float32)


@pytest.fixture(scope="module")
def family():
    return ENG.build_engine_family(CFG, fracs=(1.0,))


def _graph():
    return CG.ConfigGraph.from_dict(CFG.name, {("x1", 16): 1})


def _prompts(lens, seed=0, shared=0):
    rng = np.random.default_rng(seed)
    pre = rng.integers(0, CFG.vocab_size, size=shared).astype(np.int32)
    out = []
    for n in lens:
        p = rng.integers(0, CFG.vocab_size, size=int(n)).astype(np.int32)
        if shared:
            p = np.concatenate([pre, p])
        out.append(p)
    return out


def _pair(family, **kw):
    """(pipelined, synchronous-reference) engines with identical layout."""
    mk = lambda pipe: ENG.RealEngine(family, n_slots=4, max_len=48,
                                     kv_layout="paged", block_size=8,
                                     max_seqs=4, decode_pipeline=pipe, **kw)
    pipe, sync = mk(True), mk(False)
    pipe.configure(_graph())
    sync.configure(_graph())
    return pipe, sync


def _assert_same_outputs(a: ENG.RealEngine, b: ENG.RealEngine):
    assert set(a.last_outputs) == set(b.last_outputs)
    for rid in a.last_outputs:
        np.testing.assert_array_equal(a.last_outputs[rid],
                                      b.last_outputs[rid])


# =============================================================================
# fused multi-step decode: bit-exact vs host-fed single steps (model level)
# =============================================================================
def test_decode_paged_multi_matches_single_steps(family):
    """``decode_paged_multi`` (lax.fori_loop with on-device greedy feedback)
    must be BIT-identical to k host-fed ``decode_paged`` calls — the
    property that lets the engine fuse dispatches without ever changing
    tokens, including an inactive row whose state must not move."""
    ev = family[0]
    k_steps, bs, nb = 4, 8, 12
    arena0 = R.make_block_arena(ev.cfg, nb, bs, dtype=jnp.float32)
    rng = np.random.default_rng(3)
    arena0 = {
        key: jnp.asarray(rng.standard_normal(v.shape) * 0.02, v.dtype)
        for key, v in arena0.items()}
    b = 3
    n_pages = 3                                  # headroom for k more tokens
    tables = jnp.asarray(
        rng.permutation(np.arange(1, nb))[:b * n_pages]
        .reshape(b, n_pages).astype(np.int32))
    lengths = jnp.asarray(np.array([5, 9, 7], np.int32))
    active = jnp.asarray(np.array([True, True, False]))
    nxt0 = jnp.asarray(rng.integers(1, ev.cfg.vocab_size,
                                    size=(b, 1)).astype(np.int32))

    toks_m, _, nxt_m, ln_m = R.decode_paged_multi(
        ev.params, {k: v for k, v in arena0.items()}, {"tokens": nxt0},
        ev.cfg, tables, lengths, active, k_steps)

    arena = {k: v for k, v in arena0.items()}
    cur, ln = nxt0, lengths
    toks_ref = []
    for _ in range(k_steps):
        logits, arena = R.decode_paged(ev.params, arena, {"tokens": cur},
                                       ev.cfg, tables, ln, active)
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        toks_ref.append(np.asarray(tok))
        cur = jnp.where(active[:, None], tok[:, None], cur)
        ln = ln + active.astype(jnp.int32)

    np.testing.assert_array_equal(np.asarray(toks_m), np.stack(toks_ref))
    np.testing.assert_array_equal(np.asarray(nxt_m), np.asarray(cur))
    np.testing.assert_array_equal(np.asarray(ln_m), np.asarray(ln))


# =============================================================================
# greedy parity: pipelined loop vs synchronous reference
# =============================================================================
def test_pipelined_parity_admission_release(family):
    """Mixed prompt lengths with staggered completions (different n_new via
    mixed lengths): admissions, releases, and bucket changes all force
    event re-uploads mid-stream — outputs must not change."""
    prompts = _prompts((6, 14, 9, 22, 6, 11), seed=1)
    pipe, sync = _pair(family)
    m_pipe = pipe._serve_prompts(prompts, n_new=10)
    m_sync = sync._serve_prompts(prompts, n_new=10)
    _assert_same_outputs(pipe, sync)
    assert m_pipe["served"] == m_sync["served"] == len(prompts)
    assert m_pipe["tokens"] == m_sync["tokens"]
    # batched step counts may differ by a tick or two (completions LAND one
    # tick later, shifting re-admission packing) — tokens must not; with
    # staggered lifetimes some row always has remaining < fused_steps, so
    # fusion correctly stays out (every dispatch lands exactly one step)
    assert m_pipe["decode_dispatches"] == m_pipe["decode_steps"]
    assert m_sync["decode_dispatches"] == m_sync["decode_steps"]


def test_pipelined_parity_preemption_and_partial_swapin(family):
    """The hard case: an overcommitted arena forces decode-time preemption
    (staged async swap-out, partial swap-in through the radix tree) while
    in-flight pipelined work must be landed before every victim snapshot.
    Greedy outputs must equal the synchronous reference's exactly."""
    _check_preemption_parity(family)


@pytest.mark.parametrize("heads", [dict(n_heads=4, n_kv_heads=2, d_head=64),
                                   dict(n_heads=16, n_kv_heads=8,
                                        d_head=128)],
                         ids=["kv2x64", "kv8x128"])
def test_preemption_parity_at_served_head_shapes(heads):
    """The same preemption + partial swap-in parity at the head shapes the
    benchmark serves, whose arena rows are K·dh = 128 and 1024 wide: the
    staged page images and restores move whole rows of that width."""
    _check_preemption_parity(
        ENG.build_engine_family(CFG.with_(**heads), fracs=(1.0,)))


def _check_preemption_parity(family):
    prompts = _prompts((6, 6, 6, 6), seed=5, shared=16)
    pipe, sync = _pair(family, n_blocks=14, preemption=True)
    m_pipe = pipe._serve_prompts(prompts, n_new=16)
    m_sync = sync._serve_prompts(prompts, n_new=16)
    _assert_same_outputs(pipe, sync)
    assert m_pipe["preemptions"] >= 1 and m_sync["preemptions"] >= 1
    # a restore actually happened (pages copied or tree-resident)
    assert (m_pipe["swapin_pages_copied"]
            + m_pipe["partial_swapin_pages_saved"]) >= 1
    # swap churn reclaimed fully in both loops
    for eng in (pipe, sync):
        inst = eng.instances[0]
        inst.alloc.check()
        assert all(s is None for s in inst.rows)
        assert not inst._inflight and not inst._pending_first


# =============================================================================
# compile accounting: one trace per (row bucket, k), never after warmup
# =============================================================================
def test_fused_decode_compiles_once_per_bucket(family):
    """Warmup seeds every (row-bucket, k) fused-decode shape; serving —
    including a second warm session at a different concurrency — must
    never retrace."""
    eng = ENG.RealEngine(family, n_slots=4, max_len=48, kv_layout="paged",
                         block_size=8, max_seqs=4)
    eng.configure(_graph())
    inst = eng.instances[0]
    for B in (1, 2, 4):
        for k in (1, inst.fused_steps):
            assert ("decode_multi", B, k) in inst._shapes
    m1 = eng._serve_prompts(_prompts((6, 6, 6, 6), seed=2), n_new=12)
    assert m1["compile_retraces"] == 0
    m2 = eng._serve_prompts(_prompts((6, 9), seed=4), n_new=12)
    assert m2["compile_retraces"] == 0
    assert m1["decode_dispatches"] < m1["decode_steps"]  # fusion engaged


# =============================================================================
# steady-state host traffic: zero per-tick uploads, zero blocking syncs
# =============================================================================
def test_steady_state_decode_has_no_per_tick_host_traffic(family):
    """The regression gate behind the hot path: in steady-state decode the
    pipelined loop adds ZERO H2D uploads per tick (uploads stay bound to
    events — here 2 per prefill chunk plus one 4-buffer upload per event)
    and ZERO blocking host round-trips, while the synchronous reference
    pays its fixed per-step freight."""
    prompts = _prompts((6, 6, 6, 6), seed=7)
    pipe, sync = _pair(family)
    m_pipe = pipe._serve_prompts(prompts, n_new=32)
    m_sync = sync._serve_prompts(prompts, n_new=32)
    _assert_same_outputs(pipe, sync)
    steps = m_pipe["decode_steps"]
    assert steps >= 30
    # every pipelined upload is accounted to an EVENT — a prefill chunk
    # (2 transfers) or a 4-buffer loop-state push after an activation /
    # release wave (at most one per admission + one per completion wave) —
    # never to a steady-state tick; the synchronous loop pays 4 per step
    n_events = len(prompts) + len(prompts)
    event_budget = 2 * m_pipe["prefill_chunks"] + 4 * n_events
    assert m_pipe["h2d_transfers"] <= event_budget
    assert m_pipe["h2d_transfers"] * 3 < m_sync["h2d_transfers"]
    assert m_sync["h2d_transfers"] >= 4 * m_sync["decode_steps"]
    # overlapped landings only: no same-tick blocking readback
    assert m_pipe["host_syncs"] == 0
    assert m_sync["host_syncs"] >= m_sync["decode_steps"]
    # fused dispatch: one jitted call covers fused_steps model steps
    assert m_pipe["decode_dispatches"] * 2 <= m_pipe["decode_steps"]
