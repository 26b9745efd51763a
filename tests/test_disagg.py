"""Prefill/decode disaggregated serving (serving.disagg) and the mesh
plumbing underneath it: RealEngine(roles=...) dispatch, handoff token
parity with the monolithic engine, per-role energy conservation,
decode-side preemption after handoff, the paged-arena sharding rule's
explicit non-divisible error, and make_mesh_for sizing.

Single-device tier-1 coverage; the 8-host-device parity scenarios live in
multidev_scenarios.py (sharded_paged_decode_parity / disagg_vs_monolithic
/ disagg_smoke) and the carbon/throughput acceptance numbers in the
``disagg_serving`` bench stage.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core import config_graph as CG
from repro.launch.mesh import make_mesh_for
from repro.obs.validate import check_disagg_conservation
from repro.serving import engine as ENG
from repro.serving.api import InferenceRequest, serve_workload
from repro.serving.disagg import BlockHandoff, DisaggEngine
from repro.sharding import rules as SR

CFG = get_smoke_config("qwen3-1.7b").with_(n_layers=2, dtype=jnp.float32)


@pytest.fixture(scope="module")
def family():
    return ENG.build_engine_family(CFG, fracs=(1.0,))


def _graph():
    return CG.ConfigGraph.from_dict(CFG.name, {("x1", 16): 1})


def _prompts(lens, seed=3):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, CFG.vocab_size, size=L).astype(np.int32)
            for L in lens]


def _requests(prompts, n_new=6, **kw):
    return [InferenceRequest(rid=i, prompt=p, max_new_tokens=n_new, **kw)
            for i, p in enumerate(prompts)]


class _FakeMesh:
    """arena_spec only reads mesh.shape — enough to unit-test the rule on a
    one-device box (real meshes are exercised in multidev_scenarios.py)."""

    def __init__(self, **axes):
        self.shape = dict(axes)


# =============================================================================
# construction / dispatch
# =============================================================================
def test_roles_kwarg_builds_disagg_engine(family):
    eng = ENG.RealEngine(family, n_slots=2, max_len=32, kv_layout="paged",
                         roles={"prefill": 1, "decode": 1})
    assert isinstance(eng, DisaggEngine)
    assert eng.roles == {"prefill": 1, "decode": 1}
    # tuple shorthand normalizes; roles=None stays a plain RealEngine
    eng2 = ENG.RealEngine(family, kv_layout="paged", roles=(2, 1))
    assert isinstance(eng2, DisaggEngine) and eng2.roles["prefill"] == 2
    mono = ENG.RealEngine(family, kv_layout="paged", roles=None)
    assert type(mono) is ENG.RealEngine
    with pytest.raises(AssertionError):
        ENG.RealEngine(family, kv_layout="slotted", roles=(1, 1))
    with pytest.raises(AssertionError):
        ENG.RealEngine(family, kv_layout="paged", roles={"prefill": 1})


def test_configure_builds_role_split_workers(family):
    eng = ENG.RealEngine(family, n_slots=2, max_len=32, kv_layout="paged",
                         roles={"prefill": 2, "decode": 1})
    eng.configure(_graph())
    roles = sorted(i.role for i in eng.instances)
    assert roles == ["decode", "prefill", "prefill"]
    # role profilers are distinct and role-tagged (phase latency splits
    # prefill-pool vs decode-pool in the exposition)
    assert eng.profilers["prefill"].role == "prefill"
    assert eng.profilers["decode"].role == "decode"
    for inst in eng.instances:
        assert inst.profiler is eng.profilers[inst.role]


# =============================================================================
# token parity + conservation
# =============================================================================
def test_disagg_token_parity_and_role_conservation(family):
    prompts = _prompts((7, 13, 5, 9, 11, 6), seed=0)

    mono = ENG.RealEngine(family, n_slots=2, max_len=32, kv_layout="paged")
    mono.configure(_graph())
    rm = {r.rid: r for r in serve_workload(mono, _requests(prompts))}
    sm = mono.stats()

    dis = ENG.RealEngine(family, n_slots=2, max_len=32, kv_layout="paged",
                         roles={"prefill": 1, "decode": 1})
    dis.configure(_graph())
    rd = {r.rid: r for r in serve_workload(dis, _requests(prompts))}
    sd = dis.stats()

    for rid in rm:
        np.testing.assert_array_equal(rm[rid].tokens, rd[rid].tokens)

    # every request was handed off exactly once, pages moved with them
    assert sd["handoffs"] == len(prompts)
    assert sd["handoff_pages"] >= len(prompts)
    assert sm["handoffs"] == 0 and sm["handoff_pages"] == 0

    # per-role joules: disagg splits, monolithic carries "both"; both
    # shapes conserve against the session total exactly
    check_disagg_conservation(sd)
    check_disagg_conservation(sm)
    assert sd["prefill_energy_j"] > 0 and sd["decode_energy_j"] > 0
    assert sd["handoff_energy_j"] > 0 and sd["both_energy_j"] == 0.0
    assert sm["both_energy_j"] == sm["energy_j"]
    assert sm["prefill_energy_j"] == sm["decode_energy_j"] == 0.0

    # per-response role split sums to each response's energy_j
    for r in rd.values():
        assert set(r.energy_by_role) <= {"prefill", "decode", "handoff"}
        assert sum(r.energy_by_role.values()) == \
            pytest.approx(r.energy_j, rel=1e-9)
    for r in rm.values():
        assert set(r.energy_by_role) == {"both"}
        assert r.energy_by_role["both"] == pytest.approx(r.energy_j,
                                                         rel=1e-9)


def test_disagg_decode_preemption_token_identical(family):
    """Decode-side preemption after handoff: a starved decode arena swaps
    victims out and restores them bit-exactly — outputs match a monolithic
    engine with a roomy arena (preemption- AND handoff-invariance)."""
    prompts = _prompts((6, 6, 6, 6), seed=5)
    n_new = 20

    ref = ENG.RealEngine(family, n_slots=2, max_len=48, kv_layout="paged",
                         block_size=8, max_seqs=4, n_blocks=33)
    ref.configure(_graph())
    ref._serve_prompts(prompts, n_new=n_new)
    assert ref.stats()["preemptions"] == 0

    eng = ENG.RealEngine(family, n_slots=2, max_len=48, kv_layout="paged",
                         block_size=8, max_seqs=4, n_blocks=9,
                         preemption=True, prefix_caching=False,
                         roles={"prefill": 1, "decode": 1})
    eng.configure(_graph())
    responses = serve_workload(eng, _requests(prompts, n_new=n_new))
    m = eng.stats()
    assert m["preemptions"] >= 1, "starved decode arena must preempt"
    assert m["handoffs"] == len(prompts)
    assert m["served"] == len(prompts)
    for rid, toks in ref.last_outputs.items():
        np.testing.assert_array_equal(toks, eng.last_outputs[rid])
    # handoffs are planned swaps: they never count as preemptions
    assert sum(r.preemptions for r in responses) == m["preemptions"]
    check_disagg_conservation(m)
    # full reclamation on every worker after the churn
    for inst in eng.instances:
        inst.alloc.check()
        assert inst.alloc.num_free == inst.alloc.num_allocatable


def test_handoff_stage_requires_landed_first_token(family):
    eng = ENG.RealEngine(family, n_slots=2, max_len=32, kv_layout="paged",
                         roles=(1, 1))
    eng.configure(_graph())
    pre = next(i for i in eng.instances if i.role == "prefill")
    eng.submit(InferenceRequest(rid=0, prompt=_prompts((7,))[0],
                                max_new_tokens=4))
    # step until the prefill worker holds the sequence mid-prefill or with
    # its first token still in flight — staging then must be refused
    eng.step()
    seqs = [q for q in pre.rows if q is not None]
    if seqs and not (seqs[0].prefilled and seqs[0].pending_first is None):
        with pytest.raises(AssertionError):
            BlockHandoff.stage(pre, seqs[0])
    eng.drain()
    assert eng.stats()["handoffs"] == 1


# =============================================================================
# sharding rules + mesh helpers (unit; real meshes in multidev scenarios)
# =============================================================================
def test_arena_spec_explicit_error_on_non_divisible_heads():
    from jax.sharding import PartitionSpec as P
    glm4 = get_smoke_config("glm4-9b")          # n_kv_heads=2, GQA
    assert glm4.n_kv_heads == 2
    # divisible: each token row's whole KV heads shard over model,
    # block-map dims stay host-side
    assert SR.arena_spec(_FakeMesh(data=4, model=2), glm4) == \
        P(None, None, None, "model")
    # model axis 1: fully replicated (the single-device serving path)
    assert SR.arena_spec(_FakeMesh(data=8, model=1), glm4) == \
        P(None, None, None, None)
    # non-divisible: an explicit error, not silent GSPMD padding
    with pytest.raises(ValueError, match="n_kv_heads"):
        SR.arena_spec(_FakeMesh(data=2, model=4), glm4)


def test_make_mesh_for_sizing_and_errors():
    mesh = make_mesh_for(1)
    assert mesh.axis_names == ("data", "model")
    assert mesh.shape["data"] == 1 and mesh.shape["model"] == 1
    with pytest.raises(ValueError, match="does not divide"):
        make_mesh_for(8, model_parallel=3)
    with pytest.raises(ValueError, match="does not divide"):
        make_mesh_for(4, model_parallel=8)


def test_single_device_mesh_paged_parity(family):
    """mesh= on a 1-device mesh runs the whole sharded-arena code path
    (committed arena, sharded params cache, row placement) and must be
    token-identical to the unsharded engine."""
    prompts = _prompts((7, 13, 5), seed=2)
    mono = ENG.RealEngine(family, n_slots=2, max_len=32, kv_layout="paged")
    mono.configure(_graph())
    rm = {r.rid: r for r in serve_workload(mono, _requests(prompts))}

    eng = ENG.RealEngine(family, n_slots=2, max_len=32, kv_layout="paged",
                         mesh=make_mesh_for(1))
    eng.configure(_graph())
    rs = {r.rid: r for r in serve_workload(eng, _requests(prompts))}
    for rid in rm:
        np.testing.assert_array_equal(rm[rid].tokens, rs[rid].tokens)
