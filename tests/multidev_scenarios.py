"""Multi-device test scenarios, run in a subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count=8 (set by the parent BEFORE
jax initializes — conftest deliberately leaves the main process at 1 device).

Each function prints "SCENARIO OK" on success; test_multidev.py asserts it.
"""
import os
import sys

if __name__ == "__main__":
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import numpy as np


def scenario_lower_all_smoke_shapes():
    import jax
    import jax.numpy as jnp
    from repro.configs import get_smoke_config
    import repro.configs.shapes as SH
    SH.SHAPES = {
        "train_4k": SH.ShapeCell("train_4k", 64, 8, "train"),
        "prefill_32k": SH.ShapeCell("prefill_32k", 128, 4, "prefill"),
        "decode_32k": SH.ShapeCell("decode_32k", 128, 8, "decode"),
        "long_500k": SH.ShapeCell("long_500k", 256, 1, "decode"),
    }
    from repro.launch import steps
    mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"))
    archs = ["qwen3-moe-30b-a3b", "gemma3-27b", "mamba2-2.7b", "zamba2-2.7b",
             "seamless-m4t-large-v2", "qwen2-vl-7b"]
    with mesh:
        for arch in archs:
            cfg = get_smoke_config(arch).with_(dtype=jnp.bfloat16)
            for shape in SH.SHAPES:
                jitted, sds = steps.build_step_for_cell(cfg, mesh, shape)
                compiled = jitted.lower(*sds).compile()
                assert compiled.memory_analysis().temp_size_in_bytes >= 0


def scenario_ddp_compressed_training():
    import jax
    import jax.numpy as jnp
    from repro.configs import get_smoke_config
    from repro.models import registry as R
    from repro.train import optimizer as O
    from repro.train import train_loop as TL
    from repro.train import data as DATA
    cfg = get_smoke_config("qwen2-0.5b").with_(
        n_layers=2, d_model=32, n_heads=4, n_kv_heads=2, d_head=8, d_ff=64,
        vocab_size=64, dtype=jnp.float32)
    mesh = jax.make_mesh((8,), ("data",))
    params = R.init_params(jax.random.PRNGKey(0), cfg)
    opt_cfg = O.AdamWConfig(lr=1e-3)
    ds = DATA.SyntheticLM(DATA.DataConfig(cfg.vocab_size, 16, 16))
    batch = {k: jnp.asarray(v) for k, v in ds.batch_at(0).items()}

    results = {}
    for comp in (None, "bf16", "int8"):
        state = {"params": R.init_params(jax.random.PRNGKey(0), cfg),
                 "opt": O.init_opt_state(params)}
        step = TL.make_ddp_train_step(cfg, opt_cfg, mesh, compressor=comp)
        with mesh:
            state, m = step(state, batch)
        results[comp] = (float(m["loss"]),
                         [np.asarray(x) for x in jax.tree.leaves(state["params"])])
    # compressed training must track f32 within tolerance after one step
    for comp in ("bf16", "int8"):
        assert abs(results[comp][0] - results[None][0]) < 1e-2
        for a, b in zip(results[comp][1], results[None][1]):
            np.testing.assert_allclose(a, b, rtol=0.1, atol=2e-3)


def scenario_elastic_checkpoint_restore():
    """Save on a (2,4) mesh layout, restore onto a (8,) mesh — device-count
    elasticity through the checkpoint path."""
    import tempfile
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs import get_smoke_config
    from repro.models import registry as R
    from repro.sharding import rules
    from repro.train import checkpoint as CKPT
    cfg = get_smoke_config("qwen3-1.7b").with_(dtype=jnp.float32)
    mesh_a = jax.make_mesh((2, 4), ("data", "model"))
    params = R.init_params(jax.random.PRNGKey(0), cfg)
    specs = rules.param_specs(jax.eval_shape(lambda: params), cfg, mesh_a)
    sharded = jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh_a, s)), params, specs)
    with tempfile.TemporaryDirectory() as d:
        CKPT.save({"params": sharded}, 1, d)
        mesh_b = jax.make_mesh((8,), ("model",))
        specs_b = rules.param_specs(jax.eval_shape(lambda: params), cfg, mesh_b)
        shards_b = jax.tree.map(lambda s: NamedSharding(mesh_b, s), specs_b)
        restored = CKPT.restore(d, {"params": jax.eval_shape(lambda: params)},
                                shardings={"params": shards_b})
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(restored["params"])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def scenario_gspmd_vs_single_device_numerics():
    """The sharded train step computes the same loss as single-device."""
    import jax
    import jax.numpy as jnp
    from repro.configs import get_smoke_config
    import repro.configs.shapes as SH
    SH.SHAPES = {"train_4k": SH.ShapeCell("train_4k", 32, 8, "train")}
    from repro.launch import steps
    from repro.models import registry as R
    from repro.train import optimizer as O, train_loop as TL, data as DATA
    cfg = get_smoke_config("qwen3-1.7b").with_(dtype=jnp.float32)
    mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"))
    opt_cfg = O.AdamWConfig()
    params = R.init_params(jax.random.PRNGKey(0), cfg)
    state = TL.make_train_state(params, opt_cfg)
    ds = DATA.SyntheticLM(DATA.DataConfig(cfg.vocab_size, 32, 8))
    batch = {k: jnp.asarray(v) for k, v in ds.batch_at(0).items()}

    loss_1dev = float(TL.lm_loss(params, batch, cfg)[0])
    with mesh:
        jitted, _ = steps.build_train(cfg, mesh, "train_4k", opt_cfg=opt_cfg,
                                      accum=1)
        new_state, metrics = jitted(state, batch)
        loss_sharded = float(metrics["loss"])
    assert abs(loss_sharded - loss_1dev) / loss_1dev < 5e-4, \
        (loss_sharded, loss_1dev)


def scenario_seq_sharded_decode_numerics():
    """Sequence-sharded KV decode == single-device decode logits."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
    from repro.configs import get_smoke_config
    from repro.models import registry as R
    from repro.sharding import rules
    cfg = get_smoke_config("glm4-9b").with_(dtype=jnp.float32)
    params = R.init_params(jax.random.PRNGKey(0), cfg)
    cache = R.make_cache(params, cfg, 2, 64, dtype=jnp.float32)
    toks = jnp.array([[3], [5]], dtype=jnp.int32)
    ref_logits, _ = R.decode_step(params, cache, {"tokens": toks}, cfg)

    mesh = jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    cache_specs = rules.cache_specs(jax.eval_shape(lambda: cache), mesh, cfg,
                                    seq_shard=True)
    with mesh:
        p_specs = rules.param_specs(jax.eval_shape(lambda: params), cfg, mesh)
        ps = jax.tree.map(lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
                          params, p_specs)
        cs = jax.tree.map(lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
                          cache, cache_specs)
        logits, _ = jax.jit(lambda p, c, t: R.decode_step(p, c, {"tokens": t}, cfg))(
            ps, cs, toks)
    np.testing.assert_allclose(np.asarray(ref_logits), np.asarray(logits),
                               rtol=2e-4, atol=2e-4)


def _paged_workload(cfg, lens, seed=0, shared=12):
    rng = np.random.default_rng(seed)
    pre = rng.integers(0, cfg.vocab_size, size=shared).astype(np.int32)
    return [np.concatenate(
        [pre, rng.integers(0, cfg.vocab_size, size=int(n)).astype(np.int32)])
        for n in lens]


def scenario_sharded_paged_decode_parity():
    """One PagedInstance sharded across a ("data","model")=(4,2) mesh of 8
    host devices (params under spec_for_param, KV arena over "model" on the
    head dim, block tables host-side) is token-identical to the unsharded
    engine on a mixed-length shared-prefix workload — radix prefix sharing
    and the pipelined fused decode loop run unchanged on the sharded arena.
    Also pins the arena rule's explicit non-divisible error on a real mesh
    (glm4-like n_kv_heads=2 on a 4-way model axis)."""
    import jax.numpy as jnp
    from repro.configs import get_smoke_config
    from repro.core import config_graph as CG
    from repro.launch.mesh import make_mesh_for
    from repro.serving import engine as ENG
    from repro.sharding import rules as SR

    cfg = get_smoke_config("qwen3-1.7b").with_(n_layers=2, dtype=jnp.float32)
    fam = ENG.build_engine_family(cfg, fracs=(1.0,))
    g = CG.ConfigGraph.from_dict(cfg.name, {("x1", 16): 1})
    prompts = _paged_workload(cfg, (6, 14, 9, 22, 6, 11), seed=1)

    ref = ENG.RealEngine(fam, n_slots=4, max_len=64, kv_layout="paged",
                         block_size=8, max_seqs=4)
    ref.configure(g)
    m_ref = ref._serve_prompts(prompts, n_new=10)

    mesh = make_mesh_for(8, model_parallel=2)    # n_kv_heads=2 → divisible
    assert mesh.shape["data"] == 4 and mesh.shape["model"] == 2
    eng = ENG.RealEngine(fam, n_slots=4, max_len=64, kv_layout="paged",
                         block_size=8, max_seqs=4, mesh=mesh)
    eng.configure(g)
    m = eng._serve_prompts(prompts, n_new=10)
    assert set(ref.last_outputs) == set(eng.last_outputs)
    for rid in ref.last_outputs:
        np.testing.assert_array_equal(ref.last_outputs[rid],
                                      eng.last_outputs[rid])
    assert m["prefix_hit_tokens"] == m_ref["prefix_hit_tokens"] > 0
    # the arena really is committed over "model" (not replicated)
    inst = eng.instances[0]
    assert not inst.arena["k"].sharding.is_fully_replicated

    glm4 = get_smoke_config("glm4-9b")           # n_kv_heads=2
    try:
        SR.arena_spec(make_mesh_for(8, model_parallel=4), glm4)
    except ValueError as e:
        assert "n_kv_heads" in str(e)
    else:
        raise AssertionError("non-divisible arena sharding must error")


def scenario_disagg_vs_monolithic_parity():
    """Disaggregated prefill/decode workers on the 8-device mesh match the
    monolithic engine bit-for-bit — INCLUDING through decode-side
    preemption and partial (radix-tree-backed) swap-in on the decode
    worker — and the per-role joules split conserves exactly."""
    import jax.numpy as jnp
    from repro.configs import get_smoke_config
    from repro.core import config_graph as CG
    from repro.launch.mesh import make_mesh_for
    from repro.obs.validate import check_disagg_conservation
    from repro.serving import engine as ENG
    from repro.serving.api import InferenceRequest, serve_workload

    cfg = get_smoke_config("qwen3-1.7b").with_(n_layers=2, dtype=jnp.float32)
    fam = ENG.build_engine_family(cfg, fracs=(1.0,))
    g = CG.ConfigGraph.from_dict(cfg.name, {("x1", 16): 1})
    prompts = _paged_workload(cfg, (6, 6, 6, 6), seed=5, shared=16)
    n_new = 16

    ref = ENG.RealEngine(fam, n_slots=4, max_len=48, kv_layout="paged",
                         block_size=8, max_seqs=4, n_blocks=33)
    ref.configure(g)
    ref._serve_prompts(prompts, n_new=n_new)
    assert ref.stats()["preemptions"] == 0

    eng = ENG.RealEngine(fam, n_slots=4, max_len=48, kv_layout="paged",
                         block_size=8, max_seqs=4, n_blocks=14,
                         preemption=True, mesh=make_mesh_for(8, 2),
                         roles={"prefill": 1, "decode": 1})
    eng.configure(g)
    reqs = [InferenceRequest(rid=i, prompt=p, max_new_tokens=n_new)
            for i, p in enumerate(prompts)]
    serve_workload(eng, reqs)
    m = eng.stats()
    assert m["handoffs"] == len(prompts)
    assert m["preemptions"] >= 1, "starved decode arena must preempt"
    assert (m["swapin_pages_copied"]
            + m["partial_swapin_pages_saved"]) >= 1, "no swap-in happened"
    assert m["partial_swapin_pages_saved"] >= 1, \
        "decode-side radix tree must make the swap-in partial"
    for rid in ref.last_outputs:
        np.testing.assert_array_equal(ref.last_outputs[rid],
                                      eng.last_outputs[rid])
    check_disagg_conservation(m)
    assert m["prefill_energy_j"] > 0 and m["decode_energy_j"] > 0


def scenario_disagg_smoke():
    """Fast 8-device disagg smoke for scripts/check.sh: sharded split
    workers serve a tiny workload, hand off every sequence, and conserve
    the role energy split."""
    import jax.numpy as jnp
    from repro.configs import get_smoke_config
    from repro.core import config_graph as CG
    from repro.launch.mesh import make_mesh_for
    from repro.obs.validate import check_disagg_conservation
    from repro.serving import engine as ENG

    cfg = get_smoke_config("qwen3-1.7b").with_(n_layers=2, dtype=jnp.float32)
    fam = ENG.build_engine_family(cfg, fracs=(1.0,))
    eng = ENG.RealEngine(fam, n_slots=2, max_len=32, kv_layout="paged",
                         mesh=make_mesh_for(8, model_parallel=2),
                         roles=(1, 1))
    eng.configure(CG.ConfigGraph.from_dict(cfg.name, {("x1", 16): 1}))
    prompts = _paged_workload(cfg, (5, 9, 7), seed=0, shared=0)
    m = eng._serve_prompts(prompts, n_new=4)
    assert m["served"] == len(prompts)
    assert m["handoffs"] == len(prompts)
    check_disagg_conservation(m)


def scenario_chip_smoke_four_chips():
    """``chip_smoke.py --chips 4``'s phases on four of the virtual devices
    at the smoke widths (KV heads 4, so the model axis of 4 divides them):
    sharded paged serving, the unsharded engine, disaggregation with token
    identity, and the sharded-vs-unsharded prefill logit bound."""
    import jax
    import jax.numpy as jnp
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    import chip_smoke as CS
    from repro.configs import get_smoke_config

    cfg = get_smoke_config(CS.ARCH).with_(n_heads=8, n_kv_heads=4,
                                          dtype=jnp.bfloat16)
    plan = CS.Plan(prompt_lens=(8, 20, 33, 47), new_tokens=(4, 6, 8, 5),
                   shared_prefix=16, block_size=8, n_slots=3, max_seqs=4)
    CS.run_four_chips(cfg, plan, 0, jax.devices()[:4])


if __name__ == "__main__":
    name = sys.argv[1]
    globals()[f"scenario_{name}"]()
    print("SCENARIO OK")
