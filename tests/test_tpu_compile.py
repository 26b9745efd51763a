"""Compile the served path for a described TPU v5e at qwen3-1.7b's published
widths, with no chip attached: the engine's step programs (chunked prefill,
fused paged decode at k=1 and k=8, the slotted prefill and decode) must fit
one chip's 16 GiB, the paged programs must also compile sharded over the
four chips of a v5e:2x2, and the Pallas kernels must lower to Mosaic
(``tpu_custom_call``).

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and the tests of this file run in the
process that does.  Shapes are those ``chip_smoke.py`` runs."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec, \
    SingleDeviceSharding

sys.path.insert(0, os.path.abspath(os.path.join(os.path.dirname(__file__),
                                                "..")))

import chip_smoke as CS  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.models import registry as R  # noqa: E402
from repro.serving.engine import serve_buckets  # noqa: E402
from repro.sharding import rules as SR  # noqa: E402

HBM_BYTES = 16 * 2**30
CFG = get_config(CS.ARCH)
PLAN = CS.Plan()
BATCH = PLAN.max_seqs
N_PAGES = -(-PLAN.max_len // PLAN.block_size)
N_BLOCKS = -(-PLAN.n_slots * PLAN.max_len // PLAN.block_size) + 1


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no description
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of these tests
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shapes(tree, sharding):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.fixture(scope="module")
def four_chips(topo):
    # chip_smoke --chips 4: tensor parallel over all four, KV heads 8 / 4
    return Mesh(np.asarray(topo.devices).reshape(1, 4), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)


def _params_shape():
    return jax.eval_shape(lambda: R.init_params(jax.random.PRNGKey(0), CFG))


def _arena_shape():
    # the engine's arena: f32 blocks, sized as chip_smoke sizes it
    return jax.eval_shape(lambda: R.make_block_arena(
        CFG, N_BLOCKS, PLAN.block_size, dtype=jnp.float32))


@pytest.fixture(scope="module")
def params(one_chip):
    return _shapes(_params_shape(), one_chip)


@pytest.fixture(scope="module")
def arena(one_chip):
    return _shapes(_arena_shape(), one_chip)


def _fits_one_chip(compiled) -> int:
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             - m.alias_size_in_bytes + m.temp_size_in_bytes)
    assert total <= HBM_BYTES, f"{total / 2**30:.2f} GiB > 16 GiB"
    return total


def _prefill_paged(params, arena, sharding):
    C = PLAN.chunk_blocks * PLAN.block_size
    fn = jax.jit(lambda p, t, ar, tb, n0, tc: R.prefill_paged(
        p, {"tokens": t}, CFG, ar, tb, n0, tc), donate_argnums=(2,))
    return fn.lower(
        params, _sds((1, C), jnp.int32, sharding), arena,
        _sds((N_PAGES,), jnp.int32, sharding),
        _sds((), jnp.int32, sharding), _sds((), jnp.int32, sharding)
    ).compile()


def _decode_paged_multi(params, arena, sharding, k):
    fn = jax.jit(lambda p, ar, t, tb, ln, act: R.decode_paged_multi(
        p, ar, {"tokens": t}, CFG, tb, ln, act, k),
        donate_argnums=(1, 2, 4))
    return fn.lower(
        params, arena, _sds((BATCH, 1), jnp.int32, sharding),
        _sds((BATCH, N_PAGES), jnp.int32, sharding),
        _sds((BATCH,), jnp.int32, sharding),
        _sds((BATCH,), jnp.bool_, sharding)).compile()


def test_prefill_paged_compiles_and_fits(params, arena, one_chip):
    _fits_one_chip(_prefill_paged(params, arena, one_chip))


@pytest.mark.parametrize("k", [1, 8])
def test_decode_paged_multi_compiles_and_fits(params, arena, one_chip, k):
    _fits_one_chip(_decode_paged_multi(params, arena, one_chip, k))


def test_slotted_prefill_compiles_and_fits(params, one_chip):
    # the largest prompt bucket the slotted engine warms up
    b = serve_buckets(PLAN.max_len)[-1]
    fn = jax.jit(lambda p, t: R.prefill_kv(p, {"tokens": t}, CFG))
    _fits_one_chip(fn.lower(params,
                            _sds((1, b), jnp.int32, one_chip)).compile())


def test_slotted_decode_compiles_and_fits(params, one_chip):
    cache = _shapes(jax.eval_shape(lambda: R.make_slot_cache(
        CFG, PLAN.n_slots, PLAN.max_len, dtype=jnp.float32)), one_chip)
    fn = jax.jit(lambda p, c, t, a: R.decode_slots(p, c, {"tokens": t}, CFG,
                                                   a))
    _fits_one_chip(fn.lower(
        params, cache, _sds((PLAN.n_slots, 1), jnp.int32, one_chip),
        _sds((PLAN.n_slots,), jnp.bool_, one_chip)).compile())


@pytest.mark.parametrize("step", ["prefill", "decode_k8"])
def test_sharded_paged_step_compiles_on_four_chips(four_chips, step):
    shapes = _params_shape()
    params = jax.tree.map(
        lambda x, sh: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sh),
        shapes, SR.param_shardings(shapes, CFG, four_chips))
    arena = _shapes(_arena_shape(), SR.arena_shardings(four_chips, CFG))
    replicated = NamedSharding(four_chips, PartitionSpec())
    if step == "prefill":
        compiled = _prefill_paged(params, arena, replicated)
    else:
        compiled = _decode_paged_multi(params, arena, replicated, 8)
    # per-device bytes: each chip holds a quarter of the weights and arena
    _fits_one_chip(compiled)
    assert "all-reduce" in compiled.as_text()


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_paged_attention_kernel_lowers_to_mosaic(one_chip, dtype):
    from repro.kernels import paged_attention as PA
    H, K, dh = CFG.n_heads, CFG.n_kv_heads, CFG.d_head
    fn = jax.jit(lambda q, ka, va, tb, ln: PA.paged_decode_attention(
        q, ka, va, tb, ln, interpret=False))
    compiled = fn.lower(
        _sds((BATCH, H, dh), dtype, one_chip),
        _sds((N_BLOCKS, PLAN.block_size, K, dh), dtype, one_chip),
        _sds((N_BLOCKS, PLAN.block_size, K, dh), dtype, one_chip),
        _sds((BATCH, N_PAGES), jnp.int32, one_chip),
        _sds((BATCH,), jnp.int32, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_flash_attention_kernel_lowers_to_mosaic(one_chip):
    from repro.kernels import flash_attention as FA
    H, K, dh = CFG.n_heads, CFG.n_kv_heads, CFG.d_head
    S = 2048
    fn = jax.jit(lambda q, k, v: FA.flash_attention(q, k, v, causal=True,
                                                    interpret=False))
    compiled = fn.lower(_sds((1, S, H, dh), jnp.bfloat16, one_chip),
                        _sds((1, S, K, dh), jnp.bfloat16, one_chip),
                        _sds((1, S, K, dh), jnp.bfloat16, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()
