"""The benchmark's float32 reference and its own weights, against the
program at smoke size on the CPU: the same weights give the same logits,
and the gap functions read what they should."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from bench_smoke import smoke  # noqa: E402
from models import dense_lm  # noqa: E402

CELLS = ["qwen3-1.7b.chat-poisson", "qwen2-0.5b.batch-closed"]


def model(workload, **size):
    spec, pc = smoke(workload, dtype="float32", **size)
    m = dense_lm.dims(spec["cfg"])
    w = dense_lm.make_weights(spec["cfg"], 2**31 + 7)
    return spec, pc, m, w


@pytest.mark.parametrize("workload", CELLS)
def test_reference_matches_the_program_forward(workload):
    from repro.models import registry as R
    spec, pc, m, w = model(workload)
    assert m["qk_norm"] == pc.qk_norm and m["qkv_bias"] == pc.qkv_bias
    tokens = np.random.default_rng(0).integers(0, m["V"], 40).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        got, _ = R.forward(dense_lm.to_program(w, spec["cfg"]),
                           {"tokens": jnp.asarray(tokens)[None]}, pc)
        x = dense_lm.hidden(w, jnp.asarray(tokens), m)
        ref = x @ dense_lm.head(w, m).astype(jnp.float32)
    got = np.asarray(got[0, :, :m["V"]], np.float32)
    ref = np.asarray(ref)
    assert ref.std() > 0.1
    np.testing.assert_allclose(got, ref, atol=2e-4 * np.abs(ref).max(),
                               rtol=1e-4)


@pytest.mark.parametrize("workload", CELLS)
def test_program_parameters_share_the_weights(workload):
    from repro.models import registry as R
    spec, pc, m, w = model(workload)
    p = dense_lm.to_program(w, spec["cfg"])
    want = jax.eval_shape(lambda: R.init_params(jax.random.PRNGKey(0), pc))
    assert (jax.tree.map(lambda a: (a.shape, a.dtype), want)
            == jax.tree.map(lambda a: (a.shape, a.dtype), p))
    assert p["embed"]["table"] is w["embed"]
    assert p["layers"]["mlp"]["wo"]["w"] is w["layers"]["w_down"]


def test_gaps_are_zero_for_the_reference_choice_and_positive_otherwise():
    # wide enough, with vocabulary enough, that fp8 flips some choices
    spec, pc, m, w = model(CELLS[0], d_model=256, n_layers=4, vocab=8192)
    S = 256
    tokens = np.random.default_rng(1).integers(0, m["V"], S).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        x = dense_lm.hidden(w, jnp.asarray(tokens), m)
        lg = np.asarray(x @ dense_lm.head(w, m).astype(jnp.float32))
        best = lg.argmax(-1).astype(np.int32)
        g0 = np.asarray(dense_lm.served_gaps(w, jnp.asarray(tokens),
                                             jnp.asarray(best), m, rows=64))
        worst = lg.argmin(-1).astype(np.int32)
        g1 = np.asarray(dense_lm.served_gaps(w, jnp.asarray(tokens),
                                             jnp.asarray(worst), m, rows=64))
        gc = np.asarray(dense_lm.control_gaps(w, jnp.asarray(tokens), m,
                                              rows=64))
    np.testing.assert_allclose(g0, 0.0, atol=1e-5)
    np.testing.assert_allclose(g1, lg.max(-1) - lg.min(-1), rtol=1e-5)
    assert gc.min() >= 0 and gc.max() > 0


def test_weights_are_drawn_from_any_whole_number():
    spec, _, m, _ = model(CELLS[0])
    a = dense_lm.make_weights(spec["cfg"], 2**40 + 3)
    b = dense_lm.make_weights(spec["cfg"], 2**40 + 3)
    c = dense_lm.make_weights(spec["cfg"], 3)
    assert np.array_equal(a["embed"], b["embed"])
    assert not np.array_equal(a["embed"], c["embed"])
    assert np.all(np.asarray(a["embed"][m["V"]:]) == 0)
