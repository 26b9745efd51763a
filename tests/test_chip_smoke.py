"""CPU rehearsal of ``chip_smoke.py``: its phases, called as functions on
the qwen3-1.7b smoke preset at a small plan.  The script itself refuses to
run without a TPU; this test supplies the config and accepts the CPU."""
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

_ROOT = os.path.join(os.path.dirname(__file__), "..")
sys.path.insert(0, os.path.abspath(_ROOT))

import chip_smoke as CS  # noqa: E402
from repro.configs import get_smoke_config  # noqa: E402

PLAN = CS.Plan(prompt_lens=(8, 20, 33, 47, 12, 26, 40, 56),
               new_tokens=(4, 6, 8, 4, 6, 8, 5, 8), shared_prefix=16,
               block_size=8, chunk_blocks=2, n_slots=3, max_seqs=4)


@pytest.fixture(scope="module")
def cfg():
    # bf16 like the chip run, four layers so the ladder is 1/2/4 deep
    return get_smoke_config(CS.ARCH).with_(n_layers=4, dtype=jnp.bfloat16)


def test_one_chip_phases_rehearse_on_cpu(cfg):
    CS.run_one_chip(cfg, PLAN, seed=0)


def test_requests_cover_every_rung_and_share_a_prefix(cfg):
    reqs = CS.make_requests(cfg, PLAN, seed=0, hints=["x1", "x0.5", "x0.25"])
    assert [q.prompt_len for q in reqs] == list(PLAN.prompt_lens)
    assert {q.quality_hint for q in reqs} == {"x1", "x0.5", "x0.25"}
    long = [q.prompt for q in reqs if q.prompt_len > PLAN.shared_prefix]
    assert all(np.array_equal(p[:16], long[0][:16]) for p in long)
    assert PLAN.max_len % PLAN.block_size == 0
    assert PLAN.max_len >= max(p + n for p, n in
                               zip(PLAN.prompt_lens, PLAN.new_tokens))


def test_check_served_rejects_short_and_out_of_vocab_answers(cfg):
    from repro.serving.api import InferenceResponse
    reqs = CS.make_requests(cfg, PLAN, seed=0)[:1]
    stats = {"compile_retraces": 0, "decode_dispatches": 1, "decode_steps": 1,
             "served": 1, "tokens": 4, "host_syncs": 0,
             "prefix_hit_tokens": 0, "wall_s": 0.0}
    ok = InferenceResponse(rid=0, tokens=np.zeros(4, np.int64))
    CS.check_served("ok", [ok], reqs, stats, cfg.vocab_size)
    short = InferenceResponse(rid=0, tokens=np.zeros(3, np.int64))
    with pytest.raises(CS.SmokeFailure):
        CS.check_served("short", [short], reqs, stats, cfg.vocab_size)
    oov = InferenceResponse(rid=0, tokens=np.full(4, cfg.vocab_size))
    with pytest.raises(CS.SmokeFailure):
        CS.check_served("oov", [oov], reqs, stats, cfg.vocab_size)
    with pytest.raises(CS.SmokeFailure):
        CS.check_served("retraced", [ok], reqs,
                        {**stats, "compile_retraces": 1}, cfg.vocab_size)


def test_script_refuses_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    script = os.path.join(_ROOT, "chip_smoke.py")
    out = subprocess.run([sys.executable, script], capture_output=True,
                         text=True, timeout=300, env=env)
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout
