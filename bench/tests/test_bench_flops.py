"""bench/flops.py against a count by hand, for both configurations, and
the family module's FLOPs, which ``step_mfu`` reads, against it."""
import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import flops  # noqa: E402
import run as RUN  # noqa: E402
from models import dense_lm  # noqa: E402


def config(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def dims(name):
    return dense_lm.dims(config(name))


# per layer: q (d x H dh), k and v (d x K dh), o (H dh x d), gate/up/down
HAND = {
    # 28 x (2048 x 32 x 128 + 16 x 128 x 2048 + 3 x 2048 x 6144)
    "qwen3-1.7b": dict(layers=28 * (8_388_608 + 4_194_304 + 37_748_736),
                       head=2048 * 151_936, attn_per_ctx=4 * 28 * 16 * 128),
    # 24 x (896 x 18 x 64 + 14 x 64 x 896 + 3 x 896 x 4864)
    "qwen2-0.5b": dict(layers=24 * (1_032_192 + 802_816 + 13_074_432),
                       head=896 * 151_936, attn_per_ctx=4 * 24 * 14 * 64),
}


@pytest.mark.parametrize("name", sorted(HAND))
def test_weights_match_a_hand_count(name):
    m, h = dims(name), HAND[name]
    assert flops.layer_weights(m) == h["layers"]
    assert flops.head_weights(m) == h["head"]
    assert flops.decode_token(m, 1000) == (
        2 * (h["layers"] + h["head"]) + h["attn_per_ctx"] * 1000)


def test_qwen3_decode_token_in_round_numbers():
    # 1.409 G layer weights + 0.311 G readout: 3.44 GFLOP a token, plus
    # 0.229 MFLOP per position attended
    m = dims("qwen3-1.7b")
    assert flops.layer_weights(m) == 1_409_286_144
    assert flops.decode_token(m, 0) == 3_440_902_144
    assert flops.attention(m, 1) == 229_376


@pytest.mark.parametrize("name", sorted(HAND))
def test_prefill_and_decode_are_sums_over_tokens(name):
    m, h = dims(name), HAND[name]
    p, n, c = 37, 9, 16
    by_token = sum(2 * h["layers"] + h["attn_per_ctx"] * i
                   for i in range(c + 1, p + 1)) + 2 * h["head"]
    assert flops.prefill(m, p, c) == pytest.approx(by_token, rel=1e-12)
    assert flops.prefill(m, p) == pytest.approx(
        by_token + sum(2 * h["layers"] + h["attn_per_ctx"] * i
                       for i in range(1, c + 1)), rel=1e-12)
    steps = sum(flops.decode_token(m, p + j) for j in range(1, n))
    assert flops.decode(m, p, n) == pytest.approx(steps, rel=1e-12)
    assert flops.decode(m, p, 1) == 0


@pytest.mark.parametrize("name", ["qwen3-1.7b", "qwen2-0.5b", "glm4-9b"])
def test_the_family_counts_a_dense_decoders_flops(name):
    cfg = config(name)
    fam, m = RUN.family(cfg), dims(name)
    for p, c in ((1, 0), (37, 0), (1024, 512.5), (1536, 1024)):
        assert fam.prefill(m, p, c) == flops.prefill(m, p, c)
        assert fam.prefill(m, p) == flops.prefill(m, p)
    for ctx in (1, 513, 2047):
        assert fam.decode_token(m, ctx) == flops.decode_token(m, ctx)
