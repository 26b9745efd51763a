"""Model FLOPs of a dense decoder, from its shapes.

A token costs two FLOPs per weight it is multiplied with (``2 N``), plus
causal attention over the ``c`` positions it sees: ``q k^T`` and ``p v`` at
``2 H d_head c`` each, per layer.  The readout (``d x V``) counts only where
its logits are used: the last prompt position and every decode step.  Norms,
rotary embeddings, biases and the softmax are left out (under 0.5% here).
"""
from __future__ import annotations

from typing import Dict


def layer_weights(m: Dict) -> int:
    """Weights one token is multiplied with in the layer stack."""
    d, H, K, dh, ff = m["d"], m["H"], m["K"], m["dh"], m["ff"]
    return m["L"] * (d * (H + 2 * K) * dh + H * dh * d + 3 * d * ff)


def head_weights(m: Dict) -> int:
    return m["d"] * m["V"]


def attention(m: Dict, ctx_sum: float) -> float:
    """Attention FLOPs of tokens whose contexts sum to ``ctx_sum``."""
    return 4.0 * m["L"] * m["H"] * m["dh"] * ctx_sum


def prefill(m: Dict, prompt: int, cached: float = 0.0) -> float:
    """A prompt of ``prompt`` tokens whose first ``cached`` came from the
    prefix cache: positions ``cached+1 .. prompt`` are computed."""
    n = prompt - cached
    ctx = (prompt * (prompt + 1) - cached * (cached + 1)) / 2.0
    return 2.0 * layer_weights(m) * n + attention(m, ctx) + 2.0 * head_weights(m)


def decode_token(m: Dict, ctx: int) -> float:
    """One decode step of one row that attends over ``ctx`` positions."""
    return 2.0 * (layer_weights(m) + head_weights(m)) + attention(m, ctx)


def decode(m: Dict, prompt: int, n_out: int) -> float:
    """The ``n_out - 1`` decode steps after the first token (which the
    prefill's last position gives): step ``j`` sees ``prompt + j``."""
    steps = n_out - 1
    ctx = steps * prompt + steps * (steps + 1) / 2.0
    return 2.0 * (layer_weights(m) + head_weights(m)) * steps + attention(m, ctx)
