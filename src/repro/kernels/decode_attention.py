"""Flash-decode attention — Pallas TPU kernel for one-token serving steps.

One new token attends over a long KV cache: grid (batch, kv-head, kv-block)
with the kv-block axis sequential, carrying online-softmax state in VMEM
scratch.  The valid-prefix ``length`` arrives in SMEM (scalar), masking the
cache tail.  The g grouped q-heads ride in the block's penultimate dim, so the
score contraction is a (g × dh) · (dh × bk) MXU matmul per block.

This kernel is the TPU-native replacement for GSPMD's all-gather-the-cache
fallback on sequence-sharded KV (see §Perf decode hillclimb): each shard runs
the kernel over its local KV range, then shards combine partial (m, l, acc)
with one tiny all-reduce.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
                   *, sm_scale: float, block_k: int):
    kb = pl.program_id(2)
    nkb = pl.num_programs(2)
    length = len_ref[pl.program_id(0)]       # per-row valid prefix (SMEM)

    @pl.when(kb == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0]                                  # (g, dh)
    k = k_ref[0, 0]                                  # (block_k, dh)
    v = v_ref[0, 0]
    g, dh = q.shape

    s = jax.lax.dot_general(q.astype(jnp.float32), k.astype(jnp.float32),
                            (((1,), (1,)), ((), ()))) * sm_scale   # (g, bk)
    kpos = kb * block_k + jax.lax.broadcasted_iota(jnp.int32, (g, block_k), 1)
    s = jnp.where(kpos < length, s, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        p, v.astype(jnp.float32), (((1,), (0,)), ((), ())))
    m_scr[...] = m_new

    @pl.when(kb == nkb - 1)
    def _finalize():
        o_ref[0, 0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
                       ).astype(o_ref.dtype)


def decode_attention(q: jnp.ndarray, k_cache: jnp.ndarray, v_cache: jnp.ndarray,
                     length, *, block_k: int = 256,
                     interpret: bool = True) -> jnp.ndarray:
    """q: (b, H, dh); caches: (b, S, K, dh); length: () / python int shared
    across rows, or (b,) per-row valid prefixes (slotted batched decode).
    Returns (b, H, dh)."""
    b, H, dh = q.shape
    S, K = k_cache.shape[1], k_cache.shape[2]
    g = H // K
    block_k = min(block_k, S)
    assert S % block_k == 0, (S, block_k)
    nk = S // block_k

    qr = q.reshape(b, K, g, dh)
    kr = k_cache.transpose(0, 2, 1, 3)               # (b, K, S, dh)
    vr = v_cache.transpose(0, 2, 1, 3)
    length_arr = jnp.broadcast_to(
        jnp.asarray(length, jnp.int32).reshape(-1), (b,))

    out = pl.pallas_call(
        functools.partial(_decode_kernel, sm_scale=dh ** -0.5, block_k=block_k),
        grid=(b, K, nk),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, g, dh), lambda bi, ki, kj: (bi, ki, 0, 0)),
            pl.BlockSpec((1, 1, block_k, dh), lambda bi, ki, kj: (bi, ki, kj, 0)),
            pl.BlockSpec((1, 1, block_k, dh), lambda bi, ki, kj: (bi, ki, kj, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, dh), lambda bi, ki, kj: (bi, ki, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, K, g, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, dh), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(length_arr, qr, kr, vr)
    return out.reshape(b, H, dh)
