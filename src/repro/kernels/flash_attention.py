"""Flash attention (prefill) — Pallas TPU kernel.

HBM→VMEM tiling: grid (batch, kv-head, q-block, kv-block); the kv-block axis
is innermost (sequential on TPU), carrying the online-softmax state
(m, l, acc) in VMEM scratch across kv blocks.  Block shapes are multiples of
the MXU tile (q/kv blocks × d_head, d_head ∈ {64, 128}); GQA folds the
q-head group into the block's second-minor dim so the q·kᵀ contraction is a
(g·bq × dh) · (dh × bk) MXU matmul.

Causal + sliding-window masking is applied per block; fully-masked blocks
still run (structural simplicity; the §Perf log quantifies the causal 2×
overcount, and skipping is a recorded optimization).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  sm_scale: float, block_q: int, block_k: int,
                  causal: bool, window: int, seq_kv: int):
    qb = pl.program_id(2)
    kb = pl.program_id(3)
    nkb = pl.num_programs(3)

    @pl.when(kb == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0]                                # (g, block_q, dh)
    k = k_ref[0, 0]                                # (block_k, dh)
    v = v_ref[0, 0]
    g, bq, dh = q.shape

    s = jax.lax.dot_general(
        q.reshape(g * bq, dh).astype(jnp.float32),
        k.astype(jnp.float32),
        (((1,), (1,)), ((), ()))) * sm_scale       # (g·bq, block_k)

    qpos = qb * block_q + jax.lax.broadcasted_iota(jnp.int32, (g * bq, block_k), 0) % bq
    # NOTE: rows are (g, bq) flattened with q position = row % bq?  rows are
    # g-major: row = gi * bq + qi, so qi = row % bq — matches the iota above.
    kpos = kb * block_k + jax.lax.broadcasted_iota(jnp.int32, (g * bq, block_k), 1)
    mask = kpos < seq_kv
    if causal:
        mask &= qpos >= kpos
    if window > 0:
        mask &= (qpos - kpos) < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]                            # (g·bq, 1)
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)                         # (g·bq, block_k)
    alpha = jnp.exp(m_prev - m_new)
    l_new = alpha * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        p, v.astype(jnp.float32), (((1,), (0,)), ((), ())))
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(kb == nkb - 1)
    def _finalize():
        out = acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = out.reshape(g, bq, dh).astype(o_ref.dtype)


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, window: int = 0,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = True) -> jnp.ndarray:
    """q: (b, sq, H, dh); k, v: (b, skv, K, dh) -> (b, sq, H, dh)."""
    b, sq, H, dh = q.shape
    skv, K = k.shape[1], k.shape[2]
    g = H // K
    block_q = min(block_q, sq)
    block_k = min(block_k, skv)
    nq = pl.cdiv(sq, block_q)
    nk = pl.cdiv(skv, block_k)
    assert sq % block_q == 0 and skv % block_k == 0, (sq, skv, block_q, block_k)

    # layout: q (b, K, g, sq, dh); kv (b, K, skv, dh)
    qr = q.reshape(b, sq, K, g, dh).transpose(0, 2, 3, 1, 4)
    kr = k.transpose(0, 2, 1, 3)
    vr = v.transpose(0, 2, 1, 3)

    grid = (b, K, nq, nk)
    out = pl.pallas_call(
        functools.partial(_flash_kernel, sm_scale=dh ** -0.5, block_q=block_q,
                          block_k=block_k, causal=causal, window=window,
                          seq_kv=skv),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, g, block_q, dh),
                         lambda bi, ki, qi, kj: (bi, ki, 0, qi, 0)),
            pl.BlockSpec((1, 1, block_k, dh),
                         lambda bi, ki, qi, kj: (bi, ki, kj, 0)),
            pl.BlockSpec((1, 1, block_k, dh),
                         lambda bi, ki, qi, kj: (bi, ki, kj, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, block_q, dh),
                               lambda bi, ki, qi, kj: (bi, ki, 0, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, K, g, sq, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((g * block_q, 1), jnp.float32),
            pltpu.VMEM((g * block_q, 1), jnp.float32),
            pltpu.VMEM((g * block_q, dh), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(qr, kr, vr)
    return out.transpose(0, 3, 1, 2, 4).reshape(b, sq, H, dh)
