"""Paged flash-decode attention — Pallas TPU kernel for block-table KV.

One new token attends over a KV cache scattered across fixed-size arena
blocks (the paged KV pool): grid (batch, kv-head, page) with the page axis
sequential, carrying online-softmax state in VMEM scratch exactly like
``decode_attention``.  The physical gather happens in the BlockSpec index
map: the per-sequence block table arrives via **scalar prefetch**
(``PrefetchScalarGridSpec``), so page ``pj`` of sequence ``bi`` DMAs arena
block ``table[bi, pj]`` into VMEM — no materialized contiguous copy of the
cache ever exists.  Padded table entries point at the junk block (id 0);
their positions sit at or past ``lengths[bi]`` and are masked.

**Page-skip contract**: pages whose first position is at or past
``lengths[bi]`` (``pj * block_size >= lengths[bi]`` — exactly the junk-
padded table tail) run ZERO compute under a per-page ``pl.when`` guard
instead of compute-then-mask; only init (``pj == 0``) and finalize
(``pj == npj - 1``) stay unconditional.  This is bit-identical to the
masked path for every ``lengths[bi] >= 1``, because a fully-masked page
contributes exactly nothing to the online softmax (``alpha == 1``,
``p == 0``).  Callers must pass ``lengths >= 1`` per row — the serving
engine only decodes rows with a prefilled prompt, so a zero length never
reaches the kernel (a hypothetical ``lengths == 0`` row now outputs zeros
instead of an average over junk, both garbage by the masking contract).

``kernels/ref.py::paged_decode_attention_ref`` is the CPU oracle (gather +
``decode_attention_ref``), sharing the valid-prefix masking contract with
the slotted kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _paged_kernel(tbl_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                  m_scr, l_scr, acc_scr, *, sm_scale: float, block_size: int):
    del tbl_ref                               # consumed by the index maps
    pj = pl.program_id(2)
    npj = pl.num_programs(2)
    length = len_ref[pl.program_id(0)]        # per-row valid prefix (SMEM)

    @pl.when(pj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # page-skip: a page starting at or past the valid prefix is exactly the
    # junk-padded table tail — every position would mask to NEG_INF and
    # contribute nothing (alpha == 1, p == 0), so skip the dot products and
    # the softmax update entirely instead of computing-then-masking.
    # Bit-identical for lengths >= 1 (see module docstring).
    @pl.when(pj * block_size < length)
    def _page():
        q = q_ref[0, 0]                               # (g, dh)
        k = k_ref[0, 0]                               # (block_size, dh)
        v = v_ref[0, 0]
        g, _ = q.shape

        s = jax.lax.dot_general(q.astype(jnp.float32), k.astype(jnp.float32),
                                (((1,), (1,)), ((), ()))) * sm_scale  # (g, bs)
        # logical position of this page's entries in the sequence
        kpos = pj * block_size + jax.lax.broadcasted_iota(
            jnp.int32, (g, block_size), 1)
        s = jnp.where(kpos < length, s, NEG_INF)

        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v.astype(jnp.float32), (((1,), (0,)), ((), ())))
        m_scr[...] = m_new

    @pl.when(pj == npj - 1)
    def _finalize():
        o_ref[0, 0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
                       ).astype(o_ref.dtype)


def paged_decode_attention(q: jnp.ndarray, k_arena: jnp.ndarray,
                           v_arena: jnp.ndarray, block_tables: jnp.ndarray,
                           lengths, *, interpret: bool = True) -> jnp.ndarray:
    """q: (b, H, dh); arenas: (n_blocks, block_size, K, dh);
    block_tables: (b, n_pages) i32 arena block ids (0-padded past each row's
    allocation); lengths: (b,) i32 valid token counts, **each >= 1** (pages
    at or past a row's length are skipped, not masked — see the module
    docstring's page-skip contract).  Returns (b, H, dh)."""
    b, H, dh = q.shape
    _, bs, K, _ = k_arena.shape
    n_pages = block_tables.shape[1]
    g = H // K

    qr = q.reshape(b, K, g, dh)
    kr = k_arena.transpose(0, 2, 1, 3)               # (n_blocks, K, bs, dh)
    vr = v_arena.transpose(0, 2, 1, 3)
    tables = jnp.asarray(block_tables, jnp.int32)
    lengths_arr = jnp.broadcast_to(
        jnp.asarray(lengths, jnp.int32).reshape(-1), (b,))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                       # tables, lengths
        grid=(b, K, n_pages),
        in_specs=[
            pl.BlockSpec((1, 1, g, dh),
                         lambda bi, ki, pj, tbl, ln: (bi, ki, 0, 0)),
            # the paged gather: page pj of row bi reads arena block
            # tbl[bi, pj] (junk block 0 for padded entries — masked above)
            pl.BlockSpec((1, 1, bs, dh),
                         lambda bi, ki, pj, tbl, ln: (tbl[bi, pj], ki, 0, 0)),
            pl.BlockSpec((1, 1, bs, dh),
                         lambda bi, ki, pj, tbl, ln: (tbl[bi, pj], ki, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, dh),
                               lambda bi, ki, pj, tbl, ln: (bi, ki, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, dh), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_kernel, sm_scale=dh ** -0.5, block_size=bs),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, K, g, dh), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(tables, lengths_arr, qr, kr, vr)
    return out.reshape(b, H, dh)
