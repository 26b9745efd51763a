"""Mamba2 SSD chunked scan — Pallas TPU kernel.

Grid (batch, head, chunk) with the chunk axis sequential: the inter-chunk SSM
state (P × N) lives in VMEM scratch and is carried across chunks — the whole
state-space-duality scan (within-chunk quadratic dual + across-chunk linear
recurrence, arXiv:2405.21060 §6) runs in one kernel with no HBM state
round-trips.  Per-chunk compute is three MXU matmuls:
  scores = (C Bᵀ) ⊙ exp(segsum(dA));  Y_diag = scores · (dt·x);
  Y_off  = (C · stateᵀ) ⊙ exp(cumsum dA);
  state' = exp(ΣdA)·state + (dt·x·decay)ᵀ · B.
The chunk length (default 128) × P(64)/N(64-128) tiles fit VMEM comfortably
(< 1 MiB per buffer).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(a_ref, x_ref, dt_ref, b_ref, c_ref, y_ref, state_out_ref,
                state_scr, *, chunk: int):
    ci = pl.program_id(2)
    nc = pl.num_programs(2)
    hi = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    A = a_ref[hi]                                        # () scalar decay rate
    x = x_ref[0, 0].astype(jnp.float32)                  # (chunk, P)
    dt = dt_ref[0, 0].astype(jnp.float32)                # (chunk,) -> reshaped
    B = b_ref[0, 0].astype(jnp.float32)                  # (chunk, N)
    C = c_ref[0, 0].astype(jnp.float32)                  # (chunk, N)

    dA = dt * A                                          # (chunk,)
    cum = jnp.cumsum(dA)                                 # (chunk,)
    # within-chunk decay matrix L[i, j] = exp(cum_i - cum_j) for j <= i
    li = cum[:, None] - cum[None, :]
    tri = (jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
           >= jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1))
    Lmat = jnp.where(tri, jnp.exp(li), 0.0)

    xdt = x * dt[:, None]                                # (chunk, P)
    scores = jax.lax.dot_general(C, B, (((1,), (1,)), ((), ())))   # (c, c)
    Y_diag = jax.lax.dot_general(scores * Lmat, xdt,
                                 (((1,), (0,)), ((), ())))         # (c, P)

    state = state_scr[...]                               # (P, N)
    Y_off = jax.lax.dot_general(C, state, (((1,), (1,)), ((), ())))
    Y_off = Y_off * jnp.exp(cum)[:, None]                # (c, P)

    decay_states = jnp.exp(cum[-1] - cum)                # (c,)
    new_state = (state * jnp.exp(cum[-1])
                 + jax.lax.dot_general(xdt * decay_states[:, None], B,
                                       (((0,), (0,)), ((), ()))))  # (P, N)
    state_scr[...] = new_state
    y_ref[0, 0] = (Y_diag + Y_off).astype(y_ref.dtype)

    @pl.when(ci == nc - 1)
    def _emit_state():
        state_out_ref[0, 0] = new_state.astype(state_out_ref.dtype)


def ssd_chunked(x: jnp.ndarray, dt: jnp.ndarray, A: jnp.ndarray,
                B: jnp.ndarray, C: jnp.ndarray, *, chunk: int = 128,
                interpret: bool = True) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: (b, s, H, P); dt: (b, s, H); A: (H,); B, C: (b, s, G, N).
    Returns (y (b, s, H, P) f32, final_state (b, H, P, N) f32)."""
    b, s, H, P = x.shape
    G, N = B.shape[2], B.shape[3]
    hpg = H // G
    assert s % chunk == 0, (s, chunk)
    nc = s // chunk

    # head-major layouts; broadcast groups to heads
    xr = x.transpose(0, 2, 1, 3)                                 # (b, H, s, P)
    dtr = dt.transpose(0, 2, 1)                                  # (b, H, s)
    Br = jnp.repeat(B.transpose(0, 2, 1, 3), hpg, axis=1)        # (b, H, s, N)
    Cr = jnp.repeat(C.transpose(0, 2, 1, 3), hpg, axis=1)

    y, state = pl.pallas_call(
        functools.partial(_ssd_kernel, chunk=chunk),
        grid=(b, H, nc),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),               # A (H,)
            pl.BlockSpec((1, 1, chunk, P), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, 1, chunk), lambda bi, hi, ci: (bi, hi, ci)),
            pl.BlockSpec((1, 1, chunk, N), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, 1, chunk, N), lambda bi, hi, ci: (bi, hi, ci, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, P), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, 1, P, N), lambda bi, hi, ci: (bi, hi, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, H, s, P), jnp.float32),
            jax.ShapeDtypeStruct((b, H, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(A.astype(jnp.float32), xr, dtr, Br, Cr)
    return y.transpose(0, 2, 1, 3), state
