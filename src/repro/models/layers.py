"""Core NN building blocks (pure JAX, explicit param pytrees — no flax).

Every block is an (init_*, *_apply) function pair.  Params are plain dicts of
jnp arrays so they stack cleanly under ``jax.vmap`` for scan-over-layers and
shard cleanly under GSPMD via the logical rules in ``repro.sharding.rules``.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig

Params = dict


def truncated_normal(key, shape, dtype, stddev):
    return (stddev * jax.random.truncated_normal(key, -2.0, 2.0, shape)).astype(dtype)


# =============================================================================
# Norms
# =============================================================================
def init_rmsnorm(key, dim: int, dtype) -> Params:
    del key
    return {"scale": jnp.ones((dim,), dtype=dtype)}


def rmsnorm_apply(p: Params, x: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return y.astype(dt) * p["scale"]


def init_layernorm(key, dim: int, dtype) -> Params:
    del key
    return {"scale": jnp.ones((dim,), dtype=dtype), "bias": jnp.zeros((dim,), dtype=dtype)}


def layernorm_apply(p: Params, x: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return y.astype(dt) * p["scale"] + p["bias"]


# =============================================================================
# Dense
# =============================================================================
def init_dense(key, d_in: int, d_out: int, dtype, bias: bool = False,
               stddev: Optional[float] = None) -> Params:
    stddev = stddev if stddev is not None else d_in ** -0.5
    p = {"w": truncated_normal(key, (d_in, d_out), dtype, stddev)}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype=dtype)
    return p


def dense_apply(p: Params, x: jnp.ndarray) -> jnp.ndarray:
    y = x @ p["w"]
    if "b" in p:
        y = y + p["b"]
    return y


# =============================================================================
# Rotary position embeddings (RoPE / partial rotary / M-RoPE)
# =============================================================================
def rope_table(positions: jnp.ndarray, d_rot: int, theta: float) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """cos/sin tables.  positions: (..., s) int32 -> (..., s, d_rot//2) f32."""
    half = d_rot // 2
    freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.cos(ang), jnp.sin(ang)


def mrope_table(positions: jnp.ndarray, d_rot: int, theta: float,
                sections: Tuple[int, ...]) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Multimodal RoPE (qwen2-vl).  positions: (3, b, s) — temporal/height/width
    streams; ``sections`` partitions the d_rot//2 frequency dims among streams."""
    assert sum(sections) == d_rot // 2, (sections, d_rot)
    cos_all, sin_all = rope_table(positions, d_rot, theta)  # (3, b, s, half)
    cos_parts, sin_parts = [], []
    off = 0
    for i, sec in enumerate(sections):
        cos_parts.append(cos_all[i, ..., off:off + sec])
        sin_parts.append(sin_all[i, ..., off:off + sec])
        off += sec
    return jnp.concatenate(cos_parts, -1), jnp.concatenate(sin_parts, -1)


def apply_rope(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray,
               partial: float = 1.0) -> jnp.ndarray:
    """x: (b, s, h, d).  cos/sin: (b, s, d_rot//2) or (s, d_rot//2)."""
    d = x.shape[-1]
    d_rot = int(d * partial)
    x_rot, x_pass = x[..., :d_rot], x[..., d_rot:]
    x1, x2 = jnp.split(x_rot, 2, axis=-1)
    if cos.ndim == 2:           # (s, half) -> broadcast over batch & heads
        cos_b = cos[None, :, None, :]
        sin_b = sin[None, :, None, :]
    else:                        # (b, s, half)
        cos_b = cos[:, :, None, :]
        sin_b = sin[:, :, None, :]
    cos_b = cos_b.astype(x.dtype)
    sin_b = sin_b.astype(x.dtype)
    r1 = x1 * cos_b - x2 * sin_b
    r2 = x2 * cos_b + x1 * sin_b
    out = jnp.concatenate([r1, r2], axis=-1)
    if d_rot < d:
        out = jnp.concatenate([out, x_pass], axis=-1)
    return out


# =============================================================================
# Attention (GQA + qk-norm + bias + sliding window), blocked for memory
# =============================================================================
def init_attention(key, cfg: ModelConfig) -> Params:
    ks = jax.random.split(key, 6)
    d, dh, H, K = cfg.d_model, cfg.d_head, cfg.n_heads, cfg.n_kv_heads
    p = {
        "wq": init_dense(ks[0], d, H * dh, cfg.dtype, bias=cfg.qkv_bias),
        "wk": init_dense(ks[1], d, K * dh, cfg.dtype, bias=cfg.qkv_bias),
        "wv": init_dense(ks[2], d, K * dh, cfg.dtype, bias=cfg.qkv_bias),
        "wo": init_dense(ks[3], H * dh, d, cfg.dtype, stddev=(H * dh) ** -0.5 / (2 * cfg.n_layers) ** 0.5),
    }
    if cfg.qk_norm:
        p["q_norm"] = init_rmsnorm(ks[4], dh, cfg.dtype)
        p["k_norm"] = init_rmsnorm(ks[5], dh, cfg.dtype)
    return p


def _project_qkv(p: Params, x: jnp.ndarray, cfg: ModelConfig, cos, sin):
    b, s, _ = x.shape
    q = dense_apply(p["wq"], x).reshape(b, s, cfg.n_heads, cfg.d_head)
    k = dense_apply(p["wk"], x).reshape(b, s, cfg.n_kv_heads, cfg.d_head)
    v = dense_apply(p["wv"], x).reshape(b, s, cfg.n_kv_heads, cfg.d_head)
    if cfg.qk_norm:
        q = rmsnorm_apply(p["q_norm"], q, cfg.norm_eps)
        k = rmsnorm_apply(p["k_norm"], k, cfg.norm_eps)
    if cos is not None:
        q = apply_rope(q, cos, sin, cfg.partial_rotary)
        k = apply_rope(k, cos, sin, cfg.partial_rotary)
    return q, k, v


def blocked_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                      *, causal: bool, window=None,
                      q_offset: int = 0, block_q: int = 512) -> jnp.ndarray:
    """Memory-bounded attention: scan over q blocks against full K/V.

    q: (b, sq, H, dh); k,v: (b, skv, K, dh).  GQA via head-group reshape.
    ``window``: None = full attention; otherwise an int *or traced scalar*
    (gemma3 scans a per-layer window through the layer stack) where a value
    <= 0 also means full attention.  Softmax in f32.
    O(block_q · skv) live score memory instead of O(sq · skv).
    """
    b, sq, H, dh = q.shape
    skv, K = k.shape[1], k.shape[2]
    g = H // K
    scale = dh ** -0.5
    nb = max(sq // block_q, 1)
    block_q = sq // nb
    assert sq % block_q == 0, (sq, block_q)

    kg = k.transpose(0, 2, 1, 3)                    # (b, K, skv, dh)
    vg = v.transpose(0, 2, 1, 3)
    qb = q.reshape(b, nb, block_q, K, g, dh).transpose(1, 0, 3, 4, 2, 5)
    # qb: (nb, b, K, g, block_q, dh)
    kv_pos = jnp.arange(skv)

    def one_block(carry, inp):
        qi, blk_idx = inp
        q_pos = q_offset + blk_idx * block_q + jnp.arange(block_q)
        s = jnp.einsum("bkgqd,bknd->bkgqn", (qi * scale).astype(kg.dtype), kg,
                       preferred_element_type=jnp.float32)
        mask = jnp.ones((block_q, skv), dtype=bool)
        if causal:
            mask &= q_pos[:, None] >= kv_pos[None, :]
        if window is not None:
            eff = jnp.where(jnp.asarray(window) > 0, jnp.asarray(window, jnp.int32),
                            jnp.int32(2**30))
            mask &= (q_pos[:, None] - kv_pos[None, :]) < eff
        s = jnp.where(mask[None, None, None], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bkgqn,bknd->bkgqd", p.astype(vg.dtype), vg,
                       preferred_element_type=jnp.float32)
        return carry, o.astype(q.dtype)

    # remat each q-block: without this, the scan's backward saves the f32
    # (block_q × skv) score/prob tensors of *every* block — O(sq·skv) memory,
    # exactly what blocking is meant to avoid.  Forward-only paths unaffected.
    one_block = jax.checkpoint(one_block)
    _, ob = jax.lax.scan(one_block, None, (qb, jnp.arange(nb)))
    # ob: (nb, b, K, g, block_q, dh)
    out = ob.transpose(1, 0, 4, 2, 3, 5).reshape(b, sq, H, dh)
    return out


def decode_attention_ref(q: jnp.ndarray, k_cache: jnp.ndarray, v_cache: jnp.ndarray,
                         length: jnp.ndarray, *, window=None) -> jnp.ndarray:
    """Single-position attention against a KV cache.

    q: (b, 1, H, dh); caches: (b, S, K, dh); length: () shared valid length,
    or (b,) per-row valid lengths — the slotted continuous-batching decode,
    same masking contract as ``kernels.decode_attention`` with
    ``kernels.ref`` as the CPU oracle.  The new token's position is
    length - 1 (per row).  ``window`` as in :func:`blocked_attention`.
    Returns (b, 1, H, dh).
    """
    b, _, H, dh = q.shape
    S, K = k_cache.shape[1], k_cache.shape[2]
    g = H // K
    scale = dh ** -0.5
    qg = q.reshape(b, K, g, dh)
    s = jnp.einsum("bkgd,bnkd->bkgn", (qg * scale).astype(k_cache.dtype),
                   k_cache, preferred_element_type=jnp.float32)
    pos = jnp.arange(S)[None, :]
    ln = jnp.asarray(length).reshape(-1, 1)     # () -> (1,1); (b,) -> (b,1)
    mask = pos < ln
    if window is not None:
        eff = jnp.where(jnp.asarray(window) > 0, jnp.asarray(window, jnp.int32),
                        jnp.int32(2**30))
        mask &= pos > (ln - 1 - eff)
    s = jnp.where(mask[:, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgn,bnkd->bkgd", p.astype(v_cache.dtype), v_cache,
                   preferred_element_type=jnp.float32)
    return o.reshape(b, 1, H, dh).astype(q.dtype)


def attention_apply(p: Params, x: jnp.ndarray, cfg: ModelConfig, cos, sin,
                    *, causal: bool = True, window=None,
                    kv: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None) -> jnp.ndarray:
    """Full-sequence attention (training / prefill).  ``kv`` overrides the
    self-attention K/V (cross-attention when not None)."""
    q, k, v = _project_qkv(p, x, cfg, cos, sin)
    if kv is not None:
        k, v = kv
        causal, window = False, None
    o = blocked_attention(q, k, v, causal=causal, window=window)
    b, s = x.shape[:2]
    return dense_apply(p["wo"], o.reshape(b, s, cfg.n_heads * cfg.d_head))


def attention_prefill_apply(p: Params, x: jnp.ndarray, cfg: ModelConfig,
                            cos, sin, *, causal: bool = True, window=None
                            ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Full-sequence attention that also returns the rotated K/V so a prefill
    pass can populate a decode cache in one forward (no teacher-forcing
    replay).  Returns (out (b,s,d), k (b,s,K,dh), v (b,s,K,dh))."""
    q, k, v = _project_qkv(p, x, cfg, cos, sin)
    o = blocked_attention(q, k, v, causal=causal, window=window)
    b, s = x.shape[:2]
    return dense_apply(p["wo"], o.reshape(b, s, cfg.n_heads * cfg.d_head)), k, v


def attention_decode_slots_apply(p: Params, x: jnp.ndarray, cfg: ModelConfig,
                                 cos, sin, cache_k: jnp.ndarray,
                                 cache_v: jnp.ndarray, lengths: jnp.ndarray,
                                 *, window=None):
    """One continuous-batching decode step: each row scatters its new K/V at
    its own position ``lengths[i]`` and attends over its own valid prefix.
    x: (b, 1, d); caches (b, S, K, dh); lengths (b,) i32.
    Returns (out (b,1,d), new_cache_k, new_cache_v)."""
    q, k, v = _project_qkv(p, x, cfg, cos, sin)
    b = x.shape[0]
    rows = jnp.arange(b)
    cache_k = cache_k.at[rows, lengths].set(k[:, 0].astype(cache_k.dtype),
                                            mode="drop")
    cache_v = cache_v.at[rows, lengths].set(v[:, 0].astype(cache_v.dtype),
                                            mode="drop")
    o = decode_attention_ref(q, cache_k, cache_v, lengths + 1, window=window)
    out = dense_apply(p["wo"], o.reshape(b, 1, cfg.n_heads * cfg.d_head))
    return out, cache_k, cache_v


def gather_paged_kv(arena_k: jnp.ndarray, arena_v: jnp.ndarray,
                    layer: jnp.ndarray, tables: jnp.ndarray, n_kv_heads: int):
    """Materialize one layer's per-row contiguous KV views from a paged arena.

    arenas: (L, n_blocks, block_size, K·dh) per-token rows; layer: scalar
    i32; tables: (b, n_pages) i32 arena block ids (0-padded — block 0 is the
    junk sink, masked by lengths at the attention).  Returns (k, v) shaped
    (b, n_pages·block_size, K, dh).  The layer and the pages are indexed in
    ONE gather: slicing ``arena[layer]`` first would materialise a whole
    layer of the arena per step.  On TPU the Pallas
    ``kernels.paged_attention`` kernel performs this gather inside the
    BlockSpec index map instead of materializing it."""
    b, n_pages = tables.shape
    bs, row = arena_k.shape[2:]
    shape = (b, n_pages * bs, n_kv_heads, row // n_kv_heads)
    return (arena_k[layer, tables].reshape(shape),
            arena_v[layer, tables].reshape(shape))


def write_paged_kv(arena_k: jnp.ndarray, arena_v: jnp.ndarray,
                   layer: jnp.ndarray, write_bid: jnp.ndarray,
                   write_off: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray):
    """Scatter n new tokens' K/V (n, K, dh) into rows
    ``[layer, write_bid[i], write_off[i]]`` of the (L, n_blocks, bs, K·dh)
    arena — only those rows are written, so a donated arena carried through
    the layer scan is updated in place."""
    n = k.shape[0]
    arena_k = arena_k.at[layer, write_bid, write_off].set(
        k.reshape(n, -1).astype(arena_k.dtype))
    arena_v = arena_v.at[layer, write_bid, write_off].set(
        v.reshape(n, -1).astype(arena_v.dtype))
    return arena_k, arena_v


def attention_decode_paged_apply(p: Params, x: jnp.ndarray, cfg: ModelConfig,
                                 cos, sin, arena_k: jnp.ndarray,
                                 arena_v: jnp.ndarray, layer: jnp.ndarray,
                                 tables: jnp.ndarray, lengths: jnp.ndarray,
                                 write_bid: jnp.ndarray,
                                 write_off: jnp.ndarray, *, window=None):
    """One paged continuous-batching decode step of layer ``layer``: each row
    scatters its new K/V into arena block ``write_bid[i]`` at offset
    ``write_off[i]`` (the junk block 0 for inactive rows) and attends over
    its own block table's valid prefix.  x: (b, 1, d); arenas (L, n_blocks,
    bs, K·dh); tables (b, n_pages); lengths (b,) i32.  Returns (out,
    arena_k, arena_v)."""
    q, k, v = _project_qkv(p, x, cfg, cos, sin)
    b = x.shape[0]
    arena_k, arena_v = write_paged_kv(arena_k, arena_v, layer, write_bid,
                                      write_off, k[:, 0], v[:, 0])
    kc, vc = gather_paged_kv(arena_k, arena_v, layer, tables, cfg.n_kv_heads)
    o = decode_attention_ref(q, kc, vc, lengths + 1, window=window)
    out = dense_apply(p["wo"], o.reshape(b, 1, cfg.n_heads * cfg.d_head))
    return out, arena_k, arena_v


def attention_prefill_paged_apply(p: Params, x: jnp.ndarray, cfg: ModelConfig,
                                  cos, sin, arena_k: jnp.ndarray,
                                  arena_v: jnp.ndarray, layer: jnp.ndarray,
                                  table: jnp.ndarray, positions: jnp.ndarray,
                                  write_bid: jnp.ndarray,
                                  write_off: jnp.ndarray, *, window=None):
    """One chunk of chunked prefill of layer ``layer`` for a single sequence
    against the paged arena: the chunk's rotated K/V scatter into their
    arena rows (junk block 0 for the padded tail), then the chunk's queries
    attend causally over the whole gathered table — cached prefix blocks
    included, so a prefix-cache hit never replays shared tokens.

    x: (1, C, d); table: (n_pages,) i32; positions: (C,) absolute token
    positions of the chunk.  Returns (out (1, C, d), arena_k, arena_v)."""
    q, k, v = _project_qkv(p, x, cfg, cos, sin)
    C = x.shape[1]
    arena_k, arena_v = write_paged_kv(arena_k, arena_v, layer, write_bid,
                                      write_off, k[0], v[0])
    kc, vc = gather_paged_kv(arena_k, arena_v, layer, table[None],
                             cfg.n_kv_heads)                  # (1, S, K, dh)
    S, K = kc.shape[1], kc.shape[2]
    H, dh = cfg.n_heads, cfg.d_head
    g = H // K
    qg = q.reshape(1, C, K, g, dh)
    s = jnp.einsum("bqkgd,bnkd->bkgqn", (qg * dh ** -0.5).astype(kc.dtype),
                   kc, preferred_element_type=jnp.float32)
    kv_pos = jnp.arange(S)
    mask = kv_pos[None, :] <= positions[:, None]              # causal (C, S)
    if window is not None:
        eff = jnp.where(jnp.asarray(window) > 0,
                        jnp.asarray(window, jnp.int32), jnp.int32(2**30))
        mask &= (positions[:, None] - kv_pos[None, :]) < eff
    s = jnp.where(mask[None, None, None], s, -1e30)
    pr = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqn,bnkd->bqkgd", pr.astype(vc.dtype), vc,
                   preferred_element_type=jnp.float32)
    out = dense_apply(p["wo"], o.astype(q.dtype).reshape(1, C, H * dh))
    return out, arena_k, arena_v


def cross_kv(p: Params, memory: jnp.ndarray, cfg: ModelConfig):
    """Precompute cross-attention K/V from encoder memory."""
    b, s, _ = memory.shape
    k = dense_apply(p["wk"], memory).reshape(b, s, cfg.n_kv_heads, cfg.d_head)
    v = dense_apply(p["wv"], memory).reshape(b, s, cfg.n_kv_heads, cfg.d_head)
    if cfg.qk_norm:
        k = rmsnorm_apply(p["k_norm"], k, cfg.norm_eps)
    return k, v


def decode_attention_parts(q: jnp.ndarray, k_cache: jnp.ndarray,
                           v_cache: jnp.ndarray, length, *,
                           pos_offset=0, query_pos=None, window=None):
    """Unnormalized single-position attention over one KV segment: returns
    (acc (b,K,g,dh) f32, m (b,K,g) f32, l (b,K,g) f32) for online-softmax
    combination across segments (flash-decode partials).

    ``pos_offset`` — absolute position of the segment's slot 0 (suffix
    segments sit after the prefix); ``query_pos`` — absolute position of the
    query token (for windowed masks)."""
    b, _, H, dh = q.shape
    S, K = k_cache.shape[1], k_cache.shape[2]
    g = H // K
    scale = dh ** -0.5
    qg = q.reshape(b, K, g, dh)
    s = jnp.einsum("bkgd,bnkd->bkgn", (qg * scale).astype(k_cache.dtype),
                   k_cache, preferred_element_type=jnp.float32)
    pos = pos_offset + jnp.arange(S)
    mask = pos[None, :] < (pos_offset + length)
    if window is not None and query_pos is not None:
        eff = jnp.where(jnp.asarray(window) > 0, jnp.asarray(window, jnp.int32),
                        jnp.int32(2**30))
        mask &= pos[None, :] > (query_pos - eff)
    s = jnp.where(mask[:, None, None, :], s, -jnp.inf)
    m = jnp.max(s, axis=-1)
    p = jnp.exp(s - jnp.maximum(m[..., None], -1e30))
    l = jnp.sum(p, axis=-1)
    acc = jnp.einsum("bkgn,bnkd->bkgd", p.astype(v_cache.dtype), v_cache,
                     preferred_element_type=jnp.float32)
    return acc, jnp.maximum(m, -1e30), l


def combine_attention_parts(parts):
    """Merge flash-decode partials [(acc, m, l), ...] into (b, K, g, dh)."""
    m = parts[0][1]
    for _, mi, _ in parts[1:]:
        m = jnp.maximum(m, mi)
    acc = sum(a * jnp.exp(mi - m)[..., None] for a, mi, _ in parts)
    l = sum(li * jnp.exp(mi - m) for _, mi, li in parts)
    return acc / jnp.maximum(l, 1e-30)[..., None]


def attention_decode_split_apply(p: Params, x: jnp.ndarray, cfg: ModelConfig,
                                 cos, sin, prefix_k, prefix_v, sk, sv,
                                 pos: jnp.ndarray, prefix_len: jnp.ndarray,
                                 *, window=None):
    """Append-buffer decode (§Perf): the big prefix cache is read-only (so it
    can be sequence-sharded with zero update cost); the new token's K/V goes
    into a small replicated suffix ring via a local dynamic-update-slice.
    Returns (out, new_sk, new_sv) — prefix buffers are untouched."""
    q, k, v = _project_qkv(p, x, cfg, cos, sin)
    slot = pos - prefix_len
    sk = jax.lax.dynamic_update_slice(sk, k.astype(sk.dtype), (0, slot, 0, 0))
    sv = jax.lax.dynamic_update_slice(sv, v.astype(sv.dtype), (0, slot, 0, 0))
    part_prefix = decode_attention_parts(q, prefix_k, prefix_v, prefix_len,
                                         pos_offset=0, query_pos=pos,
                                         window=window)
    part_suffix = decode_attention_parts(q, sk, sv, slot + 1,
                                         pos_offset=prefix_len, query_pos=pos,
                                         window=window)
    o = combine_attention_parts([part_prefix, part_suffix]).astype(q.dtype)
    b = x.shape[0]
    out = dense_apply(p["wo"], o.reshape(b, 1, cfg.n_heads * cfg.d_head))
    return out, sk, sv


def attention_decode_apply(p: Params, x: jnp.ndarray, cfg: ModelConfig, cos, sin,
                           cache_k: jnp.ndarray, cache_v: jnp.ndarray,
                           pos: jnp.ndarray, *, window=None):
    """One-token decode.  x: (b, 1, d); caches (b, S, K, dh); pos: () int32.

    Returns (out (b,1,d), new_cache_k, new_cache_v)."""
    q, k, v = _project_qkv(p, x, cfg, cos, sin)
    cache_k = jax.lax.dynamic_update_slice(cache_k, k.astype(cache_k.dtype), (0, pos, 0, 0))
    cache_v = jax.lax.dynamic_update_slice(cache_v, v.astype(cache_v.dtype), (0, pos, 0, 0))
    o = decode_attention_ref(q, cache_k, cache_v, pos + 1, window=window)
    b = x.shape[0]
    out = dense_apply(p["wo"], o.reshape(b, 1, cfg.n_heads * cfg.d_head))
    return out, cache_k, cache_v


# =============================================================================
# MLP (SwiGLU or plain GELU)
# =============================================================================
def init_mlp(key, cfg: ModelConfig, d_ff: Optional[int] = None) -> Params:
    d_ff = d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    d = cfg.d_model
    out_std = d_ff ** -0.5 / (2 * cfg.n_layers) ** 0.5
    if cfg.act == "silu":
        return {
            "wi_gate": init_dense(ks[0], d, d_ff, cfg.dtype),
            "wi_up": init_dense(ks[1], d, d_ff, cfg.dtype),
            "wo": init_dense(ks[2], d_ff, d, cfg.dtype, stddev=out_std),
        }
    return {
        "wi_up": init_dense(ks[1], d, d_ff, cfg.dtype),
        "wo": init_dense(ks[2], d_ff, d, cfg.dtype, stddev=out_std),
    }


def mlp_apply(p: Params, x: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    up = dense_apply(p["wi_up"], x)
    if "wi_gate" in p:
        h = jax.nn.silu(dense_apply(p["wi_gate"], x)) * up
    else:
        h = jax.nn.gelu(up)
    return dense_apply(p["wo"], h)


# =============================================================================
# Embedding
# =============================================================================
def init_embedding(key, vocab: int, d: int, dtype) -> Params:
    return {"table": truncated_normal(key, (vocab, d), dtype, 1.0)}


def embedding_apply(p: Params, ids: jnp.ndarray, one_hot: bool = False) -> jnp.ndarray:
    """``one_hot=True`` (training): lookup as a one-hot contraction.  The
    gather's backward is a scatter-add into the vocab-sharded table, which
    GSPMD implements by all-gathering the full f32 hidden cotangent across the
    data axis (measured 5 GiB/microbatch on gemma3 — EXPERIMENTS.md §Perf A5);
    the contraction form keeps everything as partial-summed matmuls."""
    if one_hot:
        table = p["table"]
        oh = jax.nn.one_hot(ids, table.shape[0], dtype=table.dtype)
        return oh @ table
    return jnp.take(p["table"], ids, axis=0)


def embedding_logits(p: Params, x: jnp.ndarray) -> jnp.ndarray:
    """Tied-softmax readout."""
    return x @ p["table"].T
