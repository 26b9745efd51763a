"""Decoder-only LM stack (dense / MoE / SSM / hybrid / VLM) with
scan-over-layers so HLO size — and XLA compile time at 512 devices — is O(1)
in depth.  Layer params are stacked on a leading L axis via vmap'd init.

Three entry points per model:
  * forward_lm     — full-sequence (training / prefill) -> (logits, aux_loss)
  * init_kv_cache  — allocate decode state (KV caches / SSM states)
  * decode_step_lm — one-token decode against the cache

Heterogeneous layer stacks (gemma3's 5 local : 1 global pattern) stay inside a
single scan by passing the per-layer window / rope-selector as *scanned data*
rather than unrolling the stack.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.ad_checkpoint
import jax.numpy as jnp
import numpy as np

from repro.models import layers as L
from repro.models import moe as M
from repro.models import ssm as S
from repro.models.config import ModelConfig

Params = dict


def _maybe_remat(fn, cfg: ModelConfig):
    """Per-layer activation checkpointing.  "full" = nothing saveable (layer
    inputs only — memory-lean default), "dots" = save matmul outputs (less
    recompute, more HBM), "collectives" = save the post-all-reduce block
    outputs so the backward's remat never re-runs the TP collectives (the
    §Perf collective-bound fix), "none" = no remat."""
    if not cfg.remat or cfg.remat_policy == "none":
        return fn
    if cfg.remat_policy == "dots":
        return jax.checkpoint(fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    if cfg.remat_policy == "collectives":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.save_only_these_names(
                "attn_out", "block_out"))
    return jax.checkpoint(fn)


def _sp_constraint(x, cfg: ModelConfig):
    """Megatron-style sequence parallelism: keep the residual stream sharded
    over the model axis on the sequence dim between blocks.  GSPMD turns the
    per-block TP all-reduce into reduce-scatter (+ all-gather at the next
    block's entry) and the saved scan carries shrink by the TP degree."""
    if not cfg.seq_parallel:
        return x
    from jax.sharding import PartitionSpec as P
    U = P.UNCONSTRAINED
    return jax.lax.with_sharding_constraint(x, P(U, "model", U))


# =============================================================================
# per-layer pattern (windows / local-global rope selection)
# =============================================================================
def layer_pattern(cfg: ModelConfig) -> Tuple[np.ndarray, np.ndarray]:
    """Returns (windows (L,), is_global (L,)) as host arrays.

    gemma3: pattern of ``local_global_ratio`` local layers followed by one
    global layer; local layers use sliding_window + rope_theta, global layers
    use full attention + global_rope_theta.
    """
    n = cfg.n_layers
    if cfg.local_global_ratio > 0:
        r = cfg.local_global_ratio
        is_global = np.array([(i % (r + 1)) == r for i in range(n)])
        windows = np.where(is_global, 0, cfg.sliding_window).astype(np.int32)
    else:
        is_global = np.ones((n,), dtype=bool)
        windows = np.full((n,), cfg.sliding_window, dtype=np.int32)
    return windows, is_global


def _has_window(cfg: ModelConfig) -> bool:
    return cfg.sliding_window > 0


# =============================================================================
# init
# =============================================================================
def init_decoder_layer(key, cfg: ModelConfig) -> Params:
    ks = jax.random.split(key, 4)
    p = {
        "ln1": L.init_rmsnorm(None, cfg.d_model, cfg.dtype),
        "attn": L.init_attention(ks[0], cfg),
        "ln2": L.init_rmsnorm(None, cfg.d_model, cfg.dtype),
    }
    if cfg.is_moe:
        p["moe"] = M.init_moe(ks[1], cfg)
    else:
        p["mlp"] = L.init_mlp(ks[1], cfg)
    return p


def init_ssm_layer(key, cfg: ModelConfig) -> Params:
    return {
        "ln": L.init_rmsnorm(None, cfg.d_model, cfg.dtype),
        "mamba": S.init_mamba2(key, cfg),
    }


def init_lm(key, cfg: ModelConfig) -> Params:
    k_emb, k_layers, k_shared, k_head = jax.random.split(key, 4)
    params: Params = {
        "embed": L.init_embedding(k_emb, cfg.padded_vocab, cfg.d_model, cfg.dtype),
        "final_norm": L.init_rmsnorm(None, cfg.d_model, cfg.dtype),
    }
    layer_keys = jax.random.split(k_layers, cfg.n_layers)
    if cfg.family == "ssm":
        params["layers"] = jax.vmap(lambda k: init_ssm_layer(k, cfg))(layer_keys)
    elif cfg.family == "hybrid":
        params["layers"] = jax.vmap(lambda k: init_ssm_layer(k, cfg))(layer_keys)
        params["shared"] = {
            "ln1": L.init_rmsnorm(None, cfg.d_model, cfg.dtype),
            "attn": L.init_attention(jax.random.fold_in(k_shared, 0), cfg),
            "ln2": L.init_rmsnorm(None, cfg.d_model, cfg.dtype),
            "mlp": L.init_mlp(jax.random.fold_in(k_shared, 1), cfg),
        }
    else:
        params["layers"] = jax.vmap(lambda k: init_decoder_layer(k, cfg))(layer_keys)
    if not cfg.tie_embeddings:
        params["lm_head"] = L.init_dense(k_head, cfg.d_model, cfg.padded_vocab, cfg.dtype)
    return params


# =============================================================================
# rope tables
# =============================================================================
def _rope_tables(cfg: ModelConfig, positions: jnp.ndarray,
                 mrope_positions: Optional[jnp.ndarray] = None):
    """Returns ((cos_l, sin_l), (cos_g, sin_g)) — local/global theta tables.
    Non-gemma archs get identical tables for both."""
    d_rot = int(cfg.d_head * cfg.partial_rotary)
    if cfg.mrope_sections and mrope_positions is not None:
        cos, sin = L.mrope_table(mrope_positions, d_rot, cfg.rope_theta, cfg.mrope_sections)
        return (cos, sin), (cos, sin)
    cos_l, sin_l = L.rope_table(positions, d_rot, cfg.rope_theta)
    if cfg.local_global_ratio > 0:
        cos_g, sin_g = L.rope_table(positions, d_rot, cfg.global_rope_theta)
    else:
        cos_g, sin_g = cos_l, sin_l
    return (cos_l, sin_l), (cos_g, sin_g)


# =============================================================================
# LM head
# =============================================================================
def lm_logits(params: Params, x: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    """Readout over the padded vocabulary (tied table or ``lm_head``).  The
    padding rows past ``cfg.vocab_size`` exist only for even sharding: their
    logits are pinned to the dtype's lowest value, so no greedy step or
    softmax ever picks a token outside the vocabulary."""
    if cfg.tie_embeddings:
        logits = L.embedding_logits(params["embed"], x)
    else:
        logits = L.dense_apply(params["lm_head"], x)
    if logits.shape[-1] > cfg.vocab_size:
        ids = jax.lax.broadcasted_iota(jnp.int32, logits.shape,
                                       logits.ndim - 1)
        logits = jnp.where(ids < cfg.vocab_size, logits,
                           jnp.finfo(logits.dtype).min)
    return logits


# =============================================================================
# forward (train / prefill)
# =============================================================================
def forward_lm(params: Params, tokens: jnp.ndarray, cfg: ModelConfig, *,
               mrope_positions: Optional[jnp.ndarray] = None,
               train: bool = False,
               inputs_embeds: Optional[jnp.ndarray] = None,
               return_hidden: bool = False) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """tokens: (b, s) int32 -> (logits (b, s, V), aux_loss ()).
    ``return_hidden`` skips the LM head and returns the final hidden states
    (the chunked training loss applies the head chunk-by-chunk instead)."""
    if inputs_embeds is not None:
        x = inputs_embeds.astype(cfg.dtype)
        b, s = x.shape[:2]
    else:
        b, s = tokens.shape
        x = L.embedding_apply(params["embed"], tokens)
    positions = jnp.arange(s, dtype=jnp.int32)
    (cos_l, sin_l), (cos_g, sin_g) = _rope_tables(cfg, positions, mrope_positions)
    windows_np, is_global_np = layer_pattern(cfg)
    windows = jnp.asarray(windows_np)
    is_global = jnp.asarray(is_global_np)
    has_win = _has_window(cfg)

    if cfg.family == "ssm":
        def body(x, p):
            x = x + S.mamba2_apply(p["mamba"], L.rmsnorm_apply(p["ln"], x, cfg.norm_eps), cfg)
            return x, None
        if train:
            body = _maybe_remat(body, cfg)
        x, _ = jax.lax.scan(body, x, params["layers"])
        aux = jnp.zeros((), jnp.float32)

    elif cfg.family == "hybrid":
        per = cfg.attn_every
        G = cfg.n_layers // per
        grouped = jax.tree.map(lambda a: a.reshape(G, per, *a.shape[1:]), params["layers"])
        sh = params["shared"]

        def group_body(x, gp):
            def mbody(x, p):
                x = x + S.mamba2_apply(p["mamba"], L.rmsnorm_apply(p["ln"], x, cfg.norm_eps), cfg)
                return x, None
            x, _ = jax.lax.scan(mbody, x, gp)
            h = L.rmsnorm_apply(sh["ln1"], x, cfg.norm_eps)
            x = x + L.attention_apply(sh["attn"], h, cfg, cos_l, sin_l, causal=True)
            h = L.rmsnorm_apply(sh["ln2"], x, cfg.norm_eps)
            x = x + L.mlp_apply(sh["mlp"], h, cfg)
            return x, None

        if train:
            group_body = _maybe_remat(group_body, cfg)
        x, _ = jax.lax.scan(group_body, x, grouped)
        aux = jnp.zeros((), jnp.float32)

    else:
        def body(carry, xs):
            x, aux = carry
            p, win, isg = xs
            cos = jnp.where(isg, cos_g, cos_l)
            sin = jnp.where(isg, sin_g, sin_l)
            x = _sp_constraint(x, cfg)
            h = L.rmsnorm_apply(p["ln1"], x, cfg.norm_eps)
            a_out = L.attention_apply(p["attn"], h, cfg, cos, sin, causal=True,
                                      window=win if has_win else None)
            x = x + jax.ad_checkpoint.checkpoint_name(a_out, "attn_out")
            h = L.rmsnorm_apply(p["ln2"], x, cfg.norm_eps)
            if cfg.is_moe:
                y, a = M.moe_apply(p["moe"], h, cfg)
                aux = aux + a
            else:
                y = L.mlp_apply(p["mlp"], h, cfg)
            out = _sp_constraint(x + y, cfg)
            return (jax.ad_checkpoint.checkpoint_name(out, "block_out"), aux), None

        if train:
            body = _maybe_remat(body, cfg)
        (x, aux), _ = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                                   (params["layers"], windows, is_global))

    x = L.rmsnorm_apply(params["final_norm"], x, cfg.norm_eps)
    if return_hidden:
        return x, aux
    logits = lm_logits(params, x, cfg)
    return logits, aux


# =============================================================================
# decode
# =============================================================================
def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int,
                  dtype=jnp.bfloat16) -> Params:
    """Allocate decode state.  Attention families: stacked (L, b, S, K, dh) KV;
    SSM families: O(1) conv + state buffers; hybrid: both (one KV per shared-
    attention application)."""
    K, dh = cfg.n_kv_heads, cfg.d_head
    if cfg.family == "ssm":
        caches = jax.vmap(lambda _: S.init_ssm_cache(cfg, batch, dtype))(
            jnp.arange(cfg.n_layers))
        return {"ssm": caches, "pos": jnp.zeros((), jnp.int32)}
    if cfg.family == "hybrid":
        G = cfg.n_layers // cfg.attn_every
        ssm = jax.vmap(lambda _: S.init_ssm_cache(cfg, batch, dtype))(
            jnp.arange(cfg.n_layers))
        return {
            "ssm": ssm,
            "k": jnp.zeros((G, batch, max_len, K, dh), dtype),
            "v": jnp.zeros((G, batch, max_len, K, dh), dtype),
            "pos": jnp.zeros((), jnp.int32),
        }
    cache = {
        "k": jnp.zeros((cfg.n_layers, batch, max_len, K, dh), dtype),
        "v": jnp.zeros((cfg.n_layers, batch, max_len, K, dh), dtype),
        "pos": jnp.zeros((), jnp.int32),
    }
    if cfg.decode_window > 0:
        # append-buffer decode (§Perf): read-only prefix + small write suffix
        W = cfg.decode_window
        cache["sk"] = jnp.zeros((cfg.n_layers, batch, W, K, dh), dtype)
        cache["sv"] = jnp.zeros((cfg.n_layers, batch, W, K, dh), dtype)
        cache["prefix_len"] = jnp.zeros((), jnp.int32)
    return cache


def decode_step_lm(params: Params, cache: Params, tokens: jnp.ndarray,
                   cfg: ModelConfig, *,
                   mrope_positions: Optional[jnp.ndarray] = None
                   ) -> Tuple[jnp.ndarray, Params]:
    """One decode step.  tokens: (b, 1) int32.  Returns (logits (b, V), cache)."""
    pos = cache["pos"]
    x = L.embedding_apply(params["embed"], tokens)        # (b, 1, d)
    positions = pos[None].astype(jnp.int32)               # (1,)
    (cos_l, sin_l), (cos_g, sin_g) = _rope_tables(cfg, positions, mrope_positions)
    windows_np, is_global_np = layer_pattern(cfg)
    has_win = _has_window(cfg)

    if cfg.family == "ssm":
        def body(x, xs):
            p, c = xs
            y, c2 = S.mamba2_decode_step(p["mamba"], L.rmsnorm_apply(p["ln"], x, cfg.norm_eps), c, cfg)
            return x + y, c2
        x, new_ssm = jax.lax.scan(body, x, (params["layers"], cache["ssm"]))
        new_cache = {"ssm": new_ssm, "pos": pos + 1}

    elif cfg.family == "hybrid":
        per = cfg.attn_every
        G = cfg.n_layers // per
        grouped_p = jax.tree.map(lambda a: a.reshape(G, per, *a.shape[1:]), params["layers"])
        grouped_c = jax.tree.map(lambda a: a.reshape(G, per, *a.shape[1:]), cache["ssm"])
        sh = params["shared"]

        def group_body(x, xs):
            gp, gc, kc, vc = xs
            def mbody(x, inner):
                p, c = inner
                y, c2 = S.mamba2_decode_step(p["mamba"], L.rmsnorm_apply(p["ln"], x, cfg.norm_eps), c, cfg)
                return x + y, c2
            x, gc2 = jax.lax.scan(mbody, x, (gp, gc))
            h = L.rmsnorm_apply(sh["ln1"], x, cfg.norm_eps)
            a, kc2, vc2 = L.attention_decode_apply(sh["attn"], h, cfg, cos_l, sin_l, kc, vc, pos)
            x = x + a
            h = L.rmsnorm_apply(sh["ln2"], x, cfg.norm_eps)
            x = x + L.mlp_apply(sh["mlp"], h, cfg)
            return x, (gc2, kc2, vc2)

        x, (ssm2, k2, v2) = jax.lax.scan(group_body, x,
                                         (grouped_p, grouped_c, cache["k"], cache["v"]))
        ssm2 = jax.tree.map(lambda a: a.reshape(cfg.n_layers, *a.shape[2:]), ssm2)
        new_cache = {"ssm": ssm2, "k": k2, "v": v2, "pos": pos + 1}

    else:
        windows = jnp.asarray(windows_np)
        is_global = jnp.asarray(is_global_np)
        split = cfg.decode_window > 0

        def body(x, xs):
            if split:
                p, kc, vc, sk, sv, win, isg = xs
            else:
                p, kc, vc, win, isg = xs
            cos = jnp.where(isg, cos_g, cos_l)
            sin = jnp.where(isg, sin_g, sin_l)
            h = L.rmsnorm_apply(p["ln1"], x, cfg.norm_eps)
            if split:
                a, sk2, sv2 = L.attention_decode_split_apply(
                    p["attn"], h, cfg, cos, sin, kc, vc, sk, sv, pos,
                    cache["prefix_len"], window=win if has_win else None)
                ys = (sk2, sv2)
            else:
                a, kc2, vc2 = L.attention_decode_apply(
                    p["attn"], h, cfg, cos, sin, kc, vc, pos,
                    window=win if has_win else None)
                ys = (kc2, vc2)
            x = x + a
            h = L.rmsnorm_apply(p["ln2"], x, cfg.norm_eps)
            if cfg.is_moe:
                y, _ = M.moe_apply(p["moe"], h, cfg)
            else:
                y = L.mlp_apply(p["mlp"], h, cfg)
            return x + y, ys

        if split:
            x, (sk2, sv2) = jax.lax.scan(
                body, x, (params["layers"], cache["k"], cache["v"],
                          cache["sk"], cache["sv"], windows, is_global))
            new_cache = {"k": cache["k"], "v": cache["v"], "sk": sk2,
                         "sv": sv2, "prefix_len": cache["prefix_len"],
                         "pos": pos + 1}
        else:
            x, (k2, v2) = jax.lax.scan(
                body, x, (params["layers"], cache["k"], cache["v"], windows,
                          is_global))
            new_cache = {"k": k2, "v": v2, "pos": pos + 1}

    x = L.rmsnorm_apply(params["final_norm"], x, cfg.norm_eps)
    logits = lm_logits(params, x, cfg)
    return logits[:, 0, :], new_cache


# =============================================================================
# slotted continuous-batching decode (serving engine)
# =============================================================================
def supports_slots(cfg: ModelConfig) -> bool:
    """Families the slotted batched KV cache covers: pure-attention decoders
    (dense / MoE) with a classic DUS cache — no encoder, no SSM state, no
    per-stream M-RoPE positions."""
    return (cfg.family in ("dense", "moe") and cfg.n_enc_layers == 0
            and not cfg.mrope_sections)


def init_slot_cache(cfg: ModelConfig, n_slots: int, max_len: int,
                    dtype=jnp.bfloat16) -> Params:
    """Fixed-capacity batched KV cache: ``n_slots`` independent sequences,
    each with its own valid-prefix ``lengths[i]`` (the continuous-batching
    analogue of ``init_kv_cache``'s single scalar ``pos``)."""
    assert supports_slots(cfg), f"slotted cache unsupported for {cfg.family}"
    K, dh = cfg.n_kv_heads, cfg.d_head
    return {
        "k": jnp.zeros((cfg.n_layers, n_slots, max_len, K, dh), dtype),
        "v": jnp.zeros((cfg.n_layers, n_slots, max_len, K, dh), dtype),
        "lengths": jnp.zeros((n_slots,), jnp.int32),
    }


def prefill_kv_lm(params: Params, tokens: jnp.ndarray, cfg: ModelConfig
                  ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Full-sequence forward that also emits every layer's rotated K/V, so a
    serving engine populates a decode cache in ONE pass instead of replaying
    the prompt token-by-token through ``decode_step_lm``.

    tokens: (b, s) i32 -> (logits (b, s, V), k (L, b, s, K, dh), v (...))."""
    assert supports_slots(cfg), f"prefill-kv unsupported for {cfg.family}"
    b, s = tokens.shape
    x = L.embedding_apply(params["embed"], tokens)
    positions = jnp.arange(s, dtype=jnp.int32)
    (cos_l, sin_l), (cos_g, sin_g) = _rope_tables(cfg, positions)
    windows_np, is_global_np = layer_pattern(cfg)
    windows = jnp.asarray(windows_np)
    is_global = jnp.asarray(is_global_np)
    has_win = _has_window(cfg)

    def body(x, xs):
        p, win, isg = xs
        cos = jnp.where(isg, cos_g, cos_l)
        sin = jnp.where(isg, sin_g, sin_l)
        h = L.rmsnorm_apply(p["ln1"], x, cfg.norm_eps)
        a_out, k, v = L.attention_prefill_apply(
            p["attn"], h, cfg, cos, sin, causal=True,
            window=win if has_win else None)
        x = x + a_out
        h = L.rmsnorm_apply(p["ln2"], x, cfg.norm_eps)
        if cfg.is_moe:
            y, _ = M.moe_apply(p["moe"], h, cfg)
        else:
            y = L.mlp_apply(p["mlp"], h, cfg)
        return x + y, (k, v)

    x, (k_all, v_all) = jax.lax.scan(body, x,
                                     (params["layers"], windows, is_global))
    x = L.rmsnorm_apply(params["final_norm"], x, cfg.norm_eps)
    logits = lm_logits(params, x, cfg)
    return logits, k_all, v_all


def decode_slots_lm(params: Params, cache: Params, tokens: jnp.ndarray,
                    cfg: ModelConfig, active: jnp.ndarray
                    ) -> Tuple[jnp.ndarray, Params]:
    """One batched decode step over ALL slots of a slot cache.

    tokens: (n_slots, 1) i32 — one column across slots; ``active``:
    (n_slots,) bool — slots currently serving a request.  Free slots still
    compute (the batch shape is static for jit) but their cache writes land
    on positions a future admission's prefill overwrites, and their lengths
    do not advance.  Returns (logits (n_slots, V), new_cache)."""
    lengths = cache["lengths"]
    x = L.embedding_apply(params["embed"], tokens)
    positions = lengths[:, None]               # (n_slots, 1) per-slot position
    (cos_l, sin_l), (cos_g, sin_g) = _rope_tables(cfg, positions)
    windows_np, is_global_np = layer_pattern(cfg)
    windows = jnp.asarray(windows_np)
    is_global = jnp.asarray(is_global_np)
    has_win = _has_window(cfg)

    def body(x, xs):
        p, kc, vc, win, isg = xs
        cos = jnp.where(isg, cos_g, cos_l)
        sin = jnp.where(isg, sin_g, sin_l)
        h = L.rmsnorm_apply(p["ln1"], x, cfg.norm_eps)
        a, kc2, vc2 = L.attention_decode_slots_apply(
            p["attn"], h, cfg, cos, sin, kc, vc, lengths,
            window=win if has_win else None)
        x = x + a
        h = L.rmsnorm_apply(p["ln2"], x, cfg.norm_eps)
        if cfg.is_moe:
            y, _ = M.moe_apply(p["moe"], h, cfg)
        else:
            y = L.mlp_apply(p["mlp"], h, cfg)
        return x + y, (kc2, vc2)

    x, (k2, v2) = jax.lax.scan(body, x, (params["layers"], cache["k"],
                                         cache["v"], windows, is_global))
    x = L.rmsnorm_apply(params["final_norm"], x, cfg.norm_eps)
    logits = lm_logits(params, x, cfg)
    new_cache = {"k": k2, "v": v2,
                 "lengths": lengths + active.astype(jnp.int32)}
    return logits[:, 0, :], new_cache


# =============================================================================
# paged KV arena (kvpool serving engine)
# =============================================================================
def init_block_arena(cfg: ModelConfig, n_blocks: int, block_size: int,
                     dtype=jnp.bfloat16, mesh=None) -> Params:
    """Paged KV arena: every sequence's cache is a list of fixed-size blocks
    carved from this one allocation (``serving.kvpool`` owns the map: free
    list, refcounts, block tables).  Block 0 is the junk sink for masked
    writes — it is never handed to a sequence.

    Each of ``k``/``v`` is ``(L, n_blocks, block_size, K·dh)``: one row per
    token per layer, its KV heads side by side.  The paged programs carry
    the arena through the layer scan and scatter only each step's new rows
    into it; with this row layout XLA keeps that scatter in place for every
    head shape (split ``(K, dh)`` minor dims such as 2×64 make it relayout
    and copy the whole arena instead).

    With ``mesh`` the arena comes back committed under the GSPMD rule
    (whole KV heads of each row over "model", block dims unsharded —
    ``sharding.rules.arena_spec``) so the serving engine's donated
    prefill/decode jits specialize to the sharded layout."""
    assert supports_slots(cfg), f"paged arena unsupported for {cfg.family}"
    shape = (cfg.n_layers, n_blocks, block_size, cfg.n_kv_heads * cfg.d_head)
    if mesh is None:
        return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
    import jax
    from repro.sharding import rules as SR
    sh = SR.arena_shardings(mesh, cfg)
    zeros = jax.jit(lambda: jnp.zeros(shape, dtype), out_shardings=sh)
    return {"k": zeros(), "v": zeros()}


def _scan_paged_layers(body, x, params: Params, arena: Params, windows,
                       is_global):
    """Run ``body`` over the layers with the arena in the scan CARRY, never
    among the scanned inputs or stacked outputs: a scanned arena is sliced
    per layer and restacked into a fresh buffer, rewriting all of it every
    step, while a carried one takes each layer's scatter in place.  The
    layer index rides in ``xs`` for the helpers' ``arena[layer, ...]``.
    Returns (x, arena_k, arena_v)."""
    n_layers = arena["k"].shape[0]
    (x, k2, v2), _ = jax.lax.scan(
        body, (x, arena["k"], arena["v"]),
        (params["layers"], jnp.arange(n_layers, dtype=jnp.int32), windows,
         is_global))
    return x, k2, v2


def prefill_paged_lm(params: Params, tokens: jnp.ndarray, cfg: ModelConfig,
                     arena: Params, table: jnp.ndarray, n_past: jnp.ndarray,
                     true_c: jnp.ndarray) -> Tuple[jnp.ndarray, Params]:
    """One CHUNK of chunked prefill for a single sequence.

    tokens: (1, C) i32 — the chunk, zero-padded past ``true_c``; table:
    (n_pages,) i32 block table (covers the whole sequence, 0-padded);
    n_past: scalar i32 tokens already in the arena (prefix-cache hits plus
    previously prefilled chunks); true_c: scalar i32 real chunk length.
    The chunk's K/V land at absolute positions ``n_past .. n_past+true_c-1``
    (padded tail rows scatter into the junk block); its queries attend
    causally over everything cached so far, which is exactly full-sequence
    causal attention computed incrementally — chunking changes scheduling,
    not math.  Returns (logits (1, C, V), new_arena)."""
    assert supports_slots(cfg), f"paged prefill unsupported for {cfg.family}"
    _, C = tokens.shape
    bs = arena["k"].shape[2]
    n_pages = table.shape[0]
    x = L.embedding_apply(params["embed"], tokens)
    positions = n_past + jnp.arange(C, dtype=jnp.int32)
    (cos_l, sin_l), (cos_g, sin_g) = _rope_tables(cfg, positions)
    windows_np, is_global_np = layer_pattern(cfg)
    has_win = _has_window(cfg)
    valid = positions < n_past + true_c
    write_bid = jnp.where(
        valid, table[jnp.clip(positions // bs, 0, n_pages - 1)], 0)
    write_off = positions % bs

    windows = jnp.asarray(windows_np)
    is_global = jnp.asarray(is_global_np)

    def body(carry, xs):
        x, ak, av = carry
        p, layer, win, isg = xs
        cos = jnp.where(isg, cos_g, cos_l)
        sin = jnp.where(isg, sin_g, sin_l)
        h = L.rmsnorm_apply(p["ln1"], x, cfg.norm_eps)
        a, ak, av = L.attention_prefill_paged_apply(
            p["attn"], h, cfg, cos, sin, ak, av, layer, table, positions,
            write_bid, write_off, window=win if has_win else None)
        x = x + a
        h = L.rmsnorm_apply(p["ln2"], x, cfg.norm_eps)
        if cfg.is_moe:
            y, _ = M.moe_apply(p["moe"], h, cfg)
        else:
            y = L.mlp_apply(p["mlp"], h, cfg)
        return (x + y, ak, av), None

    x, k2, v2 = _scan_paged_layers(body, x, params, arena, windows, is_global)
    x = L.rmsnorm_apply(params["final_norm"], x, cfg.norm_eps)
    logits = lm_logits(params, x, cfg)
    return logits, {"k": k2, "v": v2}


def decode_paged_lm(params: Params, arena: Params, tokens: jnp.ndarray,
                    cfg: ModelConfig, tables: jnp.ndarray,
                    lengths: jnp.ndarray, active: jnp.ndarray
                    ) -> Tuple[jnp.ndarray, Params]:
    """One batched decode step over all rows of a paged batch.

    tokens: (b, 1) i32; tables: (b, n_pages) i32; lengths: (b,) i32 valid
    token counts; active: (b,) bool.  Inactive rows ride along for static
    shapes but scatter into the junk block and their outputs are garbage —
    the host does not advance them (``lengths`` stay host-managed, unlike
    the slotted cache's device-side vector).  Returns (logits (b, V),
    new_arena)."""
    bs = arena["k"].shape[2]
    n_pages = tables.shape[1]
    b = tokens.shape[0]
    x = L.embedding_apply(params["embed"], tokens)
    positions = lengths[:, None]
    (cos_l, sin_l), (cos_g, sin_g) = _rope_tables(cfg, positions)
    windows_np, is_global_np = layer_pattern(cfg)
    has_win = _has_window(cfg)
    page = jnp.clip(lengths // bs, 0, n_pages - 1)
    write_bid = jnp.where(
        active, jnp.take_along_axis(tables, page[:, None], axis=1)[:, 0], 0)
    write_off = jnp.where(active, lengths % bs, 0)

    windows = jnp.asarray(windows_np)
    is_global = jnp.asarray(is_global_np)

    def body(carry, xs):
        x, ak, av = carry
        p, layer, win, isg = xs
        cos = jnp.where(isg, cos_g, cos_l)
        sin = jnp.where(isg, sin_g, sin_l)
        h = L.rmsnorm_apply(p["ln1"], x, cfg.norm_eps)
        a, ak, av = L.attention_decode_paged_apply(
            p["attn"], h, cfg, cos, sin, ak, av, layer, tables, lengths,
            write_bid, write_off, window=win if has_win else None)
        x = x + a
        h = L.rmsnorm_apply(p["ln2"], x, cfg.norm_eps)
        if cfg.is_moe:
            y, _ = M.moe_apply(p["moe"], h, cfg)
        else:
            y = L.mlp_apply(p["mlp"], h, cfg)
        return (x + y, ak, av), None

    x, k2, v2 = _scan_paged_layers(body, x, params, arena, windows, is_global)
    x = L.rmsnorm_apply(params["final_norm"], x, cfg.norm_eps)
    logits = lm_logits(params, x, cfg)
    return logits[:, 0, :], {"k": k2, "v": v2}


def decode_paged_multi_lm(params: Params, arena: Params, tokens: jnp.ndarray,
                          cfg: ModelConfig, tables: jnp.ndarray,
                          lengths: jnp.ndarray, active: jnp.ndarray,
                          n_steps: int
                          ) -> Tuple[jnp.ndarray, Params, jnp.ndarray,
                                     jnp.ndarray]:
    """``n_steps`` fused greedy decode steps over a paged batch — the
    device-resident decode loop.

    Each iteration is exactly one :func:`decode_paged_lm` step followed by
    the greedy feedback the serving engine used to run on the host: the
    argmax token becomes the next input for active rows and their lengths
    advance by one, all inside a single ``lax.fori_loop`` so the host never
    sees intermediate state.  Inactive rows keep their token/length and
    scatter into the junk block.  The caller guarantees every active row's
    block table covers ``lengths + n_steps`` positions and no row finishes
    mid-loop (``remaining >= n_steps``).

    tokens: (b, 1) i32; returns ``(toks (n_steps, b) i32, new_arena,
    next (b, 1) i32, lengths (b,) i32)`` — the greedy tokens of every step
    plus the advanced loop state, bit-identical to ``n_steps`` separate
    ``decode_paged_lm`` calls with host feedback."""
    act_col = active[:, None]
    act_i = active.astype(jnp.int32)

    def body(i, carry):
        arena, nxt, ln, toks = carry
        logits, arena = decode_paged_lm(params, arena, nxt, cfg, tables,
                                        ln, active)
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        nxt = jnp.where(act_col, tok[:, None], nxt)
        ln = ln + act_i
        toks = jax.lax.dynamic_update_index_in_dim(toks, tok, i, 0)
        return (arena, nxt, ln, toks)

    toks0 = jnp.zeros((n_steps, tokens.shape[0]), jnp.int32)
    arena, nxt, lengths, toks = jax.lax.fori_loop(
        0, n_steps, body, (arena, tokens, lengths, toks0))
    return toks, arena, nxt, lengths


# =============================================================================
# VLM helper — merge precomputed patch embeddings into the token stream
# =============================================================================
def merge_patch_embeds(token_embeds: jnp.ndarray, patch_embeds: jnp.ndarray,
                       image_mask: jnp.ndarray) -> jnp.ndarray:
    """Scatter patch embeddings over positions where image_mask is set.

    token_embeds: (b, s, d); patch_embeds: (b, n_patch, d);
    image_mask: (b, s) bool with exactly n_patch True per row (stub frontend:
    the vision tower output arrives precomputed, per the assignment spec).
    """
    b, s, d = token_embeds.shape
    idx = jnp.cumsum(image_mask.astype(jnp.int32), axis=1) - 1
    idx = jnp.clip(idx, 0, patch_embeds.shape[1] - 1)
    gathered = jnp.take_along_axis(patch_embeds, idx[..., None], axis=1)
    return jnp.where(image_mask[..., None], gathered, token_embeds)


def default_mrope_positions(batch: int, seq: int) -> jnp.ndarray:
    """Text-only M-RoPE positions: all three streams equal (qwen2-vl)."""
    pos = jnp.broadcast_to(jnp.arange(seq, dtype=jnp.int32)[None], (batch, seq))
    return jnp.broadcast_to(pos[None], (3, batch, seq))
