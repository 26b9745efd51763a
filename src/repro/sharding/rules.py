"""GSPMD sharding rules: parameter-path → PartitionSpec.

Logical layout on the production mesh (pod, data, model):
  * parameters replicated over (pod, data); tensor-parallel / expert-parallel
    over ``model`` (Megatron-style column→row pairs; MoE experts over model).
  * batch over (pod, data); long-context KV optionally sequence-sharded (SP).

Non-divisible cases (14 heads / 16-way model, vocab 256206) rely on GSPMD
padding — correct, with the padding overhead surfaced by the dry-run's
memory analysis and discussed in EXPERIMENTS.md.
"""
from __future__ import annotations

import re
from typing import Optional, Sequence, Tuple

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.models.config import ModelConfig

MODEL_AXIS = "model"

# Ordered (regex on 'a/b/c' param path, spec for the *unstacked* leaf).
# First match wins.  ``M`` marks the model axis position.
#
# Head-alignment guards (resolved against cfg × mesh in spec_for_param):
#   * attention q projections shard over model ONLY if n_heads    % model == 0
#   * attention k/v projections            ONLY if n_kv_heads % model == 0
#   (otherwise GSPMD slices *inside* d_head and partial-dh dot products get
#    all-reduced at activation size — observed 1.5 GiB per layer on glm4-like
#    configs.  Replicated KV projections = the standard GQA TP fallback.)
#   * MoE experts shard over model if n_experts % model == 0 (EP), else the
#     expert-FF dim shards (TP-within-expert; qwen2-moe's 60 experts on a
#     16-way axis).
_RULES: Sequence[Tuple[str, Tuple[Optional[str], ...]]] = (
    (r"embed/table$",            ("M", None)),          # vocab-sharded
    (r"lm_head/w$",              (None, "M")),
    (r"wq/w$",                   (None, "Q")),
    (r"wq/b$",                   ("Q",)),
    (r"(wk|wv)/w$",              (None, "K")),
    (r"(wk|wv)/b$",              ("K",)),
    (r"attn/wo/w$",              ("Q", None)),
    (r"xattn/wo/w$",             ("Q", None)),
    (r"(q_norm|k_norm)/scale$",  (None,)),
    (r"(wi_gate|wi_up)/w$",      (None, "M")),
    (r"mlp/wo/w$",               ("M", None)),
    (r"shared/wo/w$",            ("M", None)),          # MoE shared-expert down
    (r"moe/router$",             (None, None)),
    (r"(w_gate|w_up)$",          ("E", None, "F")),     # expert- or FF-parallel
    (r"w_down$",                 ("E", "F", None)),
    # --- mamba2 ---------------------------------------------------------
    (r"(wz|wx|wdt)/w$",          (None, "M")),
    (r"(wB|wC)/w$",              (None, None)),
    (r"conv_x$",                 (None, "M")),
    (r"(conv_B|conv_C)$",        (None, None)),
    (r"conv_bx$",                ("M",)),
    (r"(conv_bB|conv_bC)$",      (None,)),
    (r"(A_log|D|dt_bias)$",      ("M",)),
    (r"out_proj/w$",             ("M", None)),
    # --- norms / default ---------------------------------------------------
    (r"scale$",                  (None,)),
    (r"bias$",                   (None,)),
    (r"b$",                      (None,)),
)

_STACKED_PREFIXES = ("layers/", "enc_layers/", "dec_layers/")


def _path_str(path) -> str:
    parts = []
    for k in path:
        if hasattr(k, "key"):
            parts.append(str(k.key))
        else:
            parts.append(str(k))
    return "/".join(parts)


def spec_for_param(path_str: str, ndim: int, cfg: ModelConfig,
                   n_model: int) -> P:
    stacked = path_str.startswith(_STACKED_PREFIXES)
    eff_ndim = ndim - 1 if stacked else ndim
    q_ok = cfg.n_heads > 0 and cfg.n_heads % n_model == 0
    k_ok = cfg.n_kv_heads > 0 and cfg.n_kv_heads % n_model == 0
    e_ok = cfg.n_experts > 0 and cfg.padded_experts % n_model == 0

    def resolve(a):
        if a == "M":
            return MODEL_AXIS
        if a == "Q":
            return MODEL_AXIS if q_ok else None
        if a == "K":
            return MODEL_AXIS if k_ok else None
        if a == "E":
            return MODEL_AXIS if e_ok else None
        if a == "F":
            return None if e_ok else MODEL_AXIS
        return None

    for pat, spec in _RULES:
        if re.search(pat, path_str):
            axes = tuple(resolve(a) for a in spec)
            if len(axes) != eff_ndim:
                # rank-mismatched rule (e.g. scalar norm) → replicate
                axes = (None,) * eff_ndim
            if stacked:
                axes = (None,) + axes
            return P(*axes)
    return P(*([None] * ndim))


def param_specs(params_shape_tree, cfg: ModelConfig, mesh) -> dict:
    """PartitionSpec pytree matching an (eval_shape'd) params tree."""
    n_model = mesh.shape[MODEL_AXIS]
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: spec_for_param(_path_str(path), len(leaf.shape),
                                          cfg, n_model),
        params_shape_tree)


def param_shardings(params_shape_tree, cfg: ModelConfig, mesh) -> dict:
    return jax.tree.map(lambda s: NamedSharding(mesh, s),
                        param_specs(params_shape_tree, cfg, mesh))


def opt_state_specs(params_shape_tree, cfg: ModelConfig, mesh) -> dict:
    """ZeRO-1: AdamW m/v shards over the data axes *in addition to* the model
    axis.  Per leaf, greedily (a) extend the model-sharded dim across
    (pod, data) when divisible, else (b) shard the largest replicated dim over
    the data axes.  GSPMD inserts the reduce-scatter / all-gather pair this
    implies around the optimizer update — the ZeRO-1 communication pattern."""
    p_specs = param_specs(params_shape_tree, cfg, mesh)
    dp = data_axes(mesh)
    dp_size = axes_size(mesh, dp)
    n_model = mesh.shape[MODEL_AXIS]

    def extend(spec: P, leaf) -> P:
        if dp_size == 1 or not leaf.shape:
            return spec
        dims = list(spec) + [None] * (len(leaf.shape) - len(spec))
        for i, ax in enumerate(dims):          # (a) widen the model dim
            if ax == MODEL_AXIS and leaf.shape[i] % (n_model * dp_size) == 0:
                dims[i] = (MODEL_AXIS,) + dp
                return P(*dims)
        order = sorted(range(len(dims)), key=lambda i: -leaf.shape[i])
        for i in order:                        # (b) shard a replicated dim
            if dims[i] is None and leaf.shape[i] % dp_size == 0 and leaf.shape[i] >= dp_size:
                dims[i] = dp
                return P(*dims)
        return spec

    return jax.tree.map(extend, p_specs, params_shape_tree)


# =============================================================================
# activation / batch / cache specs
# =============================================================================
def data_axes(mesh) -> Tuple[str, ...]:
    """Batch-parallel mesh axes: ('pod', 'data') when pod axis exists."""
    names = mesh.axis_names
    return tuple(a for a in ("pod", "data") if a in names)


def axes_size(mesh, axes) -> int:
    n = 1
    for a in (axes if isinstance(axes, (tuple, list)) else [axes]):
        n *= mesh.shape[a]
    return n


def fit_axes(mesh, axes, size: int):
    """Largest prefix of ``axes`` whose product divides ``size`` (batch=1
    long-context cells keep the batch dim unsharded)."""
    chosen = []
    for a in (axes if isinstance(axes, (tuple, list)) else [axes]):
        if size % (axes_size(mesh, chosen + [a])) == 0:
            chosen.append(a)
    if not chosen:
        return None
    return tuple(chosen)


def batch_specs(batch_shape_tree, mesh) -> dict:
    """Shard the leading batch dim over (pod, data); mrope positions have the
    batch dim second.  Falls back to fewer/no axes when not divisible."""
    dp = data_axes(mesh)

    def spec(path, leaf):
        name = _path_str(path)
        nd = len(leaf.shape)
        if "mrope" in name:                       # (3, b, s)
            ax = fit_axes(mesh, dp, leaf.shape[1])
            return P(None, ax, *([None] * (nd - 2)))
        ax = fit_axes(mesh, dp, leaf.shape[0])
        return P(ax, *([None] * (nd - 1)))

    return jax.tree_util.tree_map_with_path(spec, batch_shape_tree)


def cache_specs(cache_shape_tree, mesh, cfg: ModelConfig, *,
                seq_shard: bool = False) -> dict:
    """Decode-state sharding.

    Default: batch → (pod, data); KV heads → model (GSPMD pads non-divisible
    head counts).  ``seq_shard=True`` (long-context, batch too small to
    data-shard): KV sequence dim → (data, model) jointly — the SP layout.
    SSM states: batch → (pod, data); head/channel dims → model.
    """
    dp = data_axes(mesh)

    def spec(path, leaf):
        name = _path_str(path)
        nd = len(leaf.shape)
        if name in ("pos", "prefix_len"):
            return P()
        if name in ("sk", "sv"):
            # append-buffer suffix: small, replicated over model (local DUS)
            ax = fit_axes(mesh, dp, leaf.shape[1])
            return P(None, ax, None, None, None)
        if "ssm" in name:
            # stacked (L, b, ...) buffers: conv_* (L,b,k-1,C) / state (L,b,H,P,N)
            ax = fit_axes(mesh, dp, leaf.shape[1])
            if name.endswith("state"):
                return P(None, ax, MODEL_AXIS, None, None)
            if name.endswith("conv_x"):
                return P(None, ax, None, MODEL_AXIS)
            return P(None, ax, None, None)
        # KV caches, (L, b, S, K, dh) (self or cross)
        if nd == 5:
            if seq_shard:
                sp = fit_axes(mesh, ("data", MODEL_AXIS), leaf.shape[2])
                return P(None, None, sp, None, None)
            ax = fit_axes(mesh, dp, leaf.shape[1])
            if leaf.shape[3] % mesh.shape[MODEL_AXIS] == 0:
                return P(None, ax, None, MODEL_AXIS, None)   # KV heads → model
            # few-KV-head archs (glm4 kv=2, qwen3-moe kv=4): sequence → model
            sp = fit_axes(mesh, (MODEL_AXIS,), leaf.shape[2])
            return P(None, ax, sp, None, None)
        return P(*([None] * nd))

    return jax.tree_util.tree_map_with_path(spec, cache_shape_tree)


def arena_spec(mesh, cfg: ModelConfig) -> P:
    """PartitionSpec for the paged KV arena ``(L, n_blocks, bs, K·dh)``.

    Each token row's KV heads shard over ``model`` — the row dim splits
    only when ``n_kv_heads`` divides the axis, so every shard holds whole
    heads; the block map dims (n_blocks, bs) stay unsharded because block
    tables live host-side and index whole pages.

    Unlike :func:`cache_specs`, non-divisible head counts are an explicit
    ERROR here rather than a silent fallback: a paged arena has no
    contiguous sequence dim to sequence-shard (blocks *are* the map), and
    letting GSPMD pad inside the trailing head dims would resolve
    ``d_head % model != 0`` by slicing partial-dh dot products that get
    all-reduced at activation size on every donated decode step — the
    glm4-like (n_kv_heads=2) failure mode the dense rules warn about.
    """
    n_model = mesh.shape[MODEL_AXIS]
    if n_model == 1:
        return P(None, None, None, None)
    if cfg.n_kv_heads % n_model == 0:
        return P(None, None, None, MODEL_AXIS)
    raise ValueError(
        f"paged KV arena cannot shard on the head dim: n_kv_heads="
        f"{cfg.n_kv_heads} is not divisible by the mesh's model axis "
        f"({n_model}), and padding would slice inside d_head "
        f"({cfg.d_head} % {n_model} = {cfg.d_head % n_model}) — GSPMD would "
        f"silently all-reduce partial-head products every decode step. "
        f"Build the mesh with launch.mesh.make_mesh_for(n, model_parallel=m) "
        f"for an m dividing n_kv_heads.")


def arena_shardings(mesh, cfg: ModelConfig) -> NamedSharding:
    """NamedSharding for every leaf of a paged arena ``{"k","v"}`` tree."""
    return NamedSharding(mesh, arena_spec(mesh, cfg))


def logits_spec(mesh) -> P:
    return P(data_axes(mesh), None, MODEL_AXIS)
