"""Dense decoder LMs of the Qwen2/Qwen3/GLM-4 kind: the benchmark's own
weights and its plain float32 reference.

The layer equations (HF ``Qwen2ForCausalLM`` / ``Qwen3ForCausalLM`` /
``GlmForCausalLM``):

    h   = x + o_proj(attn(rope(qn(q_proj(rms1(x)))), rope(kn(k_proj(.))), v_proj(.)))
    out = h + down(silu(gate(rms2(h))) * up(rms2(h)))

with RMSNorm ``x * rsqrt(mean(x^2) + eps) * scale``, rotary embeddings on
the first ``partial_rotary_factor`` of each head's dims (all of them for
Qwen, half for GLM-4; the rest pass through), grouped-query attention
(query head ``i`` reads key/value head ``i // (H / K)``), causal softmax at
scale ``d_head^-0.5``, per-head RMSNorm of q and k before the rotation
(``qk_norm``, Qwen3) or biases on the q/k/v projections (``qkv_bias``,
Qwen2, GLM-4), a final RMSNorm, and logits against the tied embedding table
or an untied ``lm_head`` (GLM-4).  Qwen rotates the two halves of the
rotated dims (``rotate_half``); GLM-4 rotates interleaved pairs
``(x[2i], x[2i+1])`` (``rope_interleaved``).

Nothing here imports the program.  The weights are drawn from the seed by
one jitted call, in bf16, and stacked over layers; ``to_program`` re-keys
these same arrays into the program's parameter tree (no copy), and the
reference reads them as they are.  One layout change is not a re-key: the
program rotates halves, so for an interleaved model ``to_program`` reorders
each head's rotated columns of ``wq``, ``wk`` and their biases (pair ``i``
to columns ``i`` and ``i + r/2``).  Queries and keys are reordered alike,
so every attention score, and so every logit, is the same.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def dims(cfg: Dict) -> Dict:
    c, a = cfg["config"], cfg["architecture"]
    H = c["num_attention_heads"]
    dh = c.get("head_dim") or a.get("head_dim") or c["hidden_size"] // H
    V = c["vocab_size"]
    return dict(L=c["num_hidden_layers"], d=c["hidden_size"], H=H,
                K=c["num_key_value_heads"], dh=dh, ff=c["intermediate_size"],
                V=V, Vpad=-(-V // 512) * 512 if V >= 4096 else -(-V // 16) * 16,
                eps=c["rms_norm_eps"], theta=float(c["rope_theta"]),
                qk_norm=bool(a["qk_norm"]), qkv_bias=bool(a["qkv_bias"]),
                partial=float(c.get("partial_rotary_factor", 1.0)),
                interleaved=bool(a.get("rope_interleaved", False)),
                tied=bool(c["tie_word_embeddings"]),
                init_std=float(c["initializer_range"]),
                dtype=jnp.dtype(c["torch_dtype"]))


def check_program(pcfg, m: Dict) -> None:
    """Refuse a program model (``repro.configs``, handed in) that is not the
    configuration file's: every width, the norms and biases, the rotated
    share of each head, the dtype."""
    want = {"n_layers": m["L"], "d_model": m["d"], "n_heads": m["H"],
            "n_kv_heads": m["K"], "d_head": m["dh"], "d_ff": m["ff"],
            "vocab_size": m["V"], "qk_norm": m["qk_norm"],
            "qkv_bias": m["qkv_bias"], "tie_embeddings": m["tied"],
            "partial_rotary": m["partial"],
            "rope_theta": m["theta"], "norm_eps": m["eps"],
            "padded_vocab": m["Vpad"], "family": "dense", "act": "silu"}
    bad = {k: (getattr(pcfg, k), v) for k, v in want.items()
           if getattr(pcfg, k) != v}
    if jnp.dtype(pcfg.dtype) != m["dtype"]:
        bad["dtype"] = (pcfg.dtype, m["dtype"])
    if bad:
        raise RuntimeError(f"program model {pcfg.name} differs from the "
                           f"configuration file: {bad}")


def key_of(seed: int):
    """A PRNG key from any whole number, 2**32 and above included."""
    s = int(seed) % 2**64
    return jax.random.fold_in(jax.random.PRNGKey(s & 0xFFFFFFFF), s >> 32)


def _draw(key, m: Dict) -> Dict:
    """HF's initialisation of these models: every matrix and the embedding
    N(0, ``initializer_range``^2); norm scales 1 and biases 0 there, drawn
    here as 1 + N(0, 0.05^2) and N(0, ``initializer_range``^2) so that each
    reaches the logits."""
    L, d, H, K, dh, ff = m["L"], m["d"], m["H"], m["K"], m["dh"], m["ff"]
    dt, std = m["dtype"], m["init_std"]
    ks = iter(jax.random.split(key, 16))

    def normal(shape):
        return (std * jax.random.normal(next(ks), shape, F32)).astype(dt)

    def scale(shape):
        return (1.0 + 0.05 * jax.random.normal(next(ks), shape, F32)).astype(dt)

    layers = {
        "ln1": scale((L, d)), "ln2": scale((L, d)),
        "wq": normal((L, d, H * dh)), "wk": normal((L, d, K * dh)),
        "wv": normal((L, d, K * dh)), "wo": normal((L, H * dh, d)),
        "w_gate": normal((L, d, ff)), "w_up": normal((L, d, ff)),
        "w_down": normal((L, ff, d)),
    }
    if m["qkv_bias"]:
        layers["bq"] = normal((L, H * dh))
        layers["bk"] = normal((L, K * dh))
        layers["bv"] = normal((L, K * dh))
    if m["qk_norm"]:
        layers["q_norm"] = scale((L, dh))
        layers["k_norm"] = scale((L, dh))
    table = normal((m["Vpad"], d))
    table = jnp.where(jnp.arange(m["Vpad"])[:, None] < m["V"], table, 0)
    w = {"embed": table.astype(dt), "final_norm": scale((d,)), "layers": layers}
    if not m["tied"]:
        w["lm_head"] = normal((d, m["Vpad"]))
    return w


def make_weights(cfg: Dict, seed: int, shardings=None) -> Dict:
    """All weights from the seed, on the device, in one jitted call.
    ``shardings``: the program's parameter shardings (a tree shaped as
    ``to_program``'s); each weight is then drawn laid out as the program's
    parameter that holds it, so no device holds more than its share."""
    m = dims(cfg)
    if shardings is None:
        return jax.jit(lambda k: _draw(k, m))(key_of(seed))
    return jax.jit(lambda k: _draw(k, m),
                   out_shardings=_on_reference(shardings, m))(key_of(seed))


def _on_reference(shardings, m: Dict):
    """The program's shardings laid on the reference's tree: each weight
    takes the sharding of the parameter that ``_rekey`` puts it in."""
    shape = jax.eval_shape(lambda: _draw(jax.random.PRNGKey(0), m))
    names = jax.tree_util.tree_map_with_path(
        lambda path, _: jax.tree_util.keystr(path), shape)
    keyed = _rekey(names, m)
    if jax.tree.structure(keyed) != jax.tree.structure(shardings):
        raise RuntimeError("shardings do not fit the program's parameters")
    placed = dict(zip(jax.tree.leaves(keyed), jax.tree.leaves(shardings)))
    return jax.tree.map(placed.get, names)


def _rotary_order(m: Dict) -> np.ndarray:
    """Program column ``j`` of a head takes reference column ``order[j]``:
    the interleaved pairs' first members, then their second, then the dims
    that do not rotate."""
    dh, r = m["dh"], int(m["dh"] * m["partial"])
    return np.concatenate([np.arange(0, r, 2), np.arange(1, r, 2),
                           np.arange(r, dh)])


@jax.jit
def _halves(arrs: Dict, order) -> Dict:
    """Each head's columns of every array in ``arrs`` taken in ``order``."""
    dh = order.shape[0]
    return {k: jnp.take(a.reshape(*a.shape[:-1], -1, dh), order,
                        axis=-1).reshape(a.shape) for k, a in arrs.items()}


def to_program(w: Dict, cfg: Dict) -> Dict:
    """The same arrays under the program's parameter names, with an
    interleaved model's rotated columns in the program's order."""
    m = dims(cfg)
    p = _rekey(w, m)
    if m["interleaved"]:
        attn = p["layers"]["attn"]
        arrs = {(k, part): attn[k][part] for k in ("wq", "wk")
                for part in attn[k]}
        for (k, part), a in _halves(arrs, _rotary_order(m)).items():
            attn[k] = dict(attn[k], **{part: a})
    return p


def _rekey(w: Dict, m: Dict) -> Dict:
    """``w``'s leaves, whatever they are, under the program's names."""
    lw = w["layers"]

    def dense(name, bias):
        p = {"w": lw[name]}
        if m["qkv_bias"] and bias:
            p["b"] = lw[bias]
        return p

    attn = {"wq": dense("wq", "bq"), "wk": dense("wk", "bk"),
            "wv": dense("wv", "bv"), "wo": {"w": lw["wo"]}}
    if m["qk_norm"]:
        attn["q_norm"] = {"scale": lw["q_norm"]}
        attn["k_norm"] = {"scale": lw["k_norm"]}
    p = {"embed": {"table": w["embed"]},
         "final_norm": {"scale": w["final_norm"]},
         "layers": {"ln1": {"scale": lw["ln1"]}, "ln2": {"scale": lw["ln2"]},
                    "attn": attn,
                    "mlp": {"wi_gate": {"w": lw["w_gate"]},
                            "wi_up": {"w": lw["w_up"]},
                            "wo": {"w": lw["w_down"]}}}}
    if not m["tied"]:
        p["lm_head"] = {"w": w["lm_head"]}
    return p


# --- the plain float32 reference -------------------------------------------------
def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, pos, theta, partial=1.0, interleaved=False):
    """x (S, heads, dh).  The first ``partial`` of each head's dims rotate,
    in pairs ``(x[i], x[i + r/2])`` (``rotate_half``) or, ``interleaved``,
    ``(x[2i], x[2i+1])``; pair ``i`` turns at ``theta^(-2i/r)``.  The other
    dims pass through."""
    r = int(x.shape[-1] * partial)
    half = r // 2
    freq = 1.0 / theta ** (jnp.arange(half, dtype=F32) / half)
    ang = pos.astype(F32)[:, None] * freq
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    xr = x[..., :r]
    if interleaved:
        x1, x2 = xr[..., 0::2], xr[..., 1::2]
        out = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                        -1).reshape(xr.shape)
    else:
        x1, x2 = xr[..., :half], xr[..., half:]
        out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return out if r == x.shape[-1] else jnp.concatenate([out, x[..., r:]], -1)


def _identity(x):
    return x


def hidden(w: Dict, tokens, m: Dict, q=_identity):
    """Final hidden states (S, d) of one sequence, in float32.  ``q`` rounds
    each matmul operand (the identity for the reference; the lower-precision
    control passes its quantiser)."""
    S = tokens.shape[0]
    pos = jnp.arange(S)
    H, K, dh = m["H"], m["K"], m["dh"]
    causal = pos[None, :] <= pos[:, None]

    def mm(a, b):
        return q(a) @ q(b.T).T

    def layer(x, lw):
        lw = jax.tree.map(lambda a: a.astype(F32), lw)
        h = _rms(x, lw["ln1"], m["eps"])
        qh, kh, vh = mm(h, lw["wq"]), mm(h, lw["wk"]), mm(h, lw["wv"])
        if m["qkv_bias"]:
            qh, kh, vh = qh + lw["bq"], kh + lw["bk"], vh + lw["bv"]
        qh, kh, vh = (qh.reshape(S, H, dh), kh.reshape(S, K, dh),
                      vh.reshape(S, K, dh))
        if m["qk_norm"]:
            qh = _rms(qh, lw["q_norm"], m["eps"])
            kh = _rms(kh, lw["k_norm"], m["eps"])
        rot = dict(partial=m["partial"], interleaved=m["interleaved"])
        qh = _rope(qh, pos, m["theta"], **rot)
        kh = _rope(kh, pos, m["theta"], **rot)
        kh = jnp.repeat(kh, H // K, axis=1)
        vh = jnp.repeat(vh, H // K, axis=1)
        s = jnp.einsum("qhd,khd->hqk", q(qh), q(kh)) * dh ** -0.5
        s = jnp.where(causal[None], s, -jnp.inf)
        o = jnp.einsum("hqk,khd->qhd", q(jax.nn.softmax(s, -1)), q(vh))
        x = x + mm(o.reshape(S, H * dh), lw["wo"])
        h = _rms(x, lw["ln2"], m["eps"])
        g = jax.nn.silu(mm(h, lw["w_gate"])) * mm(h, lw["w_up"])
        return x + mm(g, lw["w_down"]), None

    x = w["embed"][tokens].astype(F32)
    x, _ = jax.lax.scan(layer, x, w["layers"])
    return _rms(x, w["final_norm"].astype(F32), m["eps"])


def head(w: Dict, m: Dict):
    """(d, V) readout over the real vocabulary."""
    if m["tied"]:
        return w["embed"][:m["V"]].T
    return w["lm_head"][:, :m["V"]]


def _readout(x, hd, rows, fn):
    """Apply ``fn(logits_block, row_index)`` to ``rows`` positions at a
    time, so the (S, V) logits never exist whole."""
    S = x.shape[0]
    idx = jnp.arange(S).reshape(S // rows, rows)
    return jax.lax.map(lambda a: fn(a[0] @ hd, a[1]),
                       (x.reshape(S // rows, rows, -1), idx)).reshape(S)


def served_gaps(w: Dict, tokens, targets, m: Dict, rows: int = 256):
    """Per position, how far the reference's logit of ``targets[i]`` lies
    below its best logit at position ``i`` (0 where they agree)."""
    hd = head(w, m).astype(F32)
    x = hidden(w, tokens, m)

    def gap(lg, i):
        t = targets[i]
        return jnp.max(lg, -1) - jnp.take_along_axis(lg, t[:, None], -1)[:, 0]
    return _readout(x, hd, rows, gap)


def control_gaps(w: Dict, tokens, m: Dict, q=None, rows: int = 256):
    """The lower-precision control: at each position, the token that the
    forward computed through ``q`` (fp8 by default) puts first, and how far
    the float32 reference's logit of it lies below the reference's best."""
    q = fp8 if q is None else q
    hd = head(w, m).astype(F32)
    xr = hidden(w, tokens, m)
    xc = hidden(w, tokens, m, q)
    qhd = q(hd.T).T
    S = xr.shape[0]

    def one(a):
        r, c = a
        ref = r @ hd
        pick = jnp.argmax(q(c) @ qhd, -1)
        return jnp.max(ref, -1) - jnp.take_along_axis(ref, pick[:, None], -1)[:, 0]
    return jax.lax.map(one, (xr.reshape(S // rows, rows, -1),
                             xc.reshape(S // rows, rows, -1))).reshape(S)


def fp8(x):
    """Round to float8 e4m3, with one scale for each vector along the last
    axis (per token for activations, per output channel for weights, which
    reach here transposed), as fp8 inference stores them."""
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    s = jnp.where(amax > 0, amax, 1.0) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s


def pad_to(tokens: np.ndarray, length: int) -> np.ndarray:
    out = np.zeros((length,), np.int32)
    out[:len(tokens)] = tokens
    return out
