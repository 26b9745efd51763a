"""Train-step builders: GSPMD (jit + shardings) and explicit-DP (shard_map
with compressed gradient collectives).

The GSPMD path is what the multi-pod dry-run lowers; the shard_map DDP path
exists to exercise gradient compression / straggler-tolerant semantics
explicitly and is covered by tests on host devices.
"""
from __future__ import annotations

from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import registry as R
from repro.models.config import ModelConfig
from repro.train import optimizer as O


# =============================================================================
# loss
# =============================================================================
def chunked_xent(x: jnp.ndarray, w: jnp.ndarray, labels: jnp.ndarray,
                 vocab: int, chunk: int = 512) -> jnp.ndarray:
    """Mean next-token NLL, computed seq-chunk-by-chunk with per-chunk remat.

    Never materializes the full (b, s, V) logits in f32: one (b, chunk, V)
    slab is live at a time (forward *and* backward).  The label term is a
    one-hot contraction over V — vocab-sharding safe (partial sums +
    all-reduce) instead of a gather that would all-gather the logits.
    """
    b, s, d = x.shape
    nc = max(s // chunk, 1)
    chunk = s // nc
    assert s % chunk == 0, (s, chunk)
    xc = x.reshape(b, nc, chunk, d).transpose(1, 0, 2, 3)
    lc = labels.reshape(b, nc, chunk).transpose(1, 0, 2)

    def body(acc, inp):
        xi, li = inp
        logits = (xi @ w).astype(jnp.float32)                    # (b, c, V)
        lse = jax.nn.logsumexp(logits, axis=-1)
        onehot = jax.nn.one_hot(li, vocab, dtype=logits.dtype)
        label_logit = jnp.einsum("bcv,bcv->bc", logits, onehot)
        return acc + jnp.sum(lse - label_logit), None

    body = jax.checkpoint(body)
    total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (xc, lc))
    return total / (b * s)


def lm_loss(params, batch, cfg: ModelConfig) -> Tuple[jnp.ndarray, dict]:
    """Next-token cross-entropy over the final hidden states (LM head applied
    inside the chunked loss — see chunked_xent)."""
    hidden, aux = R.forward(params, batch, cfg, train=True, return_hidden=True)
    w = R.head_weights(params, cfg)
    nll = chunked_xent(hidden, w, batch["labels"], cfg.padded_vocab)
    loss = nll + aux
    metrics = {"loss": loss, "aux_loss": aux, "ppl_proxy": nll}
    return loss, metrics


# =============================================================================
# GSPMD train step
# =============================================================================
def make_train_state(params, opt_cfg: O.AdamWConfig) -> dict:
    return {"params": params, "opt": O.init_opt_state(params)}


def make_train_step(cfg: ModelConfig, opt_cfg: O.AdamWConfig) -> Callable:
    def train_step(state, batch):
        (loss, metrics), grads = jax.value_and_grad(
            lambda p: lm_loss(p, batch, cfg), has_aux=True)(state["params"])
        new_params, new_opt, opt_metrics = O.adamw_update(
            opt_cfg, grads, state["opt"], state["params"])
        metrics = {**metrics, **opt_metrics}
        return {"params": new_params, "opt": new_opt}, metrics
    return train_step


def make_grad_accum_train_step(cfg: ModelConfig, opt_cfg: O.AdamWConfig,
                               accum: int, batch_axes=("data",)) -> Callable:
    """Microbatched gradient accumulation: the (global_batch, ...) batch is
    reshaped to (accum, global_batch/accum, ...) and scanned, dividing live
    activation memory by ``accum``.  Each microbatch keeps the batch dim
    sharded over the data axes (sharding constraint after the reshape).
    Gradients accumulate in f32; the optimizer runs once."""
    from jax.sharding import PartitionSpec as P

    def split(x, batch_dim=0):
        b = x.shape[batch_dim]
        assert b % accum == 0, (b, accum)
        shp = list(x.shape)
        shp[batch_dim:batch_dim + 1] = [accum, b // accum]
        y = x.reshape(shp)
        y = jnp.moveaxis(y, batch_dim, 0)
        if not batch_axes:
            return y
        spec = [None] * y.ndim
        spec[1 + batch_dim] = batch_axes
        return jax.lax.with_sharding_constraint(y, P(*spec))

    def train_step(state, batch):
        mbs = {k: split(v, 1 if k == "mrope_positions" else 0)
               for k, v in batch.items()}

        def micro(carry, mb):
            g_acc, l_acc = carry
            (loss, _), grads = jax.value_and_grad(
                lambda p: lm_loss(p, mb, cfg), has_aux=True)(state["params"])
            g_acc = jax.tree.map(lambda a, g: a + g.astype(jnp.float32) / accum,
                                 g_acc, grads)
            return (g_acc, l_acc + loss / accum), None

        g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), state["params"])
        (grads, loss), _ = jax.lax.scan(micro, (g0, jnp.zeros((), jnp.float32)), mbs)
        new_params, new_opt, opt_metrics = O.adamw_update(
            opt_cfg, grads, state["opt"], state["params"])
        metrics = {"loss": loss, "aux_loss": jnp.zeros((), jnp.float32),
                   "ppl_proxy": loss, **opt_metrics}
        return {"params": new_params, "opt": new_opt}, metrics
    return train_step


# =============================================================================
# explicit-DP (shard_map) with compressed gradient all-reduce
# =============================================================================
def make_ddp_train_step(cfg: ModelConfig, opt_cfg: O.AdamWConfig, mesh,
                        compressor: Optional[str] = None) -> Callable:
    """Pure data-parallel step over mesh axis 'data' with an explicit,
    optionally compressed, gradient all-reduce (see train.compression)."""
    from jax.sharding import PartitionSpec as P
    from repro.train import compression as C

    axis = "data"

    def step(state, batch):
        (loss, metrics), grads = jax.value_and_grad(
            lambda p: lm_loss(p, batch, cfg), has_aux=True)(state["params"])
        grads = C.all_reduce_mean(grads, axis, method=compressor)
        loss = jax.lax.pmean(loss, axis)
        new_params, new_opt, opt_metrics = O.adamw_update(
            opt_cfg, grads, state["opt"], state["params"])
        return {"params": new_params, "opt": new_opt}, {"loss": loss, **opt_metrics}

    def wrapped(state, batch):
        pspec = jax.tree.map(lambda _: P(), state)
        bspec = jax.tree.map(lambda _: P(axis), batch)
        f = jax.shard_map(step, mesh=mesh,
                          in_specs=(pspec, bspec),
                          out_specs=(pspec, jax.tree.map(lambda _: P(), {"loss": 0, "grad_norm": 0, "lr": 0})),
                          check_vma=False)
        return jax.jit(f)(state, batch)   # shard_map bodies with named remat
                                          # (checkpoint_name) require jit

    return wrapped
