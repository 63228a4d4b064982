"""Jitted train / prefill / decode step factories shared by the launcher,
the dry-run, and the tests."""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro import optim as O
from repro import sharding as SH
from repro.configs.base import ModelConfig
from repro.models import transformer as T

AUX_WEIGHT = 0.01  # load-balancing loss weight
LOSS_CHUNK = 512   # sequence-chunked cross-entropy (bounds fp32 logits)


def chunked_ce(hidden, head, labels, chunk: int = LOSS_CHUNK):
    """Cross-entropy without materializing (B, S, V) fp32 logits: scan over
    sequence chunks, unembedding and reducing one chunk at a time."""
    B, S, D = hidden.shape
    chunk = min(chunk, S)
    pad = (-S) % chunk
    if pad:
        hidden = jnp.pad(hidden, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)), constant_values=-1)
    nc = hidden.shape[1] // chunk
    hs = hidden.reshape(B, nc, chunk, D).transpose(1, 0, 2, 3)
    ls = labels.reshape(B, nc, chunk).transpose(1, 0, 2)

    def step(acc, xs):
        h, lab = xs
        logits = (h @ head).astype(jnp.float32)  # (B, chunk, V)
        lse = jax.nn.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(
            logits, jnp.maximum(lab, 0)[..., None], axis=-1)[..., 0]
        valid = (lab >= 0).astype(jnp.float32)
        nll = (lse - tgt) * valid
        return (acc[0] + nll.sum(), acc[1] + valid.sum()), None

    (tot, cnt), _ = jax.lax.scan(
        step, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
        (hs, ls))
    return tot / jnp.maximum(cnt, 1.0)


def loss_fn(params, cfg: ModelConfig, batch, interpret=None):
    hidden, aux = T.forward_hidden(params, cfg, batch["tokens"],
                                   frontend_embeds=batch.get("frontend"),
                                   interpret=interpret)
    loss = chunked_ce(hidden, T.unembed(params, cfg), batch["labels"])
    return loss + AUX_WEIGHT * aux, (loss, aux)


def make_train_step(cfg: ModelConfig, oc: O.OptimizerConfig,
                    interpret: Optional[bool] = None):
    """(params, opt_state, batch) -> (params, opt_state, metrics)."""

    def train_step(params, opt_state, batch):
        grad_fn = jax.value_and_grad(loss_fn, has_aux=True)
        (total, (loss, aux)), grads = grad_fn(params, cfg, batch,
                                              interpret=interpret)
        params, opt_state, om = O.adamw_update(params, grads, opt_state, oc)
        metrics = {"loss": loss, "aux_loss": aux, "total_loss": total, **om}
        return params, opt_state, metrics

    return train_step


def make_prefill_step(cfg: ModelConfig, interpret: Optional[bool] = None):
    """Inference prefill: logits for a full prompt batch."""

    def prefill_step(params, batch):
        logits, _ = T.forward(params, cfg, batch["tokens"],
                              frontend_embeds=batch.get("frontend"),
                              interpret=interpret)
        return logits

    return prefill_step


def make_decode_step(cfg: ModelConfig, kv_seq_axis: Optional[str] = None):
    """One-token greedy decode: (params, cache, token, pos) ->
    (next_token, cache)."""

    def decode_step(params, cache, token, pos, cross_kv=None):
        logits, cache = T.decode_step(params, cfg, cache, token, pos,
                                      cross_kv=cross_kv,
                                      kv_seq_axis=kv_seq_axis)
        nxt = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
        return nxt, cache

    return decode_step


def make_compressed_ddp_step(cfg: ModelConfig, oc: O.OptimizerConfig, mesh,
                             axis: str = "data",
                             interpret: Optional[bool] = None):
    """Data-parallel train step whose gradient all-reduce wire is int8
    (error-feedback quantization, `optim.compressed_psum`) — the
    distributed-optimization option for bandwidth-constrained (e.g.
    cross-pod) gradient reduction.

    Params are replicated over ``axis``; each shard computes grads on its
    batch slice inside ``shard_map``, reduces them at int8 width, and the
    optimizer update runs identically on every shard.  Returns
    ``step(params, opt_state, err, batch) -> (params, opt_state, err,
    metrics)`` where ``err`` is the per-shard error-feedback residual
    pytree (init = zeros_like(params) on each shard).
    """
    from jax.sharding import PartitionSpec as P

    def local_step(params, opt_state, err, batch):
        grad_fn = jax.value_and_grad(loss_fn, has_aux=True)
        (total, (loss, aux)), grads = grad_fn(params, cfg, batch,
                                              interpret=interpret)

        # err leaves carry a leading per-shard dim (global (D, *shape))
        def reduce_leaf(g, e):
            mean, e_new = O.compressed_psum(g, axis, e[0])
            return mean, e_new[None]

        flat = jax.tree.map(reduce_leaf, grads, err)
        grads = jax.tree.map(lambda t: t[0], flat,
                             is_leaf=lambda t: isinstance(t, tuple))
        err_new = jax.tree.map(lambda t: t[1], flat,
                               is_leaf=lambda t: isinstance(t, tuple))
        params, opt_state, om = O.adamw_update(params, grads, opt_state, oc)
        loss = jax.lax.pmean(loss, axis)
        metrics = {"loss": loss, "aux_loss": aux, **om}
        return params, opt_state, err_new, metrics

    rep = P()
    return jax.jit(jax.shard_map(
        local_step, mesh=mesh,
        in_specs=(rep, rep, P(axis), P(axis)),
        out_specs=(rep, rep, P(axis), rep),
        check_vma=False,
    ))


def init_error_feedback(params, mesh, axis: str = "data"):
    """Per-shard error-feedback residuals: (D, *param_shape) zeros."""
    D = mesh.shape[axis]
    return jax.tree.map(
        lambda p: jnp.zeros((D,) + p.shape, jnp.float32), params)


# ---------------------------------------------------------------------------
# sharded (pjit) wrappers
# ---------------------------------------------------------------------------


def shard_train_step(train_step, mesh, params, opt_state, batch_example,
                     cfg: ModelConfig):
    """jit with explicit in/out shardings for the production mesh.

    ``params``/``opt_state``/``batch_example`` may be ShapeDtypeStructs
    (dry-run) or real arrays."""
    from jax.sharding import NamedSharding

    from repro.models import act_sharding
    act_sharding.set_batch_axes(SH.batch_axes(mesh), mesh)

    p_spec = SH.param_specs(params, cfg, mesh)
    p_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), p_spec)
    o_sh = {
        "mu": jax.tree.map(lambda s: NamedSharding(mesh, s), p_spec),
        "nu": jax.tree.map(lambda s: NamedSharding(mesh, s), p_spec),
        "step": NamedSharding(mesh, jax.sharding.PartitionSpec()),
    }
    b_sh = jax.tree.map(lambda s: NamedSharding(mesh, s),
                        SH.data_specs(mesh, batch_example))
    rep = NamedSharding(mesh, jax.sharding.PartitionSpec())
    return jax.jit(
        train_step,
        in_shardings=(p_sh, o_sh, b_sh),
        out_shardings=(p_sh, o_sh, rep),
        donate_argnums=(0, 1),
    )
