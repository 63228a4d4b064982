"""FractalMoE: top-k mixture-of-experts with fractal-sort token dispatch.

Routing T tokens to E experts is a ``ceil(log2 E)``-bit key sort; the
fractal pipeline (kernels/moe_dispatch) yields, in one streaming pass each:

* ``counts`` — per-expert load (the histogram leaf level; doubles as the
  load-balancing-loss statistic, so it is free),
* ``rank``   — each assignment's slot in expert-grouped order (stable),
* dispatch   — a capacity-bounded scatter into the (E, C, D) expert buffer.

This replaces the ``jnp.argsort`` of reference MoE implementations (an
O(T log T) comparison sort moving full-width keys) with the O(T)
bandwidth-minimal fractal pass — the paper's technique on the hot path.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels import ops
from repro.models.layers import dense_init


def moe_init(key, cfg: ModelConfig, dtype):
    m = cfg.moe
    ks = jax.random.split(key, 4)
    E, D, F = m.num_experts, cfg.d_model, m.d_ff
    s_in, s_out = 1.0 / math.sqrt(D), 1.0 / math.sqrt(F)
    return {
        "router": dense_init(ks[0], D, E, jnp.float32),  # fp32 routing
        "wi": (jax.random.normal(ks[1], (E, D, F)) * s_in).astype(dtype),
        "wg": (jax.random.normal(ks[2], (E, D, F)) * s_in).astype(dtype),
        "wd": (jax.random.normal(ks[3], (E, F, D)) * s_out).astype(dtype),
    }


def _dispatch_and_scatter(xf, ids, E: int, C: int, interpret):
    """Local (per-DP-shard) fractal dispatch + capacity scatter.

    xf: (T, D) local tokens repeated over k (gathered by caller);
    ids: (T,) local expert assignments.  Returns (buf (E, C, D), slot,
    keep, counts) — everything needed for the combine gather.
    """
    T = ids.shape[0]
    _, rank, counts = ops.moe_dispatch(ids, E, interpret=interpret)
    start = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                             jnp.cumsum(counts)[:-1]])
    slot = rank - start[ids]  # position within the expert's group
    keep = slot < C
    flat = jnp.where(keep, ids * C + slot, E * C)  # flat row scatter
    buf = jnp.zeros((E * C, xf.shape[-1]), xf.dtype).at[flat].set(
        xf, mode="drop").reshape(E, C, xf.shape[-1])
    return buf, slot, keep, counts


def _moe_ffn_local(xf, router, wi, wg, wd, *, cfg: ModelConfig, k: int,
                   C: int, interpret, fsdp_axes, dp_axes, tp_axis):
    """Whole MoE FFN for one (data, model) mesh cell, inside shard_map.

    xf: (Tl, D) local tokens (replicated over `model`); router: this
    cell's (D/fsdp, E) router slice; wi/wg/wd: expert-weight slices
    (experts or F over `model`, D FSDP over `data`).

    EVERYTHING per-token — routing (softmax + top_k), fractal dispatch,
    expert FFN — runs shard-locally (routing outside the shard_map was
    measured at 45 GiB of top_k all-gathers per step, §Perf qwen3-moe
    iteration 3b); one ``psum`` over `model` combines.  Returns
    (out (Tl, D), counts (E,), probs_sum (E,) for the aux loss).
    """
    m = cfg.moe
    E = m.num_experts
    D = cfg.d_model
    Tl = xf.shape[0]
    Tk = Tl * k

    # routing, shard-local (fp32)
    router = jax.lax.all_gather(router, fsdp_axes, axis=0, tiled=True)
    logits = xf.astype(jnp.float32) @ router
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, k)
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
    ids = top_e.reshape(Tk).astype(jnp.int32)
    w = top_p.reshape(Tk)
    # token replication over top-k stays shard-local (a global-iota gather
    # here would lower to a dense masked all-reduce per layer)
    xrep = xf[jnp.arange(Tk, dtype=jnp.int32) // k]

    # FSDP all-gather of this rank's expert weights over the data axis.
    def gather_d(a, dim):
        return jax.lax.all_gather(a, fsdp_axes, axis=dim, tiled=True)

    wi = gather_d(wi, 1)
    wg = gather_d(wg, 1)
    wd = gather_d(wd, 2)

    # local fractal dispatch (full histogram; counts are the aux statistic)
    _, rank, counts = ops.moe_dispatch(ids, E, interpret=interpret)
    start = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                             jnp.cumsum(counts)[:-1]])
    slot = rank - start[ids]

    if m.shard_axis == "experts":
        tp = jax.lax.psum(1, tp_axis)
        mr = jax.lax.axis_index(tp_axis)
        e_local = E // tp
        mine = (ids >= mr * e_local) & (ids < (mr + 1) * e_local) & (slot < C)
        ids_l = jnp.where(mine, ids - mr * e_local, e_local)
    else:  # grok-style tensor-parallel experts: all experts, F sliced
        e_local = E
        mine = slot < C
        ids_l = jnp.where(mine, ids, e_local)
    slot_l = jnp.where(mine, slot, 0)

    # flat row indices: a 2-D (ids, slot) scatter/gather lowers to a
    # broadcast (Tk, D)-sized index tensor (4 GB/layer at this scale,
    # §Perf qwen3-moe iteration 2b); flat 1-D row indexing does not.
    flat = jnp.where(mine, ids_l * C + slot_l, e_local * C)
    buf = jnp.zeros((e_local * C, D), xrep.dtype).at[flat].set(
        jnp.where(mine[:, None], xrep, 0), mode="drop").reshape(
        e_local, C, D)

    h = jnp.einsum("ecd,edf->ecf", buf, wi)
    g = jnp.einsum("ecd,edf->ecf", buf, wg)
    y = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * h, wd)

    out = jnp.take(y.reshape(e_local * C, D),
                   jnp.where(mine, ids_l * C + slot_l, 0), axis=0)
    out = out * jnp.where(mine, w, 0.0)[:, None].astype(out.dtype)
    out = out.reshape(Tk // k, k, D).sum(axis=1)
    # combine across model ranks (expert slices / partial F contractions)
    out = jax.lax.psum(out, tp_axis)
    counts = jax.lax.psum(counts, dp_axes)  # global expert load
    probs_sum = jax.lax.psum(probs.sum(axis=0), dp_axes)
    return out, counts, probs_sum


def moe_apply(p, cfg: ModelConfig, x, *, interpret: Optional[bool] = None):
    """x: (B, S, D) -> (out (B, S, D), aux_loss scalar).

    Under a mesh (set via act_sharding) the whole expert FFN runs inside
    one ``shard_map``: per-shard fractal dispatch (routing is per-token
    independent — the paper's "no input bucketing"), expert-parallel
    compute, one psum combine.  The global expert load is the psum of
    local histograms — the paper's local→global merge on the mesh.
    """
    from repro.models import act_sharding

    m = cfg.moe
    B, S, D = x.shape
    E, k = m.num_experts, m.top_k
    T = B * S
    xf = x.reshape(T, D)

    mesh = act_sharding.get_mesh()
    axes = act_sharding.get_batch_axes()
    if mesh is not None and axes is not None:
        from jax.sharding import PartitionSpec as P

        n_dp = 1
        for a in axes:
            n_dp *= mesh.shape[a]
        C = max(k, math.ceil(m.capacity_factor * (T // n_dp) * k / E))
        if m.shard_axis == "experts":
            w_spec = {"wi": P("model", "data", None),
                      "wg": P("model", "data", None),
                      "wd": P("model", None, "data")}
        else:
            w_spec = {"wi": P(None, "data", "model"),
                      "wg": P(None, "data", "model"),
                      "wd": P(None, "model", "data")}
        body = functools.partial(
            _moe_ffn_local, cfg=cfg, k=k, C=C, interpret=interpret,
            fsdp_axes="data", dp_axes=tuple(axes), tp_axis="model")
        out, counts, probs_sum = jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(axes), P("data", None),
                      w_spec["wi"], w_spec["wg"], w_spec["wd"]),
            out_specs=(P(axes), P(), P()),
            check_vma=False,  # lowered from ShapeDtypeStructs in the dry-run
        )(xf, p["router"], p["wi"], p["wg"], p["wd"])
        out = out.reshape(B, S, D)
        frac_probs = probs_sum / jnp.maximum(T, 1)
    else:
        logits = (xf.astype(jnp.float32) @ p["router"])  # (T, E)
        probs = jax.nn.softmax(logits, axis=-1)
        top_p, top_e = jax.lax.top_k(probs, k)  # (T, k)
        top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
        ids = top_e.reshape(T * k).astype(jnp.int32)
        w = top_p.reshape(T * k)
        C_total = max(k, math.ceil(m.capacity_factor * T * k / E))
        xrep = xf[jnp.arange(T * k, dtype=jnp.int32) // k]
        buf, slot, keep, counts = _dispatch_and_scatter(
            xrep, ids, E, C_total, interpret)
        h = jnp.einsum("ecd,edf->ecf", buf, p["wi"])
        g = jnp.einsum("ecd,edf->ecf", buf, p["wg"])
        y = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * h, p["wd"])
        ww = jnp.where(keep, w, 0.0)
        out = y[jnp.where(keep, ids, 0), jnp.where(keep, slot, 0)]
        out = out * ww[:, None].astype(out.dtype)
        out = out.reshape(T, k, D).sum(axis=1).reshape(B, S, D)
        frac_probs = probs.mean(axis=0)

    # Switch-style load-balancing loss; `counts` is free from the histogram.
    frac_tokens = counts.astype(jnp.float32) / jnp.maximum(T * k, 1)
    aux = E * jnp.sum(frac_tokens * frac_probs)
    return out.astype(x.dtype), aux
