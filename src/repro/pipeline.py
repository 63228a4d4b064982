"""Pipeline parallelism (GPipe-style) over a mesh axis.

Complements the DP/FSDP/TP/EP/SP axes: stage s holds layers
[s*L/S, (s+1)*L/S); microbatches stream through with activations handed
stage-to-stage by ``collective_permute``.  The bubble fraction is the usual
(S-1)/(S-1+M); the multi-pod deployment story is stages across the `pod`
axis (inter-pod links carry only microbatch activations, once per stage
boundary, instead of every gradient).

This is the substrate + correctness contract (== sequential execution, see
tests/test_pipeline.py); wiring it into the main train loop is a config
choice on real hardware where stage placement follows the physical
topology.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P



def gpipe_apply(stage_fn: Callable, mesh, axis: str, stage_params, x_micro):
    """Run ``stage_fn(params_s, x) -> y`` as an S-stage pipeline.

    stage_params: pytree stacked on a leading stage dim (sharded over
    ``axis``); x_micro: (M, mb, ...) microbatched input (replicated).
    Returns (M, mb, ...) outputs, numerically identical to applying the S
    stages sequentially to each microbatch.
    """
    S = mesh.shape[axis]
    M = x_micro.shape[0]
    T = M + S - 1  # schedule length (fill + steady state)

    def body(params_local, xs):
        params_local = jax.tree.map(lambda a: a[0], params_local)  # unstack
        sid = jax.lax.axis_index(axis)

        def step(carry, t):
            buf_in, outs = carry
            mb = t - sid  # microbatch index at this stage, this tick
            valid = (mb >= 0) & (mb < M)
            x_in = jnp.where(sid == 0,
                             xs[jnp.clip(mb, 0, M - 1)], buf_in)
            y = stage_fn(params_local, x_in)
            y = jnp.where(valid, y, buf_in * 0)
            # hand activations to the next stage
            buf_next = jax.lax.ppermute(
                y, axis, [(i, i + 1) for i in range(S - 1)])
            # last stage commits its finished microbatch
            take = valid & (sid == S - 1)
            idx = jnp.clip(mb, 0, M - 1)
            outs = outs.at[idx].set(
                jnp.where(take, y, outs[idx]))
            return (buf_next, outs), None

        buf0 = jnp.zeros_like(xs[0])
        outs0 = jnp.zeros_like(xs)
        (_, outs), _ = jax.lax.scan(step, (buf0, outs0),
                                    jnp.arange(T))
        # only the last stage holds real outputs; broadcast them
        outs = jnp.where(sid == S - 1, outs, 0)
        return jax.lax.psum(outs, axis)

    in_specs = (jax.tree.map(lambda _: P(axis), stage_params), P())
    return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                         out_specs=P(), check_vma=False)(stage_params,
                                                         x_micro)
