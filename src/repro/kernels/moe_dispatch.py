"""Fused MoE token-dispatch pipeline built from the fractal kernels.

Routing tokens to experts *is* a ``p = ceil(log2 E)``-bit fractal sort:

* the leaf histogram  = per-expert token load (needed for capacity and the
  load-balancing loss anyway — it is free here),
* the rank pass       = each token's slot in expert-grouped order,
* the inverse perm    = the gather order that groups tokens by expert.

One streaming read of the expert-id array for the histogram, one for the
ranks; both VMEM-resident tables.  Replaces the usual ``jnp.argsort`` (XLA
comparison sort, O(T log T) with full-width key movement) with the O(T)
bandwidth-minimal fractal pipeline.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.fractal_histogram import fractal_histogram
from repro.kernels.fractal_rank import fractal_rank_kernel


@functools.partial(jax.jit, static_argnames=("num_experts", "block", "interpret"))
def moe_dispatch(expert_ids: jnp.ndarray, num_experts: int,
                 block: int = 1024, interpret: Optional[bool] = None):
    """Dispatch metadata for flattened top-k expert assignments.

    Args:
      expert_ids: (T,) int32 in [0, num_experts) — token i's routed expert
        (already flattened over the top-k dimension).
      num_experts: E.

    Returns:
      perm:   (T,) int32 — gather order; ``expert_ids[perm]`` is sorted and
              tokens of expert e occupy slots [start[e], start[e]+counts[e]).
      rank:   (T,) int32 — inverse of perm (token i's slot), for combine.
      counts: (E,) int32 — per-expert load (histogram leaf level).
    """
    T = expert_ids.shape[0]
    ids = expert_ids.astype(jnp.int32)
    counts = fractal_histogram(ids, num_experts, block=block,
                               interpret=interpret)
    bin_start = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(counts)[:-1]])
    rank = fractal_rank_kernel(ids, bin_start, num_experts, block=block,
                               interpret=interpret)
    perm = jnp.zeros((T,), jnp.int32).at[rank].set(
        jnp.arange(T, dtype=jnp.int32))
    return perm, rank, counts
