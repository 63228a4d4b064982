"""Pod-scale FractalSort via shard_map — the paper's local→global histogram
merge (§III.A/B) mapped onto JAX collectives.

The paper's two-phase update — per-thread local compressed tree, then an
O(log n) merge into the global LLC-resident tree — becomes, on a mesh axis
of D devices:

1. every device builds the local histogram of its key shard (one bincount;
   no atomics — the reduction is associative);
2. one ``psum`` over the axis merges the histograms (the reduction tree of
   the ICI ring *is* the paper's merge tree; a tapered uint16 wire dtype cuts
   the AllReduce payload — counter-width compression applied to the
   collective);
3. global bin starts come from one exclusive scan of the merged counts; each
   device's *arrival offset* inside every bin comes from an exclusive scan
   over devices (``all_gather`` of local counts + masked sum — devices are
   ordered, so the sort is stable across the pod);
4. every key knows its exact global output slot with **no sampling, no
   splitter exchange, no repartition round-trip** — the paper's
   distribution-independence claim at cluster scale.  Keys move exactly once
   per pass, via ``all_to_all`` into equal output shards.

A pass ranks on a full ``<=16``-bit field so placement is *exact* (same-key
ties break by (device, arrival) — stable).  ``p <= 16`` needs one pass;
``p <= 32`` runs two stable LSD passes (low half, then high half), matching
the single-host "compressed entries" scheme.

The all_to_all uses fixed-capacity destination buckets; under heavy
duplicate skew one device's equal keys occupy *consecutive* global slots and
can all target one destination, so worst-case capacity is the full local
shard (``capacity_factor = axis size``).  An overflow flag is returned so
callers can rerun with a higher factor — same contract as the tapered
counters' saturation flag (paper §IV.A skew caveat).

Pass sequencing lives in :class:`~repro.core.executor.PlanExecutor`; this
module provides the per-pass collective primitive (:func:`_distributed_pass`)
that :class:`~repro.core.executor.DistributedBackend` wraps.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.executor import DistributedBackend, PlanExecutor
from repro.core.fractal_sort import fractal_rank, rank_engine
from repro.core.sort_plan import (default_engine, make_sort_plan,
                                   scatter_tile_len)

__all__ = [
    "distributed_fractal_sort",
    "distributed_fractal_argsort",
    "make_distributed_argsort",
    "make_distributed_sort",
    "make_distributed_sort_pairs",
    "make_fragment_placer",
]

#: Distributed plans default to the paper's wide two-field ICI scheme:
#: every extra pass costs one more all_to_all round, and the local rank of
#: a 2**16-bin field routes through the scatter engine, so 16-bit digits
#: (<= 2 passes for p <= 32) win on the wire.
DISTRIBUTED_MAX_BINS_LOG2 = 16


def _distributed_pass(u: jnp.ndarray, shift: int, bits: int, axis: str,
                      capacity: int, batch: int, taper_wire: bool,
                      payloads: tuple = (), engine: Optional[str] = None):
    """One stable distributed counting pass on key bits [shift, shift+bits).

    ``u`` is this device's uint32 key shard; returns the re-shuffled shard
    ``(u, *payloads)`` (keys placed at their exact global rank for this
    field, payload arrays routed through the same all_to_all buckets) +
    overflow flag.  ``engine`` picks the *local* rank engine for the
    pass's field (the wide-pass ICI scheme — ``max_bins_log2=16``, one
    all_to_all per 16-bit field — needs the scatter engine locally or the
    2**16-bin one-hot tile dominates the collective); ``None`` picks by
    digit width (:func:`~repro.core.sort_plan.default_engine`).
    """
    n_local = u.shape[0]
    D = jax.lax.psum(1, axis)
    me = jax.lax.axis_index(axis)
    n_bins = 1 << bits
    field = ((u >> shift) & (n_bins - 1)).astype(jnp.int32)

    # (1) local histogram.
    local_counts = jnp.zeros((n_bins,), jnp.int32).at[field].add(1)

    # (2) global merge — tapered wire dtype (uint16 holds any local shard of
    # <= 64Ki keys per bin; psum accumulates in int32 after the cast).
    wire = local_counts.astype(jnp.uint16) if taper_wire and n_local < (1 << 16) else local_counts
    global_counts = jax.lax.psum(wire.astype(jnp.int32), axis)

    # (3) exclusive scan over devices: my arrival offset within each bin.
    all_counts = jax.lax.all_gather(wire, axis).astype(jnp.int32)  # (D, bins)
    before_me = jnp.where(jnp.arange(D)[:, None] < me, all_counts, 0).sum(axis=0)
    global_start = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(global_counts)[:-1]])

    # local stable intra-bin arrival ranks (engine per the pass hint or
    # the digit width — wide fields rank via the scatter engine).
    if engine is None:
        engine = default_engine(bits)
    rank_batch = scatter_tile_len(n_bins, batch) if engine == "scatter" \
        else batch
    rank_local, _, _ = rank_engine(engine)(field, n_bins, batch=rank_batch)
    local_start = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(local_counts)[:-1]])
    intra = rank_local - local_start[field]
    global_rank = global_start[field] + before_me[field] + intra

    # (4) route each key to the device owning its output slot.
    shard_size = n_local  # equal shards by construction
    dest = jnp.clip(global_rank // shard_size, 0, D - 1)
    slot_in_dest = global_rank - dest * shard_size

    dest_rank, dest_counts, _ = fractal_rank(dest, D, batch=batch)
    dest_start = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(dest_counts)[:-1]])
    pos_in_bucket = dest_rank - dest_start[dest]
    overflow = jax.lax.psum(
        jnp.any(dest_counts > capacity).astype(jnp.int32), axis) > 0

    # fixed-capacity buckets; overflowing entries drop (flagged above).
    def route(vals):
        send = jnp.zeros((D, capacity), vals.dtype).at[
            dest, pos_in_bucket].set(vals, mode="drop")
        return jax.lax.all_to_all(send, axis, split_axis=0,
                                  concat_axis=0).reshape(-1)

    send_slot = jnp.full((D, capacity), -1, jnp.int32).at[
        dest, pos_in_bucket].set(slot_in_dest, mode="drop")
    recv_slot = jax.lax.all_to_all(send_slot, axis, split_axis=0,
                                   concat_axis=0).reshape(-1)
    recv_keys = route(u)

    valid = recv_slot >= 0
    slot = jnp.where(valid, recv_slot, n_local)

    def place(recv, dtype):
        return jnp.zeros((n_local,), dtype).at[slot].set(
            jnp.where(valid, recv, 0), mode="drop")

    out = place(recv_keys, jnp.uint32)
    # payload carry: each payload column rides its own all_to_all through
    # the same buckets/slots (one extra collective per column per pass).
    out_payloads = tuple(place(route(pv), pv.dtype) for pv in payloads)
    return (out, *out_payloads), overflow


def _sort_body(keys, plan, axis: str, capacity: int, batch: int,
               taper_wire: bool):
    """Executor over the DistributedBackend — every plan pass is exact
    placement on its field (``reconstructs = False``), so the composition
    is a stable full-precision sort.  Runs inside the shard_map region."""
    backend = DistributedBackend(axis=axis, capacity=capacity, batch=batch,
                                 taper_wire=taper_wire)
    out = PlanExecutor(backend).run(keys, plan)
    overflow = (backend.overflow if backend.overflow is not None
                else jnp.zeros((), jnp.bool_))
    return out.astype(keys.dtype), overflow


def _make_distributed(body_fn, mesh, axis: str, p: int,
                      capacity_factor: Optional[float],
                      batch: int, taper_wire: bool,
                      max_bins_log2: Optional[int],
                      num_payloads: int = 0, payloads_out: int = 0):
    """Shared scaffolding for the distributed entry points: plan build,
    the capacity/overflow rule, and the shard_map wrapping — so sort,
    argsort and the pairs sort can never diverge on them.  ``body_fn``
    runs inside the shard_map region over ``1 + num_payloads`` sharded
    inputs (keys first) and returns ``1 + payloads_out`` sharded outputs
    plus the replicated overflow flag."""
    D = mesh.shape[axis]
    cf = capacity_factor if capacity_factor is not None else float(D)
    if max_bins_log2 is None:
        max_bins_log2 = DISTRIBUTED_MAX_BINS_LOG2

    def fn(keys, *payloads):
        assert len(payloads) == num_payloads, (
            f"expected {num_payloads} payload columns, got {len(payloads)}")
        n = keys.shape[0]
        plan = make_sort_plan(n, p, max_bins_log2=max_bins_log2)
        cap = min(int(cf * (n // D) / D) + 1, n // D)
        body = functools.partial(
            body_fn, plan=plan, axis=axis, capacity=cap, batch=batch,
            taper_wire=taper_wire)
        return jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(axis),) * (1 + num_payloads),
            out_specs=(P(axis),) * (1 + payloads_out) + (P(),),
        )(keys, *payloads)

    return fn


def make_distributed_sort(mesh, axis: str, p: int,
                          capacity_factor: Optional[float] = None,
                          batch: int = 1024,
                          taper_wire: bool = True,
                          max_bins_log2: Optional[int] = None):
    """Build a jit-able distributed sort over ``mesh[axis]``.

    Returns ``fn(keys_global) -> (sorted_global, overflow)``; keys sharded
    ``P(axis)`` on axis 0, values in ``[0, 2**p)``, ``p <= 32``, global
    length divisible by the axis size.  ``capacity_factor`` defaults to the
    axis size (worst-case-safe); pass e.g. 2.0 to shrink the all_to_all
    buffers for known-low-duplication keys.  ``max_bins_log2`` bounds the
    per-pass bin count via the SortPlan digit decomposition (each extra
    pass costs one more all_to_all round, so the wide two-field scheme —
    :data:`DISTRIBUTED_MAX_BINS_LOG2` — is the default; local wide ranks
    route through the scatter engine).
    """
    return _make_distributed(_sort_body, mesh, axis, p, capacity_factor,
                             batch, taper_wire, max_bins_log2)


def distributed_fractal_sort(keys, mesh, axis: str, p: int, **kw):
    """One-shot convenience wrapper around :func:`make_distributed_sort`."""
    return make_distributed_sort(mesh, axis, p, **kw)(keys)


def _argsort_body(keys, plan, axis: str, capacity: int, batch: int,
                  taper_wire: bool):
    """Pairs run over the DistributedBackend with the *global* arrival
    index as the payload: every pass is exact placement, so the payload
    lands at its key's global rank — the stable permutation, sharded like
    the keys.  Runs inside the shard_map region."""
    n_local = keys.shape[0]
    me = jax.lax.axis_index(axis)
    idx = me * n_local + jnp.arange(n_local, dtype=jnp.int32)
    backend = DistributedBackend(axis=axis, capacity=capacity, batch=batch,
                                 taper_wire=taper_wire)
    _, perm = PlanExecutor(backend).run_pairs(keys, idx, plan)
    overflow = (backend.overflow if backend.overflow is not None
                else jnp.zeros((), jnp.bool_))
    return perm, overflow


def make_distributed_argsort(mesh, axis: str, p: int,
                             capacity_factor: Optional[float] = None,
                             batch: int = 1024,
                             taper_wire: bool = True,
                             max_bins_log2: Optional[int] = None):
    """Build a jit-able distributed *argsort* over ``mesh[axis]``.

    Returns ``fn(keys_global) -> (perm_global, overflow)`` with
    ``keys[perm]`` stably sorted — same contract as
    :func:`~repro.core.fractal_sort.fractal_argsort`, same sharding and
    capacity rules as :func:`make_distributed_sort`.  The permutation is
    the payload column of an executor pairs run, so duplicates keep
    (device, arrival) order — the join/group-by hot case at pod scale.
    """
    return _make_distributed(_argsort_body, mesh, axis, p, capacity_factor,
                             batch, taper_wire, max_bins_log2)


def distributed_fractal_argsort(keys, mesh, axis: str, p: int, **kw):
    """One-shot convenience wrapper around :func:`make_distributed_argsort`."""
    return make_distributed_argsort(mesh, axis, p, **kw)(keys)


def _pairs_body(keys, *payloads, plan, axis: str, capacity: int, batch: int,
                taper_wire: bool):
    """Executor pairs run over the DistributedBackend: keys *and* every
    payload column ride the same all_to_all buckets through every pass
    (``DistributedBackend.lsd_pass_pairs``), so the outputs are the keys
    at their exact global ranks with each payload next to its key.  Runs
    inside the shard_map region."""
    backend = DistributedBackend(axis=axis, capacity=capacity, batch=batch,
                                 taper_wire=taper_wire)
    out_keys, out_payloads = PlanExecutor(backend).run_pairs(
        keys, tuple(payloads), plan)
    overflow = (backend.overflow if backend.overflow is not None
                else jnp.zeros((), jnp.bool_))
    return (out_keys.astype(keys.dtype), *out_payloads, overflow)


def make_distributed_sort_pairs(mesh, axis: str, p: int,
                                num_payloads: int = 1,
                                capacity_factor: Optional[float] = None,
                                batch: int = 1024,
                                taper_wire: bool = True,
                                max_bins_log2: Optional[int] = None):
    """Build a jit-able distributed key–value sort over ``mesh[axis]``.

    Returns ``fn(keys_global, *payloads_global) -> (sorted_keys,
    *payloads_in_sorted_key_order, overflow)`` — the distributed twin of
    :meth:`~repro.core.executor.PlanExecutor.run_pairs`, with every
    payload column routed through one extra all_to_all per pass alongside
    the keys.  Same sharding/capacity rules as
    :func:`make_distributed_sort`; stability is (device, arrival) order,
    so an int32 arrival-index payload comes back as the stable
    permutation.  This is the pass the distributed StreamTable operators
    bottom out in: each histogram partition's rows sort here with their
    row permutation riding as the payload.
    """
    return _make_distributed(_pairs_body, mesh, axis, p, capacity_factor,
                             batch, taper_wire, max_bins_log2,
                             num_payloads=num_payloads,
                             payloads_out=num_payloads)


def make_fragment_placer(mesh, axis: str, num_words: int,
                         batch: int = 1024):
    """Build the chunk→device fragment-placement collective of the
    distributed external sort.

    Returns ``fn(words_global (t, num_words) uint32, dest_global (t,)
    int32, tag_global (t,) int32) -> (landed_words (D*t, num_words),
    landed_tags (D*t,))``: every row travels to device ``dest[i]`` via
    one bucket ``all_to_all`` per word column (plus one for the tags),
    replacing the disk path's per-partition spill with mesh placement.
    Rows with ``dest < 0`` (pruned partitions) are dropped on the wire —
    they never land anywhere.  Device ``d``'s landing buffer is the
    global slice ``[d*t, (d+1)*t)``; slots with ``tag < 0`` are empty
    padding, and valid rows appear in (source device, arrival) order —
    i.e. global arrival order, since shards are contiguous arrival
    ranges — so fragment stability is free.

    Bucket capacity is the full local shard (``t // D``): one source
    device can address all of its rows to a single destination, and at
    that capacity overflow is impossible — placement needs no retry
    contract.  The landing buffer is D× the chunk (each device can in
    the worst case receive *every* row); chunks are budget-sized, so
    this is a bounded constant, not a dataset-scale cost.
    """
    D = mesh.shape[axis]

    def body(words, dest, tag):
        n_local = dest.shape[0]
        # dest < 0 → row index D, out of the send buffer's range: dropped
        safe = jnp.where(dest >= 0, dest, D)
        rank, counts, _ = fractal_rank(safe, D + 1, batch=batch)
        start = jnp.concatenate(
            [jnp.zeros((1,), jnp.int32), jnp.cumsum(counts)[:-1]])
        pos = rank - start[safe]

        def route(vals, fill):
            send = jnp.full((D, n_local), fill, vals.dtype).at[
                safe, pos].set(vals, mode="drop")
            return jax.lax.all_to_all(send, axis, split_axis=0,
                                      concat_axis=0).reshape(-1)

        landed_tag = route(tag, -1)
        landed_words = jnp.stack(
            [route(words[:, j], jnp.uint32(0)) for j in range(num_words)],
            axis=1)
        return landed_words, landed_tag

    def fn(words, dest, tag):
        assert words.ndim == 2 and words.shape[1] == num_words
        return jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(axis), P(axis), P(axis)),
            out_specs=(P(axis), P(axis)),
        )(words, dest, tag)

    return fn
