"""FractalSort: histogram → rank → reconstruct (paper Algorithms 1–5).

Pipeline for ``n`` keys of ``p`` bits with trie depth ``l_n``:

1. **Histogram** — bincount of the ``l_n``-bit MSB prefixes (the trie leaf
   level; upper levels by pairwise reduction).  No input bucketing, no
   sampling: every key contributes independently (paper contributions 1/2).
2. **Rank** — stable output position per key:
   ``rank = bin_start[prefix] + carry[prefix] + intra_chunk_arrival``,
   computed by a **two-phase chunk-parallel engine** (the independent-
   counting / cross-chunk-scan / parallel-placement structure of Stehle &
   Jacobsen's hybrid radix and Wassenberg & Sanders' bandwidth-bounded
   radix).  Phase 1 builds every fixed-size chunk's digit histogram at
   once (an int32 sum of the chunk's one-hot hit mask — no sequential
   dependence); phase 2 derives every chunk's carry from one exclusive
   scan over the ``(chunks, n_bins)`` histogram matrix and ranks all
   chunks in parallel, the intra-chunk arrival coming from one MXU
   matmul of the hit mask against a strict-triangular matrix (the Pallas
   rank kernel's formulation) and each per-key pick from a masked sum
   over the bins, not a gather.  The streaming carry API
   (``carry_in``/``carry_out``/``bin_start``) lets batched and
   distributed consumers stream slices through one cached histogram
   (paper §III.C/D).
3. **Reconstruct** (Algorithm 5 / FractalSortCPUA) — the sorted array is
   rebuilt from (bin counts, per-bin stable order, trailing bits).  The top
   ``l_n`` bits of every output key are *recovered from the bin position*,
   never moved through memory; only ``p - l_n`` trailing bits travel.  When
   ``n >= 2**p`` (e.g. the paper's n=2^29, p=16 headline) entries carry zero
   payload and the output is ``repeat(bin_value, counts)`` — the extreme
   bandwidth win.

**SortPlan pass decomposition (§III.G).**  A ``p``-bit sort executes a
:class:`~repro.core.sort_plan.SortPlan`: stable LSD digit passes over the
trailing bits followed by one MSD *fractal* pass over the ``depth``-bit
prefix.  For digit width ``w`` the trade is

    passes  = ceil((p - depth) / w) + 1
    work    = O(n * 2**w * passes)        (one-hot rank tiles, bounded)
    traffic = O(n * passes) key moves  +  n * ceil((p - depth)/8) entry
              payload bytes + n output writes (prefix bits reconstructed
              from bin position, never moved)

Fewer, wider passes move fewer bytes (the paper's "reduced number of radix
passes on compressed entries", one 2**16-counter pass per 16-bit field);
narrower digits bound the one-hot rank tile at ``batch * 2**w`` and keep
the arithmetic cost linear in ``n`` — the multi-digit scheme of Stehle &
Jacobsen and Wassenberg & Sanders.  :func:`fractal_sort` defaults to
``max_bins_log2 = 4`` for execution (measured fastest on this CPU host —
see ``benchmarks/bench_sortplan.py``); :func:`fractal_sort_stats` defaults
to the paper's 16-bit-field plan for the analytic bandwidth model, and
accepts any plan to account per-pass traffic.

:func:`fractal_sort_stats` returns an *analytic* DRAM-traffic model so
benchmarks can report the paper's bandwidth efficiency
``b_eff = T_actual / B_DRAM`` (Eq. 1) exactly, independent of host hardware.

**Execution.**  Every public sort here is a thin wrapper: it builds a
:class:`SortPlan` and hands it to a
:class:`~repro.core.executor.PlanExecutor` over the pure-jnp
:class:`~repro.core.executor.JnpBackend` — the same pass loop the Pallas
kernel driver and the distributed sort run through their own backends.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import fractal_tree as ft
from repro.core.executor import JnpBackend, PlanExecutor
from repro.core.sort_plan import (
    DEFAULT_MAX_BINS_LOG2,
    SortPlan,
    make_sort_plan,
    rank_chunk_len,
    scatter_tile_len,
)

__all__ = [
    "PassStats",
    "SortStats",
    "fractal_rank",
    "fractal_rank_scatter",
    "fractal_rank_serial",
    "fractal_sort",
    "fractal_argsort",
    "fractal_sort_batched",
    "fractal_sort_pairs",
    "fractal_sort_stats",
    "rank_engine",
    "reconstruct",
]


@dataclasses.dataclass(frozen=True)
class PassStats:
    """Analytic DRAM traffic of one plan pass (bytes)."""

    shift: int
    bits: int
    kind: str
    bytes_read: int
    bytes_written: int

    @property
    def n_bins(self) -> int:
        return 1 << self.bits


@dataclasses.dataclass(frozen=True)
class SortStats:
    """Analytic DRAM-traffic model for one sort call (bytes)."""

    n: int
    p: int
    l_n: int
    passes: int
    bytes_read: int
    bytes_written: int
    histogram_bytes: int  # tapered trie footprint (on-chip resident)
    pass_stats: tuple = ()  # tuple[PassStats], LSD -> MSD

    @property
    def bytes_total(self) -> int:
        return self.bytes_read + self.bytes_written

    @property
    def bytes_per_key(self) -> float:
        return self.bytes_total / max(self.n, 1)


def _key_bytes(p: int) -> int:
    return 4 if p > 16 else 2


def fractal_sort_stats(n: int, p: int, l_n: Optional[int] = None,
                       with_index: bool = False,
                       plan: Optional[SortPlan] = None) -> SortStats:
    """Analytic traffic of a plan execution (feeds the b_eff benchmark).

    Per LSD pass: one streaming read of the keys, one full-key scatter
    write.  The final MSD pass reads the keys once, writes entry payloads
    (trailing bits only, rounded to whole bytes; zero when the trie covers
    the field), and writes the output reconstructed from bin positions.
    The tapered trie lives on-chip (VMEM/LLC) and is counted once in
    ``histogram_bytes``, not in DRAM traffic — the paper's p=16 claim that
    the compressed histogram fits entirely in LLC (§IV.F.1).

    ``plan`` defaults to the *paper* plan (16-bit fields, the trade the
    analytic model targets); pass any :class:`SortPlan` to account the
    execution plan actually run — per-pass traffic lands in
    ``SortStats.pass_stats``.
    """
    if plan is None:
        plan = make_sort_plan(n, p, l_n=l_n, max_bins_log2=16)
    kb = _key_bytes(p)
    if with_index:
        # stable payload tracking (paper Alg. 5): the index array maps each
        # sorted slot to its arrival position; width tapers with the intra-
        # bin count (<= 2 bytes for the paper's regimes) — one write at
        # rank time, one sequential read at reconstruction, per pass.
        idx_bytes = 2 if (plan.depth >= ft.ceil_log2(n) - 16) else 4
    else:
        idx_bytes = 0
    per_pass = []
    for dpass in plan.passes:
        rd = n * kb + n * idx_bytes
        if dpass.kind == "msd":
            trailing_bytes = (dpass.shift + 7) // 8 if dpass.shift else 0
            wr = n * trailing_bytes + n * kb + n * idx_bytes
        else:
            wr = n * kb + n * idx_bytes
        per_pass.append(PassStats(shift=dpass.shift, bits=dpass.bits,
                                  kind=dpass.kind,
                                  bytes_read=rd, bytes_written=wr))
    h_bytes = sum(
        (1 << l) * jnp.dtype(ft.tapered_dtype(l, ft.ceil_log2(n))).itemsize
        for l in range(plan.depth + 1)
    )
    return SortStats(
        n=n, p=p, l_n=plan.depth, passes=len(per_pass),
        bytes_read=sum(ps.bytes_read for ps in per_pass),
        bytes_written=sum(ps.bytes_written for ps in per_pass),
        histogram_bytes=int(h_bytes),
        pass_stats=tuple(per_pass),
    )


# ---------------------------------------------------------------------------
# Rank: two-phase chunk-parallel stable ranks with cached histogram carry
# ---------------------------------------------------------------------------


def _rank_chunks(prefix: jnp.ndarray, n: int, n_bins: int, batch: int):
    """Pad to a whole number of fixed-size chunks and reshape.

    Padding uses bin id ``n_bins``, which matches no one-hot column and is
    out of bounds for the bincount scatter (dropped), so padded rows
    contribute nothing to counts or carries.  The chunk length bounds the
    materialized one-hot tile (chunk x n_bins) — the locality/parallelism
    trade the paper tunes in §III.C; :func:`rank_chunk_len` is the shared
    per-pass execution hint.
    """
    batch = min(rank_chunk_len(n_bins, batch), max(n, 1))
    pad = (-n) % batch
    if pad:
        prefix = jnp.concatenate(
            [prefix, jnp.full((pad,), n_bins, jnp.int32)])
    return prefix.reshape(-1, batch)


def _rank_finish(prefix, ranks, counts, carry_in, bin_start, n_bins):
    """Shared tail: derive bin starts, add them, emit the carry triple."""
    carry_out = carry_in + counts
    if bin_start is None:
        bin_start = ft.exclusive_cumsum(counts)
    rank = bin_start[jnp.clip(prefix, 0, n_bins - 1)] + ranks
    return rank, counts, carry_out


def _rank_empty(n_bins, carry_in, bin_start):
    counts = jnp.zeros((n_bins,), jnp.int32)
    return jnp.zeros((0,), jnp.int32), counts, carry_in


# Chunk length cap of the one-hot engine: one MXU weight tile of the
# strict-triangular arrival matrix.  It also keeps every chunk histogram
# (<= 128) exact in bfloat16, the operand of the in-group chunk scan.
_RANK_CHUNK = 128

# Per-group caps of the chunk-parallel rank: the (chunks x n_bins x chunk)
# hit mask in elements, and the chunks, whose (chunks, chunks) triangle
# the in-group chunk scan multiplies by.  Each group is one step of the
# sequential scan, so larger groups cut the per-step overhead: 2**21
# elements (1024 chunks of 128 at 16 bins) measured faster on a TPU v5e
# than 2**19, or 2**22 with 2048 chunks, and faster on the host than
# 2**19.
_RANK_GROUP_ELEMS = 1 << 21
_RANK_GROUP_CHUNKS = 1 << 10


def _strictly_before(m: int) -> jnp.ndarray:
    """``(m, m)`` bfloat16 matrix with ``[j, i] = 1`` where ``j < i``."""
    pos = jnp.arange(m, dtype=jnp.int32)
    return (pos[:, None] < pos[None, :]).astype(jnp.bfloat16)


def fractal_rank(
    prefix: jnp.ndarray,
    n_bins: int,
    batch: int = 1024,
    carry_in: Optional[jnp.ndarray] = None,
    bin_start: Optional[jnp.ndarray] = None,
):
    """Stable output position for each key given its bin id ``prefix``.

    ``rank[i] = bin_start[prefix[i]] + carry[prefix[i]] + arrivals before i``
    — the scatter-index computation of a counting/radix sort, evaluated by
    the **two-phase chunk-parallel engine** over fixed-size chunks (at most
    ``_RANK_CHUNK`` keys, and no more than ``batch`` allows), in groups of
    at most ``_RANK_GROUP_CHUNKS`` chunks whose hit mask holds at most
    about ``_RANK_GROUP_ELEMS`` elements.  For each group, from its
    lane-dense ``(chunks, n_bins, chunk)`` hit mask:

    * every key's intra-chunk arrival is one MXU matmul of all
      ``chunks * n_bins`` hit rows against the strict-triangular
      ``(chunk, chunk)`` matrix — bfloat16 0/1 operands, float32
      accumulation, exact for counts below 2**24 — picked per key by a
      masked sum over the bins;
    * every chunk's histogram is an int32 sum of the mask over positions;
    * every chunk's carry is the group's running int32 carry plus an
      exclusive scan of the histograms over the group's chunks (a second,
      small triangular matmul: its values are bounded by the group's key
      count), picked per key by an int32 masked sum over the bins —
      carries reach ``n``, past float32's exact range, so they never pass
      through a matmul.

    Inside the group scan nothing is gathered and nothing is a cumulative
    sum over positions (on TPU a cumsum lowers to a reduce-window as long
    as its axis).  Only the tiny ``(n_bins,)`` carry crosses group
    boundaries (a ``lax.scan``); when the whole input fits one group
    there is no sequential step at all.

    ``carry_in`` lets callers stream several key batches through one
    cached histogram (paper §III.D); ``bin_start`` may be supplied when
    the global histogram is already known (e.g. after the psum merge in
    the distributed sort).  :func:`fractal_rank_serial` is the equivalent
    serial-scan engine, kept as the property-test oracle and benchmark
    baseline.

    Returns ``(rank, counts, carry_out)``.
    """
    n = prefix.shape[0]
    prefix = prefix.astype(jnp.int32)
    if carry_in is None:
        carry_in = jnp.zeros((n_bins,), jnp.int32)
    if n == 0:
        return _rank_empty(n_bins, carry_in, bin_start)
    # Inherit the data's varying-manual-axes so the group-scan carry
    # typechecks under shard_map (VMA tracking); no-op numerically.
    carry_in = carry_in + prefix[0] * 0
    chunks = _rank_chunks(prefix, n, n_bins, min(batch, _RANK_CHUNK))
    num_chunks, chunk_len = chunks.shape
    group = min(num_chunks, _RANK_GROUP_CHUNKS,
                max(1, _RANK_GROUP_ELEMS // (chunk_len * n_bins)))
    gpad = (-num_chunks) % group
    if gpad:  # sentinel chunks: contribute nothing, ranks sliced off
        chunks = jnp.concatenate(
            [chunks, jnp.full((gpad, chunk_len), n_bins, jnp.int32)])
    groups = chunks.reshape(-1, group, chunk_len)
    # a constant, not an iota: XLA's CPU fusions of the mask run twice as
    # fast against it
    bins = np.arange(n_bins, dtype=np.int32)

    def group_body(carry, gchunks):
        hit = gchunks[:, None, :] == bins[None, :, None]
        running = jnp.einsum("cbj,ji->cbi", hit.astype(jnp.bfloat16),
                             _strictly_before(chunk_len),
                             preferred_element_type=jnp.float32)
        intra = jnp.sum(jnp.where(hit, running, 0.0), axis=1)
        hists = jnp.sum(hit, axis=2, dtype=jnp.int32)
        earlier = jnp.einsum("cb,cd->db", hists.astype(jnp.bfloat16),
                             _strictly_before(group),
                             preferred_element_type=jnp.float32)
        chunk_carry = carry[None, :] + earlier.astype(jnp.int32)
        base = jnp.sum(jnp.where(hit, chunk_carry[:, :, None], 0), axis=1)
        return carry + hists.sum(axis=0), base + intra.astype(jnp.int32)

    carry_out, ranks = jax.lax.scan(group_body, carry_in, groups)
    ranks = ranks.reshape(-1)[:n]
    return _rank_finish(prefix, ranks, carry_out - carry_in, carry_in,
                        bin_start, n_bins)


def fractal_rank_serial(
    prefix: jnp.ndarray,
    n_bins: int,
    batch: int = 1024,
    carry_in: Optional[jnp.ndarray] = None,
    bin_start: Optional[jnp.ndarray] = None,
):
    """Serial-scan rank engine (the pre-executor implementation): a
    ``lax.scan`` over chunks threading the running per-bin histogram.
    Same contract as :func:`fractal_rank`; kept as the oracle for the
    chunk-parallel engine's property tests and for the
    ``bench_sortplan.py`` serial-vs-parallel comparison."""
    n = prefix.shape[0]
    prefix = prefix.astype(jnp.int32)
    if carry_in is None:
        carry_in = jnp.zeros((n_bins,), jnp.int32)
    if n == 0:
        return _rank_empty(n_bins, carry_in, bin_start)
    # Inherit the data's varying-manual-axes so the scan carry typechecks
    # under shard_map (JAX >= 0.8 VMA tracking); no-op numerically.
    carry_in = carry_in + prefix[0] * 0
    chunks = _rank_chunks(prefix, n, n_bins, batch)
    bins = jnp.arange(n_bins, dtype=jnp.int32)

    def body(carry, chunk):
        onehot = (chunk[:, None] == bins[None, :]).astype(jnp.int32)
        running = jnp.cumsum(onehot, axis=0) - onehot
        safe = jnp.clip(chunk, 0, n_bins - 1)
        intra = jnp.take_along_axis(running, safe[:, None], axis=1)[:, 0]
        return carry + onehot.sum(axis=0), carry[safe] + intra

    carry_out, ranks = jax.lax.scan(body, carry_in, chunks)
    ranks = ranks.reshape(-1)[:n]
    return _rank_finish(prefix, ranks, carry_out - carry_in, carry_in,
                        bin_start, n_bins)


def fractal_rank_scatter(
    prefix: jnp.ndarray,
    n_bins: int,
    batch: int = 1024,
    carry_in: Optional[jnp.ndarray] = None,
    bin_start: Optional[jnp.ndarray] = None,
):
    """Scatter/bincount + searchsorted rank engine: O(n log tile) per pass,
    *independent of the digit width* — the engine that makes wide passes
    executable on CPU (the one-hot engines above do O(n * n_bins) work on
    a materialized tile, which is what forced ``DEFAULT_MAX_BINS_LOG2=4``).

    Same contract and results as :func:`fractal_rank` /
    :func:`fractal_rank_serial` (``(rank, counts, carry_out)``, streaming
    ``carry_in``/``bin_start`` injection), different arithmetic:

    * the stream is cut into power-of-two *tiles* (``batch`` elements,
      LLC-sized); each tile packs digit and arrival position into one
      word — ``comp = digit << log2(tile) | pos`` — and sorts the packed
      words (a single-operand XLA sort, no payload: position rides the
      low bits, so the sort is stable by construction and both fields
      shift/mask back out);
    * per-tile digit histograms come from one scatter-add (bincount) over
      (tile, digit) pairs — or, when the digit range is narrow, from
      ``searchsorted`` probes of the sorted composites at the tile's
      digit boundaries (O(tiles * n_bins * log tile), cheaper than the
      O(n) scatter when bins are few);
    * at sorted position ``i`` of a tile, the intra-tile arrival is just
      ``i - (elements of the tile with smaller digits)`` — the exclusive
      digit cumsum the probe/bincount table already holds; the cross-tile
      carry is one exclusive scan over the (tiles, n_bins) table, exactly
      the chunk-carry structure of the one-hot engine;
    * one scatter through the unpacked positions returns ranks to arrival
      order.

    Memory: O(n + tiles * n_bins).  ``batch`` is the tile length (rounded
    down to a power of two; :func:`~repro.core.sort_plan.scatter_tile_len`
    is the per-pass executor hint — unlike the one-hot chunk hint it
    *grows* with ``n_bins``).
    """
    n = prefix.shape[0]
    prefix = prefix.astype(jnp.int32)
    if carry_in is None:
        carry_in = jnp.zeros((n_bins,), jnp.int32)
    if n == 0:
        return _rank_empty(n_bins, carry_in, bin_start)
    # Inherit the data's varying-manual-axes (shard_map VMA tracking).
    carry_in = carry_in + prefix[0] * 0
    bits = max(n_bins - 1, 1).bit_length()
    tlog = max(3, batch.bit_length() - 1)       # floor pow2 of the hint
    tlog = min(tlog, ft.ceil_log2(max(n, 8)),   # no tile wider than the data
               31 - bits)                       # composite packing headroom
    tile = 1 << tlog
    num_tiles = (n + tile - 1) // tile
    pad = num_tiles * tile - n
    if pad:  # pad digit n_bins: sorts to the tile tail, dropped from counts
        prefix = jnp.concatenate(
            [prefix, jnp.full((pad,), n_bins, jnp.int32)])
    tiles = prefix.reshape(num_tiles, tile).astype(jnp.uint32)
    comp = (tiles << tlog) | jnp.arange(tile, dtype=jnp.uint32)[None, :]
    sc = jnp.sort(comp, axis=1)
    ds = (sc >> tlog).astype(jnp.int32)              # digits, sorted order
    orig = (sc & jnp.uint32(tile - 1)).astype(jnp.int32)
    if num_tiles * (n_bins + 1) <= 2 * n:
        # narrow digits: per-tile (lower, counts) from boundary probes of
        # the sorted composites — bin b's tile segment starts where
        # composites reach b << tlog.
        probes = jnp.arange(n_bins + 1, dtype=jnp.uint32) << tlog
        bounds = jax.vmap(
            lambda s: jnp.searchsorted(s, probes))(sc).astype(jnp.int32)
        lower, table = bounds[:, :-1], jnp.diff(bounds, axis=1)
    else:
        # wide digits: one flat scatter-add (bincount) over (tile, digit)
        table = jnp.zeros((num_tiles, n_bins), jnp.int32).at[
            jnp.repeat(jnp.arange(num_tiles), tile), prefix
        ].add(1, mode="drop")
        lower = jnp.cumsum(table, axis=1) - table
    counts = table.sum(axis=0)
    tile_carry = carry_in[None, :] + jnp.cumsum(table, axis=0) - table
    safe = jnp.clip(ds, 0, n_bins - 1)
    if bin_start is None:
        bin_start = ft.exclusive_cumsum(counts)
    rank_sorted = (bin_start[safe]
                   + jnp.take_along_axis(tile_carry, safe, axis=1)
                   + jnp.arange(tile, dtype=jnp.int32)[None, :]
                   - jnp.take_along_axis(lower, safe, axis=1))
    rank = jnp.zeros((num_tiles, tile), jnp.int32).at[
        jnp.arange(num_tiles)[:, None], orig].set(rank_sorted)
    return rank.reshape(-1)[:n], counts, carry_in + counts


#: The pluggable rank engines (one contract, three arithmetics): "onehot"
#: is the chunk-parallel MXU-shaped tile (fast for narrow digits, TPU),
#: "scatter" the sorted-tile scatter/bincount engine (wide digits, CPU),
#: "serial" the scan-over-chunks oracle.
RANK_ENGINES = {
    "onehot": fractal_rank,
    "scatter": fractal_rank_scatter,
    "serial": fractal_rank_serial,
}


def rank_engine(name: Optional[str]):
    """Resolve an engine hint to its rank function (None = "onehot",
    the historical default)."""
    fn = RANK_ENGINES.get(name or "onehot")
    assert fn is not None, (
        f"unknown rank engine {name!r}: one of {sorted(RANK_ENGINES)}")
    return fn


# ---------------------------------------------------------------------------
# Reconstruction (Algorithm 5)
# ---------------------------------------------------------------------------


def keys_dtype(p: int):
    return jnp.int32 if p <= 31 else jnp.uint32


def reconstruct(counts: jnp.ndarray, trailing: jnp.ndarray, l_n: int, p: int,
                lsb_tree_order: bool = False) -> jnp.ndarray:
    """Algorithm 5 (FractalSortCPUA), vectorized.

    ``trailing`` is the entry array already permuted to sorted order (the
    index-array gather of Alg. 5 line 8); each output key is rebuilt as
    ``bin_bits << t | trailing`` where the bin bits come from the bin
    *position* — the l_n prefix bits never travel through memory.  With
    ``lsb_tree_order=True`` bins are interpreted in the paper's LSB-first
    tree-walk order and un-reversed with BitReverse (oracle-equivalence
    tests); the MSB-first layout makes that the identity.
    """
    n = trailing.shape[0]
    ends = jnp.cumsum(counts.astype(jnp.int32))
    slot_bin = jnp.searchsorted(ends, jnp.arange(n, dtype=jnp.int32), side="right")
    if lsb_tree_order:
        slot_bin = ft.bit_reverse(slot_bin, l_n)
    t = p - l_n
    hi = slot_bin.astype(jnp.uint32) << t if t > 0 else slot_bin.astype(jnp.uint32)
    return (hi | trailing.astype(jnp.uint32)).astype(keys_dtype(p))


# ---------------------------------------------------------------------------
# Public sorts — thin wrappers: resolve a SortPlan, hand it to a PlanExecutor
# ---------------------------------------------------------------------------


def _resolve_plan(n: int, p: int, l_n: Optional[int],
                  max_bins_log2: Optional[int],
                  plan: Optional[SortPlan]) -> SortPlan:
    """Plan resolution shared by every entry point: an explicit ``plan``
    wins; otherwise :func:`~repro.core.sort_plan.make_sort_plan` builds
    it from ``l_n`` and ``max_bins_log2`` (each defaulting there)."""
    if plan is not None:
        assert plan.p == p, f"plan is for p={plan.p}, sort asked p={p}"
        return plan
    return make_sort_plan(n, p, l_n=l_n, max_bins_log2=max_bins_log2)


@functools.partial(jax.jit,
                   static_argnames=("p", "l_n", "batch", "max_bins_log2",
                                    "plan"))
def fractal_sort(keys: jnp.ndarray, p: int, l_n: Optional[int] = None,
                 batch: int = 1024,
                 max_bins_log2: Optional[int] = None,
                 plan: Optional[SortPlan] = None) -> jnp.ndarray:
    """Sort integer keys in [0, 2**p) by executing a :class:`SortPlan`:
    bounded-width stable LSD digit passes plus one fractal MSD pass
    ("compressed entries").  ``max_bins_log2`` caps per-pass bins at
    ``2**max_bins_log2``; ``plan`` pins an exact plan; all-defaults runs
    the ``DEFAULT_MAX_BINS_LOG2`` plan."""
    n = keys.shape[0]
    plan = _resolve_plan(n, p, l_n, max_bins_log2, plan)
    return PlanExecutor(JnpBackend(batch=batch)).run(keys, plan)


@functools.partial(jax.jit,
                   static_argnames=("p", "l_n", "batch", "max_bins_log2",
                                    "plan"))
def fractal_sort_pairs(keys: jnp.ndarray, values: jnp.ndarray, p: int,
                       l_n: Optional[int] = None, batch: int = 1024,
                       max_bins_log2: Optional[int] = None,
                       plan: Optional[SortPlan] = None):
    """Key–value sort: ``(sorted_keys, values_in_sorted_key_order)`` for
    integer keys in [0, 2**p) and one payload column of equal length (any
    fixed-width dtype — the query layer passes int32 row ids).

    The payload rides the executor's scatter path on *every* pass: full
    keys + payload through the LSD passes, then payload + compressed
    trailing-bit entries through the fractal MSD pass, whose prefix bits
    are still reconstructed from bin positions (Alg. 5) — sorting
    (key, row-id) pairs costs the payload's bytes but keeps the
    compressed-entry bandwidth win on the keys.  Stable: equal keys keep
    arrival order, which `order_by` and the sort-merge join rely on."""
    plan = _resolve_plan(keys.shape[0], p, l_n, max_bins_log2, plan)
    return PlanExecutor(JnpBackend(batch=batch)).run_pairs(keys, values, plan)


@functools.partial(jax.jit, static_argnames=("p", "batch", "max_bins_log2",
                                             "plan"))
def fractal_argsort(keys: jnp.ndarray, p: int, batch: int = 1024,
                    max_bins_log2: Optional[int] = None,
                    plan: Optional[SortPlan] = None) -> jnp.ndarray:
    """Stable permutation ``perm`` with ``keys[perm]`` sorted (exact, full
    ``p``-bit precision — the MoE dispatch form where p = ceil(log2 E)).

    Runs every plan pass as a payload-carrying LSD pass (the permutation is
    the payload, so there is nothing to reconstruct from bin positions)."""
    assert p <= 32, "argsort covers p <= 32 via the digit plan"
    plan = _resolve_plan(keys.shape[0], p, None, max_bins_log2, plan)
    return PlanExecutor(JnpBackend(batch=batch)).run_argsort(keys, plan)


def fractal_sort_batched(keys: jnp.ndarray, p: int, num_batches: int,
                         l_n: Optional[int] = None, batch: int = 1024,
                         max_bins_log2: Optional[int] = None,
                         plan: Optional[SortPlan] = None):
    """Streaming variant (paper §III.C/D): the input arrives in
    ``num_batches`` equal slices; the trie histogram is *cached and merged*
    across slices, then ranks stream through the shared carry and a single
    scatter groups entries by the plan's MSD prefix; the trailing bits are
    ordered in place by the executor's segment-aware grouped-trailing
    passes (no full-plan re-run over the grouped array).

    Returns ``(sorted_keys, per-slice histograms)`` so tests can check the
    merge telescopes: ``merge(h_1..h_B) == build(all keys)``.
    """
    plan = _resolve_plan(keys.shape[0], p, l_n, max_bins_log2, plan)
    return PlanExecutor(JnpBackend(batch=batch)).run_streaming(
        keys, plan, num_batches)
