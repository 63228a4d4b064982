"""PlanExecutor: the one pass loop every FractalSort entry point runs.

The paper's pipeline (histogram → rank → scatter → reconstruct, Algs. 1–5)
used to be hand-rolled three times — the jnp path, the Pallas kernel
driver, and the distributed sort — each walking the same
:class:`~repro.core.sort_plan.SortPlan` with its own loop.  This module
owns that loop once and delegates the per-pass *primitives* to a pluggable
:class:`PassBackend`:

* :class:`JnpBackend` — pure-jnp primitives built on the chunk-parallel
  two-phase :func:`~repro.core.fractal_sort.fractal_rank`;
* :class:`PallasBackend` — the TPU kernels (histogram / rank / reconstruct,
  interpret-mode off-TPU) from ``repro.kernels``;
* :class:`DistributedBackend` — one ``shard_map`` collective pass per plan
  digit (local rank + psum histogram merge + all_to_all placement),
  wrapping :func:`~repro.core.distributed._distributed_pass`.

Executor responsibilities (backend-independent):

* **digit extraction** — each pass ranks on key bits
  ``[shift, shift + bits)``;
* **pass sequencing** — stable LSD digit passes, then the fractal MSD pass;
* **payload carry** — full keys through LSD passes, the argsort
  permutation, or only the compressed trailing-bit entries into the MSD
  scatter;
* **final fractal reconstruct** — prefix bits rebuilt from bin positions
  (Algorithm 5) for backends that support it; backends that place keys at
  exact global slots every pass (distributed) set ``reconstructs = False``
  and run the MSD digit as one more exact pass;
* **empty-input guard** — ``n == 0`` returns immediately (no pass ranks an
  empty stream).

Three executor modes beyond the plain sort:

* :meth:`PlanExecutor.run_pairs` carries an arbitrary payload column
  (e.g. a query row id) through every pass — including the fractal MSD
  pass, where the key prefix is reconstructed from bin positions but the
  payload still moves with its entry.  The query operators
  (``repro.query``) bottom out here.
* :meth:`PlanExecutor.run_argsort` carries the arrival index as the
  payload through *every* pass (nothing to reconstruct — the permutation
  is the output).
* :meth:`PlanExecutor.run_grouped_trailing` is the **segment-aware** mode
  used by the streaming/batched sort: the array is already grouped by the
  MSD prefix (segments), and each trailing LSD pass re-ranks *within*
  segments, so the final MSD pass is never re-run.  The within-segment
  rank needs no composite-bin one-hot: a pass's ordinary global rank gives
  each key its arrival among equal digits, and a cheap
  ``(segments, n_bins)`` scatter-add table converts that to the
  within-segment arrival (subtract equal-digit arrivals from earlier
  segments) plus the smaller-digit offset.  Per pass this costs one
  ordinary rank + one O(n) table build — the same order as a plain LSD
  pass — versus the full plan re-run (all LSD passes *plus* a fresh MSD
  histogram/rank/scatter) the batched path used to pay.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.fractal_tree import exclusive_cumsum
from repro.core.sort_plan import DigitPass, SortPlan
from repro.kernels import default_interpret
from repro.obs import trace

__all__ = [
    "PassBackend",
    "JnpBackend",
    "PallasBackend",
    "DistributedBackend",
    "PlanExecutor",
    "sort_placement",
]


def _digit_of(u: jnp.ndarray, dp: DigitPass) -> jnp.ndarray:
    """The ``dp.bits``-wide digit of each (uint32) key at ``dp.shift``."""
    return ((u >> dp.shift) & (dp.n_bins - 1)).astype(jnp.int32)


def _as_key_stream(keys, encode) -> jnp.ndarray:
    """The uint32 key stream a run ranks on: ``keys`` directly, or —
    with an ``encode`` hook — the traceable order-preserving transform of
    a *raw* input (a codec ``encode_fn`` word column).  Inside a jitted
    run XLA fuses the elementwise encode into pass 0's digit extraction,
    so the first histogram/rank reads raw-encoded digits with no
    materialized code array between — the paper's fused key-based
    histogram-update shape.  Every backend picks the hook up for free:
    the encoded stream is what reaches ``rank``/``histogram``
    (the Pallas ``fractal_rank``/``fractal_histogram`` kernels included).
    """
    if encode is None:
        return keys.astype(jnp.uint32)
    return encode(keys).astype(jnp.uint32)


#: fewest rows a TPU placement sorts.  XLA's TPU scatter itself sorts by
#: the indices from ~4.1M rows up (none at 4,050,000 rows, a sort at
#: 4,100,000; compiled for a v5e), and there one sort of all the arrays
#: saves its scatter fusions.  Below, the scatter is a native fusion, and
#: a sort would add 15-26 s of TPU compile for each new n (2 or 3
#: operands at 1,460,000 rows, against ~2 s): a set-up cost that shapes
#: following the data, as a join's output, pay on every new input.
SORT_PLACEMENT_MIN_ROWS = 1 << 22


def _scatter_placement(rank: jnp.ndarray, *arrays: jnp.ndarray) -> tuple:
    """Each array's elements placed at their ranks, one scatter an array."""
    return tuple(jnp.zeros_like(a).at[rank].set(a) for a in arrays)


def sort_placement(rank: jnp.ndarray, *arrays: jnp.ndarray) -> tuple:
    """Each array's elements placed at their ranks, all arrays by one sort
    keyed by rank.  A pass's ranks are a permutation of ``[0, n)``, so
    the arrays in rank order are the placed arrays.  The ranks are
    unique, so the sort need not be stable."""
    return tuple(jax.lax.sort((rank, *arrays), num_keys=1,
                              is_stable=False)[1:])


class PassBackend:
    """Per-pass primitives a :class:`PlanExecutor` composes into a sort.

    A backend provides stable digit *ranking* plus (optionally) its own
    scatter and Algorithm-5 reconstruction.  Backends whose passes place
    keys at exact global output slots themselves (the distributed
    all_to_all pass) override :meth:`lsd_pass` wholesale and set
    ``reconstructs = False``.
    """

    #: whether the MSD pass compresses entries + rebuilds prefix bits from
    #: bin positions (Alg. 5); False runs it as one more exact full pass.
    reconstructs: bool = True

    #: base chunk length the per-pass ``rank_batch`` hints derive from;
    #: backends with a user-facing batch/block knob override this so the
    #: knob reaches the rank engine.
    rank_base: int = 1024

    def begin_run(self) -> None:
        """Reset per-run backend state.  Called by the executor at the
        start of every ``run*`` — backends accumulating flags across
        passes (the distributed overflow bit) reset them here so a reused
        executor never leaks one run's state into the next."""

    def rank(self, digit: jnp.ndarray, n_bins: int, *,
             batch_hint: Optional[int] = None,
             carry_in: Optional[jnp.ndarray] = None,
             bin_start: Optional[jnp.ndarray] = None,
             engine: Optional[str] = None):
        """Stable output slot per key for one digit stream.

        Returns ``(rank, counts, carry_out)`` — the streaming-carry
        contract of :func:`~repro.core.fractal_sort.fractal_rank`.
        ``engine`` is the pass's rank-engine hint ("onehot"/"scatter");
        ``None`` lets the backend pick (by digit width or its native
        tile).
        """
        raise NotImplementedError

    def histogram(self, digit: jnp.ndarray, n_bins: int,
                  init: Optional[jnp.ndarray] = None) -> jnp.ndarray:
        """Bin counts of one digit stream; values outside ``[0, n_bins)``
        (e.g. the ``n_bins`` chunk-padding sentinel) contribute nothing.
        The histogram half of a pass, exposed on its own so streaming
        consumers can accumulate counts across chunks without ranking —
        ``init`` seeds the counts with the carry from previous chunks
        (one fused scatter-add here; the Pallas kernel seeds its pinned
        VMEM accumulator)."""
        base = jnp.zeros((n_bins,), jnp.int32) if init is None else init
        return base.at[digit].add(1, mode="drop")

    def scatter(self, rank: jnp.ndarray, *arrays: jnp.ndarray):
        """Place each array's elements at their ranks (payload carry).

        The barrier keeps the rank arithmetic out of the placement's
        fusion: fused, the TPU compile of one pass grows with n (100 s
        at 2**26 keys on v5e, against 16 s unfused).

        Lowered for TPU, 1-D arrays of at least
        :data:`SORT_PLACEMENT_MIN_ROWS` all ride one
        :func:`sort_placement`: there XLA lowers a scatter to a sort by
        the indices and a scatter of the sorted values into the slots
        they already hold, once per array.  Elsewhere XLA's scatter is a
        direct O(n) store loop, which a sort would only slow."""
        rank, arrays = jax.lax.optimization_barrier((rank, arrays))
        if (rank.shape[0] < SORT_PLACEMENT_MIN_ROWS
                or any(a.shape != rank.shape for a in arrays)):
            return _scatter_placement(rank, *arrays)
        return jax.lax.platform_dependent(rank, *arrays, tpu=sort_placement,
                                          default=_scatter_placement)

    def lsd_pass(self, u: jnp.ndarray, dp: DigitPass) -> jnp.ndarray:
        """One stable counting pass scattering the full keys by a digit."""
        u, = self.lsd_pass_pairs(u, (), dp)
        return u

    def lsd_pass_pairs(self, u: jnp.ndarray, payloads: tuple,
                       dp: DigitPass) -> tuple:
        """One stable counting pass moving the keys *and* every payload
        array to the digit's rank order.  Returns ``(u, *payloads)``.
        Backends that fuse rank + placement (distributed) override this so
        payloads ride the same routing as the keys."""
        rank, _, _ = self.rank(_digit_of(u, dp), dp.n_bins,
                               batch_hint=dp.rank_batch(self.rank_base),
                               engine=dp.engine)
        return self.scatter(rank, u, *payloads)

    def reconstruct(self, counts: jnp.ndarray, trailing: jnp.ndarray,
                    plan: SortPlan) -> jnp.ndarray:
        """Algorithm 5: sorted keys from bin counts + permuted trailing
        entries; prefix bits recovered from bin position."""
        raise NotImplementedError


class JnpBackend(PassBackend):
    """Pure-jnp primitives (chunk-parallel one-hot rank, sorted-tile
    scatter rank, jnp scatter).

    Engine selection: an explicit per-pass hint (``DigitPass.engine``)
    wins; without one the digit width picks
    (:func:`~repro.core.sort_plan.default_engine`) — narrow digits run
    the one-hot tile, wide digits the scatter engine.
    """

    def __init__(self, batch: int = 1024):
        self.batch = batch
        self.rank_base = batch  # the user batch knob feeds the pass hints

    def rank(self, digit, n_bins, *, batch_hint=None, carry_in=None,
             bin_start=None, engine=None):
        from repro.core.fractal_sort import rank_engine
        from repro.core.sort_plan import default_engine, scatter_tile_len

        if engine is None:
            engine = default_engine(max(n_bins - 1, 1).bit_length())
            # a hint computed for the other engine's tile shape must not
            # leak in: re-derive it for the picked engine.
            if engine == "scatter":
                batch_hint = scatter_tile_len(n_bins, self.batch)
        batch = self.batch if batch_hint is None else batch_hint
        return rank_engine(engine)(digit, n_bins, batch=batch,
                                   carry_in=carry_in, bin_start=bin_start)

    def reconstruct(self, counts, trailing, plan):
        from repro.core.fractal_sort import reconstruct

        last = plan.passes[-1]
        return reconstruct(counts, trailing.astype(jnp.uint32),
                           last.bits, plan.p)


class PallasBackend(PassBackend):
    """TPU-kernel primitives (interpret mode executes the kernel bodies
    on CPU; on a real TPU backend the kernels compile)."""

    def __init__(self, block: int = 1024, interpret: Optional[bool] = None):
        self.block = block
        self.interpret = default_interpret(interpret)

    def rank(self, digit, n_bins, *, batch_hint=None, carry_in=None,
             bin_start=None, engine=None):
        if carry_in is not None:
            raise NotImplementedError(
                "streaming carry is a JnpBackend mode; the rank kernel "
                "holds its carry in VMEM scratch per call")
        from repro.kernels.fractal_rank import fractal_rank_counts

        return fractal_rank_counts(digit, n_bins, block=self.block,
                                   interpret=self.interpret,
                                   bin_start=bin_start, engine=engine)

    def histogram(self, digit, n_bins, init=None):
        from repro.kernels.fractal_histogram import fractal_histogram

        return fractal_histogram(digit, n_bins, block=self.block,
                                 interpret=self.interpret, init=init)

    def reconstruct(self, counts, trailing, plan):
        from repro.kernels.fractal_reconstruct import fractal_reconstruct_plan

        return fractal_reconstruct_plan(counts, trailing.astype(jnp.int32),
                                        plan, block=self.block,
                                        interpret=self.interpret)


class DistributedBackend(PassBackend):
    """One collective pass per plan digit, inside a ``shard_map`` body.

    Every pass is *exact* global placement on its field (local rank +
    psum histogram merge injecting the global ``bin_start`` and the
    cross-device carry, then all_to_all routing), so there is nothing to
    reconstruct — the MSD digit runs as one more exact pass
    (``reconstructs = False``).  Bucket-overflow flags accumulate across
    passes *within one run* (:meth:`begin_run` resets them, so a reused
    executor never reports a previous run's overflow); read
    :attr:`overflow` after the run.
    """

    reconstructs = False

    def __init__(self, axis: str, capacity: int, batch: int = 1024,
                 taper_wire: bool = True):
        self.axis = axis
        self.capacity = capacity
        self.batch = batch
        self.taper_wire = taper_wire
        self.overflow = None  # traced bool, set by the first pass of a run

    def begin_run(self):
        self.overflow = None

    def rank(self, digit, n_bins, *, batch_hint=None, carry_in=None,
             bin_start=None, engine=None):
        raise NotImplementedError(
            "the distributed pass fuses rank + placement; use lsd_pass")

    def lsd_pass(self, u, dp):
        u, = self.lsd_pass_pairs(u, (), dp)
        return u

    def lsd_pass_pairs(self, u, payloads, dp):
        from repro.core.distributed import _distributed_pass

        out, ov = _distributed_pass(u, dp.shift, dp.bits, self.axis,
                                    self.capacity, self.batch,
                                    self.taper_wire, payloads=payloads,
                                    engine=dp.engine)
        self.overflow = ov if self.overflow is None else self.overflow | ov
        return out


class PlanExecutor:
    """Runs a :class:`SortPlan` against one :class:`PassBackend`.

    The *only* pass loop in the codebase: every public sort entry point
    (`fractal_sort`, `fractal_argsort`, `fractal_sort_batched`,
    `fractal_sort_kernel`, `make_distributed_sort`) builds a plan and
    hands it here.
    """

    def __init__(self, backend: PassBackend):
        self.backend = backend

    # -- per-pass tracing ---------------------------------------------------

    def _pass_stats(self, u, plan: SortPlan, with_index: bool):
        """Per-pass byte ledger for span attribution, or None when spans
        are off for this run.

        Spans only fire on *eager* runs: the public sort entry points are
        themselves jitted, and a span opened while jax is tracing would
        measure trace time, not pass time — a Tracer input disables the
        ledger.  The bytes attached are the analytic model's per-pass
        read/write volumes (:func:`~repro.core.fractal_sort.
        fractal_sort_stats`) — the quantities the paper's bandwidth model
        counts — paired with *measured* per-pass wall, which is what
        ``obs.bandwidth_report`` turns into measured bytes/s and
        measured b_eff."""
        if not trace.enabled():
            return None
        if isinstance(u, jax.core.Tracer):
            return None
        from repro.core.fractal_sort import fractal_sort_stats
        n = int(u.shape[0])
        try:
            stats = fractal_sort_stats(n, plan.p, with_index=with_index,
                                       plan=plan)
        except Exception:
            return None
        if len(stats.pass_stats) != len(plan.passes):
            return None
        return stats.pass_stats

    @staticmethod
    def _pass_span(pass_stats, index: int, dp: DigitPass):
        if pass_stats is None:
            return trace.NULL
        ps = pass_stats[index]
        return trace.span(
            "executor.pass", index=index, kind=ps.kind, shift=dp.shift,
            bits=dp.bits, bytes_read=ps.bytes_read,
            bytes_written=ps.bytes_written)

    @staticmethod
    def _sync(*arrays) -> None:
        """Drain async dispatch so a pass span's wall covers its work."""
        jax.block_until_ready(arrays)

    # -- plain sort ---------------------------------------------------------

    def run(self, keys: jnp.ndarray, plan: SortPlan,
            encode=None) -> jnp.ndarray:
        """Sorted keys.  Backends with ``reconstructs`` return the
        Algorithm-5 output dtype (int32/uint32 by ``plan.p``); others
        return the uint32 key stream — callers cast as needed.

        ``encode`` (here and on every ``run*`` mode) is the fused-encode
        hook: a traceable order-preserving transform applied to ``keys``
        *inside* the run (:func:`_as_key_stream`), so raw columns enter
        and pass 0 extracts digits straight off the encoded stream."""
        self.backend.begin_run()
        u = _as_key_stream(keys, encode)
        if u.shape[0] == 0 or not plan.passes:
            # empty input, or the p=0 identity plan
            return u if encode is not None else keys
        pass_stats = self._pass_stats(u, plan, with_index=False)
        for i, dp in enumerate(plan.passes[:-1]):
            with self._pass_span(pass_stats, i, dp):
                u = self.backend.lsd_pass(u, dp)
                if pass_stats is not None:
                    self._sync(u)
        last = plan.passes[-1]
        with self._pass_span(pass_stats, len(plan.passes) - 1, last):
            if not self.backend.reconstructs:
                out = self.backend.lsd_pass(u, last)
            else:
                rank, counts, _ = self.backend.rank(
                    _digit_of(u, last), last.n_bins,
                    batch_hint=last.rank_batch(self.backend.rank_base),
                    engine=last.engine)
                if last.shift:
                    # compressed entries: only the trailing bits travel;
                    # the prefix is rebuilt from bin positions.
                    (trailing,) = self.backend.scatter(
                        rank, u & jnp.uint32((1 << last.shift) - 1))
                else:
                    # zero-payload regime: output from bin positions alone.
                    trailing = jnp.zeros_like(u)
                out = self.backend.reconstruct(counts, trailing, plan)
            if pass_stats is not None:
                self._sync(out)
        return out

    # -- key–value (pairs) sort ---------------------------------------------

    def run_pairs(self, keys: jnp.ndarray, values, plan: SortPlan,
                  encode=None):
        """Sort key–payload pairs by key: every LSD pass carries the
        payload alongside the keys, and the final fractal MSD pass scatters
        the payload next to the compressed trailing-bit entries — the
        prefix bits are still reconstructed from bin positions (Alg. 5),
        only the payload and trailing bits travel.  ``values`` is one
        payload array, or a tuple of payload arrays all carried through
        the same passes (the distributed StreamTable path rides several
        columns at once).  Returns ``(sorted_keys,
        values_in_sorted_key_order)`` with values shaped like the input
        (array in, array out; tuple in, tuple out); ties keep arrival
        order (stable), which is what the query operators lean on for
        multi-word keys and reproducible joins."""
        single = not isinstance(values, tuple)
        payloads = (values,) if single else tuple(values)
        self.backend.begin_run()
        u = _as_key_stream(keys, encode)
        if u.shape[0] == 0 or not plan.passes:
            # empty input, or the p=0 identity plan
            return (u if encode is not None else keys), values
        pass_stats = self._pass_stats(u, plan, with_index=True)
        for i, dp in enumerate(plan.passes[:-1]):
            with self._pass_span(pass_stats, i, dp):
                u, *payloads = self.backend.lsd_pass_pairs(
                    u, tuple(payloads), dp)
                if pass_stats is not None:
                    self._sync(u, *payloads)
        last = plan.passes[-1]
        with self._pass_span(pass_stats, len(plan.passes) - 1, last):
            if not self.backend.reconstructs:
                u, *payloads = self.backend.lsd_pass_pairs(
                    u, tuple(payloads), last)
                if pass_stats is not None:
                    self._sync(u, *payloads)
                return u, (payloads[0] if single else tuple(payloads))
            rank, counts, _ = self.backend.rank(
                _digit_of(u, last), last.n_bins,
                batch_hint=last.rank_batch(self.backend.rank_base),
                engine=last.engine)
            if last.shift:
                trailing, *payloads = self.backend.scatter(
                    rank, u & jnp.uint32((1 << last.shift) - 1), *payloads)
            else:
                payloads = self.backend.scatter(rank, *payloads)
                trailing = jnp.zeros_like(u)
            keys_out = self.backend.reconstruct(counts, trailing, plan)
            if pass_stats is not None:
                self._sync(keys_out, *payloads)
        return keys_out, (payloads[0] if single else tuple(payloads))

    # -- argsort ------------------------------------------------------------

    def run_argsort(self, keys: jnp.ndarray, plan: SortPlan,
                    encode=None) -> jnp.ndarray:
        """Stable permutation with ``keys[perm]`` sorted: every pass is a
        payload-carrying LSD pass (the permutation is the payload, so
        there is nothing to reconstruct from bin positions)."""
        self.backend.begin_run()
        u = _as_key_stream(keys, encode)
        n = u.shape[0]
        idx = jnp.arange(n, dtype=jnp.int32)
        if n == 0 or not plan.passes:
            return idx  # p=0: all keys equal, stable perm is the identity
        pass_stats = self._pass_stats(u, plan, with_index=True)
        for i, dp in enumerate(plan.passes):
            with self._pass_span(pass_stats, i, dp):
                u, idx = self.backend.lsd_pass_pairs(u, (idx,), dp)
                if pass_stats is not None:
                    self._sync(u, idx)
        return idx

    # -- segmented argsort (batched equal-length sorts) ----------------------

    def run_segmented_argsort(self, keys: jnp.ndarray, plan: SortPlan,
                              seg_len_log2: int,
                              encode=None) -> jnp.ndarray:
        """Stable argsort *within* equal-length power-of-two segments.

        ``keys`` is ``B`` independent arrays of length ``2**seg_len_log2``
        laid end to end; the returned permutation sorts each segment in
        place (``keys[perm]`` is sorted within every segment, and
        ``perm[b*L:(b+1)*L]`` stays inside ``[b*L, (b+1)*L)``).  This is
        the batched partition-sort mode: B padded partitions rank through
        ONE jitted program instead of B chain dispatches, reusing the
        grouped-trailing within-segment re-rank (a pass's global rank
        gives the arrival among equal digits; a ``(B, n_bins)``
        scatter-add table converts that to the within-segment rank).
        Segment membership is *positional* (``slot >> seg_len_log2``), so
        — unlike :meth:`run_grouped_trailing`, whose segments come from
        bin counts — the map is trivially scatter-invariant: ranks never
        cross segments.
        """
        self.backend.begin_run()
        u = _as_key_stream(keys, encode)
        n = u.shape[0]
        idx = jnp.arange(n, dtype=jnp.int32)
        if n == 0 or not plan.passes:
            return idx  # empty batch, or p=0: identity within each segment
        nseg = n >> seg_len_log2
        seg = (idx >> seg_len_log2).astype(jnp.int32)
        seg_start = (seg << seg_len_log2).astype(jnp.int32)
        for dp in plan.passes:
            digit = _digit_of(u, dp)
            # zero bin_start: rank IS the arrival among equal digits in
            # array (= segment-major) order, same trick as grouped mode.
            arr_g, _, _ = self.backend.rank(
                digit, dp.n_bins,
                batch_hint=dp.rank_batch(self.backend.rank_base),
                bin_start=jnp.zeros((dp.n_bins,), jnp.int32),
                engine=dp.engine)
            table = jnp.zeros((nseg, dp.n_bins), jnp.int32).at[
                seg, digit].add(1)
            before_seg = jnp.cumsum(table, axis=0) - table  # earlier segments
            lower = jnp.cumsum(table, axis=1) - table       # smaller digits
            rank = (seg_start + lower[seg, digit]
                    + arr_g - before_seg[seg, digit])
            u, idx = self.backend.scatter(rank, u, idx)
        return idx

    # -- per-chunk histogram accumulation (streaming consumers) --------------

    def digit_counts(self, keys: jnp.ndarray, dp: DigitPass,
                     init: Optional[jnp.ndarray] = None,
                     pad_to: Optional[int] = None) -> jnp.ndarray:
        """One chunk's histogram of ``dp``'s digit, accumulated onto
        ``init`` — the hook the out-of-core subsystem
        (:mod:`repro.stream`) streams a :class:`~repro.stream.ChunkSource`
        through: one call per chunk, the running counts carried across
        chunks exactly like the two-phase rank carries its per-chunk
        histograms (paper §III.D, applied at dataset scale).

        ``pad_to`` pads the digit stream with the out-of-range sentinel
        ``dp.n_bins`` (dropped by every backend's histogram) so ragged
        tail chunks keep one jit trace.
        """
        digit = _digit_of(keys.astype(jnp.uint32), dp)
        if pad_to is not None and pad_to > digit.shape[0]:
            digit = jnp.concatenate([
                digit,
                jnp.full((pad_to - digit.shape[0],), dp.n_bins, jnp.int32)])
        return self.backend.histogram(digit, dp.n_bins, init=init)

    # -- segment-aware grouped-trailing mode --------------------------------

    def run_grouped_trailing(self, entries: jnp.ndarray, counts: jnp.ndarray,
                             plan: SortPlan) -> jnp.ndarray:
        """Finish a sort whose array is already grouped by the MSD prefix.

        ``entries`` holds, per slot, the ``plan.trailing_bits`` trailing
        bits of a key whose prefix is implied by its segment (the slot's
        bin, from ``counts``); each trailing LSD pass re-ranks *within*
        segments so grouping is invariant and the MSD pass never re-runs.
        Returns the reconstructed sorted keys.
        """
        self.backend.begin_run()
        n = entries.shape[0]
        last = plan.passes[-1]
        if n == 0 or last.shift == 0:
            return self.backend.reconstruct(counts, jnp.zeros_like(entries),
                                            plan)
        ends = jnp.cumsum(counts.astype(jnp.int32))
        seg_start = ends - counts
        # slot -> segment; ranks never cross segments, so this map is
        # invariant across every trailing pass (computed once).
        seg = jnp.searchsorted(ends, jnp.arange(n, dtype=jnp.int32),
                               side="right").astype(jnp.int32)
        u = entries.astype(jnp.uint32)
        for dp in plan.passes[:-1]:
            digit = _digit_of(u, dp)
            # zero bin_start: the rank IS the arrival among equal digits,
            # in array (= segment-major) order — no global-start round-trip
            arr_g, _, _ = self.backend.rank(
                digit, dp.n_bins,
                batch_hint=dp.rank_batch(self.backend.rank_base),
                bin_start=jnp.zeros((dp.n_bins,), jnp.int32),
                engine=dp.engine)
            # (segments, n_bins) digit table: one O(n) scatter-add
            table = jnp.zeros((last.n_bins, dp.n_bins), jnp.int32).at[
                seg, digit].add(1)
            before_seg = jnp.cumsum(table, axis=0) - table  # earlier segments
            lower = jnp.cumsum(table, axis=1) - table       # smaller digits
            rank = (seg_start[seg] + lower[seg, digit]
                    + arr_g - before_seg[seg, digit])
            (u,) = self.backend.scatter(rank, u)
        return self.backend.reconstruct(counts, u, plan)

    # -- streaming (batched) mode -------------------------------------------

    def run_streaming(self, keys: jnp.ndarray, plan: SortPlan,
                      num_batches: int):
        """Streaming sort (paper §III.C/D): the input arrives in
        ``num_batches`` slices; the trie histogram is cached and merged
        across slices, ranks stream through the shared carry, and one
        scatter groups entries by the plan's MSD prefix.  The trailing
        bits then sort segment-aware (:meth:`run_grouped_trailing`) when
        the plan supports it, falling back to a full re-plan for very
        wide plans.  Returns ``(sorted_keys, per-slice histograms)``.
        """
        from repro.core import fractal_tree as ft

        self.backend.begin_run()
        if not plan.passes:
            return keys, []  # the p=0 identity plan: nothing to histogram
        n = keys.shape[0]
        depth, t = plan.depth, plan.trailing_bits
        last = plan.passes[-1]
        slices = jnp.array_split(keys, num_batches)
        hists = [ft.build_histogram(s, plan.p, depth) for s in slices]
        merged = functools.reduce(ft.merge_histograms, hists)
        counts = merged.leaf_counts
        bin_start = exclusive_cumsum(counts)
        carry = jnp.zeros((1 << depth,), jnp.int32)
        grouped = t == 0 or plan.supports_grouped_trailing
        mask = jnp.uint32((1 << t) - 1)
        out = jnp.zeros((n,), jnp.uint32)
        for s in slices:
            su = s.astype(jnp.uint32)
            prefix = (su >> t).astype(jnp.int32)
            rank, _, carry = self.backend.rank(
                prefix, 1 << depth, carry_in=carry, bin_start=bin_start,
                engine=last.engine)
            # grouped mode scatters only the compressed trailing entries
            # (the prefix is implied by the destination segment); the
            # fallback must carry full keys for its plan re-run.
            out = out.at[rank].set(su & mask if grouped else su)
        if grouped:  # covers t == 0: reconstruct from counts alone
            sorted_u = self.run_grouped_trailing(out, counts, plan)
        else:
            sorted_u = self.run(out, plan)
        return sorted_u.astype(keys.dtype), hists
