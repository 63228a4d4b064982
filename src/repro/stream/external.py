"""External sort: budget-bounded sorting of datasets ≫ RAM.

Three streaming passes, the classic distribution-sort shape driven by the
fractal histogram instead of sampled splitters:

1. **histogram** — one read of the :class:`~repro.stream.chunks.
   ChunkSource`, accumulating the leading MSD field's counts across
   chunks (:func:`~repro.stream.partition.streamed_field_counts`; one
   executor ``digit_counts`` call per chunk, counts carried like the
   two-phase rank's chunk histograms);
2. **distribute** — a second read; each chunk's rows route to their
   budget-fitting partition (:func:`~repro.stream.partition.
   partition_bins`) and *place* as per-partition fragments through the
   :class:`~repro.stream.chunks.PlacementStore` (disk spill on the run
   store, one mesh ``all_to_all`` on
   :class:`~repro.stream.device_store.DeviceShardStore`), arrival order
   preserved;
3. **sort-and-emit** — partitions load one at a time (they fit the
   budget by prediction), sort through the store's
   :meth:`~repro.stream.chunks.PlacementStore.sort_rows` (the executor
   pass chain on disk, the DistributedBackend pairs path on devices),
   and stream out.  Partitions are disjoint key ranges, so concatenation
   *is* the stable total order — no k-way merge (that path exists for
   pre-sorted runs in :mod:`~repro.stream.merge`).

This loop never names a placement: it histograms, plans partitions, and
asks the store to distribute and sort — "shards are runs".  Two
placement-independent cuts ride the loop:

* **narrowed partition sorts** — a partition's bin range pins the top
  bits of its partitioning field (:meth:`~repro.stream.partition.
  KeyPartition.shared_field_bits`), so each partition sorts only its
  undetermined low bits (~1/3 of the pass work gone at p=32 under
  10 partition bits);
* **overlapped sort + spill I/O** — with ``REPRO_STREAM_WORKERS > 1``
  (and a store that allows concurrent sorts) upcoming partitions load
  and sort on a thread pool while earlier ones stream out, overlapping
  fragment reads with compute; emission order, and therefore output,
  is bit-identical at any worker count.

A partition the histogram predicts oversized is always a single bin
(greedy merging never overfills), so every key in it shares that bin's
digit: the sort **recursively re-partitions** it on the next field down —
the skew fallback — terminating at fully-equal keys, which stream out in
arrival order (trivially sorted, stability free).

Fault tolerance rides the same placement seam:

* **resumable manifests** (``journal=``/``resume=``) — the loop journals
  its progress (histogram snapshot, fragment ids, per-partition done
  run ids) through the store's verified log channel; after a crash,
  ``resume=`` replays completed partitions from their spilled result
  runs and recomputes **zero** of them — bit-identical to an
  uninterrupted run (requires a store on a durable root);
* **graceful degradation** — a store whose partition sort dies with a
  :class:`~repro.core.faults.StorePermanentError` and advertises
  ``failover_to_disk`` (the device store: its fragments keep host
  mirrors) has its remaining fragments migrated to a fresh disk store
  via :func:`~repro.stream.chunks.temp_store`, and emission continues
  bit-exact;
* **prompt failure** — a worker-pool partition sort that raises cancels
  every pending lookahead future and surfaces immediately; the pool
  never hangs emission on doomed work.

Everything here operates on ``(n, W)`` uint32 code-word matrices (the
query codec layout), so one core serves plain ≤ 32-bit keys
(:func:`external_sort` / :func:`external_argsort`) and the StreamTable
operators' arbitrarily wide composite codes.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, Iterator, Optional, Tuple

import numpy as np

from repro.core.executor import PlanExecutor
from repro.core.faults import StoreError, StorePermanentError
from repro.core.fractal_tree import ceil_log2
from repro.core.sort_plan import DigitPass, make_sort_plan, quantize_sort_bits
from repro.obs import metrics, trace
from repro.query.codec import word_widths
from repro.stream.chunks import (
    ChunkSource,
    MemoryBudget,
    PlacementStore,
    temp_store,
)
from repro.stream.partition import (
    DEFAULT_PARTITION_BITS,
    bin_to_partition,
    partition_bins,
    streamed_field_counts,
)

__all__ = [
    "external_argsort",
    "external_sort",
    "row_cost_bytes",
    "stream_sorted_words",
]


def row_cost_bytes(num_words: int, payload_bytes: int = 0) -> int:
    """Per-row byte cost the budget's ``rows()`` divides by, modeling the
    *partition-sort moment* — the subsystem's residency peak.  There a
    row's code words exist up to three times at up to 2× power-of-two
    padding (host padded matrix + device input + device sorted output:
    ``24 * num_words`` B/row), the padded row ids twice (device + host,
    ~12 B/row), and each payload column twice (spilled + gathered).
    ``MemoryBudget.rows()`` already halves for headroom, so the model
    here carries half the worst case; the store's ``sort_rows`` charges
    the same moments to the tracker, keeping the asserted ``peak_bytes``
    honest against this sizing."""
    return 12 * num_words + 6 + payload_bytes


def _emitted(words: np.ndarray, payloads: tuple) -> None:
    """Account one emitted chunk: registry counters always, plus a
    zero-width ``stream.emit`` marker span (the byte ledger of the emit
    phase) when tracing.  Crucially *closed before the caller yields* —
    a span held open across a generator ``yield`` would dangle on the
    consumer thread's stack."""
    rows = int(words.shape[0])
    nbytes = int(words.nbytes) + sum(int(p.nbytes) for p in payloads)
    metrics.counter("stream.emit.rows").inc(rows)
    metrics.counter("stream.emit.bytes").inc(nbytes)
    if trace.enabled():
        with trace.span("stream.emit", rows=rows, bytes=nbytes):
            pass


def _stream_workers() -> int:
    """Worker threads for the overlapped load+sort path: the
    ``REPRO_STREAM_WORKERS`` env knob, default 1 (fully sequential).
    Read per call so tests can flip it without re-importing."""
    try:
        return max(1, int(os.environ.get("REPRO_STREAM_WORKERS", "1")))
    except ValueError:
        return 1


def _extract_field(words: np.ndarray, bits: int, shift: int,
                   width: int) -> np.ndarray:
    """Code bits ``[shift, shift + width)`` (LSB-based) of every row of an
    MSB-first ``(n, W)`` uint32 word matrix, as uint32 values.  The numpy
    twin of :meth:`~repro.query.codec.CompositeCodec._extract`, offset
    from the LSB because partitioning peels fields MSD→LSD."""
    assert 0 < width <= 32 and shift + width <= bits
    widths = word_widths(bits)
    out = np.zeros((words.shape[0],), np.uint32)
    off = bits  # walking MSB-first, word j covers [off - widths[j], off)
    for j, wj in enumerate(widths):
        off -= wj
        lo = max(shift, off)
        hi = min(shift + width, off + wj)
        if lo >= hi:
            continue
        piece = (words[:, j] >> np.uint32(lo - off)) \
            & np.uint32((1 << (hi - lo)) - 1)
        out |= (piece << np.uint32(lo - shift)).astype(np.uint32)
    return out


def _load_fragments(store: PlacementStore, frag_ids, n_payloads: int,
                    budget: MemoryBudget):
    """One partition back from its placed fragments, arrival order."""
    pieces = [store.get(rid) for rid in frag_ids]
    words = np.concatenate([p[0] for p in pieces]) if pieces else \
        np.zeros((0, 1), np.uint32)
    payloads = tuple(
        np.concatenate([p[1 + i] for p in pieces])
        for i in range(n_payloads))
    budget.charge(words, *payloads)
    return words, payloads


def stream_sorted_words(
    chunks_fn: Callable[[], Iterator[tuple]],
    bits: int,
    budget: MemoryBudget,
    store: PlacementStore,
    row_bytes: int,
    hi: Optional[int] = None,
    executor: Optional[PlanExecutor] = None,
    partition_bits: int = DEFAULT_PARTITION_BITS,
    limit_rows: Optional[int] = None,
    journal: Optional[str] = None,
    resume=None,
) -> Iterator[Tuple[np.ndarray, tuple]]:
    """The recursive external-sort core over ``(words, payloads)`` chunks.

    ``chunks_fn`` is a re-iterable factory (called once for the histogram
    pass, once for the distribution pass) yielding ``(words, payloads)``
    tuples — ``words`` an ``(m, W)`` uint32 code matrix, ``payloads`` a
    tuple of equal-length arrays riding along.  Yields the same shape in
    global stable code order, every yielded chunk within the budget.

    ``store`` is any :class:`~repro.stream.chunks.PlacementStore`: this
    loop only ever distributes chunks into partition fragments, reads
    fragments back, and asks the store to sort one partition — where
    fragments live (disk runs, device shards) is the store's business.

    ``hi`` is the number of undetermined low code bits (every row already
    shares bits ``[hi, bits)`` — the recursion invariant; level 0 streams
    arrival order, which for fully-equal codes is the stable sorted
    order).  ``limit_rows`` stops after that many rows *and prunes ahead
    of the distribution pass*: partitions the histogram proves past the
    limit are never placed, let alone loaded — the top-k path (on a
    device store, pruned partitions' owner devices receive nothing).

    ``journal`` names a manifest on the store's log channel that this
    call keeps current: histogram snapshot once counted, fragment ids
    once distributed, and each partition's spilled result-run ids the
    moment it completes — so a crash at any partition boundary leaves a
    resumable record next to the fragments it indexes.  ``resume`` is a
    prior run's manifest (the dict, or its journal name to read from the
    store); completed partitions replay from their result runs with zero
    recomputation and the rest proceed normally, so the concatenated
    output is bit-identical to the uninterrupted run.  Both require a
    store on a durable root and the same budget, and neither composes
    with ``limit_rows`` (a pruned sort re-plans under a new limit).
    """
    hi = bits if hi is None else hi
    emitted = 0
    if journal is not None or resume is not None:
        assert limit_rows is None, \
            "journal/resume do not compose with limit_rows"
    manifest = None
    if resume is not None:
        manifest = store.read_log(resume) if isinstance(resume, str) \
            else resume
        if isinstance(resume, str) and journal is None:
            journal = resume  # keep journaling where we resumed from
        if manifest is not None and manifest.get("complete"):
            manifest = None  # finished runs have nothing to replay

    def room() -> Optional[int]:
        return None if limit_rows is None else max(limit_rows - emitted, 0)

    def clip(words, payloads):
        r = room()
        if r is not None and words.shape[0] > r:
            return words[:r], tuple(p[:r] for p in payloads)
        return words, payloads

    if hi == 0:
        # every code fully determined: arrival order is the stable sort
        for words, payloads in chunks_fn():
            budget.charge(words, *payloads)
            words, payloads = clip(words, payloads)
            if words.shape[0]:
                _emitted(words, payloads)
                yield words, payloads
                emitted += int(words.shape[0])
            if room() == 0:
                return
        return

    w = min(partition_bits, hi)
    dp = DigitPass(shift=0, bits=w)
    n_payloads = None
    hist_bytes = [0]  # code-word bytes the histogram pass streamed

    def field_chunks():
        nonlocal n_payloads
        for words, payloads in chunks_fn():
            if n_payloads is None:
                n_payloads = len(payloads)
            budget.charge(words, *payloads)
            hist_bytes[0] += int(words.nbytes)
            yield _extract_field(words, bits, hi - w, w)

    if manifest is not None:
        # resume: the histogram pass already ran and was journaled; the
        # partition plan must re-derive identically (deterministic from
        # counts + budget), so the shape invariants are asserted
        assert (manifest["bits"] == bits and manifest["hi"] == hi
                and manifest["w"] == w), "resume manifest shape mismatch"
        counts = np.asarray(manifest["counts"], np.int64)
        n_total = int(manifest["n_total"])
        n_payloads = int(manifest["n_payloads"])
        budget_rows = budget.rows(row_bytes)
        assert budget_rows == int(manifest["budget_rows"]), (
            "resume requires the same memory budget (the partition plan "
            "derives from it)")
    else:
        with trace.span("stream.histogram", level_bits=hi, width=w) as hsp:
            counts, n_total = streamed_field_counts(field_chunks(), dp,
                                                    executor)
            hsp.set(rows=int(n_total), bytes_in=hist_bytes[0])
        if n_total == 0:
            return
        budget_rows = budget.rows(row_bytes)
        if journal is not None:
            manifest = {
                "version": 1, "bits": bits, "hi": hi, "w": w,
                "budget_rows": budget_rows, "n_total": n_total,
                "n_payloads": n_payloads,
                "counts": [int(c) for c in counts],
                "done": {}, "complete": False,
            }
            store.write_log(journal, manifest)
    if manifest is None:
        manifest = {"done": {}}  # uniform access below; never journaled
    done: dict = dict(manifest.get("done", {}))

    if n_total <= budget_rows:
        # the data fit after all: one in-memory sort, no placement pass
        pieces = list(chunks_fn())
        words = np.concatenate([p[0] for p in pieces])
        payloads = tuple(np.concatenate([p[1][i] for p in pieces])
                         for i in range(n_payloads))
        words, payloads = store.sort_rows(words, payloads, bits, hi, budget)
        words, payloads = clip(words, payloads)
        if words.shape[0]:
            _emitted(words, payloads)
            yield words, payloads
        if journal is not None:
            manifest["complete"] = True
            store.write_log(journal, manifest)
        return

    partitions = list(partition_bins(counts, budget_rows))
    if limit_rows is not None:
        # histogram pruning: the first partitions whose cumulative count
        # reaches the limit are the only ones top-k rows can live in
        keep, cum = 0, 0
        while keep < len(partitions) and cum < limit_rows:
            cum += partitions[keep].count
            keep += 1
        partitions = partitions[:keep]
    lut = bin_to_partition(tuple(partitions), 1 << w)

    # distribution pass: the store places every row at its partition's
    # fragments (disk spill / device all_to_all — same call).  A resumed
    # run whose manifest reached this phase reuses the recovered
    # fragments instead (a crash *mid*-distribution resumes from the
    # histogram and redistributes; the torn pass's orphans are never
    # referenced).
    if manifest.get("frag_ids") is not None:
        frag_ids = [list(ids) for ids in manifest["frag_ids"]]
        assert len(frag_ids) == len(partitions), "resume manifest mismatch"
    else:
        frag_ids = [[] for _ in partitions]
        with trace.span("stream.distribute",
                        partitions=len(partitions)) as dsp:
            dist_rows, dist_bytes = 0, 0
            for words, payloads in chunks_fn():
                budget.charge(words, *payloads)
                dist_rows += int(words.shape[0])
                dist_bytes += int(words.nbytes) + sum(
                    int(p.nbytes) for p in payloads)
                digit = _extract_field(words, bits, hi - w,
                                       w).astype(np.int64)
                pid = lut[digit]
                for i, ids in enumerate(
                        store.distribute(words, payloads, pid,
                                         len(partitions))):
                    frag_ids[i].extend(ids)
            # rows/bytes are what the pass *streamed*; the spilled bytes
            # live on the nested store.put spans (no double counting)
            dsp.set(rows=dist_rows, bytes_in=dist_bytes)
        if journal is not None:
            manifest["frag_ids"] = [
                [int(r) for r in ids] for ids in frag_ids]
            store.write_log(journal, manifest)

    def plans_for(padded_len, sort_bits):
        """One default plan per active word, sized for the bucket's
        padded length: every partition of a (length, sort-bits) bucket
        gets equal plans, so they share one compiled chain."""
        from repro.query.operators import active_words

        return tuple(make_sort_plan(padded_len, eff)
                     for _, eff in active_words(bits, sort_bits))

    def part_bucket(part):
        """(padded pow2 length, quantized sort bits) — the shared-trace
        bucket a partition sorts in.  Sort bits round up to multiples of
        8 (the rounded-up bits are shared-prefix, ranking them reorders
        nothing), so near-miss widths share one compiled chain."""
        L = 1 << ceil_log2(max(part.count, 1))
        sort_bits = quantize_sort_bits(hi - part.shared_field_bits(w), bits)
        return L, sort_bits

    # `st` is the store partitions currently sort/emit through; it starts
    # as the caller's placement and swaps to a disk fallback if that
    # placement dies permanently mid-sort (failover below).  Fragments,
    # spilled batch members, and deletions all follow it.
    st = store
    fallback: Optional[PlacementStore] = None

    def sorted_partition(part, frags):
        # runs on pool worker threads too: the span parents under the
        # submitter's context via trace.wrap_ctx at submit time
        with trace.span("stream.partition_sort", rows=part.count) as sp:
            words, payloads = _load_fragments(st, frags, n_payloads,
                                              budget)
            sp.set(bytes_in=int(words.nbytes) + sum(
                int(p.nbytes) for p in payloads))
            # the partition's bin range pins the top shared_field_bits of
            # its field: only the code bits below stay undetermined, so
            # the sort narrows to them (a single-bin partition drops the
            # whole field)
            L, sort_bits = part_bucket(part)
            return st.sort_rows(words, payloads, bits, sort_bits, budget,
                                plans=plans_for(L, sort_bits))

    def fail_over(from_idx):
        """Migrate every not-yet-emitted fragment to a fresh disk store
        and swap ``st`` — graceful degradation when a placement's sort
        is permanently gone but its fragments (host mirrors) are not.
        Output stays bit-exact: fragments move whole, in order."""
        nonlocal st, fallback
        fb = temp_store()
        for j in range(from_idx, len(items)):
            pj, fj = items[j]
            moved = []
            for rid in fj:
                arrays = st.get(rid)
                moved.append(fb.put(arrays[0], *arrays[1:]))
                try:
                    st.delete(rid)
                except StoreError:
                    pass  # the dying store's cleanup is best-effort
            items[j] = (pj, moved)
        for i, rid in list(presorted.items()):
            arrays = st.get(rid)
            presorted[i] = fb.put(arrays[0], *arrays[1:])
            try:
                st.delete(rid)
            except StoreError:
                pass
        st = fallback = fb

    # sort-and-emit, partition (= key range) order.  With workers > 1 a
    # lookahead pool loads+sorts upcoming in-budget partitions while the
    # current one streams out (sort/spill-I/O overlap); consumption stays
    # strictly in partition order, so output is worker-count-invariant.
    # The pool is skipped under limit_rows (speculative loads would touch
    # partitions the prune proves dead) and on stores whose sorts are
    # collective (concurrent shard_map dispatch from threads interleaves).
    items = list(zip(partitions, frag_ids))
    workers = _stream_workers()
    pool: Optional[ThreadPoolExecutor] = None
    pending: dict = {}
    if workers > 1 and limit_rows is None and store.supports_concurrent_sorts:
        pool = ThreadPoolExecutor(max_workers=workers)

    # batched dispatch: same-bucket (padded pow2 length, quantized sort
    # bits) partitions small enough that several padded copies fit the
    # budget at once sort as ONE segment-aware program.  Greedy packing
    # makes two *consecutive* in-budget partitions always overflow a
    # shared load (adjacent counts sum past budget_rows by construction),
    # so groups form across intervening partitions — the skew regime,
    # where tiny flushed partitions interleave with oversized single
    # bins.  Out-of-order members' sorted rows spill back to the store as
    # one pre-sorted fragment and re-load at their emission turn, so
    # emission order, peak residency, and output stay exactly the serial
    # path's (any stable decomposition of the same partition yields THE
    # stable order).  Everything stays a singleton under limit_rows
    # (batching would load fragments the prune proves dead), under the
    # worker pool (the pool already pipelines), and on stores whose
    # sorts can't concatenate.
    group_of: dict = {}      # head index -> member indices, partition order
    if (pool is None and limit_rows is None and journal is None
            and not done and store.supports_batched_sorts):
        open_heads: dict = {}  # bucket -> open group's head index
        for i, (part, _) in enumerate(items):
            if part.oversized(budget_rows):
                continue
            L, qb = part_bucket(part)
            b_max = budget_rows // L
            if b_max < 2 or qb == 0:
                continue  # batch-ineligible: full-budget load, or no-op sort
            head = open_heads.get((L, qb))
            if head is not None and len(group_of[head]) < b_max:
                group_of[head].append(i)
            else:
                open_heads[(L, qb)] = i
                group_of[i] = [i]
        group_of = {h: g for h, g in group_of.items() if len(g) > 1}
    presorted: dict = {}     # member index -> spilled pre-sorted fragment

    def journal_done(idx, rids):
        """Record partition ``idx`` complete (its sorted output spilled
        as ``rids``) — the crash-resume commit point."""
        done[str(idx)] = [int(r) for r in rids]
        manifest["done"] = done
        store.write_log(journal, manifest)

    try:
        for idx in range(len(items)):
            part, frags = items[idx]
            if str(idx) in done:
                # a previous (crashed) run completed this partition and
                # spilled its sorted output: replay the result runs —
                # zero rows re-sorted, bit-identical emission
                for rid in done[str(idx)]:
                    arrays = store.get(rid)
                    words, payloads = arrays[0], tuple(arrays[1:])
                    budget.charge(words, *payloads)
                    if words.shape[0]:
                        _emitted(words, payloads)
                        yield words, payloads
                        emitted += int(words.shape[0])
                for rid in frags:
                    # fragments a crash left behind between the commit
                    # point and their deletion
                    if rid in store:
                        store.delete(rid)
                continue
            if idx in group_of:
                entries = [items[i] for i in group_of[idx]]
                L, sort_bits = part_bucket(part)
                with trace.span("stream.partition_sort",
                                segments=len(entries)) as bsp:
                    loaded = [
                        _load_fragments(st, fr, n_payloads, budget)
                        for _, fr in entries]
                    bsp.set(rows=sum(int(w_.shape[0])
                                     for w_, _ in loaded),
                            bytes_in=sum(
                                int(w_.nbytes) + sum(int(p.nbytes)
                                                     for p in ps)
                                for w_, ps in loaded))
                    results = st.sort_rows_batched(
                        loaded, bits, sort_bits, budget,
                        plans=plans_for(L, sort_bits))
                # head emits now; later members spill back pre-sorted and
                # re-load in partition order at their own turn
                for i, (_, fr), (words, payloads) in zip(
                        group_of[idx], entries, results):
                    if i != idx:
                        presorted[i] = st.put(words, *payloads)
                    for rid in fr:
                        st.delete(rid)
                words, payloads = results[0]
                if words.shape[0]:
                    _emitted(words, payloads)
                    yield words, payloads
                    emitted += int(words.shape[0])
                continue
            if idx in presorted:
                rid = presorted.pop(idx)
                arrays = st.get(rid)
                words, payloads = arrays[0], tuple(arrays[1:])
                budget.charge(words, *payloads)
                if words.shape[0]:
                    _emitted(words, payloads)
                    yield words, payloads
                    emitted += int(words.shape[0])
                st.delete(rid)
                continue
            if room() == 0:
                for rid in frags:
                    st.delete(rid)
                continue
            if not part.oversized(budget_rows):
                if pool is not None:
                    j = idx  # keep up to `workers` upcoming sorts in flight
                    while len(pending) < workers and j < len(items):
                        pj, fj = items[j]
                        if (j not in pending and str(j) not in done
                                and not pj.oversized(budget_rows)):
                            # wrap_ctx re-parents the worker thread's
                            # spans under this thread's active span
                            pending[j] = pool.submit(
                                trace.wrap_ctx(sorted_partition), pj, fj)
                        j += 1
                    try:
                        words, payloads = pending.pop(idx).result()
                    except BaseException:
                        # a doomed sort must fail the stream promptly:
                        # drop the speculative lookahead, don't wait on it
                        for f in pending.values():
                            f.cancel()
                        raise
                else:
                    try:
                        words, payloads = sorted_partition(part, frags)
                    except StorePermanentError:
                        if not getattr(st, "failover_to_disk", False):
                            raise
                        # the placement's sort is permanently gone but its
                        # fragments are not: migrate what remains to disk
                        # and re-sort this partition there
                        fail_over(idx)
                        part, frags = items[idx]
                        words, payloads = sorted_partition(part, frags)
                words, payloads = clip(words, payloads)
                if journal is not None:
                    journal_done(idx, [store.put(words, *payloads)]
                                 if words.shape[0] else [])
                if words.shape[0]:
                    _emitted(words, payloads)
                    yield words, payloads
                    emitted += int(words.shape[0])
            else:
                # skew fallback: a single bin outgrew the budget; its keys
                # all share that bin's digit, so recurse on the next field
                # down (sequential — recursion re-enters the store)
                assert part.num_bins == 1, "only single bins can be oversized"
                sub_fn = (lambda fr: lambda: (
                    (a[0], tuple(a[1:])) for a in
                    (st.get(rid) for rid in fr)))(frags)
                rids = []
                for words, payloads in stream_sorted_words(
                        sub_fn, bits, budget, st, row_bytes, hi=hi - w,
                        executor=executor, partition_bits=partition_bits,
                        limit_rows=room()):
                    if journal is not None:
                        rids.append(store.put(words, *payloads))
                    yield words, payloads
                    emitted += int(words.shape[0])
                if journal is not None:
                    journal_done(idx, rids)
            for rid in items[idx][1]:
                # an oversized partition's recursion may itself have
                # failed over and migrated (deleted) these fragments
                if rid in st:
                    st.delete(rid)
        if journal is not None:
            # complete: the result runs served their purpose; drop them
            # and mark the manifest spent (resuming a complete manifest
            # starts fresh)
            for rids in done.values():
                for rid in rids:
                    if rid in store:
                        store.delete(rid)
            manifest["complete"] = True
            store.write_log(journal, manifest)
    finally:
        if pool is not None:
            pool.shutdown(wait=True, cancel_futures=True)
        if fallback is not None:
            fallback.close()


def _key_chunks_fn(source: ChunkSource, with_rowids: bool):
    """Adapt a 1-D key ChunkSource to the (words, payloads) protocol; the
    cell returns the input dtype for casting sorted output back."""
    dtype_cell: list = []

    def chunks_fn():
        offset = 0  # recomputed identically on every streaming pass
        for chunk in source.chunks():
            a = np.ascontiguousarray(np.asarray(chunk))
            assert a.ndim == 1, "external_sort streams 1-D key chunks"
            assert a.dtype.kind in "iu" and a.dtype.itemsize == 4, (
                f"keys must be 32-bit integers (int32/uint32), got "
                f"{a.dtype} — encode other types through repro.query "
                "codecs (StreamTable order_by)")
            if not dtype_cell:
                dtype_cell.append(a.dtype)
            words = a.view(np.uint32).reshape(-1, 1)
            payloads = ()
            if with_rowids:
                payloads = (np.arange(offset, offset + a.shape[0],
                                      dtype=np.int64),)
            offset += a.shape[0]
            yield words, payloads

    return chunks_fn, dtype_cell


def external_sort(source: ChunkSource, p: int, budget: MemoryBudget,
                  store: Optional[PlacementStore] = None,
                  executor: Optional[PlanExecutor] = None,
                  partition_bits: int = DEFAULT_PARTITION_BITS,
                  journal: Optional[str] = None,
                  resume=None,
                  ) -> Iterator[np.ndarray]:
    """Sort a streamed dataset of ``p``-bit keys under a byte budget.

    ``source`` yields 1-D int32/uint32 key chunks (each within the
    budget; :class:`~repro.stream.chunks.ArraySource` sized via
    ``budget.rows(4)`` is the in-memory case) and must be re-iterable —
    the sort streams it twice.  Yields sorted key chunks (input dtype) in
    global order; peak resident key bytes stay under ``budget`` (tracked
    — read ``budget.peak_bytes``).  ``store`` is the
    :class:`~repro.stream.chunks.PlacementStore` holding partition
    fragments — disk runs by default (an owned temp store, cleaned up
    when the generator finishes or is closed), or a
    :class:`~repro.stream.device_store.DeviceShardStore` to place
    fragments on a jax mesh and sort each partition distributed.

    ``journal`` names a crash-resume manifest kept current on the
    store's log channel; ``resume`` replays a prior journaled run
    (manifest dict or journal name), recomputing zero completed
    partitions — see :func:`stream_sorted_words`.  Both need a caller
    store on a durable root.
    """
    assert 0 <= p <= 32, f"p={p} out of range (0..32)"
    own_store = store is None
    store = temp_store() if store is None else store
    try:
        chunks_fn, dtype_cell = _key_chunks_fn(source, with_rowids=False)
        for words, _ in stream_sorted_words(
                chunks_fn, p, budget, store, row_cost_bytes(1),
                executor=executor, partition_bits=partition_bits,
                journal=journal, resume=resume):
            out = np.ascontiguousarray(words[:, 0])
            yield out.view(dtype_cell[0]) if dtype_cell else out
    finally:
        if own_store:
            store.close()


def external_argsort(source: ChunkSource, p: int, budget: MemoryBudget,
                     store: Optional[PlacementStore] = None,
                     executor: Optional[PlanExecutor] = None,
                     partition_bits: int = DEFAULT_PARTITION_BITS,
                     journal: Optional[str] = None,
                     resume=None,
                     ) -> Iterator[Tuple[np.ndarray, np.ndarray]]:
    """Like :func:`external_sort`, but each yielded chunk is ``(sorted
    keys, int64 global arrival indices)`` — the stable permutation, in
    budget-sized pieces.  Row ids are assigned by stream position, ride
    the placed fragments, and equal keys keep arrival order end to end
    (fragments place in arrival order, the store's partition sort is
    stable, and fully-equal recursion levels stream arrival order)."""
    assert 0 <= p <= 32, f"p={p} out of range (0..32)"
    own_store = store is None
    store = temp_store() if store is None else store
    try:
        chunks_fn, dtype_cell = _key_chunks_fn(source, with_rowids=True)
        for words, (rowids,) in stream_sorted_words(
                chunks_fn, p, budget, store, row_cost_bytes(1, 8),
                executor=executor, partition_bits=partition_bits,
                journal=journal, resume=resume):
            out = np.ascontiguousarray(words[:, 0])
            yield (out.view(dtype_cell[0]) if dtype_cell else out), rowids
    finally:
        if own_store:
            store.close()
