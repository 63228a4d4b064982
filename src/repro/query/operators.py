"""Sort-backed relational operators, every one bottoming out in the
:class:`~repro.core.executor.PlanExecutor`.

The paper motivates FractalSort through query execution — "sorting as a
core operation in query processing, indexing and join execution" — and
this module is that workload: ``order_by`` (multi-column asc/desc),
``sort_merge_join`` (inner), ``group_by`` (sum/count/min/max from segment
boundaries of the sorted key column), ``distinct`` and ``top_k``.

The shape is always the same:

1. **encode** — an order-preserving :mod:`~repro.query.codec` turns the
   key columns into unsigned codes whose exact bit width sizes the
   :class:`~repro.core.sort_plan.SortPlan` (an 8-bit key runs a two-pass
   plan, not a 32-bit one);
2. **pairs sort** — one executor run carries an int32 row-id payload
   through every pass (:func:`~repro.core.fractal_sort.fractal_sort_pairs`;
   the fractal MSD pass still reconstructs prefix bits from bin positions
   — only the payload and trailing bits travel).  Multi-word codes (>32
   bits: float64, wide composites) chain one stable pass set per word,
   least-significant word first — lexicographic == numeric order;
3. **gather / segment scan** — payload columns move by one gather of the
   row-id column; group/distinct boundaries fall out of the sorted key
   column; joins merge two sorted runs with two ``searchsorted`` probes.

Operators are host-level drivers (they sync small scalars like segment
counts); the data-sized work — every rank, scatter and gather — runs
through the executor's jitted primitives.  No operator grows a pass
loop: operators build plans, and the plan-pass loop stays solely in
``core/executor.py``.

``order_by`` / ``group_by`` / ``top_k`` also accept a
:class:`~repro.stream.table_ops.StreamTable` — a chunk-streamed table
larger than its memory budget — and dispatch to the out-of-core
subsystem (:mod:`repro.stream`), which routes each histogram partition
back through these same in-memory primitives.
"""

from __future__ import annotations

import functools
from typing import Mapping, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (JnpBackend, PlanExecutor, SortPlan, dispatch,
                        make_sort_plan)
from repro.obs import metrics, trace
from repro.query.codec import (
    Codec,
    ColumnSpec,
    CompositeCodec,
    infer_codec,
    word_widths,
)
from repro.query.table import Table

__all__ = [
    "order_by",
    "sort_merge_join",
    "group_by",
    "distinct",
    "top_k",
    "active_words",
    "sort_rowids",
    "sort_rowids_fused",
    "sort_rowids_batched",
]


def _fetch(x) -> np.ndarray:
    """``x`` on the host.  A device array's transfer is one
    ``query.fetch`` span and adds its ``nbytes`` to the
    ``query.d2h_bytes`` counter; a host array passes through."""
    if not isinstance(x, jax.Array):
        return np.asarray(x)
    nbytes = int(x.nbytes)
    with trace.span("query.fetch", bytes=nbytes):
        host = np.asarray(x)
    metrics.counter("query.d2h_bytes").inc(nbytes)
    return host


def _stream_ops(table):
    """The streaming-operator module when ``table`` is a StreamTable,
    else None (imported lazily: the query layer must not pull the stream
    subsystem in at import time)."""
    if isinstance(table, Table):
        return None
    from repro.stream import table_ops

    return table_ops if isinstance(table, table_ops.StreamTable) else None


def _normalize_by(by) -> Tuple[Tuple[str, bool], ...]:
    """``by``: one "col", or a list of "col" / ("col", asc-bool) /
    ("col", "asc"|"desc")."""
    if isinstance(by, str):
        by = [by]
    out = []
    for item in by:
        if isinstance(item, str):
            out.append((item, True))
        else:
            name, asc = item
            if isinstance(asc, str):
                assert asc in ("asc", "desc"), f"bad direction {asc!r}"
                asc = asc == "asc"
            out.append((name, bool(asc)))
    assert out, "need at least one key column"
    return tuple(out)


def _key_data(table: Table, by, codecs: Optional[Mapping[str, Codec]]):
    """(CompositeCodec, prepared raw key columns) — the fused-path input.

    ``prepare`` is the host-side dtype bitcast only (free for int/float32
    columns, one uint64→2×uint32 view for float64); the order-preserving
    *encode* never runs here — it traces into the sort chain
    (:func:`sort_rowids_fused`), so no operator materializes the ``(n, W)``
    code matrix on the host."""
    specs, cols = [], []
    for name, asc in _normalize_by(by):
        col = table.column(name)
        codec = (codecs or {}).get(name) or infer_codec(col)
        specs.append(ColumnSpec(codec, ascending=asc))
        cols.append(col)
    codec = CompositeCodec(specs)
    return codec, codec.prepare(cols)


def _composite_for(table: Table, by, codecs: Optional[Mapping[str, Codec]]):
    """(CompositeCodec, encoded (n, W) words) for the key columns —
    the eager-encode variant (tests and the stream path, which stores
    encoded words in fragments, still want materialized codes)."""
    codec, prepped = _key_data(table, by, codecs)
    return codec, codec.encode_fn(prepped)


def active_words(bits: int, low_bits: Optional[int] = None,
                 ) -> Tuple[Tuple[int, int], ...]:
    """``(word index, undetermined low bits)`` pairs for a ``bits``-wide
    code, MSB word first — the words a sort must actually rank.

    ``low_bits`` narrows to the undetermined low code bits when every row
    provably shares bits ``[low_bits, bits)`` (the external sort's
    partitions): fully-shared words drop out entirely and the boundary
    word keeps only its undetermined low bits.  ``None`` = all bits
    undetermined."""
    widths = word_widths(bits)
    low_bits = bits if low_bits is None else int(low_bits)
    assert 0 <= low_bits <= bits, f"low_bits={low_bits} not in 0..{bits}"
    # word j covers code bits [lo_j, lo_j + widths[j]); its undetermined
    # low bits are those below low_bits
    active, lo = [], bits
    for j, wj in enumerate(widths):
        lo -= wj
        eff = min(low_bits - lo, wj)
        if eff > 0:
            active.append((j, eff))
    return tuple(active)


def _resolve_plans(n: int, active, plans):
    """Per-active-word plans: caller-pinned, or the default plan of each
    active word's width (:func:`~repro.core.sort_plan.make_sort_plan`)."""
    if plans is None:
        plans = tuple(make_sort_plan(n, eff) for _, eff in active)
    assert len(plans) == len(active), (
        f"{len(active)} active words need {len(active)} plans, "
        f"got {len(plans)}")
    return tuple(plans)


def _argsort_chain(words, active, plans, argsort):
    """``(words[perm], perm)``: one stable ``argsort(key, plan)`` per
    active word, least significant first (the composition is
    lexicographic, i.e. numeric on the full code).

    Every word moves as its own 1-D column: a TPU lays a 2-D ``(n, W)``
    uint32 array out in (8, 128) tiles, so gathering rows of an
    ``(n, 1)`` code matrix would hold it at 128 lanes a row (a 26-bit
    key at 2^25 rows needs 16 GiB of HBM that way, against 128 MiB as a
    column)."""
    n = words.shape[0]
    cols = [words[:, j] for j in range(words.shape[1])]
    perm = jnp.arange(n, dtype=jnp.int32)
    for (j, _), plan in zip(reversed(active), reversed(plans)):
        # plan covers the word's undetermined low bits; higher bits are
        # row-invariant here, so digit passes never see them
        perm = perm[argsort(cols[j][perm], plan)]
    return jnp.stack([c[perm] for c in cols], axis=1), perm


@functools.lru_cache(maxsize=256)
def _rowid_chain(active: Tuple[Tuple[int, int], ...],
                 plans: Tuple[SortPlan, ...], pairs_path: bool):
    """One jitted pass chain per (active words, per-word plans) config.

    Multi-word codes (>32-bit composites, float64) used to retrace and
    dispatch one executor run *per word* from Python — `order_by` paid
    per-word host orchestration on every call.  The whole chain (argsort
    last active word → permute → next word up → …) now traces once into a
    single jitted function, cached here by its static configuration; jax's
    own jit cache then specializes per input shape.

    ``active`` lists ``(word index, undetermined low bits)`` pairs, MSB
    word first — the narrowed-partition path skips fully-shared words and
    sorts the boundary word on only its undetermined low bits.
    ``pairs_path`` (full-width single-word codes only) runs the executor
    pairs plan instead, where row ids ride the scatter path and the MSD
    pass *reconstructs* prefix bits from bin positions — valid only when
    the sort covers every code bit, since reconstruction rebuilds exactly
    the sorted ``p`` bits and would zero a narrowed sort's shared prefix.
    """
    assert len(active) == len(plans)

    @jax.jit
    def chain(words):
        n = words.shape[0]
        ex = PlanExecutor(JnpBackend())
        if pairs_path:
            sorted_keys, rowids = ex.run_pairs(
                words[:, 0], jnp.arange(n, dtype=jnp.int32), plans[0])
            return sorted_keys.astype(jnp.uint32)[:, None], rowids
        return _argsort_chain(words, active, plans, ex.run_argsort)

    return dispatch.wrap("query.chain", chain)


@functools.lru_cache(maxsize=256)
def _fused_chain(codec: CompositeCodec, active: Tuple[Tuple[int, int], ...],
                 plans: Tuple[SortPlan, ...], pairs_path: bool):
    """The fused encode→sort program: one jitted chain per (codec, active
    words, plans) config, taking *prepared raw columns* and tracing
    ``codec.encode_fn`` → word split → per-word pass chain as ONE program.

    The encode is elementwise, so XLA fuses it straight into pass 0's
    digit extraction (the executor's ``encode=`` hook carries it for the
    single-word pairs path) — the ``(n, W)`` code matrix exists only as a
    value inside the trace, never on the host.  Cache keying leans on
    :class:`CompositeCodec` hashing by *value* (specs), so two queries
    over equal-typed key columns share one compiled program.
    """
    assert len(active) == len(plans)

    @jax.jit
    def chain(prepped):
        n = jax.tree_util.tree_leaves(prepped)[0].shape[0]
        ex = PlanExecutor(JnpBackend())
        if pairs_path:
            # raw columns enter the executor; pass 0 reads digits straight
            # off the fused encode (single full-width word: the code IS
            # column 0, so reconstruct-on-MSD stays valid)
            sorted_keys, rowids = ex.run_pairs(
                prepped, jnp.arange(n, dtype=jnp.int32), plans[0],
                encode=lambda pre: codec.encode_fn(pre)[:, 0])
            return sorted_keys.astype(jnp.uint32)[:, None], rowids
        return _argsort_chain(codec.encode_fn(prepped), active, plans,
                              ex.run_argsort)

    return dispatch.wrap("query.chain", chain)


def sort_rowids(words: jnp.ndarray, bits: int,
                plans: Optional[Tuple[SortPlan, ...]] = None,
                low_bits: Optional[int] = None):
    """Stably sort multi-word codes: ``(sorted_words, rowids)``.

    Full-width single-word codes run one executor pairs plan (row ids
    ride the scatter path, prefix bits reconstructed on the MSD pass).
    Everything else chains one stable argsort per 32-bit word,
    least-significant word first — stability makes the composition
    lexicographic, i.e. numeric on the full code.  The whole chain runs
    as one jitted dispatch (:func:`_rowid_chain`).

    ``low_bits`` narrows the sort to the undetermined low code bits when
    every row provably shares bits ``[low_bits, bits)`` — the external
    sort's partitions, whose shared MSD prefix is implied by their bin
    range.  Fully-shared words drop out of the chain entirely and the
    boundary word sorts on only its undetermined bits, cutting pass work
    by ~``(bits - low_bits) / bits`` (the ROADMAP's ~1/3 at p=32 under
    10 partition bits).  ``low_bits == 0`` (all bits shared) returns
    arrival order — already the stable sorted order.

    ``plans`` pins per-word :class:`SortPlan`\\ s (one per *active* word
    of the code); by default each active word gets the default plan of
    its own width (:func:`~repro.core.sort_plan.make_sort_plan`).
    """
    widths = word_widths(bits)
    n = words.shape[0]
    if n == 0:
        return words, jnp.zeros((0,), jnp.int32)
    active = active_words(bits, low_bits)
    if not active:
        # every code bit shared: arrival order is the stable sorted order
        return words, jnp.arange(n, dtype=jnp.int32)
    plans = _resolve_plans(n, active, plans)
    pairs_path = len(widths) == 1 and active[0][1] == widths[0]
    return _rowid_chain(active, plans, pairs_path)(words)


@functools.lru_cache(maxsize=64)
def _mask_probe(codec: CompositeCodec):
    """One tiny jitted program per codec: the OR-reduction of
    ``word ^ word[0]`` across rows, per code word — a ``(W,)`` uint32
    mask of the bits that actually *vary* in this dataset.  Bits no two
    rows differ on cannot reorder anything, so the fused sort narrows
    each word to its varying low field (the in-memory sibling of the
    stream path's shared-prefix cut) — low-entropy keys (small int
    domains, category columns) sort in one or two passes instead of a
    full-width chain.  The probe is O(nW) reads and returns W scalars;
    it never materializes the code matrix on the host."""

    @jax.jit
    def masks(prepped):
        w = codec.encode_fn(prepped)
        return jax.lax.reduce(w ^ w[:1], np.uint32(0),
                              jax.lax.bitwise_or, (0,))

    return dispatch.wrap("query.probe", masks)


def sort_rowids_fused(codec: CompositeCodec, prepped,
                      plans: Optional[Tuple[SortPlan, ...]] = None):
    """:func:`sort_rowids` from *raw* key columns: ``(sorted_words,
    rowids)`` in one fused jitted dispatch, encode traced into the chain.

    ``prepped`` is ``codec.prepare(cols)`` — the host bitcast of the raw
    columns (see :func:`_key_data`); everything order-preserving happens
    inside the fused program (:func:`_fused_chain`).  This is the path
    every in-memory operator sorts through; the stream path keeps
    :func:`sort_rowids` because its partitions are *stored* encoded.

    When ``plans`` is not pinned, a used-bits probe (:func:`_mask_probe`)
    first narrows every word to the bits that vary across rows: the
    skipped bits are row-invariant, so the permutation is bit-identical
    to the full-width sort while low-entropy keys shed most of their
    pass work.  Narrowed single-word sorts take the argsort path — the
    pairs path's MSD reconstruct rebuilds only the sorted bits and would
    zero the shared high bits of the returned words.

    Every chain it runs adds its rows to the ``query.rows_sorted``
    counter, and to ``query.sort_min_bytes`` the least bytes the sort
    must move: the prepared key columns read once and an int32 row id
    written once a row."""
    n = jax.tree_util.tree_leaves(prepped)[0].shape[0]
    widths = word_widths(codec.bits)
    if n == 0:
        return (jnp.zeros((0, len(widths)), jnp.uint32),
                jnp.zeros((0,), jnp.int32))
    active = active_words(codec.bits)
    if plans is None:
        with trace.span("query.probe"):
            masks = _fetch(_mask_probe(codec)(prepped))
        active = tuple(
            (j, min(eff, int(masks[j]).bit_length()))
            for j, eff in active if int(masks[j]))
    plans = _resolve_plans(n, active, plans)
    pairs_path = (len(widths) == 1 and len(active) == 1
                  and active[0][1] == widths[0])
    key_bytes = sum(int(a.nbytes) for a in jax.tree_util.tree_leaves(prepped))
    metrics.counter("query.rows_sorted").inc(n)
    metrics.counter("query.sort_min_bytes").inc(key_bytes + 4 * n)
    with trace.span("query.chain"):
        return _fused_chain(codec, active, plans, pairs_path)(prepped)


@functools.lru_cache(maxsize=256)
def _segmented_chain(active: Tuple[Tuple[int, int], ...],
                     plans: Tuple[SortPlan, ...], seg_len_log2: int):
    """One jitted *batched* pass chain: B concatenated equal-length
    partitions sort independently (within-segment) in one program —
    per-word :meth:`~repro.core.executor.PlanExecutor.run_segmented_argsort`
    composed exactly like :func:`_rowid_chain`'s argsort chain.  Ranks
    never cross the positional segments, so the stable per-word
    composition is lexicographic within every partition."""
    assert len(active) == len(plans)

    @jax.jit
    def chain(words):
        ex = PlanExecutor(JnpBackend())
        return _argsort_chain(
            words, active, plans,
            lambda key, plan: ex.run_segmented_argsort(key, plan,
                                                       seg_len_log2))

    return dispatch.wrap("query.segmented_chain", chain)


def sort_rowids_batched(words: jnp.ndarray, bits: int, seg_len_log2: int,
                        plans: Optional[Tuple[SortPlan, ...]] = None,
                        low_bits: Optional[int] = None):
    """Batched :func:`sort_rowids`: ``words`` holds ``B`` independent
    partitions of ``L = 2**seg_len_log2`` rows laid end to end; every
    partition sorts stably *within its own segment* through ONE jitted
    dispatch (``rowids[b*L:(b+1)*L]`` indexes inside partition ``b``).

    This is the stream path's shared-dispatch mode: partitions padded to
    one power-of-two length with all-ones sentinel rows (which sort last
    per segment) batch into a single program instead of B chain
    dispatches.  ``low_bits``/``plans`` mean exactly what they mean in
    :func:`sort_rowids`, with plans sized for the per-partition length
    ``L`` — every segment is an independent L-row sort."""
    n = words.shape[0]
    L = 1 << seg_len_log2
    assert n % L == 0, f"batch length {n} not a multiple of L={L}"
    if n == 0:
        return words, jnp.zeros((0,), jnp.int32)
    active = active_words(bits, low_bits)
    if not active:
        return words, jnp.arange(n, dtype=jnp.int32)
    plans = _resolve_plans(L, active, plans)
    return _segmented_chain(active, plans, int(seg_len_log2))(words)


def _op_scope(name: str, rows: int):
    """The ``query.<name>`` span around one in-memory operator call (the
    shared null handle when tracing is off); the operator's phase spans
    nest under it."""
    return trace.span(f"query.{name}", rows=rows)


def order_by(table: Table, by, codecs: Optional[Mapping[str, Codec]] = None,
             plans: Optional[Tuple[SortPlan, ...]] = None,
             placement=None) -> Table:
    """Multi-column ORDER BY (stable): rows reordered by one gather of the
    pairs sort's row-id payload.  ``plans`` pins per-word sort plans
    (default: the default plans for the codec's word widths).

    A StreamTable input runs out-of-core and returns a StreamTable of
    sorted runs (:func:`~repro.stream.table_ops.stream_order_by`);
    ``placement`` (StreamTable only) is the
    :class:`~repro.stream.chunks.PlacementStore` holding the working
    partition fragments — pass a
    :class:`~repro.stream.device_store.DeviceShardStore` to run the sort
    distributed over a jax mesh."""
    stream = _stream_ops(table)
    if stream is not None:
        assert plans is None, (
            "pinned plans don't apply out-of-core: each partition "
            "resolves plans for its own length")
        return stream.stream_order_by(table, by, codecs,
                                      placement=placement)
    assert placement is None, (
        "placement is the out-of-core fragment store; an in-memory Table "
        "sorts in place — wrap it in a StreamTable to place on a mesh")
    with _op_scope("order_by", len(table)):
        codec, prepped = _key_data(table, by, codecs)
        _, rowids = sort_rowids_fused(codec, prepped, plans)
        return table.take(rowids)


# MSD digit width of the top-k pruning histogram: wide enough that a
# uniform-ish key column prunes hard (1024 bins), narrow enough that the
# histogram is negligible next to one plan pass.
_TOPK_PRUNE_BITS = 10


@functools.lru_cache(maxsize=64)
def _prune_hist(codec: CompositeCodec, top_bits: int, shift: int):
    """Jitted top-k prune histogram from prepared raw columns: fused
    encode → leading ``top_bits`` digit → bincount (+ the per-row prefix,
    which the candidate mask needs back on the host)."""

    @jax.jit
    def hist(prepped):
        w0 = codec.encode_fn(prepped)[:, 0]
        prefix = (w0 >> shift).astype(jnp.int32)
        counts = jnp.zeros((1 << top_bits,), jnp.int32).at[prefix].add(1)
        return counts, prefix

    return hist


def top_k(table: Table, by, k: int,
          codecs: Optional[Mapping[str, Codec]] = None,
          plans: Optional[Tuple[SortPlan, ...]] = None,
          placement=None) -> Table:
    """First ``k`` rows of the stable ORDER BY (ties keep arrival order),
    *without* the full sort: one MSD histogram over the code's leading
    digit finds the smallest digit value ``cut`` whose cumulative count
    reaches ``k`` — every top-k row must carry a leading digit ``<= cut``
    (at least k rows do, and they all precede every digit ``> cut`` in key
    order) — and only those candidate rows enter the pass chain.  The
    operator-level order_by+top_k fusion: on selective keys the sort runs
    over ~k-ish rows instead of n.

    Ties and stability are preserved exactly: candidate rows are taken in
    arrival order, boundary-digit ties are all candidates, and the
    candidate sort is the global stable sort restricted to a prefix-closed
    key range.  ``plans`` applies when the sort runs over all ``n`` rows
    (k >= n, or no pruning opportunity); a pruned candidate subset
    re-resolves plans for its own (smaller) length.  The histogram
    and the candidate pick are the span ``query.prune``, the final
    gather ``query.take``.
    """
    stream = _stream_ops(table)
    if stream is not None:
        assert plans is None, (
            "pinned plans don't apply out-of-core: each partition "
            "resolves plans for its own length")
        return stream.stream_top_k(table, by, k, codecs, store=placement)
    assert placement is None, (
        "placement is the out-of-core fragment store; an in-memory Table "
        "sorts in place — wrap it in a StreamTable to place on a mesh")
    if k <= 0:
        return table.head(0)
    with _op_scope("top_k", len(table)):
        return _top_k_mem(table, by, k, codecs, plans)


def _top_k_mem(table: Table, by, k: int, codecs, plans) -> Table:
    codec, prepped = _key_data(table, by, codecs)
    n = jax.tree_util.tree_leaves(prepped)[0].shape[0]
    sub_pre = None
    if k < n:
        with trace.span("query.prune"):
            top_bits = min(_TOPK_PRUNE_BITS, word_widths(codec.bits)[0])
            shift = word_widths(codec.bits)[0] - top_bits
            # one jitted dispatch: fused encode → leading-digit histogram
            counts, prefix = _prune_hist(codec, top_bits, shift)(prepped)
            cut = jnp.searchsorted(jnp.cumsum(counts), k, side="left")
            keep = prefix <= cut
            # the host sync: the candidate count sizes the nonzero
            size = int(_fetch(jnp.sum(keep, dtype=jnp.int32)))
            rows = jnp.nonzero(keep, size=size)[0].astype(jnp.int32)
            if size < n:
                sub_pre = jax.tree_util.tree_map(lambda a: a[rows], prepped)
    if sub_pre is not None:
        # the candidate subset re-resolves its own plans:
        # caller-pinned plans were sized for n rows, not ~k
        _, sub = sort_rowids_fused(codec, sub_pre)
        picked = rows[sub[:k]]
    else:
        _, rowids = sort_rowids_fused(codec, prepped, plans)
        picked = rowids[:k]
    with trace.span("query.take"):
        return table.take(picked)


def _words_searchsorted(sorted_words: np.ndarray, queries: np.ndarray,
                        side: str) -> np.ndarray:
    """Lexicographic ``searchsorted`` of each query row into a sorted
    ``(m, W)`` uint32 word matrix (word 0 most significant — the codec's
    multi-word layout, where lexicographic == numeric on the full code).

    Single words fall through to ``np.searchsorted``.  Wider codes use
    the merge trick: stable-lexsort the concatenated (sorted ∪ query)
    rows with a side-dependent tiebreak flag (queries before equal
    sorted rows for "left", after for "right"); a query's insertion
    index is then the count of sorted rows preceding it — one
    O((m+n) log(m+n)) lexsort instead of a per-word bisection."""
    m, n = sorted_words.shape[0], queries.shape[0]
    if sorted_words.shape[1] == 1:
        return np.searchsorted(sorted_words[:, 0], queries[:, 0], side=side)
    assert side in ("left", "right")
    flag_sorted = 1 if side == "left" else 0
    comb = np.concatenate([sorted_words, queries])
    flags = np.concatenate([
        np.full((m,), flag_sorted, np.uint8),
        np.full((n,), 1 - flag_sorted, np.uint8)])
    # np.lexsort: LAST key is primary -> (flag, word W-1, ..., word 0)
    order = np.lexsort((flags,) + tuple(
        comb[:, j] for j in range(comb.shape[1] - 1, -1, -1)))
    rank = np.empty((m + n,), np.int64)
    rank[order] = np.arange(m + n)
    sorted_rows_upto = np.cumsum(order < m)  # inclusive prefix of sorted rows
    # a query row never counts itself, so the inclusive prefix at its
    # sorted position is exactly the number of sorted rows before it
    return sorted_rows_upto[rank[m:]]


def _segments(w: np.ndarray) -> np.ndarray:
    """Start index of every run of equal codes in a sorted host word
    matrix."""
    if w.shape[0] == 0:
        return np.zeros((0,), np.int64)
    change = np.any(w[1:] != w[:-1], axis=1)
    return np.flatnonzero(np.concatenate([[True], change]))


def distinct(table: Table, by=None,
             codecs: Optional[Mapping[str, Codec]] = None,
             plans: Optional[Tuple[SortPlan, ...]] = None) -> Table:
    """DISTINCT ON the key columns: the first-arriving row of every
    distinct key combination, output sorted by key (the stable pairs sort
    makes "first" well-defined)."""
    assert isinstance(table, Table), (
        "distinct is in-memory only; stream through order_by/group_by "
        "(repro.stream) or materialize with StreamTable.to_table()")
    by = _normalize_by(by if by is not None else table.column_names)
    with _op_scope("distinct", len(table)):
        codec, prepped = _key_data(table, by, codecs)
        sorted_words, rowids = sort_rowids_fused(codec, prepped, plans)
        starts = _segments(_fetch(sorted_words))
        return table.take(jnp.asarray(_fetch(rowids)[starts]))


# aggregation spec: out_name -> (column | None, "sum"|"count"|"min"|"max")
_AGG_UFUNC = {"sum": np.add, "min": np.minimum, "max": np.maximum}


def group_by(table: Table, by, aggs: Mapping[str, Tuple[Optional[str], str]],
             codecs: Optional[Mapping[str, Codec]] = None,
             plans: Optional[Tuple[SortPlan, ...]] = None,
             placement=None) -> Table:
    """GROUP BY + aggregation from segment boundaries of the sorted key.

    One pairs sort groups equal keys into contiguous segments; every
    aggregate is then a ``reduceat`` over the gathered value column —
    no hashing, no per-group loops (the Leyenda-style sort-based
    aggregation).  Output: one row per group, sorted by key; key columns
    decoded from the segment-start codes.

    A StreamTable input aggregates out-of-core, partition by partition
    (:func:`~repro.stream.table_ops.stream_group_by`).
    """
    stream = _stream_ops(table)
    if stream is not None:
        assert plans is None, (
            "pinned plans don't apply out-of-core: each partition "
            "resolves plans for its own length")
        return stream.stream_group_by(table, by, aggs, codecs,
                                      placement=placement)
    assert placement is None, (
        "placement is the out-of-core fragment store; an in-memory Table "
        "sorts in place — wrap it in a StreamTable to place on a mesh")
    by = _normalize_by(by)
    with _op_scope("group_by", len(table)):
        return _group_by_mem(table, by, aggs, codecs, plans)


def _group_by_mem(table: Table, by, aggs, codecs, plans) -> Table:
    with trace.span("query.prepare"):
        codec, prepped = _key_data(table, by, codecs)
    sorted_words, rowids = sort_rowids_fused(codec, prepped, plans)
    # the first fetch also waits for the chain to finish on the device
    words = _fetch(sorted_words)
    rid = _fetch(rowids)
    with trace.span("query.segments"):
        starts = _segments(words)
    n = rid.shape[0]
    cols = {}
    with trace.span("query.decode"):
        key_cols = codec.decode(jnp.asarray(words[starts])) \
            if len(starts) else tuple(
                table.column(name)[:0] for name, _ in by)
    for (name, _), vals in zip(by, key_cols):
        cols[name] = vals
    counts = np.diff(starts, append=n)
    for out_name, (col, op) in aggs.items():
        assert op in ("sum", "count", "min", "max"), f"bad aggregate {op!r}"
        if op == "count":
            cols[out_name] = jnp.asarray(counts.astype(np.int32))
            continue
        column = _fetch(table.column(col))
        with trace.span("query.gather", column=col):
            vals = column[rid]
        if len(starts) == 0:
            cols[out_name] = jnp.asarray(vals[:0])
            continue
        with trace.span("query.reduce", column=col):
            agg = _AGG_UFUNC[op].reduceat(vals, starts)
        cols[out_name] = agg if vals.dtype == np.float64 else jnp.asarray(agg)
    return Table(cols)


def sort_merge_join(left: Table, right: Table, on,
                    codecs: Optional[Mapping[str, Codec]] = None,
                    suffixes: Tuple[str, str] = ("_l", "_r"),
                    plans: Optional[Tuple[SortPlan, ...]] = None) -> Table:
    """Inner join over two fractal-sorted runs.

    Both sides' key columns encode through the *same* composite codec
    (so equal keys share a code), each side runs one pairs sort, and the
    merge is two ``searchsorted`` probes of the left codes into the right
    run — per left row, its matching right range ``[lo, hi)`` — expanded
    into row-id pairs.  Output rows are sorted by key, ties ordered by
    (left arrival, right arrival): both sorts are stable.

    Keys of any codec width join: multi-word codes (float64, wide
    composites) probe through the lexicographic merge
    (:func:`_words_searchsorted`) over the ``(n, W)`` uint32 code
    matrices — word order is numeric order, so duplicate and
    cross-word-boundary ties behave exactly as one wide integer key.
    ``plans`` (one per code word) applies to *both* sides' sorts; leave
    it None when the two tables differ widely in size so each side
    resolves its own plan.

    Its host phases are the spans ``query.merge`` (the two probes),
    ``query.expand`` (the repeat and position arrays) and ``query.take``
    (both sides' gathers); the ``query.join_rows_out`` counter adds the
    output's rows.
    """
    assert isinstance(left, Table) and isinstance(right, Table), (
        "sort_merge_join is in-memory only (a streaming join over "
        "RunStore partitions is an open item)")
    by = _normalize_by(on)
    for name, asc in by:
        assert asc, "join keys have no direction; use plain column names"
    with _op_scope("sort_merge_join", len(left) + len(right)):
        return _join_mem(left, right, on, by, codecs, suffixes, plans)


def _join_mem(left: Table, right: Table, on, by, codecs, suffixes,
              plans) -> Table:
    codec_l, pre_l = _key_data(left, on, codecs)
    codec_r, pre_r = _key_data(right, on, codecs)
    assert [(type(s.codec), s.codec.bits) for s in codec_l.specs] == \
        [(type(s.codec), s.codec.bits) for s in codec_r.specs], (
        "join key columns must encode identically (same codec type and "
        "width per column) on both sides; pass an explicit shared codec "
        "via codecs=")
    lc, lrid = sort_rowids_fused(codec_l, pre_l, plans)
    rc, rrid = sort_rowids_fused(codec_r, pre_r, plans)
    lc, rc = _fetch(lc), _fetch(rc)
    with trace.span("query.merge"):
        lo = _words_searchsorted(rc, lc, side="left")
        hi = _words_searchsorted(rc, lc, side="right")
    with trace.span("query.expand"):
        cnt = hi - lo
        total = int(cnt.sum())
        lpos = np.repeat(np.arange(cnt.shape[0]), cnt)
        seg_start = np.repeat(np.cumsum(cnt) - cnt, cnt)
        rpos = np.asarray(lo)[lpos] + (np.arange(total) - seg_start)
    metrics.counter("query.join_rows_out").inc(total)
    with trace.span("query.take", rows=total):
        # host row ids: device columns gather on the device, host
        # (float64) columns in numpy, with no round trip of the ids
        ltab = left.take(_fetch(lrid)[lpos])
        rtab = right.take(_fetch(rrid)[rpos])
    keys = {name for name, _ in by}
    out = {name: ltab.column(name) for name, _ in by}
    for name in left.column_names:
        if name not in keys:
            clash = name in right.column_names
            out[name + suffixes[0] if clash else name] = ltab.column(name)
    for name in right.column_names:
        if name not in keys:
            clash = name in left.column_names
            out[name + suffixes[1] if clash else name] = rtab.column(name)
    return Table(out)
