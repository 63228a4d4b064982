"""SortPlan: digit decomposition of a ``p``-bit sort into bounded passes.

The paper trades *number of radix passes* against *bytes moved per pass*
(§III.G: complexity O(n * ceil(p / n_L)) with compressed entries).  The
seed implementation hard-coded that trade as "one pass per 16-bit field",
which is the right shape for the paper's LLC-resident 2**16-counter trie —
but the rank stage here materializes a (batch x n_bins) one-hot tile, so a
2**16-bin pass does O(n * 2**16) work and is catastrophically slow off-TPU.

A :class:`SortPlan` makes the trade explicit.  For keys of ``p`` bits it
emits a sequence of stable counting passes, LSD -> MSD:

* every pass ranks on a *digit* of at most ``max_bins_log2`` bits, so the
  one-hot tile is bounded at ``batch * 2**max_bins_log2`` entries;
* the final (MSD) pass is the *fractal* pass: its digit is the trie prefix,
  entries carry only the trailing ``p - depth`` bits, and the prefix bits
  are reconstructed from bin positions (Algorithm 5) — the compressed-entry
  bandwidth story is per-plan, not per-16-bit-field;
* total work is O(n * ceil(p / w) * 2**w) for digit width ``w`` — the
  multi-pass digit scheme of Stehle & Jacobsen's hybrid radix sort and
  Wassenberg & Sanders' bandwidth-bounded radix, applied to the fractal
  rank stage.

Digit widths also never exceed the trie depth scale ``~log2(n)``, so tiny
inputs (n=64, p=16) get a few 5-bit passes instead of one 1024-bin pass.

**Rank engines (per-pass execution hints).**  The O(n * 2**w) term above
is the *one-hot* engine's; the *scatter* engine
(:func:`~repro.core.fractal_sort.fractal_rank_scatter`) ranks a pass in
O(n log tile) independent of the digit width, which is what makes wide
passes executable at all.  Each :class:`DigitPass` carries an optional
``engine`` hint ("onehot" / "scatter" / ``None`` = by digit width, as
:func:`default_engine` says); hints are *execution* metadata — two plans
differing only in hints sort identically.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional

from repro.core import fractal_tree as ft

__all__ = [
    "DEFAULT_MAX_BINS_LOG2",
    "DigitPass",
    "ONEHOT_MAX_BITS",
    "SortPlan",
    "default_engine",
    "make_sort_plan",
    "quantize_sort_bits",
    "rank_chunk_len",
    "scatter_tile_len",
]

# Default per-pass bin-count cap (2**4 = 16 bins).  Swept by
# benchmarks/bench_sortplan.py: on this CPU host the rank stage is pure
# compute on the materialized one-hot tile, so total work
# O(n * 2**w * ceil(p / w)) is minimized at the narrowest digit — w=4 beats
# w=8 by ~4x and w=11 by ~16x at n=2**15, p=32 (measured), and wins at
# p=16 too.  The trade reverses on hardware where the tile maps to a
# matrix unit and passes cost bandwidth (the paper's CPU runs one
# 2**16-counter pass per field): pass max_bins_log2=16 there, or re-run
# the sweep.
DEFAULT_MAX_BINS_LOG2 = 4

# Floor on the digit width for very small inputs: below this, per-pass
# overhead dominates any one-hot-tile savings.
_MIN_DIGIT_BITS = 4

# Byte budget for the rank stage's materialized (chunk x n_bins) one-hot
# tile; wide digits trade chunk length for tile width (paper §III.C).
_RANK_TILE_BUDGET = 1 << 21

# The segment-aware grouped-trailing mode keeps a (2**depth, 2**w) per-
# segment digit table; when the table entries outnumber the keys by more
# than this margin (log2) — or exceed the absolute cap — the table dwarfs
# the key stream and the executor falls back to a full re-plan.
_GROUPED_TABLE_MARGIN_LOG2 = 4
_GROUPED_TABLE_LOG2_CAP = 20


def quantize_sort_bits(eff: int, width: int, step: int = 8) -> int:
    """Round a partition's effective sort width up to a multiple of
    ``step`` bits, capped at the stored word width.

    Safe because the rounded-up bits are part of the partition's *shared*
    prefix — equal on every row — so ranking on them reorders nothing.
    The point is trace sharing: partitions whose exact effective widths
    differ (21, 19, 23 bits...) collapse onto one quantized width (24),
    so the per-(length, bits) jitted sort program compiles once and every
    partition in the bucket reuses it — compile cost, not dispatch cost,
    dominates the external sort's per-partition loop on a cold cache.
    """
    if eff <= 0:
        return 0
    return min(-(-eff // step) * step, width)


def rank_chunk_len(n_bins: int, base: int = 1024) -> int:
    """Execution hint: rank-stage chunk length for an ``n_bins``-bin pass,
    bounding the materialized one-hot tile at ``_RANK_TILE_BUDGET``."""
    return max(8, min(base, _RANK_TILE_BUDGET // max(n_bins, 1)))


# Scatter-engine tile bounds (elements per sorted tile).  The engine sorts
# digit-and-origin composites per tile, so per-element work grows only
# log(tile); tiles below _SCATTER_TILE_MIN waste the flat per-tile
# overheads, tiles above _SCATTER_TILE_MAX stop fitting the composite
# packing headroom (tile * n_bins <= 2**31 at n_bins = 2**16) and push the
# sorted working set out of LLC.  Measured on this 2-core host: 2**11..2**13
# is flat-optimal for bins 2**4..2**11 with 2**13 best at 2**16 bins.
_SCATTER_TILE_MIN = 1 << 11
_SCATTER_TILE_MAX = 1 << 13


def scatter_tile_len(n_bins: int, base: int = 1024) -> int:
    """Execution hint: sorted-tile length for a scatter-engine pass.

    Unlike :func:`rank_chunk_len` this *grows* with ``n_bins`` (wider
    digits want wider tiles so the per-tile (tiles, n_bins) histogram
    table stays small next to the key stream); ``base`` only ever raises
    the floor — the user batch knob can widen tiles but a narrow one-hot
    chunk hint must not shrink them."""
    tile = 1 << max(n_bins - 1, 1).bit_length()  # next_pow2(n_bins)
    return max(min(max(tile, _SCATTER_TILE_MIN), _SCATTER_TILE_MAX), base)


#: Widest digit a pass without an engine hint ranks on the one-hot engine.
#: This is where the analytic cost model the engines were once picked by
#: crossed over, at every n: one-hot work grows as 2**bits a key, the
#: scatter engine's stays ~32 bin columns a key.
ONEHOT_MAX_BITS = 5


def default_engine(bits: int) -> str:
    """The rank engine of a ``bits``-wide pass that carries no hint."""
    return "onehot" if bits <= ONEHOT_MAX_BITS else "scatter"


@dataclasses.dataclass(frozen=True)
class DigitPass:
    """One stable counting pass over key bits ``[shift, shift + bits)``.

    ``engine`` is an execution hint — "onehot" (materialized one-hot tile,
    MXU-shaped), "scatter" (sorted-tile scatter/bincount engine), or
    ``None`` (by digit width: :func:`default_engine`).  Hints never change
    the sorted output, only how ranks are computed."""

    shift: int
    bits: int
    kind: str = "lsd"  # "lsd" = full-key scatter; "msd" = fractal/reconstruct
    engine: Optional[str] = None

    @property
    def n_bins(self) -> int:
        return 1 << self.bits

    def rank_batch(self, base: int = 1024) -> int:
        """Per-pass execution hint: the rank chunk length (one-hot) or
        sorted-tile length (scatter) the executor should stream this pass
        at."""
        if self.engine == "scatter":
            return scatter_tile_len(self.n_bins, base)
        return rank_chunk_len(self.n_bins, base)


@dataclasses.dataclass(frozen=True)
class SortPlan:
    """Pass sequence for a ``p``-bit sort of ``n`` keys, LSD -> MSD."""

    n: int
    p: int
    passes: tuple  # tuple[DigitPass, ...], contiguous, covering bits [0, p)

    @property
    def depth(self) -> int:
        """Trie depth of the final (MSD/fractal) pass (0 for the empty
        ``p=0`` plan — nothing to rank)."""
        return self.passes[-1].bits if self.passes else 0

    @property
    def trailing_bits(self) -> int:
        """Entry payload width of the final pass (bits below the prefix)."""
        return self.passes[-1].shift if self.passes else 0

    @property
    def num_passes(self) -> int:
        return len(self.passes)

    @property
    def grouped_table_log2(self) -> int:
        """log2 size of the (segment, digit) table the segment-aware
        grouped-trailing executor mode materializes: ``depth`` prefix
        segments x the widest trailing digit."""
        lsd_bits = max((d.bits for d in self.passes[:-1]), default=0)
        return self.depth + lsd_bits

    @property
    def supports_grouped_trailing(self) -> bool:
        """Execution hint: whether the trailing LSD passes can run
        segment-aware over the prefix-grouped array (streaming/batched
        path) instead of re-running the full plan.  False when the
        per-segment digit table would dwarf the key stream — wide plans
        (e.g. the paper's 16b+16b p=32 scheme) or wide-ish plans over
        small inputs."""
        cap = min(_GROUPED_TABLE_LOG2_CAP,
                  ft.ceil_log2(max(self.n, 1)) + _GROUPED_TABLE_MARGIN_LOG2)
        return self.trailing_bits > 0 and self.grouped_table_log2 <= cap

    def describe(self) -> str:
        return "+".join(f"{d.bits}b" for d in self.passes) or "identity"


def make_sort_plan(n: int, p: int, l_n: Optional[int] = None,
                   max_bins_log2: Optional[int] = None,
                   engine: Optional[str] = None) -> SortPlan:
    """Decompose a ``p``-bit sort of ``n`` keys into bounded digit passes.

    An explicit ``l_n`` sets the trie depth of the final pass and *wins
    over the bin cap* (the caller asked for that trie; when it is None the
    depth defaults to the paper's L = min(p, ceil(log2 n)) and is capped).
    ``max_bins_log2`` caps every other pass's bin count at
    ``2**max_bins_log2`` (default :data:`DEFAULT_MAX_BINS_LOG2`).  The
    trailing ``p - depth`` bits are split into balanced LSD digits no
    wider than the cap and no wider than the trie-depth scale, so
    ``n_bins`` never dwarfs ``n``.

    ``engine`` stamps every pass's rank-engine hint ("onehot"/"scatter";
    ``None`` leaves the choice to the digit width: :func:`default_engine`).

    Degenerate widths are legal and *skipped*, never executed: ``p = 0``
    (every key is the zero-width value — the external sort reaches this
    when recursive partitioning has consumed every key bit) yields the
    empty identity plan (no passes; the executor returns its input
    unchanged), and a zero-width trailing field never emits a 1-bin pass
    — a single-bin pass ranks nothing and only burned a full scatter.
    """
    assert 0 <= p <= 32, f"p={p} out of range (0..32)"
    assert engine in (None, "onehot", "scatter"), f"unknown engine {engine!r}"
    if p == 0:
        return SortPlan(n=n, p=0, passes=())
    w_max = DEFAULT_MAX_BINS_LOG2 if max_bins_log2 is None else max_bins_log2
    assert 1 <= w_max <= 16, f"max_bins_log2={w_max} out of range (1..16)"
    if l_n is None:
        depth = max(1, min(ft.trie_depth(n, min(p, 16)), p, w_max))
    else:
        assert 1 <= l_n <= 16, f"l_n={l_n} out of range (1..16)"
        depth = min(l_n, p)
    t = p - depth
    passes = []
    if t > 0:
        # LSD digits over the trailing bits, balanced, capped by both the
        # global bin budget and the data scale (no 2**10-bin pass for n=64).
        w = max(1, min(w_max, max(_MIN_DIGIT_BITS, depth)))
        num = math.ceil(t / w)
        base, extra = divmod(t, num)
        shift = 0
        for i in range(num):
            bits = base + (1 if i < extra else 0)
            if bits > 0:  # a zero-width field is a 1-bin no-op: skip it
                passes.append(DigitPass(shift=shift, bits=bits, kind="lsd",
                                        engine=engine))
            shift += bits
        assert shift == t
    passes.append(DigitPass(shift=t, bits=depth, kind="msd", engine=engine))
    return SortPlan(n=n, p=p, passes=tuple(passes))
