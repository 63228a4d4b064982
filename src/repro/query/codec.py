"""Order-preserving key codecs: typed columns → sortable unsigned bitstrings.

The sort core moves raw unsigned ``p``-bit integers; query columns are
signed ints, floats, bools, and multi-column compound keys.  A
:class:`Codec` maps a typed column to an unsigned code such that

    a < b  (column order)  ⇔  encode(a) < encode(b)  (unsigned order)

and back (``decode(encode(x)) == x``), reporting its exact bit width so
``make_sort_plan`` sizes radix passes from the *encoded* key — an 8-bit
status column costs two 4-bit passes, not a full 32-bit plan.

Transforms (all classical radix-key tricks, cf. the DB-middleware framing
of Stehle & Jacobsen and Leyenda's sort-based operators):

* signed ints — **bias flip**: add ``2**(bits-1)`` mod ``2**bits`` (flip
  the sign bit), mapping ``[-2**(b-1), 2**(b-1))`` monotonically onto
  ``[0, 2**b)``;
* float32/float64 — **IEEE-754 sign-magnitude transform**: non-negative
  floats get the sign bit set; negative floats are bitwise complemented
  (magnitude order reverses), yielding the IEEE total order on the
  unsigned codes (NaNs land at the extremes; -0.0 orders just below
  +0.0);
* bool — one bit;
* composite — each column's code packed **MSB-first** in key-priority
  order, per-column descending via **bit inversion** of that column's
  code (within its width).

Codes wider than 32 bits (float64, wide composites) are emitted as
**multi-word** codes: shape ``(n, W)`` uint32, word 0 most significant,
every word 32 bits wide except the last (``word_widths``).  Comparing
words lexicographically equals comparing codes numerically, so the query
operators sort them with one stable executor pass chain per word, least
significant word first.  Single-word codes are shape ``(n, 1)``.

float64 encode/decode run in numpy (the JAX side of this repo is x64-
disabled; the code *words* are uint32 and sort like any other key).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "Codec",
    "BoolCodec",
    "IntCodec",
    "UIntCodec",
    "Float32Codec",
    "Float64Codec",
    "CompositeCodec",
    "ColumnSpec",
    "infer_codec",
    "jit_encode",
    "word_widths",
]


def word_widths(bits: int) -> Tuple[int, ...]:
    """Bit width of each uint32 word of a ``bits``-wide code, MSB-first:
    all words carry 32 bits except the last, which carries the low
    ``((bits - 1) % 32) + 1`` bits (LSB-aligned)."""
    assert bits >= 1, f"code width {bits} out of range"
    last = ((bits - 1) % 32) + 1
    return (32,) * ((bits - last) // 32) + (last,)


def _mask(bits: int) -> jnp.ndarray:
    return jnp.uint32(((1 << bits) - 1) & 0xFFFFFFFF)


class Codec:
    """Order-preserving column ⇄ unsigned-code map.

    ``bits`` is the exact code width; ``encode`` returns ``(n, W)`` uint32
    words (``W = len(word_widths(bits))``), ``decode`` inverts it.

    Encoding is split into two halves so the sort can fuse it:

    * :meth:`prepare` — the host boundary: bitcast/layout-only, **never**
      order-transforming (float64 → two uint32 words via a numpy view is
      the whole reason this half exists — the repo runs JAX x64-off, so
      the 64-bit bit pattern must be split before it can enter a trace).
      Returns a pytree of ≤32-bit arrays.
    * :meth:`encode_fn` — a **pure, jit-traceable** function from the
      prepared pytree to the ``(n, W)`` uint32 code words.  Every
      order-preserving transform (bias flip, sign-magnitude, bit packing,
      descending inversion) lives here, so a caller can trace it straight
      into the first digit pass of a sort — the paper's fused
      histogram-update shape, with no host-side code matrix ever
      materialized.

    ``encode`` is always ``encode_fn(prepare(col))``; codecs are hashable
    values (frozen dataclasses; :class:`CompositeCodec` hashes by its
    specs), so jitted programs closed over a codec cache correctly.
    """

    bits: int

    @property
    def num_words(self) -> int:
        return len(word_widths(self.bits))

    def word_plans(self, n: int) -> tuple:
        """Per-word sort plans for an ``n``-row column: one default plan
        per emitted uint32 word, each sized to that word's exact bit
        width (:func:`~repro.core.sort_plan.make_sort_plan`), so
        codec-driven key widths (9-bit ids, 41-bit composites) reach the
        planner instead of a global 32-bit default."""
        from repro.core.sort_plan import make_sort_plan

        return tuple(make_sort_plan(n, w) for w in word_widths(self.bits))

    def prepare(self, col):
        """Host boundary: the column as trace-ready arrays (bitcast /
        layout only — no ordering transform happens here)."""
        return jnp.asarray(col)

    def encode_fn(self, prepped) -> jnp.ndarray:
        """Traceable order-preserving transform: prepared pytree →
        ``(n, W)`` uint32 code words."""
        raise NotImplementedError

    def encode(self, col) -> jnp.ndarray:
        return self.encode_fn(self.prepare(col))

    def decode(self, words: jnp.ndarray):
        raise NotImplementedError


@functools.lru_cache(maxsize=128)
def _encode_program(codec: "Codec"):
    """One jitted ``encode_fn`` per codec value (jax's jit cache then
    specializes per input shape) — the streaming table path encodes many
    chunks through the same codec and must not pay eager per-op dispatch
    each time."""
    return jax.jit(codec.encode_fn)


def jit_encode(codec: "Codec", col) -> jnp.ndarray:
    """``codec.encode(col)`` as one cached jitted dispatch."""
    return _encode_program(codec)(codec.prepare(col))


@dataclasses.dataclass(frozen=True)
class BoolCodec(Codec):
    bits: int = 1

    def encode_fn(self, prepped):
        return jnp.asarray(prepped).astype(bool).astype(jnp.uint32)[:, None]

    def decode(self, words):
        return words[:, 0] != 0


def _int_out_dtype(bits: int, signed: bool):
    """Narrowest dtype holding a ``bits``-wide (un)signed value: decode
    must hand back the dtype ``infer_codec`` maps to this codec, so
    operator outputs (group_by/distinct keys) re-infer the same codec —
    query steps compose."""
    if bits <= 8:
        return jnp.int8 if signed else jnp.uint8
    if bits <= 16:
        return jnp.int16 if signed else jnp.uint16
    return jnp.int32 if signed else jnp.uint32


@dataclasses.dataclass(frozen=True)
class IntCodec(Codec):
    """Signed ints in ``[-2**(bits-1), 2**(bits-1))`` via bias flip."""

    bits: int = 32

    def __post_init__(self):
        assert 2 <= self.bits <= 32, f"IntCodec bits={self.bits}"

    def encode_fn(self, prepped):
        u = jnp.asarray(prepped).astype(jnp.int32).astype(jnp.uint32)
        bias = jnp.uint32((1 << (self.bits - 1)) & 0xFFFFFFFF)
        return ((u + bias) & _mask(self.bits))[:, None]

    def decode(self, words):
        code = words[:, 0]
        if self.bits == 32:
            return jax.lax.bitcast_convert_type(
                code ^ jnp.uint32(0x80000000), jnp.int32)
        val = code.astype(jnp.int32) - (1 << (self.bits - 1))
        return val.astype(_int_out_dtype(self.bits, signed=True))


@dataclasses.dataclass(frozen=True)
class UIntCodec(Codec):
    """Unsigned ints in ``[0, 2**bits)`` — the identity codec."""

    bits: int = 32

    def __post_init__(self):
        assert 1 <= self.bits <= 32, f"UIntCodec bits={self.bits}"

    def encode_fn(self, prepped):
        return (jnp.asarray(prepped).astype(jnp.uint32)
                & _mask(self.bits))[:, None]

    def decode(self, words):
        code = words[:, 0]
        if self.bits == 32:
            return code
        return code.astype(_int_out_dtype(self.bits, signed=False))


@dataclasses.dataclass(frozen=True)
class Float32Codec(Codec):
    bits: int = 32

    def encode_fn(self, prepped):
        x = jnp.asarray(prepped).astype(jnp.float32)
        u = jax.lax.bitcast_convert_type(x, jnp.uint32)
        code = jnp.where(u >> 31 != 0, ~u, u | jnp.uint32(0x80000000))
        return code[:, None]

    def decode(self, words):
        code = words[:, 0]
        u = jnp.where(code >> 31 != 0, code ^ jnp.uint32(0x80000000), ~code)
        return jax.lax.bitcast_convert_type(u, jnp.float32)


@dataclasses.dataclass(frozen=True)
class Float64Codec(Codec):
    """Two-word code; the numpy boundary keeps full float64 precision
    while the emitted words stay uint32 (the repo runs JAX x64-off).

    ``prepare`` is a pure bitcast — the uint64 view split into (hi, lo)
    uint32 halves on the host, because x64-off jax cannot hold the 64-bit
    pattern — and the sign-magnitude transform runs per half in
    :meth:`encode_fn`: the sign lives in the hi word's top bit, so
    negative values complement both halves and non-negative values set
    only the hi half's sign bit."""

    bits: int = 64

    def prepare(self, col):
        u = np.asarray(col, np.float64).view(np.uint64)
        return (jnp.asarray((u >> np.uint64(32)).astype(np.uint32)),
                jnp.asarray(u.astype(np.uint32)))

    def encode_fn(self, prepped):
        hi, lo = prepped
        hi = jnp.asarray(hi).astype(jnp.uint32)
        lo = jnp.asarray(lo).astype(jnp.uint32)
        neg = (hi >> 31) != 0
        code_hi = jnp.where(neg, ~hi, hi | jnp.uint32(0x80000000))
        code_lo = jnp.where(neg, ~lo, lo)
        return jnp.stack([code_hi, code_lo], axis=1)

    def decode(self, words):
        w = np.asarray(words, np.uint64)
        code = (w[:, 0] << np.uint64(32)) | w[:, 1]
        u = np.where(code >> np.uint64(63) != 0,
                     code ^ np.uint64(1 << 63), ~code)
        return u.view(np.float64)


@dataclasses.dataclass(frozen=True)
class ColumnSpec:
    """One component of a composite key: its codec + sort direction."""

    codec: Codec
    ascending: bool = True


class CompositeCodec(Codec):
    """Multi-column key: component codes packed MSB-first in key-priority
    order; descending components are bit-inverted within their width, so
    one unsigned sort realizes any asc/desc mix.  ``encode`` takes a
    sequence of columns (one per spec), ``decode`` returns the tuple
    back.

    Composites compare and hash *by value* (their spec tuple): the query
    layer builds a fresh CompositeCodec per call, and the fused
    encode→sort programs are lru-cached on the codec — identity hashing
    would retrace every query."""

    def __init__(self, specs: Sequence[ColumnSpec]):
        assert len(specs) >= 1, "composite key needs at least one column"
        self.specs = tuple(specs)
        self.bits = sum(s.codec.bits for s in self.specs)

    def __eq__(self, other):
        return type(other) is CompositeCodec and self.specs == other.specs

    def __hash__(self):
        return hash(self.specs)

    def _component_chunks(self, spec: ColumnSpec, words: jnp.ndarray):
        """A component's code as (word, width) chunks, inverted if
        descending (order reversal within the component's bits)."""
        chunks = []
        for j, wbits in enumerate(word_widths(spec.codec.bits)):
            w = words[:, j]
            if not spec.ascending:
                w = w ^ _mask(wbits)
            chunks.append((w & _mask(wbits), wbits))
        return chunks

    def prepare(self, cols):
        cols = list(cols)
        assert len(cols) == len(self.specs), (
            f"composite expects {len(self.specs)} columns, got {len(cols)}")
        return tuple(spec.codec.prepare(col)
                     for spec, col in zip(self.specs, cols))

    def encode_fn(self, prepped) -> jnp.ndarray:
        assert len(prepped) == len(self.specs), (
            f"composite expects {len(self.specs)} prepared columns, "
            f"got {len(prepped)}")
        chunks = []
        for spec, pre in zip(self.specs, prepped):
            chunks.extend(
                self._component_chunks(spec, spec.codec.encode_fn(pre)))
        n = chunks[0][0].shape[0]
        out, cur, used = [], jnp.zeros((n,), jnp.uint32), 0
        for arr, w in chunks:
            while w > 0:
                take = min(32 - used, w)
                piece = (arr >> (w - take)) & _mask(take)
                cur = piece if take == 32 else ((cur << take) | piece)
                used += take
                w -= take
                if used == 32:
                    out.append(cur)
                    cur, used = jnp.zeros((n,), jnp.uint32), 0
        if used:
            out.append(cur)
        return jnp.stack(out, axis=1)

    def _extract(self, words: jnp.ndarray, bit: int, w: int) -> jnp.ndarray:
        """The ``w``-bit (≤ 32) chunk at stream offset ``bit``."""
        n = words.shape[0]
        widths = word_widths(self.bits)
        val = jnp.zeros((n,), jnp.uint32)
        while w > 0:
            j, consumed = 0, 0
            while consumed + widths[j] <= bit:
                consumed += widths[j]
                j += 1
            off = bit - consumed
            take = min(widths[j] - off, w)
            piece = (words[:, j] >> (widths[j] - off - take)) & _mask(take)
            val = piece if take == 32 else ((val << take) | piece)
            bit += take
            w -= take
        return val

    def decode(self, words: jnp.ndarray):
        cols, bit = [], 0
        for spec in self.specs:
            cw = word_widths(spec.codec.bits)
            comp = []
            for wbits in cw:
                chunk = self._extract(words, bit, wbits)
                if not spec.ascending:
                    chunk = chunk ^ _mask(wbits)
                comp.append(chunk)
                bit += wbits
            cols.append(spec.codec.decode(jnp.stack(comp, axis=1)))
        return tuple(cols)


_DTYPE_CODECS = {
    np.dtype(np.bool_): BoolCodec(),
    np.dtype(np.int8): IntCodec(8),
    np.dtype(np.int16): IntCodec(16),
    np.dtype(np.int32): IntCodec(32),
    np.dtype(np.uint8): UIntCodec(8),
    np.dtype(np.uint16): UIntCodec(16),
    np.dtype(np.uint32): UIntCodec(32),
    np.dtype(np.float32): Float32Codec(),
    np.dtype(np.float64): Float64Codec(),
}


def infer_codec(col, bits: Optional[int] = None) -> Codec:
    """The order-preserving codec for a column's dtype (``bits`` narrows
    integer codecs when the value range is known, shrinking the plan)."""
    dt = np.dtype(col.dtype)
    codec = _DTYPE_CODECS.get(dt)
    assert codec is not None, f"no codec for column dtype {dt}"
    if bits is not None and isinstance(codec, (IntCodec, UIntCodec)):
        codec = type(codec)(bits)
    return codec
