"""Pallas TPU kernels: stable fractal rank (scatter-index) computation.

Two rank engines, one contract (mirroring the jnp engines in
``core/fractal_sort.py``):

**One-hot** (:func:`fractal_rank_kernel`) — for each key, its final slot

    rank[i] = bin_start[key[i]] + carry[key[i]] + (earlier equal keys in tile)

where ``carry`` is the running per-bin count of all previous tiles — the
batch-streaming cached histogram of paper §III.C/D, held in a VMEM scratch
across the sequential grid.  The kernel is *gather-free*: every per-key
lookup is phrased through the one-hot matrix so it maps onto the MXU /
VPU instead of serialized VMEM gathers:

    base  = rowsum(onehot * (bin_start + carry))  # (block,), int32 VPU
    intra = rowsum((strict_lower_tri @ onehot) * onehot)   # MXU
    rank  = base + intra

One read of the key stream, one write of the rank stream; the carry never
leaves VMEM.  The one-hot tile costs O(block * n_bins) per step — great
while the tile feeds the MXU, ruinous for wide digits.

**Scatter** (:func:`fractal_rank_scatter_kernel`) — engine parity with
:func:`~repro.core.fractal_sort.fractal_rank_scatter`: each block packs
(digit, position) into one word, sorts the packed words in-block
(position in the low bits = stable by construction), reads the per-digit
block segment boundaries off the sorted composites with ``searchsorted``
probes, and emits ranks with one in-block scatter — O(block log block +
n_bins) per step, digit-width independent.  The same VMEM carry scratch
streams across the grid.  It runs in interpret mode only: the in-kernel
sort, ``searchsorted``, gather and scatter have no Pallas TPU lowering,
so asking for it compiled raises instead of falling back to the one-hot
kernel.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import default_interpret

DEFAULT_BLOCK = 1024


def _rank_kernel(keys_ref, bin_start_ref, rank_ref, carry_ref, *,
                 n_bins: int, block: int):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        carry_ref[...] = jnp.zeros_like(carry_ref)

    keys = keys_ref[...]  # (block,)
    cols = jax.lax.broadcasted_iota(jnp.int32, (block, n_bins), 1)
    hit = keys[:, None] == cols
    onehot = hit.astype(jnp.bfloat16)
    # strictly-earlier equal keys: strict-lower-triangular @ one-hot on the
    # MXU.  0/1 operands are exact in bf16 and the f32 accumulator is
    # exact for counts below 2**24 (a tile holds `block` keys).
    row = jax.lax.broadcasted_iota(jnp.int32, (block, block), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (block, block), 1)
    tri = (col < row).astype(jnp.bfloat16)
    running = jnp.dot(tri, onehot, preferred_element_type=jnp.float32)
    intra = jnp.sum(jnp.where(hit, running, 0.0), axis=1).astype(jnp.int32)
    # the bin's start is picked by a masked lane sum, kept in int32:
    # starts reach n, past f32's exact range.
    start = bin_start_ref[...] + carry_ref[...]
    base = jnp.sum(jnp.where(hit, start[None, :], 0), axis=1)
    rank_ref[...] = base + intra
    carry_ref[...] += jnp.sum(hit.astype(jnp.int32), axis=0)


@functools.partial(jax.jit, static_argnames=("n_bins", "block", "interpret"))
def fractal_rank_kernel(keys: jnp.ndarray, bin_start: jnp.ndarray,
                        n_bins: int, block: int = DEFAULT_BLOCK,
                        interpret: Optional[bool] = None) -> jnp.ndarray:
    """Stable output slot per key given precomputed exclusive bin starts.

    ``keys``: 1-D int32 in [0, n_bins) (pad with -1: padded ranks emit
    garbage at padded slots, callers slice).  ``bin_start``: (n_bins,) int32.
    """
    n = keys.shape[0]
    pad = (-n) % block
    if pad:
        keys = jnp.concatenate([keys, jnp.full((pad,), -1, keys.dtype)])
    grid = keys.shape[0] // block
    out = pl.pallas_call(
        functools.partial(_rank_kernel, n_bins=n_bins, block=block),
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((block,), lambda i: (i,)),
            pl.BlockSpec((n_bins,), lambda i: (0,)),  # resident all grid
        ],
        out_specs=pl.BlockSpec((block,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((keys.shape[0],), jnp.int32),
        scratch_shapes=[pltpu_scratch((n_bins,), jnp.int32)],
        interpret=default_interpret(interpret),
    )(keys.astype(jnp.int32), bin_start.astype(jnp.int32))
    return out[:n]


def pltpu_scratch(shape, dtype):
    """VMEM scratch allocation (interpret-safe)."""
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.VMEM(shape, dtype)


def _require_interpret(interpret: Optional[bool]) -> None:
    if not default_interpret(interpret):
        raise NotImplementedError(
            "the scatter rank kernel has no Pallas TPU lowering (in-kernel "
            "sort, searchsorted, gather and scatter); run it with "
            "interpret=True or use the one-hot engine")


def _rank_scatter_kernel(keys_ref, bin_start_ref, rank_ref, carry_ref, *,
                         n_bins: int, block: int):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        carry_ref[...] = jnp.zeros_like(carry_ref)

    blog = block.bit_length() - 1  # block is a power of two (driver assert)
    keys = keys_ref[...]  # (block,) digits; padding carries n_bins
    comp = (keys.astype(jnp.uint32) << blog) | \
        jax.lax.iota(jnp.uint32, block)
    sc = jnp.sort(comp)
    ds = (sc >> blog).astype(jnp.int32)          # digits, sorted order
    orig = (sc & jnp.uint32(block - 1)).astype(jnp.int32)
    # per-digit block segments off the sorted composites: bin b's segment
    # starts where composites reach b << blog (padding sorts past the
    # n_bins probe, so counts exclude it).
    probes = jax.lax.iota(jnp.uint32, n_bins + 1) << blog
    bounds = jnp.searchsorted(sc, probes).astype(jnp.int32)
    lower = jnp.searchsorted(sc, (sc >> blog) << blog).astype(jnp.int32)
    safe = jnp.minimum(ds, n_bins - 1)
    start = bin_start_ref[...] + carry_ref[...]
    rank_sorted = start[safe] + jax.lax.iota(jnp.int32, block) - lower
    rank_ref[...] = jnp.zeros((block,), jnp.int32).at[orig].set(rank_sorted)
    carry_ref[...] += bounds[1:] - bounds[:-1]


@functools.partial(jax.jit, static_argnames=("n_bins", "block", "interpret"))
def fractal_rank_scatter_kernel(keys: jnp.ndarray, bin_start: jnp.ndarray,
                                n_bins: int, block: int = DEFAULT_BLOCK,
                                interpret: Optional[bool] = None
                                ) -> jnp.ndarray:
    """Scatter-engine ranks given precomputed exclusive bin starts.

    ``keys``: 1-D int32 in [0, n_bins) (the driver pads with ``n_bins``,
    which sorts past every real composite; padded slots emit garbage
    ranks and are sliced).  Same signature and output as
    :func:`fractal_rank_kernel`, digit-width-independent arithmetic.
    Interpret mode only: compiled, it raises ``NotImplementedError``.
    """
    _require_interpret(interpret)
    assert block & (block - 1) == 0, f"block={block} must be a power of two"
    assert n_bins << (block.bit_length() - 1) < (1 << 32), (
        f"composite packing overflow: n_bins={n_bins} block={block}")
    n = keys.shape[0]
    pad = (-n) % block
    if pad:
        keys = jnp.concatenate([keys, jnp.full((pad,), n_bins, keys.dtype)])
    grid = keys.shape[0] // block
    out = pl.pallas_call(
        functools.partial(_rank_scatter_kernel, n_bins=n_bins, block=block),
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((block,), lambda i: (i,)),
            pl.BlockSpec((n_bins,), lambda i: (0,)),  # resident all grid
        ],
        out_specs=pl.BlockSpec((block,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((keys.shape[0],), jnp.int32),
        scratch_shapes=[pltpu_scratch((n_bins,), jnp.int32)],
        interpret=True,
    )(keys.astype(jnp.int32), bin_start.astype(jnp.int32))
    return out[:n]


def fractal_rank_counts(digit: jnp.ndarray, n_bins: int,
                        block: int = DEFAULT_BLOCK,
                        interpret: Optional[bool] = None,
                        bin_start: jnp.ndarray = None,
                        engine: Optional[str] = None):
    """Kernel-path rank primitive on an already-extracted digit stream:
    histogram kernel → exclusive scan (tiny: ``n_bins`` ints, host/VPU) →
    rank kernel (the ``engine``'s — one-hot tile bounded at
    ``block * n_bins``, or the width-independent scatter kernel).

    This is the :class:`~repro.core.executor.PallasBackend`'s ``rank``
    primitive, so its return matches the executor's streaming-carry
    contract: ``(rank, counts, carry_out)`` with ``carry_out == counts``
    (the kernel's carry lives in VMEM scratch and starts at zero per
    call — cross-call streaming is the jnp backend's mode).  ``bin_start``
    may be supplied when the global histogram is already known
    (distributed merge).  ``engine`` is the plan's per-pass hint; ``None``
    keeps the one-hot kernel — the MXU-shaped tile is the TPU-native
    default, so the kernel driver does *not* pick by digit width.
    ``"scatter"`` runs in interpret mode only and raises compiled.
    """
    from repro.core.fractal_tree import exclusive_cumsum
    from repro.kernels.fractal_histogram import fractal_histogram

    assert engine in (None, "onehot", "scatter"), (
        f"unknown kernel rank engine {engine!r}")
    if engine == "scatter":
        _require_interpret(interpret)
    counts = fractal_histogram(digit, n_bins, block=block,
                               interpret=interpret)
    if bin_start is None:
        bin_start = exclusive_cumsum(counts)
    kernel = (fractal_rank_scatter_kernel if engine == "scatter"
              else fractal_rank_kernel)
    rank = kernel(digit, bin_start, n_bins, block=block,
                  interpret=interpret)
    return rank, counts, counts


def fractal_rank_digit(keys: jnp.ndarray, digit_pass,
                       block: int = DEFAULT_BLOCK,
                       interpret: Optional[bool] = None,
                       bin_start: jnp.ndarray = None):
    """Multi-digit driver: stable ranks on one :class:`DigitPass` digit.

    Extracts the ``bits``-wide digit at ``shift`` from the raw key stream
    and runs :func:`fractal_rank_counts` on it under the pass's engine
    hint.

    Returns ``(rank, counts)``; ``bin_start`` may be supplied when the
    global histogram is already known (distributed merge).
    """
    dp = digit_pass
    digit = ((keys.astype(jnp.uint32) >> dp.shift)
             & (dp.n_bins - 1)).astype(jnp.int32)
    rank, counts, _ = fractal_rank_counts(digit, dp.n_bins, block=block,
                                          interpret=interpret,
                                          bin_start=bin_start,
                                          engine=dp.engine)
    return rank, counts
