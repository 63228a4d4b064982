"""Pallas TPU kernel: fractal leaf-histogram build.

The paper's per-key atomic path update (§III.B.1) becomes a conflict-free
associative reduction shaped for the TPU: each grid step streams a key tile
HBM→VMEM, expands it to a one-hot matrix, and row-sums it into a VMEM-
resident accumulator (the LLC-resident global tree of the paper).  The
one-hot sum is MXU-friendly (``ones @ onehot``); the accumulator block is
pinned across the sequential TPU grid by an index_map that returns block 0
for every step, so the histogram never round-trips through HBM until the
final spill — the kernel's whole HBM traffic is one read of the key stream
plus one ``n_bins``-sized write.

Streaming accumulation (paper §III.D): ``init`` seeds the VMEM accumulator
with a previous chunk's counts, so an out-of-core consumer folds a whole
:class:`~repro.stream.ChunkSource` into one histogram with one kernel
launch per chunk — the carried counts ride the same pinned block, and the
per-chunk HBM cost stays one key-stream read plus one ``n_bins`` read and
write.

Upper trie levels are derived outside by pairwise reduction (cheap,
``2*n_bins`` int adds); the leaf level is the only bandwidth-relevant term.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import default_interpret

DEFAULT_BLOCK = 1024


def _histogram_kernel(keys_ref, init_ref, out_ref, *, n_bins: int,
                      block: int, taper_in_tile: bool):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        # seed the pinned accumulator from the carried counts (zeros when
        # the caller streams no carry) — the §III.D batch-merge, in-kernel.
        out_ref[...] = init_ref[...]

    keys = keys_ref[...]  # (block,)
    # one-hot (block, n_bins); padded lanes carry key == -1 and match nothing.
    cols = jax.lax.broadcasted_iota(jnp.int32, (block, n_bins), 1)
    if taper_in_tile:
        # counter-width tapering inside the tile (paper §III.D.1 applied
        # to the kernel): the one-hot matrix is int8 and the in-tile
        # partial counts int16 (a tile row count never exceeds `block`),
        # quartering the VMEM footprint of the widest intermediate; only
        # the final accumulate widens to int32.
        onehot = (keys[:, None] == cols).astype(jnp.int8)
        partial = onehot.astype(jnp.int16).sum(axis=0)
        out_ref[...] += partial.astype(jnp.int32)
    else:
        onehot = (keys[:, None] == cols).astype(jnp.int32)
        out_ref[...] += onehot.sum(axis=0)


@functools.partial(jax.jit, static_argnames=("n_bins", "block", "interpret",
                                             "taper_in_tile"))
def fractal_histogram(keys: jnp.ndarray, n_bins: int,
                      block: int = DEFAULT_BLOCK,
                      interpret: Optional[bool] = None,
                      taper_in_tile: bool = True,
                      init: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Leaf counts (bincount) of ``keys`` over ``[0, n_bins)``.

    ``keys`` is 1-D int32; values outside ``[0, n_bins)`` (e.g. -1 padding)
    are ignored.  ``n_bins`` should be a multiple of 128 for MXU alignment
    at the target (any value runs under interpret).  ``taper_in_tile``
    applies the paper's counter-width tapering to the in-tile
    intermediates (int8 one-hot / int16 partials); requires
    ``block < 2**15``.  ``init`` accumulates onto carried counts from a
    previous chunk (streaming histogram build) instead of zeros.
    """
    n = keys.shape[0]
    pad = (-n) % block
    if pad:
        keys = jnp.concatenate([keys, jnp.full((pad,), -1, keys.dtype)])
    if init is None:
        init = jnp.zeros((n_bins,), jnp.int32)
    grid = keys.shape[0] // block
    taper = taper_in_tile and block < (1 << 15)
    return pl.pallas_call(
        functools.partial(_histogram_kernel, n_bins=n_bins, block=block,
                          taper_in_tile=taper),
        grid=(grid,),
        in_specs=[pl.BlockSpec((block,), lambda i: (i,)),
                  # carried counts pinned like the accumulator: read once
                  # at step 0, never re-fetched.
                  pl.BlockSpec((n_bins,), lambda i: (0,))],
        # accumulator block pinned for the whole grid (index_map -> 0).
        out_specs=pl.BlockSpec((n_bins,), lambda i: (0,)),
        out_shape=jax.ShapeDtypeStruct((n_bins,), jnp.int32),
        interpret=default_interpret(interpret),
    )(keys.astype(jnp.int32), init.astype(jnp.int32))


def digit_histograms(keys: jnp.ndarray, passes, block: int = DEFAULT_BLOCK,
                     interpret: Optional[bool] = None,
                     taper_in_tile: bool = True,
                     init=None):
    """Multi-digit driver: one leaf histogram per :class:`DigitPass`.

    ``keys`` is the raw (uint32-castable) key stream; each plan pass gets
    the bincount of its ``bits``-wide digit at ``shift``.  Every per-digit
    tile stays bounded at ``block * 2**bits`` — the SortPlan decomposition
    applied at the kernel layer.  (On TPU the digits could share one key
    read by fusing the extracts into a single grid sweep; the driver keeps
    one kernel launch per digit, which is what interpret mode can check.)

    ``init`` (optional, one counts array per pass) accumulates each
    digit's histogram onto a previous chunk's counts — the streaming
    accumulation the out-of-core partitioner carries across a
    :class:`~repro.stream.ChunkSource`, one ``digit_histograms`` call per
    chunk.

    Returns a tuple of ``(2**bits,)`` int32 count arrays, plan order.
    """
    u = keys.astype(jnp.uint32)
    if init is None:
        init = (None,) * len(tuple(passes))
    out = []
    for dp, carried in zip(passes, init):
        digit = ((u >> dp.shift) & (dp.n_bins - 1)).astype(jnp.int32)
        out.append(fractal_histogram(digit, dp.n_bins, block=block,
                                     interpret=interpret,
                                     taper_in_tile=taper_in_tile,
                                     init=carried))
    return tuple(out)
