"""Pallas TPU kernel: FractalSortCPUA sorted-array reconstruction (Alg. 5).

Rebuilds the sorted key array from (bin CDF, permuted trailing-bit entries).
The bin-identifier bits of every output key are *recovered from the output
position* against the VMEM-resident CDF — they are never read from memory
(the paper's ≈ 2·(p/8)-bytes-per-key claim).  Per output tile:

    slot_bin[j] = #{ b : cdf[b] <= slot_j }     (compare+reduce, VPU)
    key[j]      = slot_bin[j] << t | trailing[j]

HBM traffic: one read of the (narrow) trailing entries + one write of the
keys; the CDF block stays pinned in VMEM for the whole grid.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import default_interpret

DEFAULT_BLOCK = 1024


def _reconstruct_kernel(cdf_ref, trailing_ref, out_ref, *, n_bins: int,
                        block: int, t_bits: int):
    i = pl.program_id(0)
    slots = i * block + jax.lax.broadcasted_iota(jnp.int32, (block, 1), 0)[:, 0]
    cdf = cdf_ref[...]  # (n_bins,) inclusive ends
    # bin of slot j = count of bins whose end <= j  (searchsorted 'right').
    le = (cdf[None, :] <= slots[:, None]).astype(jnp.int32)  # (block, n_bins)
    slot_bin = le.sum(axis=1)
    out_ref[...] = (slot_bin << t_bits) | trailing_ref[...]


@functools.partial(jax.jit, static_argnames=("n_bins", "t_bits", "block", "interpret"))
def fractal_reconstruct(counts: jnp.ndarray, trailing: jnp.ndarray,
                        n_bins: int, t_bits: int,
                        block: int = DEFAULT_BLOCK,
                        interpret: Optional[bool] = None) -> jnp.ndarray:
    """Sorted keys from bin ``counts`` and sorted-order ``trailing`` entries.

    ``counts``: (n_bins,) int32; ``trailing``: (n,) int32 (only low
    ``t_bits`` used; pass zeros when the trie covers full precision).
    """
    n = trailing.shape[0]
    pad = (-n) % block
    if pad:
        trailing = jnp.concatenate([trailing, jnp.zeros((pad,), trailing.dtype)])
    grid = trailing.shape[0] // block
    cdf = jnp.cumsum(counts.astype(jnp.int32))
    out = pl.pallas_call(
        functools.partial(_reconstruct_kernel, n_bins=n_bins, block=block,
                          t_bits=t_bits),
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((n_bins,), lambda i: (0,)),  # CDF resident
            pl.BlockSpec((block,), lambda i: (i,)),
        ],
        out_specs=pl.BlockSpec((block,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((trailing.shape[0],), jnp.int32),
        interpret=default_interpret(interpret),
    )(cdf, trailing.astype(jnp.int32))
    return out[:n]


def fractal_reconstruct_plan(counts: jnp.ndarray, trailing: jnp.ndarray,
                             plan, block: int = DEFAULT_BLOCK,
                             interpret: Optional[bool] = None
                             ) -> jnp.ndarray:
    """Multi-digit driver: Algorithm 5 for a :class:`SortPlan`'s MSD pass.

    The plan's final pass defines both the bin space (``2**depth``) and the
    entry payload width (``trailing_bits = p - depth``); the int32 kernel
    arithmetic wraps for p=32 keys with the top bit set, which is bit-exact
    once viewed as uint32 (callers cast to the key dtype).
    """
    last = plan.passes[-1]
    return fractal_reconstruct(counts, trailing, last.n_bins, last.shift,
                               block=block, interpret=interpret)
