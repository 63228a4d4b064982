"""Uniform integer keys made on the device from a seed, and the plain
reference sort of them.

``generate`` makes ``pool`` key sets of ``rows`` keys each in one jitted
call, each set's random bits shifted and stored in one fusion, so that
set-up needs no device memory beside the keys.  The keys are
``key_bits`` wide and stored as the program's sort entry takes them
(int32 below 32 bits, uint32 at 32).  ``reference``
sorts by counting, which shares nothing with the program; ``control``
is that reference with one guarantee broken: it orders the keys on all
but their lowest bit, the result of a sort that skipped its last bit.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def stored_dtype(bits: int):
    return np.uint32 if bits == 32 else np.int32


def seed_key(seed: int):
    """A PRNG key for any non-negative seed, also one above 32 bits."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed % 2**32), seed // 2**32)


@functools.lru_cache(maxsize=8)
def _program(rows: int, pool: int, bits: int):
    dtype = stored_dtype(bits)

    @jax.jit
    def make(key):
        def one(i):
            u = jax.random.bits(jax.random.fold_in(key, i), (rows,),
                                jnp.uint32)
            return (u >> (32 - bits) if bits < 32 else u).astype(dtype)

        return tuple(one(i) for i in range(pool))

    return make


def generate(cfg: dict, seed: int, rows: int, pool: int):
    """``pool`` device arrays of ``rows`` uniform keys each."""
    return _program(int(rows), int(pool), int(cfg["key_bits"]))(
        seed_key(seed))


def reference(keys: np.ndarray, bits: int) -> np.ndarray:
    """``keys`` in ascending order, by counting."""
    counts = np.bincount(keys.astype(np.int64), minlength=1 << bits)
    return np.repeat(np.arange(1 << bits).astype(keys.dtype), counts)


def control(keys: np.ndarray, bits: int) -> np.ndarray:
    """``keys`` ordered on their ``bits - 1`` high bits only."""
    high = (keys.astype(np.int64) >> 1).astype(
        np.uint16 if bits <= 17 else np.int64)
    return keys[np.argsort(high, kind="stable")]
