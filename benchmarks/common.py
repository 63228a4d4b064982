"""Shared timing/reporting helpers for the paper-table benchmarks."""

from __future__ import annotations

import time

import jax
import numpy as np


def time_fn(fn, *args, warmup: int = 2, repeat: int = 5) -> float:
    """Median wall seconds per call of a (jitted) fn."""
    for _ in range(warmup):
        out = fn(*args)
        jax.block_until_ready(out)
    ts = []
    for _ in range(repeat):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def row(name: str, seconds: float, derived: str = ""):
    """One CSV row: name,us_per_call,derived."""
    print(f"{name},{seconds * 1e6:.1f},{derived}")


def rand_keys(rng, n: int, p: int):
    """Uniform p-bit benchmark keys in the sort entry points' dtype
    convention (uint32 for p=32, int32 below)."""
    import jax.numpy as jnp

    return jnp.asarray(
        rng.integers(0, 1 << p, n, dtype=np.uint64).astype(np.uint32),
        jnp.uint32 if p == 32 else jnp.int32)
