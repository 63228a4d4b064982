"""Public names for the Pallas kernels, plus the kernel-path sorts.

Every kernel resolves ``interpret=None`` through :func:`default_interpret`:
compiled on a TPU backend, interpreted elsewhere (the CPU test suite
checks the kernel bodies in interpret mode against the ``ref.py``
oracles).
"""

from __future__ import annotations

from repro.kernels import default_interpret
from repro.kernels.flash_attention import flash_attention_kernel as flash_attention
from repro.kernels.fractal_histogram import digit_histograms
from repro.kernels.fractal_histogram import fractal_histogram as histogram
from repro.kernels.fractal_rank import fractal_rank_digit as rank_digit
from repro.kernels.fractal_rank import fractal_rank_kernel as rank
from repro.kernels.fractal_reconstruct import fractal_reconstruct as reconstruct
from repro.kernels.moe_dispatch import moe_dispatch

__all__ = [
    "default_interpret",
    "flash_attention",
    "histogram",
    "digit_histograms",
    "rank",
    "rank_digit",
    "reconstruct",
    "moe_dispatch",
    "fractal_sort_kernel",
    "fractal_sort_pairs_kernel",
]


def _onehot_executor(n: int, p: int, block: int, interpret, max_bins_log2):
    """A one-hot plan (the rank engine that compiles for the TPU) and a
    :class:`~repro.core.executor.PlanExecutor` over the Pallas backend."""
    from repro.core.executor import PallasBackend, PlanExecutor
    from repro.core.sort_plan import make_sort_plan

    plan = make_sort_plan(n, p, max_bins_log2=max_bins_log2, engine="onehot")
    return plan, PlanExecutor(PallasBackend(block=block, interpret=interpret))


def fractal_sort_kernel(keys, p: int, block: int = 1024, interpret=None,
                        max_bins_log2=None):
    """End-to-end kernel-path sort for keys in [0, 2**p), p <= 32.

    Thin wrapper: builds a one-hot :class:`~repro.core.sort_plan.SortPlan`
    and hands it to a :class:`~repro.core.executor.PlanExecutor` over the
    :class:`~repro.core.executor.PallasBackend` — per LSD pass, histogram
    kernel → exclusive scan → rank kernel → full-key scatter; the final
    MSD pass scatters only the trailing-bit entries and rebuilds prefix
    bits from bin positions (reconstruct kernel) — the composition the
    paper calls FractalSortCPU(A), with the pass decomposition bounding
    every kernel's one-hot tile.
    """
    plan, ex = _onehot_executor(keys.shape[0], p, block, interpret,
                                max_bins_log2)
    return ex.run(keys, plan).astype(keys.dtype)


def fractal_sort_pairs_kernel(keys, values, p: int, block: int = 1024,
                              interpret=None, max_bins_log2=None):
    """Kernel-path key–value sort: the payload column rides every pass's
    scatter next to the keys (rank kernel per digit, reconstruct kernel
    for the prefix bits), mirroring
    :func:`repro.core.fractal_sort.fractal_sort_pairs` on the
    :class:`~repro.core.executor.PallasBackend`."""
    plan, ex = _onehot_executor(keys.shape[0], p, block, interpret,
                                max_bins_log2)
    out, vals = ex.run_pairs(keys, values, plan)
    return out.astype(keys.dtype), vals
