"""Entry ``fractal_sort``: one call sorts one key set with the program's
``repro.core.fractal_sort.fractal_sort(keys, p)``, all defaults.

Traffic parameters: ``rows`` keys a call; ``pool`` key sets made in
set-up, called in turn; ``sample`` how many calls' outputs the check
keeps (a reservoir over the whole window, drawn from the seed).
"""

from __future__ import annotations

import statistics
import time

import numpy as np

import jax

from repro.core.fractal_sort import fractal_sort


class SortCell:
    def __init__(self, cfg: dict, module, traffic: dict, seed: int):
        self.bits = int(cfg["key_bits"])
        self.rows = int(traffic["rows"])
        if self.rows > int(cfg["max_keys_per_sort"]):
            raise ValueError(f"{self.rows} keys a call is above the "
                             f"config's {cfg['max_keys_per_sort']}")
        self.module = module
        self.keys = list(module.generate(cfg, seed, self.rows,
                                         int(traffic.get("pool", 1))))
        self.sample = int(traffic.get("sample", 1))
        self.key_bytes = np.dtype(module.stored_dtype(self.bits)).itemsize
        self._host_keys = None

    @property
    def rows_per_call(self) -> int:
        return self.rows

    @property
    def min_bytes_per_row(self) -> int:
        """Keys read once and sorted keys written once."""
        return 2 * self.key_bytes

    def call(self, i: int):
        return fractal_sort(self.keys[i % len(self.keys)], self.bits)

    def control(self, i: int):
        keys = np.asarray(self.keys[i % len(self.keys)])
        return self.module.control(keys, self.bits)

    def oracle_ms(self) -> dict:
        """Median of three warm ``jax.lax.sort`` calls on the first key
        set: what a user would otherwise call on the same chip."""
        sort = jax.jit(jax.lax.sort)
        jax.block_until_ready(sort(self.keys[0]))
        times = []
        for _ in range(3):
            t0 = time.perf_counter()
            jax.block_until_ready(sort(self.keys[0]))
            times.append(time.perf_counter() - t0)
        return {"jax_lax_sort_ms": 1e3 * statistics.median(times)}

    def fetch(self, out) -> np.ndarray:
        return np.asarray(out)

    def release(self) -> None:
        """Bring the inputs to the host and free the device."""
        self._host_keys = [np.asarray(k) for k in self.keys]
        self.keys = [None] * len(self.keys)

    def check(self, samples) -> list:
        """For each sampled call, ``rows_out_of_place``: the keys that
        differ from the reference sort of the same input."""
        numbers = []
        refs = {}
        for i, got in samples:
            slot = i % len(self._host_keys)
            if slot not in refs:
                refs[slot] = self.module.reference(self._host_keys[slot],
                                                   self.bits)
            want = refs[slot]
            if got.shape != want.shape:
                wrong = max(got.size, want.size)
            else:
                wrong = int(np.count_nonzero(got != want))
            numbers.append({"rows_out_of_place": float(wrong)})
        return numbers


def setup(cfg: dict, module, traffic: dict, seed: int) -> SortCell:
    cell = SortCell(cfg, module, traffic, seed)
    jax.block_until_ready(cell.keys)
    return cell
