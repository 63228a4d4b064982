"""Runs of the cells at a tiny size on whatever JAX finds, for the tests:
the same set-up, window, check and report as a chip run, with the sizes
cut and the device's peaks taken as the v5e's."""

from __future__ import annotations

import argparse
import contextlib
import copy
import io
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
for p in (str(HERE), str(HERE.parents[1] / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import harness  # noqa: E402

CONFIG = {
    "paper_p16": {},
    "tpch_sf10": {"orders_rows": 3000, "lineitem_rows": 11997,
                  "part_rows": 2000},
}
TRAFFIC = {"bulk": {"rows": 4096}}


def spec(workload: str) -> harness.Spec:
    """The spec of the cell named ``<config>.<traffic>``, with its sizes
    cut; the cell need not be in BENCHMARK.json yet."""
    config, traffic = workload.split(".", 1)
    bench = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    s = harness.cell_spec({"name": workload, "config": config,
                           "traffic": traffic, "chips": 1}, bench)
    s.config = {**copy.deepcopy(s.config), **CONFIG[s.workload["config"]]}
    s.traffic = {**copy.deepcopy(s.traffic),
                 **TRAFFIC.get(s.workload["traffic"], {})}
    import jax
    s.peaks = {**s.peaks,
               jax.devices()[0].device_kind: s.peaks["TPU v5 lite"]}
    return s


def run(workload: str, seed: int = 3, seconds: float = 0.2,
        control: bool = False, cell_spec: harness.Spec = None,
        trace: int = 0, keep_trace: str = None) -> dict:
    """One tiny run; its result line as a dict."""
    import jax
    import run as bench

    s = cell_spec or spec(workload)
    args = argparse.Namespace(seed=seed, seconds=seconds, trace=trace,
                              control=control, keep_trace=keep_trace)
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = bench.measure(s, args, jax, jax.devices()[:1],
                           len(jax.devices()))
    assert rc == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])
