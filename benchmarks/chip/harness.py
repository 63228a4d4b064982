"""What every cell shares: finding a cell's files by name, the measured
window, and the statistics taken over it.

A cell is an entry of ``workloads`` in the checkout's BENCHMARK.json.
Its files are found by name, so that a new cell, configuration, traffic
mix, entry or per-layer metric is a new file and a new entry:

- ``configs/<config>.json``: the configuration, whose ``module`` names
  ``configs/<module>.py``, the seeded data generator and its reference;
- ``traffic/<traffic>.json``: the traffic mix, whose ``entry`` names
  ``entries/<entry>.py``, the code that drives the program;
- ``metrics/<metric>.py``: a per-layer metric's reader of the reduced
  trace;
- ``peaks.json``: the chips' peaks by ``device_kind``.

An entry's ``setup(cfg, module, traffic, seed)`` returns a cell object
with ``rows_per_call``, ``min_bytes_per_row``, ``sample``, ``call(i)``,
``control(i)``, ``fetch(out)``, ``release()`` and ``check(samples)``.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import random
import sys
import time
from pathlib import Path
from typing import Callable, Dict, List, Optional, Tuple

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]

CALL = "bench.call"
FETCH = "bench.fetch"
WINDOW = "bench.window"


def load_module(path: Path):
    """Import the file at ``path`` under a name of its own."""
    name = "chipbench_" + "_".join(path.relative_to(HERE).with_suffix(
        "").parts).replace(".", "_")
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[name] = module
    spec.loader.exec_module(module)
    return module


@dataclasses.dataclass
class Spec:
    """Everything a run of one cell reads from files."""

    workload: dict
    config: dict
    config_module: object
    traffic: dict
    entry: object
    end_to_end: List[dict]
    per_layer: List[dict]
    peaks: Dict[str, dict]


def _load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_spec(workload: str) -> Spec:
    """The spec of ``workload``, an entry of the checkout's
    BENCHMARK.json."""
    bench = _load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r}; have {sorted(cells)}")
    return cell_spec(cells[workload], bench)


def cell_spec(wl: dict, bench: dict) -> Spec:
    """The spec of the cell ``wl`` under BENCHMARK.json's metrics."""
    config = _load_json(HERE / "configs" / f"{wl['config']}.json")
    traffic = _load_json(HERE / "traffic" / f"{wl['traffic']}.json")

    def applies(metric):
        return "workloads" not in metric or wl["name"] in metric["workloads"]

    return Spec(
        workload=wl,
        config=config,
        config_module=load_module(HERE / "configs" / f"{config['module']}.py"),
        traffic=traffic,
        entry=load_module(HERE / "entries" / f"{traffic['entry']}.py"),
        end_to_end=[m for m in bench["end_to_end"] if applies(m)],
        per_layer=[m for m in bench["per_layer"] if applies(m)],
        peaks=_load_json(HERE / "peaks.json")["devices"],
    )


def peaks_for(peaks: Dict[str, dict], device_kind: str) -> dict:
    """The peaks of ``device_kind``; a kind not in the table is an
    error, never a default."""
    if device_kind not in peaks:
        raise KeyError(f"device_kind {device_kind!r} is not in peaks.json "
                       f"(have {sorted(peaks)}); add its published peaks")
    return peaks[device_kind]


def reader(metric: str) -> Callable:
    """The ``read(ctx)`` of ``metrics/<metric>.py``."""
    return load_module(HERE / "metrics" / f"{metric}.py").read


def nearest_rank(values, q: float) -> float:
    """The ``q`` quantile (0 < q <= 1) by nearest rank: the smallest
    value with at least a share ``q`` of the values at or below it."""
    ordered = sorted(values)
    return ordered[max(0, math.ceil(q * len(ordered)) - 1)]


@dataclasses.dataclass
class Window:
    """One closed-loop window: calls back to back from one client."""

    latencies: List[float]
    rows: int
    elapsed: float        # seconds, the check's fetches left out
    fetch_s: float        # seconds spent fetching outputs for the check
    samples: List[Tuple[int, object]]

    @property
    def attempted(self) -> int:
        return len(self.latencies)


def run_window(cell, seconds: float, seed: int,
               call: Optional[Callable] = None) -> Window:
    """Call ``call`` (the cell's ``call`` by default) back to back until
    ``seconds`` have passed; the call in flight completes.  A reservoir
    drawn from ``seed`` keeps ``cell.sample`` outputs, brought to the
    host outside the timed calls, for the check after the window."""
    import jax
    from jax.profiler import TraceAnnotation

    call = call or cell.call
    rng = random.Random(seed)
    keep = max(1, int(cell.sample))
    samples: List[Tuple[int, object]] = []
    latencies: List[float] = []
    fetch_s = 0.0
    with TraceAnnotation(WINDOW):
        begin = time.perf_counter()
        deadline = begin + seconds
        i = 0
        while time.perf_counter() < deadline:
            with TraceAnnotation(f"{CALL} {i}"):
                t0 = time.perf_counter()
                out = jax.block_until_ready(call(i))
                latencies.append(time.perf_counter() - t0)
            slot = len(samples) if len(samples) < keep else rng.randrange(i + 1)
            if slot < keep:
                with TraceAnnotation(FETCH):
                    t0 = time.perf_counter()
                    host = cell.fetch(out)
                    fetch_s += time.perf_counter() - t0
                if slot == len(samples):
                    samples.append((i, host))
                else:
                    samples[slot] = (i, host)
            del out
            i += 1
        end = time.perf_counter()
    return Window(latencies=latencies, rows=i * cell.rows_per_call,
                  elapsed=end - begin - fetch_s, fetch_s=fetch_s,
                  samples=samples)
