"""The on-chip benchmark's one command.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

runs one cell of BENCHMARK.json on the chips of this machine: set-up
(data made from the seed, the cell's shapes warmed by one call), then
a closed loop of calls for ``--seconds`` seconds, then the check of the
outputs against the plain reference.  With ``--trace 0`` it reports the
cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics,
read from a profiler trace of the window.  The last line of standard
output is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics``, ``device``, with ``--trace 1`` ``breakdown``, and last
``checks``, each number compared beside its limit.  The checks are also
the last lines of standard error.

It exits non-zero with no result line when JAX finds no TPU or fewer
chips than the cell asks for.  JAX's compile cache is
``$JAX_COMPILATION_CACHE_DIR`` when set, else ``benchmarks/chip/.jax_cache``.

``--control`` puts the configuration's control (the reference with one
guarantee broken) in the program's place; its runs must read
``correct: false``.  ``--keep-trace DIR`` keeps the traced run's
``.xplane.pb`` in DIR.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]

import harness  # noqa: E402


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--keep-trace", default=None)
    args = ap.parse_args(argv)
    if args.seed < 0 or args.seconds <= 0:
        ap.error("--seed must be >= 0 and --seconds > 0")
    return args


def use_compile_cache(jax) -> str:
    """Every program, however small, goes to a cache at a fixed path."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        HERE / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


class CompileCounter:
    """Programs JAX traces or compiles (or loads from the cache) while
    counting; the window should see none."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/core/compile/jaxpr_trace_duration")

    def __init__(self, jax):
        self.counts = dict.fromkeys(self.EVENTS, 0)
        self.on = False
        jax.monitoring.register_event_duration_secs_listener(self._seen)

    def _seen(self, event, duration, **_):
        if self.on and event in self.counts:
            self.counts[event] += 1


def memory_peak(devices) -> int:
    """Peak bytes in use on the fullest device, 0 where not reported."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def info(*parts) -> None:
    print(*parts, flush=True)


def main(argv=None) -> int:
    args = parse_args(argv)
    spec = harness.load_spec(args.workload)
    os.environ["REPRO_AUTOTUNE_CACHE"] = os.devnull
    import jax

    cache = use_compile_cache(jax)
    devices = jax.devices()
    dev = devices[0]
    chips = int(spec.workload["chips"])
    info(f"jax {jax.__version__} platform={dev.platform} "
         f"device_kind={dev.device_kind} device_count={len(devices)} "
         f"compile_cache={cache}")
    if dev.platform != "tpu":
        print(f"FAIL: no TPU (jax platform {dev.platform!r})",
              file=sys.stderr)
        return 2
    if len(devices) < chips:
        print(f"FAIL: {chips} chips asked, {len(devices)} present",
              file=sys.stderr)
        return 2
    return measure(spec, args, jax, devices[:chips], len(devices))


def measure(spec, args, jax, devices, device_count: int) -> int:
    """Set-up, window, check and report of one run."""
    from repro.core import dispatch

    dev = devices[0]
    peaks = harness.peaks_for(spec.peaks, dev.device_kind)
    compiles = CompileCounter(jax)
    cell = spec.entry.setup(spec.config, spec.config_module, spec.traffic,
                            args.seed)
    call = cell.control if args.control else cell.call
    info(f"memory after the data is made: {dev.memory_stats()}")
    jax.block_until_ready(call(0))
    setup_s = time.perf_counter() - T_START
    info(f"setup_s={setup_s} rows_per_call={cell.rows_per_call}")

    before = dispatch.counts()
    compiles.on = True
    trace_dir = tempfile.mkdtemp(prefix="chipbench-") if args.trace else None
    if args.trace:
        jax.profiler.start_trace(trace_dir)
    try:
        window = harness.run_window(cell, args.seconds, args.seed, call)
    finally:
        if args.trace:
            jax.profiler.stop_trace()
    compiles.on = False
    info(f"window: calls={window.attempted} elapsed_s={window.elapsed} "
         f"check_fetch_s={window.fetch_s} "
         f"latency_p50_ms={1e3 * harness.nearest_rank(window.latencies, .5)} "
         f"latency_max_ms={1e3 * max(window.latencies)} "
         f"samples_for_p95={window.attempted}")
    info(f"window: compile events {compiles.counts} "
         f"(should be 0); program dispatches "
         f"{dispatch.snapshot_delta(before)}")
    peak = memory_peak(devices)
    info(f"memory_peak_bytes={peak}; after the window: {dev.memory_stats()}")

    ctx = types.SimpleNamespace(cell=cell, window=window, setup_s=setup_s,
                                memory_peak=peak, peaks=peaks,
                                traffic=spec.traffic, config=spec.config,
                                trace=None)
    result_device = {"platform": dev.platform, "kind": dev.device_kind,
                     "count": device_count, "memory_peak_bytes": peak}
    breakdown = None
    if args.trace:
        import tracefile

        path = tracefile.find_xplane(trace_dir)
        if args.keep_trace:
            os.makedirs(args.keep_trace, exist_ok=True)
            shutil.copy(path, args.keep_trace)
        ctx.trace = tracefile.reduce(path)
        shutil.rmtree(trace_dir, ignore_errors=True)
        result_device["busy_s"] = ctx.trace.busy_s
        result_device["window_s"] = ctx.trace.window_s
        breakdown = ctx.trace.breakdown()
        names = ctx.trace.module_names()
        info(f"trace: device programs in the window {names}")
        metrics_wanted = spec.per_layer
    else:
        metrics_wanted = spec.end_to_end
    metrics = {}
    for m in metrics_wanted:
        value = harness.reader(m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    oracle = getattr(cell, "oracle_ms", None)
    if oracle is not None and not args.control:
        ref = oracle()
        p50 = 1e3 * harness.nearest_rank(window.latencies, 0.5)
        info(f"oracle on the same input: {ref}; call p50 over it: "
             f"{ {k: p50 / v for k, v in ref.items()} }")
    cell.release()
    del ctx
    gc.collect()

    per_sample = cell.check(window.samples)
    limits = {k: float(v) for k, v in spec.traffic["limits"].items()}
    checks = {}
    failed = 0
    for numbers in per_sample:
        if any(numbers[k] > limits[k] for k in numbers):
            failed += 1
        for k, v in numbers.items():
            checks[k] = max(checks.get(k, 0.0), v)
    correct = (window.attempted > 0 and bool(per_sample) and failed == 0
               and set(checks) == set(limits))
    result = {"correct": correct, "attempted": window.attempted,
              "failed": failed, "metrics": metrics, "device": result_device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {k: {"value": checks.get(k), "limit": limits[k]}
                        for k in limits}
    for k in limits:
        print(f"check {k}: {checks.get(k)} (limit {limits[k]}) over "
              f"{len(per_sample)} sampled calls", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
