"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (benchmarks/common.row) and
always finishes by writing ``BENCH_sort.json`` — a machine-readable record
of the core sort's perf (n, p, plan, wall seconds, analytic b_eff) so the
trajectory is tracked across PRs.

  bench_latency     Fig. 3/5 + Table II   sort latency vs baselines
  bench_memory      Fig. 6/8              footprint vs n / batch count
  bench_batches     Fig. 7                latency vs serial batch count
  bench_throughput  Fig. 9                unit throughput
  bench_bandwidth   Fig. 10               b_eff = T_actual / B_DRAM
  bench_sortplan    (beyond paper)        SortPlan digit-width sweep
  bench_query       (beyond paper)        query operators vs XLA oracle
  bench_stream      (beyond paper)        out-of-core external sort
  bench_moe_dispatch  (beyond paper)      dispatch vs argsort
  roofline          assignment §Roofline  from dry-run artifacts

``python benchmarks/run.py sort_json`` writes only the JSON record.
"""

import datetime
import functools
import json
import subprocess
import sys

# The points every PR's BENCH_sort.json records: (n, p, max_bins_log2,
# engine, smoke_guard).  max_bins_log2/engine None = the entry point's
# default plan.  The
# per-engine points pin their plan exactly — they are the engine
# trajectory across PRs, and the ``smoke_guard`` ones double as the CI
# relative-regression baselines (bench_sortplan smoke re-times them and
# fails on >2x).  The n=2**17 trio records the wide-pass acceptance
# story: w=8/16 scatter vs the old w=4 one-hot default.
SORT_JSON_POINTS = (
    (1 << 12, 16, None, None, False),
    (1 << 15, 32, None, None, False),
    (1 << 15, 32, 4, "onehot", True),
    (1 << 15, 32, 8, "scatter", True),
    (1 << 17, 32, 4, "onehot", False),
    (1 << 17, 32, 8, "scatter", False),
    (1 << 17, 32, 16, "scatter", False),
)

# Record schema history (the cross-PR reader keys on this):
#   1 — {points: [{n, p, plan, ...}]}
#   2 — + provenance {git_sha, git_dirty, date, jax} and query operator
#       points
#   3 — points carry max_bins_log2/engine/smoke_guard (per-engine
#       trajectory + CI guard baselines); default points record the
#       resolved engine hints
#   4 — query points carry the measured oracle-gap ratio + fused-chain
#       dispatch counts; the order_by point is a smoke_guard baseline for
#       the bench_query smoke's >2x relative ratio gate
#   5 — points carry MEASURED per-pass traffic (one traced eager executor
#       run per point: bytes + wall per pass, measured_b_eff beside
#       analytic_b_eff) and the record embeds the obs metrics snapshot
SORT_JSON_SCHEMA = 5


def _provenance() -> dict:
    """Who produced this record: git sha + ISO date + jax version, so the
    cross-PR perf trajectory is attributable to a commit and toolchain."""
    import jax

    try:
        sha = subprocess.run(
            ["git", "rev-parse", "HEAD"], capture_output=True, text=True,
            timeout=10).stdout.strip() or "unknown"
        dirty = bool(subprocess.run(
            ["git", "status", "--porcelain"], capture_output=True,
            text=True, timeout=10).stdout.strip())
    except (OSError, subprocess.SubprocessError):
        sha, dirty = "unknown", False
    return {
        "git_sha": sha,
        "git_dirty": dirty,  # True: numbers came from uncommitted code
        "date": datetime.datetime.now(datetime.timezone.utc).isoformat(
            timespec="seconds"),
        "jax": jax.__version__,
    }


def guard_overwrite(path: str, allow_dirty: bool = False) -> None:
    """Refuse to overwrite a *committed* ``BENCH_*.json`` from a dirty
    tree: a perf record whose provenance says ``git_dirty: true`` is
    unattributable — the numbers came from code no commit contains — and
    it silently poisons every relative regression gate keyed on it.
    ``allow_dirty`` (the ``--allow-dirty`` CLI flag) is the explicit
    local-iteration escape; untracked target paths are always fine."""
    if allow_dirty or not _provenance()["git_dirty"]:
        return
    try:
        tracked = subprocess.run(
            ["git", "ls-files", "--error-unmatch", path],
            capture_output=True, timeout=10).returncode == 0
    except (OSError, subprocess.SubprocessError):
        tracked = False
    if tracked:
        raise SystemExit(
            f"refusing to overwrite committed {path} from a dirty tree: "
            "the record would carry git_dirty provenance and corrupt the "
            "cross-PR baselines — commit first, or pass --allow-dirty "
            "for local iteration")


def allow_dirty_flag(argv) -> bool:
    """Shared ``--allow-dirty`` CLI parse for every bench writer."""
    return "--allow-dirty" in argv


def trace_flag(argv):
    """Shared ``--trace PATH`` CLI parse: pops the flag + its value from
    ``argv`` in place and returns the Perfetto export path (or None)."""
    if "--trace" not in argv:
        return None
    i = argv.index("--trace")
    if i + 1 >= len(argv):
        raise SystemExit("--trace needs an output path (e.g. "
                         "--trace trace.json)")
    path = argv[i + 1]
    del argv[i:i + 2]
    return path


def measured_sort_point(keys, plan, stats) -> dict:
    """Measured per-pass traffic for one sort point: a single traced
    *eager* executor run (the jitted entry point would hide pass
    boundaries), per-pass bytes + wall off the ``executor.pass`` spans,
    and measured b_eff beside the analytic number via
    ``obs.bandwidth_report``."""
    import jax

    from repro import obs
    from repro.core.executor import JnpBackend, PlanExecutor

    ex = PlanExecutor(JnpBackend())
    with obs.suspended():  # warm the eager op caches outside the trace
        jax.block_until_ready(ex.run(keys, plan))
    with obs.tracing() as session:
        ex.run(keys, plan)
    tr = session.trace
    report = obs.bandwidth_report(tr, analytic=stats)
    passes = [{
        "kind": span["attrs"].get("kind"),
        "bits": span["attrs"].get("bits"),
        "bytes": tr.span_bytes(span),
        "wall_s": span["t1"] - span["t0"],
    } for span in tr.find("executor.pass")]
    return {
        "measured_b_eff": report.get("measured_b_eff"),
        "measured_bytes_per_s": report.get("measured_bytes_per_s"),
        "passes": passes,
    }


def emit_sort_json(path: str = "BENCH_sort.json",
                   allow_dirty: bool = False,
                   trace_out: str = None) -> dict:
    """Time :func:`fractal_sort` at the standard points (plus the query
    operators) and write the machine-readable perf record (wall time +
    the analytic traffic model behind the paper's b_eff figure)."""
    import numpy as np

    from benchmarks.bench_bandwidth import b_eff
    from benchmarks.bench_query import query_points
    from benchmarks.common import rand_keys, time_fn
    from repro.core import fractal_sort, fractal_sort_stats, make_sort_plan

    from repro import obs

    guard_overwrite(path, allow_dirty)
    rng = np.random.default_rng(0)
    results = []
    # one outer session spanning every point: tracing() nests, so the
    # per-point measured runs land in this window too and the export is
    # the whole benchmark's span stream
    outer = obs.tracing() if trace_out else None
    outer_session = outer.__enter__() if outer is not None else None
    for n, p, w, engine, guard in SORT_JSON_POINTS:
        keys = rand_keys(rng, n, p)
        if w is None:
            plan = make_sort_plan(n, p)  # the entry points' default plan
        else:
            plan = make_sort_plan(n, p, max_bins_log2=w, engine=engine)
        with obs.suspended():  # time the sort, never the tracer
            wall_s = time_fn(functools.partial(fractal_sort, p=p,
                                               plan=plan), keys)
        st = fractal_sort_stats(n, p, plan=plan)
        measured = measured_sort_point(keys, plan, st)
        engines = sorted({dp.engine or "auto" for dp in plan.passes})
        results.append({
            "n": n,
            "p": p,
            "plan": plan.describe(),
            "passes": st.passes,
            "max_bins_log2": w,
            "engine": engine or "+".join(engines),
            "smoke_guard": guard,
            "wall_s": wall_s,
            "keys_per_s": n / wall_s,
            "analytic_bytes_per_key": st.bytes_per_key,
            "analytic_b_eff": b_eff(st),
            "measured_b_eff": measured["measured_b_eff"],
            "measured_bytes_per_s": measured["measured_bytes_per_s"],
            "measured_passes": measured["passes"],
        })
    if outer is not None:
        outer.__exit__(None, None, None)
        outer_session.trace.export(trace_out)
        print(f"wrote {trace_out} ({len(outer_session.trace)} spans)")
    record = {
        "schema": SORT_JSON_SCHEMA,
        "provenance": _provenance(),
        "points": results,
        "query": query_points(),
        "metrics": obs.metrics.snapshot(),
    }
    with open(path, "w") as f:
        json.dump(record, f, indent=2)
        f.write("\n")
    print(f"wrote {path} (sha={record['provenance']['git_sha'][:9]}): "
          + "; ".join(
              f"n={r['n']} p={r['p']} {r['wall_s'] * 1e3:.1f}ms "
              f"b_eff={r['analytic_b_eff']:.3f}" for r in results)
          + " | query: " + "; ".join(
              f"{q['op']} {q['wall_s'] * 1e3:.1f}ms"
              for q in record["query"]))
    return record


def main() -> None:
    from benchmarks import (bench_batches, bench_bandwidth, bench_latency,
                            bench_memory, bench_moe_dispatch, bench_query,
                            bench_sortplan, bench_stream, bench_throughput,
                            roofline)

    allow_dirty = allow_dirty_flag(sys.argv)
    argv = [a for a in sys.argv[1:] if a != "--allow-dirty"]
    trace_out = trace_flag(argv)
    only = argv[0] if argv else None
    if only == "sort_json":
        emit_sort_json(allow_dirty=allow_dirty, trace_out=trace_out)
        return
    mods = {
        "latency": bench_latency, "memory": bench_memory,
        "batches": bench_batches, "throughput": bench_throughput,
        "bandwidth": bench_bandwidth, "sortplan": bench_sortplan,
        "query": bench_query, "stream": bench_stream,
        "moe_dispatch": bench_moe_dispatch,
        "roofline": roofline,
    }
    print("name,us_per_call,derived")
    for name, mod in mods.items():
        if only and only != name:
            continue
        mod.run()
    emit_sort_json(allow_dirty=allow_dirty, trace_out=trace_out)


if __name__ == '__main__':
    main()
