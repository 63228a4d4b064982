"""Runs of the benchmark's command in sequence, one process at a time,
and the spread of each metric over them.

    python3 benchmarks/chip/sets.py --workload <cell> --seconds <s> \
        --seeds 11,12,13 [--sets 2] [--trace 0|1] [--control] \
        [--out .bench_runs]

Each set runs every seed once, in order; the sets use the same seeds.
Each run's result line and the end of its standard error go to
``<out>/<cell>.jsonl``.  For each set and metric it prints the median
and the spread, the distance between the first and third quartiles
(``statistics.quantiles(n=4)``) as a share of the median.  This process
never imports JAX, so each run has the chip to itself.
"""

from __future__ import annotations

import argparse
import json
import statistics
import subprocess
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def spread(values):
    if len(values) < 2:
        return 0.0
    q1, q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / q2 if q2 else float("inf")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=int, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--sets", type=int, default=1)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--out", default=".bench_runs")
    args = ap.parse_args(argv)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    seeds = [int(s) for s in args.seeds.split(",")]
    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    log = out / f"{args.workload}.jsonl"
    rc_all = 0
    for k in range(args.sets):
        per_metric = {}
        for seed in seeds:
            cmd = [sys.executable] + bench["command"][1:] + [
                "--workload", args.workload, "--seed", str(seed),
                "--seconds", str(args.seconds), "--trace", str(args.trace)]
            if args.control:
                cmd.append("--control")
            t0 = time.perf_counter()
            r = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True)
            wall = time.perf_counter() - t0
            lines = r.stdout.strip().splitlines()
            result = None
            if r.returncode == 0 and lines:
                result = json.loads(lines[-1])
            rc_all = rc_all or r.returncode
            rec = {"set": k, "seed": seed, "rc": r.returncode, "wall_s": wall,
                   "trace": args.trace, "control": args.control,
                   "result": result, "stdout": lines[:-1][-12:],
                   "stderr_tail": r.stderr[-3000:]}
            with open(log, "a") as f:
                f.write(json.dumps(rec) + "\n")
            summary = {m: v["value"] for m, v in
                       (result or {}).get("metrics", {}).items()}
            print(f"set {k} seed {seed} rc={r.returncode} wall_s={wall:.1f} "
                  f"correct={(result or {}).get('correct')} "
                  f"attempted={(result or {}).get('attempted')} {summary} "
                  f"checks={(result or {}).get('checks')}", flush=True)
            if result is None:
                print(r.stderr[-2000:], flush=True)
            for m, v in summary.items():
                per_metric.setdefault(m, []).append(v)
        for m, vs in per_metric.items():
            print(f"set {k} {m}: median={statistics.median(vs)!r} "
                  f"spread={spread(vs)!r} n={len(vs)} values={vs}",
                  flush=True)
    return rc_all


if __name__ == "__main__":
    sys.exit(main())
