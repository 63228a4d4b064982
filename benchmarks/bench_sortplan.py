"""SortPlan digit-width x rank-engine sweep.

For each digit width w the plan runs ceil(p / w)-ish passes of 2**w bins.
Under the *one-hot* engine rank work is O(n * 2**w * passes); under the
*scatter* engine it is O(n log tile * passes) — width-independent — while
key traffic is O(n * passes) for both, so wide passes stop being
compute-bound and the §III.G bandwidth trade actually bites.  This sweep
times :func:`fractal_sort` across ``max_bins_log2`` x engine and prints
the analytic per-plan traffic next to the measured wall-clock.

Modes (``python -m benchmarks.bench_sortplan <mode>``):

* (default) — the engine x width sweep table.
* ``rank`` — rank-engine comparison on identical digit streams: the
  chunk-parallel one-hot :func:`fractal_rank` vs the sorted-tile
  :func:`fractal_rank_scatter` vs the serial-scan
  :func:`fractal_rank_serial` oracle, plus end-to-end plan executions.
* ``smoke`` — the CI guard: absolute-budget points for *both* engines at
  n=2**14, then a relative check of the committed ``BENCH_sort.json``
  per-engine points (>2x regression fails).
"""

from __future__ import annotations

import functools
import json
import sys

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import rand_keys, row, time_fn
from repro.core import (
    DEFAULT_MAX_BINS_LOG2,
    JnpBackend,
    PlanExecutor,
    fractal_rank,
    fractal_rank_scatter,
    fractal_rank_serial,
    fractal_sort,
    fractal_sort_stats,
    make_sort_plan,
)


_keys = rand_keys


def run(sizes=(1 << 12, 1 << 15), p: int = 32,
        widths=(4, 5, 6, 8, 11, 16), engines=("onehot", "scatter")):
    rng = np.random.default_rng(0)
    best = {}
    for n in sizes:
        keys = _keys(rng, n, p)
        for w in widths:
            for engine in engines:
                if engine == "onehot" and w > 11:
                    continue  # O(n * 2**16) tile: the PR-1 pathology
                plan = make_sort_plan(n, p, max_bins_log2=w, engine=engine)
                st = fractal_sort_stats(n, p, plan=plan)
                t = time_fn(functools.partial(fractal_sort, p=p, plan=plan),
                            keys)
                row(f"sortplan/n{n}/p{p}/w{w}/{engine}", t,
                    f"plan={plan.describe()} passes={st.passes} "
                    f"bytes_per_key={st.bytes_per_key:.1f} "
                    f"keys_per_s={n / t:.3g}")
                if t < best.get(n, (np.inf, None, None))[0]:
                    best[n] = (t, w, engine)
    for n, (t, w, engine) in best.items():
        marker = "=static-default" if (
            w == DEFAULT_MAX_BINS_LOG2 and engine == "onehot") else \
            f"(static default w={DEFAULT_MAX_BINS_LOG2}/onehot)"
        row(f"sortplan/best/n{n}", t, f"w={w}/{engine} {marker}")
    return best


def run_rank_compare(sizes=(1 << 12, 1 << 15), p: int = 32,
                     bins_log2=(4, 8, 11)):
    """One-hot vs scatter vs serial rank engines, same digit streams and
    plans.  Reports the isolated rank stage and full plan executions.
    Returns {n: scatter_vs_onehot_sort_speedup} at w=8."""
    rng = np.random.default_rng(0)
    speedups = {}
    engines = (("onehot", fractal_rank), ("scatter", fractal_rank_scatter),
               ("serial", fractal_rank_serial))
    for n in sizes:
        for w in bins_log2:
            d = jnp.asarray(rng.integers(0, 1 << w, n).astype(np.int32))
            ts = {}
            for name, fn in engines:
                ts[name] = time_fn(jax.jit(functools.partial(
                    fn, n_bins=1 << w)), d)
                row(f"rankmode/{name}/n{n}/bins{1 << w}", ts[name],
                    f"keys_per_s={n / ts[name]:.3g}")
            row(f"rankmode/scatter_speedup/n{n}/bins{1 << w}",
                ts["scatter"], f"vs_onehot={ts['onehot'] / ts['scatter']:.2f}x"
                f" vs_serial={ts['serial'] / ts['scatter']:.2f}x")
        keys = _keys(rng, n, p)
        plan_oh = make_sort_plan(n, p, max_bins_log2=8, engine="onehot")
        plan_sc = make_sort_plan(n, p, max_bins_log2=8, engine="scatter")
        t_oh = time_fn(jax.jit(
            lambda k: PlanExecutor(JnpBackend()).run(k, plan_oh)), keys)
        t_sc = time_fn(jax.jit(
            lambda k: PlanExecutor(JnpBackend()).run(k, plan_sc)), keys)
        row(f"rankmode/sort_onehot_w8/n{n}/p{p}", t_oh,
            f"plan={plan_oh.describe()}")
        row(f"rankmode/sort_scatter_w8/n{n}/p{p}", t_sc,
            f"scatter_speedup={t_oh / t_sc:.2f}x")
        speedups[n] = t_oh / t_sc
    return speedups


# Hard wall for the CI smoke points (n=2**14, p=32, one per engine).  The
# healthy times on a 2-core runner are ~10 ms (w=4 one-hot) and ~15 ms
# (w=8 scatter); the PR-1 regression this guards against was 15.5 s —
# orders of magnitude of headroom without flaking on slow shared runners.
SMOKE_BUDGET_S = 2.0

# Relative guard: a committed per-engine BENCH_sort.json point re-timed
# slower than max(2x its committed wall, the floor) fails CI.  The floor
# absorbs host-speed skew between the recording machine and CI runners —
# the guard exists to catch engine-path regressions (the O(n * 2**w)
# variety), which blow past 2x by construction, not 1.3x noise.
SMOKE_REGRESSION_FACTOR = 2.0
SMOKE_REGRESSION_FLOOR_S = 0.5


def _baseline_points(path: str):
    """Committed per-engine guard points: (n, p, plan, engine, wall_s)."""
    try:
        with open(path) as f:
            rec = json.load(f)
    except (OSError, ValueError):
        return []
    return [pt for pt in rec.get("points", [])
            if pt.get("smoke_guard") and pt.get("engine")]


def smoke(n: int = 1 << 14, p: int = 32,
          baseline_path: str = "BENCH_sort.json",
          trace_out: str = None) -> float:
    """Both engines under a hard budget + the committed-baseline relative
    guard (CI pass-loop / engine-path regression gate).  With
    ``trace_out``, each engine also does one traced *eager* executor run
    and the per-pass span stream (bytes + walls, measured_b_eff next to
    the analytic figure) is exported as Perfetto JSON."""
    from repro import obs

    rng = np.random.default_rng(0)
    keys = _keys(rng, n, p)
    worst = 0.0
    outer = obs.tracing() if trace_out else None
    outer_session = outer.__enter__() if outer is not None else None
    for engine, w in (("onehot", 4), ("scatter", 8)):
        plan = make_sort_plan(n, p, max_bins_log2=w, engine=engine)
        with obs.suspended():  # the timed wall never includes the tracer
            t = time_fn(functools.partial(fractal_sort, p=p, plan=plan),
                        keys)
        derived = f"budget_s={SMOKE_BUDGET_S}"
        if outer is not None:
            from benchmarks.run import measured_sort_point

            st = fractal_sort_stats(n, p, plan=plan)
            m = measured_sort_point(keys, plan, st)
            derived += f" measured_b_eff={m['measured_b_eff']:.3f}"
        row(f"sortplan/smoke/n{n}/p{p}/{engine}", t, derived)
        worst = max(worst, t)
        if t > SMOKE_BUDGET_S:
            raise SystemExit(
                f"sortplan smoke point ({engine}) took {t:.2f}s > "
                f"{SMOKE_BUDGET_S}s budget: a pass-loop/rank regression "
                "landed")
    for pt in _baseline_points(baseline_path):
        bn, bp, w = pt["n"], pt["p"], pt["max_bins_log2"]
        plan = make_sort_plan(bn, bp, max_bins_log2=w, engine=pt["engine"])
        with obs.suspended():
            t = time_fn(functools.partial(fractal_sort, p=bp, plan=plan),
                        _keys(np.random.default_rng(0), bn, bp))
        limit = max(SMOKE_REGRESSION_FACTOR * pt["wall_s"],
                    SMOKE_REGRESSION_FLOOR_S)
        row(f"sortplan/guard/n{bn}/p{bp}/{pt['engine']}", t,
            f"baseline_s={pt['wall_s']:.4f} limit_s={limit:.4f}")
        if t > limit:
            raise SystemExit(
                f"committed baseline point n={bn} p={bp} "
                f"engine={pt['engine']} regressed: {t:.3f}s vs "
                f"{pt['wall_s']:.3f}s committed (limit {limit:.3f}s)")
    if outer is not None:
        outer.__exit__(None, None, None)
        outer_session.trace.export(trace_out)
        row("sortplan/smoke/trace", float(len(outer_session.trace)),
            f"perfetto={trace_out}")
    return worst


if __name__ == "__main__":
    from benchmarks.run import trace_flag

    _argv = sys.argv[1:]
    _trace_out = trace_flag(_argv)
    mode = _argv[0] if _argv else None
    if mode == "rank":
        run_rank_compare()
    elif mode == "smoke":
        smoke(trace_out=_trace_out)
    else:
        run()
