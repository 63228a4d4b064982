"""Observability layer: the span tracer, the metrics registry, and the
measured-vs-analytic bandwidth accounting.

The load-bearing contracts:

* tracing OFF is the default and near-free — ``trace.span`` returns the
  shared null object and the instrumented sort pays no measurable cost;
* the span tree is well-formed (no orphans, no unclosed spans) even when
  spans open on ``REPRO_STREAM_WORKERS`` pool threads and across the
  external sort's skew recursion;
* every byte accounting agrees: ``store.put``/``store.get`` span bytes
  == the store's put/get ledgers == the registry counters, and the
  executor's per-pass span bytes == the analytic model's
  :func:`fractal_sort_stats` prediction for the same plan (the paper's
  b_eff figure, measured);
* ``dispatch.wrap`` counts compiles exactly once under concurrent
  callers (the compile-detection race this PR fixes);
* ``with_retries`` emits a structured retry event chaos tests can
  assert on.
"""

from __future__ import annotations

import json
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import dispatch, faults
from repro.core.executor import JnpBackend, PlanExecutor
from repro.core.faults import FaultPlan
from repro.core.fractal_sort import fractal_sort, fractal_sort_stats
from repro.core.sort_plan import make_sort_plan
from repro.obs import metrics, trace
from repro.stream import ArraySource, MemoryBudget, external_sort
from repro.stream.chunks import RunStore
from repro.stream.external import row_cost_bytes


def _keys(n, p, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(0, 1 << p, n, dtype=np.uint64).astype(
        np.uint32).astype(np.int32 if p < 32 else np.uint32)


# --- metrics registry --------------------------------------------------------


def test_registry_counter_gauge_histogram():
    reg = metrics.Registry()
    reg.counter("c").inc()
    reg.counter("c").inc(41)
    assert reg.counter("c").value == 42
    reg.gauge("g").set(7)
    reg.gauge("g").set_max(3)      # lower: no effect
    assert reg.gauge("g").value == 7
    reg.gauge("g").set_max(11)
    assert reg.gauge("g").value == 11
    assert reg.gauge("g").max == 11
    reg.gauge("g").set(2)          # last-write-wins; max is sticky
    assert reg.gauge("g").value == 2
    assert reg.gauge("g").max == 11
    h = reg.histogram("h")
    for v in range(1, 101):
        h.observe(float(v))
    assert h.quantile(0.5) == pytest.approx(50, abs=1)
    assert h.quantile(0.99) == pytest.approx(99, abs=1)
    s = h.summary()
    assert s["count"] == 100 and s["min"] == 1.0 and s["max"] == 100.0
    assert s["p50"] <= s["p90"] <= s["p99"]


def test_registry_kind_mismatch_raises():
    reg = metrics.Registry()
    reg.counter("x")
    with pytest.raises(TypeError):
        reg.gauge("x")


def test_registry_snapshot_delta_and_events():
    reg = metrics.Registry()
    reg.counter("a").inc(5)
    before = reg.snapshot()
    reg.counter("a").inc(3)
    reg.event("thing", site="s", attempt=1)
    delta = reg.snapshot_delta(before)
    assert delta["a"] == 3
    assert delta["thing.count"] == 1
    evs = reg.events("thing")
    assert evs and evs[-1]["site"] == "s" and evs[-1]["attempt"] == 1


def test_metrics_track_serving_primitive():
    reg = metrics.Registry()
    with reg.track("req") as delta:
        reg.counter("work").inc(9)
    assert delta["work"] == 9
    assert delta["wall_s"] >= 0
    assert reg.counter("req.requests").value == 1
    assert reg.histogram("req.latency_s").summary()["count"] == 1


# --- dispatch.wrap compile-detection race ------------------------------------


def test_wrap_counts_concurrent_same_shape_compile_once():
    """N threads racing the same first call must record exactly ONE
    compile — the old read-cache-size-outside-a-lock pattern double (or
    zero) counted under this exact race."""
    tag = "test.obs.race"
    fn = jax.jit(lambda x: x + 1)
    wrapped = dispatch.wrap(tag, fn)
    x = jnp.arange(128)
    n_threads = 8
    barrier = threading.Barrier(n_threads)
    errs = []

    def call():
        try:
            barrier.wait()
            wrapped(x)
        except Exception as e:   # pragma: no cover - diagnostic
            errs.append(e)

    before = dispatch.counts().get(f"{tag}:compiles", 0)
    ts = [threading.Thread(target=call) for _ in range(n_threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert not errs
    seen = dispatch.counts()
    assert seen[tag] >= n_threads
    assert seen[f"{tag}:compiles"] - before == 1
    # a genuinely new shape is one more compile, counted once
    wrapped(jnp.arange(64))
    assert dispatch.counts()[f"{tag}:compiles"] - before == 2
    # warm shapes stay free
    wrapped(x)
    wrapped(jnp.arange(64))
    assert dispatch.counts()[f"{tag}:compiles"] - before == 2


def test_wrap_concurrent_distinct_shapes_total_is_exact():
    tag = "test.obs.race2"
    wrapped = dispatch.wrap(tag, jax.jit(lambda x: x * 2))
    shapes = [16, 32, 48, 64]
    barrier = threading.Barrier(len(shapes))

    def call(n):
        barrier.wait()
        wrapped(jnp.arange(n))

    ts = [threading.Thread(target=call, args=(n,)) for n in shapes]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert dispatch.counts()[f"{tag}:compiles"] == len(shapes)


# --- with_retries structured events ------------------------------------------


def test_retry_emits_structured_event():
    store = RunStore()
    before = len(metrics.events("store.retry"))
    with faults.inject(FaultPlan.single("run_store.put", "transient",
                                        seed=0)) as inj:
        for _ in range(8):
            store.put(np.arange(64, dtype=np.int32))
    assert inj.fired
    evs = metrics.events("store.retry")[before:]
    assert evs, "transient absorbed but no store.retry event emitted"
    ev = evs[0]
    assert ev["site"] == "run_store.put"
    assert ev["attempt"] == 0
    assert ev["error"] == "TransientStoreError"
    assert "backoff_s" in ev
    assert metrics.counter("store.retry.count").value >= len(evs)


# --- tracer ------------------------------------------------------------------


def test_span_off_is_null_and_cheap():
    with trace.suspended():
        assert trace.span("x", bytes=1) is trace.NULL
        t0 = time.perf_counter()
        for _ in range(100_000):
            with trace.span("hot", a=1):
                pass
        per_call = (time.perf_counter() - t0) / 100_000
    # the off path is a dict-free constant return; 5 µs/call is ~50x
    # headroom over measured, while still catching an accidental
    # always-allocate regression
    assert per_call < 5e-6, f"off-path span cost {per_call * 1e6:.2f} µs"


def test_tracing_off_sort_smoke_overhead():
    """The instrumented sort with tracing OFF stays within a few % of
    itself — i.e. the guards never allocate spans.  Asserted
    structurally (zero spans recorded, null spans returned) plus a
    generous wall sanity bound; a strict A/B wall diff would flake on
    shared CI runners."""
    keys = jnp.asarray(_keys(1 << 14, 32))
    plan = make_sort_plan(1 << 14, 32)
    with trace.suspended():
        jax.block_until_ready(fractal_sort(keys, p=32, plan=plan))
        t0 = time.perf_counter()
        out = fractal_sort(keys, p=32, plan=plan)
        jax.block_until_ready(out)
        wall_off = time.perf_counter() - t0
        assert trace.current() is None
    assert wall_off < 2.0  # warm n=2^14 runs in ms; this is pure sanity


def test_span_tree_well_formed_nested_and_threaded(monkeypatch):
    monkeypatch.setenv("REPRO_STREAM_WORKERS", "3")
    keys = _keys(1 << 14, 32)
    budget = MemoryBudget((1 << 14) * 4 // 8)
    src = ArraySource(keys, budget.rows(row_cost_bytes(1)))
    with obs.tracing() as session:
        with trace.span("outer", tag=1):
            with trace.span("inner", tag=2):
                out = np.concatenate(list(external_sort(src, 32, budget)))
    assert np.array_equal(out, np.sort(keys))
    tr = session.trace
    tr.assert_well_formed()
    names = {s["name"] for s in tr.spans}
    assert {"outer", "inner", "store.put", "store.get",
            "stream.histogram", "stream.partition_sort"} <= names
    # pool-thread spans must still parent into the submitting context
    by_sid = {s["sid"]: s for s in tr.spans}
    for s in tr.find("stream.partition_sort"):
        assert s["parent"] in by_sid


def test_trace_summary_and_perfetto_export(tmp_path):
    with obs.tracing() as session:
        with trace.span("a", bytes=10):
            with trace.span("b", bytes=5):
                pass
            with trace.span("b", bytes=7):
                pass
    tr = session.trace
    assert len(tr) == 3
    summary = tr.summary()
    assert summary["a"]["count"] == 1
    assert summary["a"]["children"]["b"]["count"] == 2
    assert summary["a"]["children"]["b"]["attrs"]["bytes"] == 12
    path = tmp_path / "trace.json"
    tr.export(str(path))
    doc = json.loads(path.read_text())
    evs = doc["traceEvents"]
    assert len(evs) == 3
    for ev in evs:
        assert ev["ph"] == "X" and ev["dur"] >= 0 and "ts" in ev
    assert {e["name"] for e in evs} == {"a", "b"}


def test_suspended_inside_session_records_nothing():
    with obs.tracing() as session:
        with trace.span("kept"):
            pass
        with trace.suspended():
            with trace.span("dropped"):
                pass
    names = [s["name"] for s in session.trace.spans]
    assert names == ["kept"]


# --- byte accounting: spans == ledgers == registry == analytic model ---------


def test_external_sort_bytes_spans_match_store_ledgers():
    store = RunStore()
    keys = _keys(1 << 14, 32)
    budget = MemoryBudget((1 << 14) * 4 // 8)
    src = ArraySource(keys, budget.rows(row_cost_bytes(1)))
    reg_before = metrics.snapshot()
    with obs.tracing() as session:
        out = np.concatenate(list(external_sort(src, 32, budget,
                                                store=store)))
    assert np.array_equal(out, np.sort(keys))
    tr = session.trace
    tr.assert_well_formed()
    span_put = tr.total("store.put", "bytes")
    span_get = tr.total("store.get", "bytes")
    reg_after = metrics.snapshot()

    def reg_delta(name):
        return reg_after.get(name, 0) - reg_before.get(name, 0)

    assert span_put == sum(store.put_log_bytes) > 0
    assert span_get == sum(store.get_log_bytes) > 0
    assert span_put == reg_delta("store.run_store.put.bytes")
    assert span_get == reg_delta("store.run_store.get.bytes")
    assert len(store.put_log_bytes) == len(store.put_log)
    assert len(store.get_log_bytes) == len(store.get_log)


@pytest.mark.parametrize("n,p,w,engine", [
    (1 << 12, 16, None, None),
    (1 << 13, 32, 4, "onehot"),
    (1 << 13, 32, 8, "scatter"),
])
def test_measured_pass_bytes_equal_analytic_model(n, p, w, engine):
    """ACCEPTANCE: the executor's per-pass spans carry exactly the byte
    traffic :func:`fractal_sort_stats` predicts for the same plan — the
    measured and analytic b_eff share one accounting."""
    kwargs = {} if w is None else {"max_bins_log2": w, "engine": engine}
    plan = make_sort_plan(n, p, **kwargs)
    st = fractal_sort_stats(n, p, plan=plan)
    keys = jnp.asarray(_keys(n, p))
    ex = PlanExecutor(JnpBackend())
    with obs.tracing() as session:
        out = ex.run(keys, plan)
    assert np.array_equal(np.asarray(out), np.sort(np.asarray(keys)))
    spans = session.trace.find("executor.pass")
    assert len(spans) == len(plan.passes) == len(st.pass_stats)
    for span, ps in zip(spans, st.pass_stats):
        assert span["attrs"]["bytes_read"] == ps.bytes_read
        assert span["attrs"]["bytes_written"] == ps.bytes_written
        assert span["attrs"]["kind"] == ps.kind
    measured_total = sum(session.trace.span_bytes(s) for s in spans)
    assert measured_total == st.bytes_total


def test_measured_pass_bytes_argsort_with_index():
    n, p = 1 << 13, 32
    plan = make_sort_plan(n, p)
    st = fractal_sort_stats(n, p, with_index=True, plan=plan)
    keys = jnp.asarray(_keys(n, p))
    ex = PlanExecutor(JnpBackend())
    with obs.tracing() as session:
        order = ex.run_argsort(keys, plan)
    assert np.array_equal(np.asarray(keys)[np.asarray(order)],
                          np.sort(np.asarray(keys)))
    spans = session.trace.find("executor.pass")
    assert sum(session.trace.span_bytes(s) for s in spans) == st.bytes_total


def test_jitted_entry_points_never_trace():
    """Inside a jit trace the executor must NOT open pass spans (byte
    totals would be recorded per-compile, not per-run)."""
    keys = jnp.asarray(_keys(1 << 12, 32))
    with obs.tracing() as session:
        jax.block_until_ready(fractal_sort(keys, p=32))
    assert not session.trace.find("executor.pass")


def test_bandwidth_report_measured_vs_analytic():
    n, p = 1 << 12, 24
    plan = make_sort_plan(n, p)
    st = fractal_sort_stats(n, p, plan=plan)
    keys = jnp.asarray(_keys(n, p))
    with obs.tracing() as session:
        PlanExecutor(JnpBackend()).run(keys, plan)
    report = obs.bandwidth_report(session.trace, analytic=st)
    assert report["measured_bytes_total"] == st.bytes_total
    assert report["analytic_b_eff"] == pytest.approx(
        report["measured_b_eff"])
    phase = report["phases"]["executor.pass"]
    assert phase["count"] == len(plan.passes)
    assert phase["bytes"] == st.bytes_total
    assert report["measured_bytes_per_s"] is None or \
        report["measured_bytes_per_s"] > 0


# --- layer counters ----------------------------------------------------------


def test_memory_budget_peak_gauge():
    budget = MemoryBudget(1 << 20)
    with budget.hold(np.zeros(1 << 14, dtype=np.int32)):
        pass
    assert metrics.gauge("budget.peak_bytes").max >= 1 << 16


def test_dispatch_record_feeds_registry():
    before = metrics.snapshot()
    dispatch.record("test.obs.tag", compiles=2)
    dispatch.record("test.obs.tag")
    after = metrics.snapshot()
    assert after.get("dispatch.test.obs.tag", 0) - \
        before.get("dispatch.test.obs.tag", 0) == 2
    assert after.get("dispatch.test.obs.tag.compiles", 0) - \
        before.get("dispatch.test.obs.tag.compiles", 0) == 2


def test_span_holds_profiler_annotation_of_its_name(monkeypatch):
    """On, every span holds a ``jax.profiler.TraceAnnotation`` of its
    name (the attributes stay in the record); off, no annotation."""
    seen = []

    class Annotation:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            seen.append(("enter", self.name))
            return self

        def __exit__(self, *exc):
            seen.append(("exit", self.name))
            return False

    with obs.tracing() as session:
        assert trace._annotation is jax.profiler.TraceAnnotation
        monkeypatch.setattr(trace, "_annotation", Annotation)
        with trace.span("outer", bytes=3):
            with trace.span("inner"):
                pass
        with trace.suspended():
            assert trace.span("off", bytes=1) is trace.NULL
            with trace.span("off"):
                pass
    assert seen == [("enter", "outer"), ("enter", "inner"),
                    ("exit", "inner"), ("exit", "outer")]
    assert session.trace.find("outer")[0]["attrs"] == {"bytes": 3}


@pytest.mark.parametrize("op", ["sort_merge_join", "top_k"])
def test_join_and_top_k_phases_nest_under_their_operator(op):
    """``sort_merge_join``'s host phases (``query.merge``,
    ``query.expand``, ``query.take``) and ``top_k``'s (``query.prune``,
    ``query.take``) are spans under the operator's own scope, beside the
    sort's ``query.probe`` and ``query.chain``."""
    from repro.query import Table, sort_merge_join, top_k

    rng = np.random.default_rng(11)
    n = 600
    t = Table({"k": jnp.asarray(rng.integers(0, 50, n).astype(np.int32)),
               "v": rng.standard_normal(n)})
    run = {"sort_merge_join": lambda: sort_merge_join(t, t.head(90), "k"),
           "top_k": lambda: top_k(t, [("v", "desc")], 7)}[op]
    phases = {"sort_merge_join": {"query.merge", "query.expand",
                                  "query.take"},
              "top_k": {"query.prune", "query.take"}}[op]
    with obs.tracing() as session:
        out = run()
    tr = session.trace
    tr.assert_well_formed()
    (scope,) = tr.find(f"query.{op}")
    children = tr.summary()[f"query.{op}"]["children"]
    assert phases | {"query.probe", "query.chain"} <= set(children)
    for name in phases:
        (s,) = tr.find(name)
        assert s["parent"] == scope["sid"]
    if op == "sort_merge_join":
        assert tr.total("query.take", "rows") == len(out) > 0
