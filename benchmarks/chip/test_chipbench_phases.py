"""The program's spans in a profiler trace (``phases.py``): on made-up
intervals, on a trace recorded on one TPU v5e (one tiny-size
``tpch_sf10.q1`` call with the program's spans on), on the sort trace
``test_chipbench_trace.py`` reads, and on a traced tiny run."""

import json

import jax
import pytest

import tiny  # noqa: F401  (puts the benchmark and the program on the path)
import harness
import phases
import tracefile
from repro import obs
from repro.obs import metrics

Q1_TRACE = harness.HERE / "testdata" / "tpch_sf10.q1.small.xplane.pb"
SORT_TRACE = harness.HERE / "testdata" / "paper_p16.small.xplane.pb"
PHASES = {"query.fetch", "query.gather", "query.reduce"}


@pytest.mark.parametrize("name,kept", [
    ("query.group_by", True), ("query.fetch", True),
    ("stream.partition_sort", True), ("bench.call 0", False),
    ("bench.fetch", False), ("bench.window", False),
    ("PjitFunction(fractal_sort)", False), ("$api.py:3097 block", False),
    ("np.asarray(jax.Array)", False), ("copy.15", False),
    ("while.1", False), ("tpu::System::Execute", False)])
def test_program_span_names(name, kept):
    assert bool(phases.PROGRAM_SPAN.match(name)) is kept


def _made_up():
    """Two calls of a made-up operator on one device, in ns; the check's
    fetch cuts 90-95."""
    return phases.Phases(
        tracefile.Reduced(
            window_ns=(0, 100), cuts_ns=[(90, 95)],
            annotations=[(harness.WINDOW, 0, 100),
                         (f"{harness.CALL} 0", 0, 40),
                         (f"{harness.CALL} 1", 50, 100)],
            devices=[tracefile.Device(
                ops=[("%a", 0, 5), ("%b", 12, 15), ("%c", 55, 65),
                     ("%d", 85, 92)], modules=[])]),
        spans=[("query.group_by", 2, 40), ("query.fetch", 10, 20),
               ("query.gather", 20, 30), ("query.group_by", 50, 98),
               ("query.gather", 60, 80)])


def test_idle_gaps_cut_at_program_spans():
    p = _made_up()
    r = p.reduced
    assert r.busy_s == pytest.approx(23e-9)
    assert r.window_s == pytest.approx(95e-9)
    by_label = p.idle_by_label()
    assert by_label == pytest.approx({
        "query.group_by": 28e-9, "query.fetch": 7e-9,
        "query.gather": 25e-9, "between calls": 10e-9,
        f"{harness.CALL} 1": 2e-9})
    assert sum(by_label.values()) == pytest.approx(r.window_s - r.busy_s)
    # eleven pieces: the ten longest, all but a 2 ns one
    gaps = p.longest_idle()
    assert gaps[0] == ["query.gather", pytest.approx(15e-9)]
    assert len(gaps) == 10
    assert sum(g for _, g in gaps) == pytest.approx(70e-9)
    own = p.self_seconds()
    assert {n: c for n, (c, _) in own.items()} == {
        "query.group_by": 2, "query.fetch": 1, "query.gather": 2}
    assert {n: s for n, (_, s) in own.items()} == pytest.approx({
        "query.group_by": 46e-9, "query.fetch": 10e-9,
        "query.gather": 30e-9})


def test_operator_idle_per_call():
    p = _made_up()
    inside = sum(s for label, s in p.idle_by_label().items()
                 if label.startswith("query."))
    assert p.calls() == 2
    assert p.idle_within("query.group_by") == pytest.approx(inside)
    assert p.idle_within("query.group_by") == pytest.approx(60e-9)
    assert p.report()["query.group_by.idle_ms_per_call"] == pytest.approx(
        1e3 * 60e-9 / 2)
    assert p.idle_within("query.nothing") is None
    assert p.report("query.nothing")["query.nothing.idle_ms_per_call"] \
        is None
    no_device = phases.Phases(
        tracefile.Reduced((0, 100), [], p.reduced.annotations, []), p.spans)
    assert no_device.idle_within("query.group_by") is None


def test_sort_trace_reduces_as_at_the_parent():
    """The recorded sort trace holds no program span, and reduces to the
    numbers it gave before program spans reached the profiler."""
    p = phases.read(str(SORT_TRACE))
    r = p.reduced
    assert p.spans == []
    assert r.busy_s == pytest.approx(0.08396597700000001, abs=1e-9)
    assert r.window_s == pytest.approx(0.09563489700000001, abs=1e-9)
    assert r.module_seconds(["jit_fractal_sort"]) == pytest.approx(
        0.08396853400000001, abs=1e-9)
    ops = r.breakdown()["device_ops"]
    assert [n for n, _ in ops] == [
        "jit_fractal_sort:%reduce-window.32",
        "jit_fractal_sort:%reduce-window.30",
        "jit_fractal_sort:%reduce-window.34",
        "jit_fractal_sort:%reduce-window.28",
        "jit_fractal_sort:%fusion.150", "jit_fractal_sort:%fusion.143",
        "jit_fractal_sort:%fusion.136", "jit_fractal_sort:%fusion.129",
        "jit_fractal_sort:%fusion.134", "jit_fractal_sort:%fusion.141"]
    assert [s for _, s in ops] == pytest.approx([
        0.011239864, 0.011239862000000002, 0.01123986, 0.01043702,
        0.004683675, 0.0046836740000000005, 0.004683672000000001,
        0.0045880750000000005, 0.00379006, 0.0037900560000000004],
        abs=1e-9)
    labels = ["bench.call 6", "bench.call 0", "bench.call 1", "bench.call 2",
              "bench.call 3", "bench.call 4", "bench.call 5",
              "between calls"]
    assert [label for label, _ in r.breakdown()["idle_gaps"][:8]] == labels
    assert [label for label, _ in p.longest_idle()[:8]] == labels
    assert sum(p.idle_by_label().values()) == pytest.approx(
        r.window_s - r.busy_s, abs=1e-12)
    assert p.report()["query.group_by.idle_ms_per_call"] is None


def test_recorded_query_trace_labels_idle_by_phase():
    """The program's spans share the device ops' clock, and the idle
    time splits by ``query.*`` phase without losing a nanosecond."""
    p = phases.read(str(Q1_TRACE))
    r = p.reduced
    lo, hi = r.window_ns
    names = {n for n, a, b in p.spans if b > lo and a < hi}
    assert {"query.group_by", "query.probe", "query.chain"} | PHASES <= names
    assert "executor.pass" not in names
    # every run of the chain program starts inside a group_by span
    ops = [(a, b) for n, a, b in p.spans if n == "query.group_by"]
    chains = [(a, b) for d in r.devices for n, a, b in d.modules
              if tracefile._program(n) == "jit_chain" and lo <= a <= hi]
    assert chains and all(any(oa <= a <= ob for oa, ob in ops)
                          for a, _ in chains)
    by_label = p.idle_by_label()
    assert sum(by_label.values()) == pytest.approx(r.window_s - r.busy_s,
                                                   abs=1e-9)
    inside = sum(s for label, s in by_label.items()
                 if label.startswith("query."))
    assert inside == pytest.approx(p.idle_within("query.group_by"),
                                   rel=1e-9)
    assert sum(label.startswith("query.")
               for label, _ in p.longest_idle()) >= 8


def test_cli_reports_the_recorded_trace(capsys):
    assert phases.main([str(Q1_TRACE), "--top", "3"]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["calls"] == 1 and len(out["longest_idle"]) == 3
    assert out["query.group_by.idle_ms_per_call"] == pytest.approx(
        1e3 * sum(s for label, s in out["idle_s_by_label"].items()
                  if label.startswith("query.")), rel=1e-9)
    assert out["span_count_and_self_s"]["query.gather"][0] == 5


def test_traced_query_run_holds_program_spans(tmp_path):
    with obs.tracing():
        r = tiny.run("tpch_sf10.q1", trace=1, keep_trace=str(tmp_path))
    assert r["correct"] is True
    p = phases.read(str(next(tmp_path.glob("*.xplane.pb"))))
    lo, hi = p.reduced.window_ns
    spans = [(n, a, b) for n, a, b in p.spans if b > lo and a < hi]
    assert "executor.pass" not in {n for n, _, _ in spans}

    def within(name, outer):
        inner = [(a, b) for n, a, b in spans if n == name]
        return inner and all(any(oa <= a and b <= ob for oa, ob in outer)
                             for a, b in inner)

    calls = [(a, b) for n, a, b in p.reduced.annotations
             if n.startswith(harness.CALL)]
    assert within("query.group_by", calls)
    ops = [(a, b) for n, a, b in spans if n == "query.group_by"]
    for phase in PHASES:
        assert within(phase, ops)


def test_query_call_moves_eight_bytes_a_row():
    """A Q1 call brings a 4 B sorted word and a 4 B row id a row to the
    host, and a few bytes of probe mask a call."""
    s = tiny.spec("tpch_sf10.q1")
    cell = s.entry.setup(s.config, s.config_module, s.traffic, 5)
    jax.block_until_ready(cell.call(0))
    before = metrics.snapshot()
    jax.block_until_ready(cell.call(1))
    moved = metrics.snapshot_delta(before)["query.d2h_bytes"]
    cell.release()
    assert 8 <= moved / cell.rows_per_call < 8.1
