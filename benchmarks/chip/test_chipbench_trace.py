"""The reduction from a profiler trace to the per-layer metrics, on a
trace recorded on one TPU v5e by ``run.py --trace 1 --keep-trace`` (the
``paper_p16`` sort at 2^16 keys a call, seven calls), and on made-up
intervals."""

import types

import pytest

import tiny  # noqa: F401
import harness
import tracefile

TRACE = harness.HERE / "testdata" / "paper_p16.small.xplane.pb"
TRACE_ROWS = 65536  # keys a call in the recorded trace


def test_union_clip_and_self_times():
    assert tracefile.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3),
                                                                (5, 8)]
    assert tracefile.clip([(0, 10)], 2, 9, [(3, 4), (6, 7)]) == [
        (2, 3), (4, 6), (7, 9)]
    ops = [("%while.1 = loop", 0, 10), ("%a = f", 1, 4), ("%b = g", 5, 6),
           ("%c = h", 12, 20)]
    self_ns = tracefile._self_times(ops, 0, 15)
    assert self_ns == {"%while.1": 6, "%a": 3, "%b": 1, "%c": 3}
    assert tracefile._program("jit_chain(123)") == "jit_chain"


def test_ops_named_by_their_program():
    modules = [("jit_b(7)", 20, 30), ("jit_a.1", 0, 10)]
    ops = [("%x = f", 1, 4), ("%y = g", 21, 29), ("%z = h", 12, 14)]
    named = [n for n, _, _ in tracefile._in_programs(ops, modules)]
    assert named == ["jit_a:%x = f", "jit_b:%y = g", "?:%z = h"]


@pytest.fixture(scope="module")
def reduced():
    return tracefile.reduce(str(TRACE))


def test_window_busy_and_programs(reduced):
    calls = [n for n, _, _ in reduced.annotations
             if n.startswith(harness.CALL)]
    assert len(reduced.devices) == 1 and calls
    assert 0 < reduced.busy_s <= reduced.window_s
    lo, hi = reduced.window_ns
    assert reduced.window_s <= (hi - lo) * 1e-9
    assert reduced.cuts_ns, "the check's fetches are cut out"
    programs = reduced.module_names()
    assert programs.get("jit_fractal_sort") == len(calls)
    device_s = reduced.module_seconds(["jit_fractal_sort"])
    assert reduced.busy_s * 0.9 < device_s <= reduced.window_s
    assert reduced.module_seconds(["no_such_program"]) == 0


def test_metrics_from_trace(reduced):
    spec = harness.load_spec("paper_p16.bulk")
    calls = sum(n.startswith(harness.CALL) for n, _, _ in reduced.annotations)
    cell = types.SimpleNamespace(rows_per_call=TRACE_ROWS,
                                 min_bytes_per_row=8)
    ctx = types.SimpleNamespace(
        trace=reduced, cell=cell, traffic=spec.traffic,
        peaks=spec.peaks["TPU v5 lite"],
        window=types.SimpleNamespace(attempted=calls))
    idle = harness.reader("device.idle_share")(ctx)
    roof = harness.reader("chain_roofline")(ctx)
    assert 0 <= idle < 100
    assert 0 < roof < 100
    ctx.traffic = {**spec.traffic, "chain_programs": ["jit_other"]}
    assert harness.reader("chain_roofline")(ctx) is None
    ctx.trace = None
    assert harness.reader("device.idle_share")(ctx) is None


def test_breakdown(reduced):
    b = reduced.breakdown()
    assert 1 <= len(b["device_ops"]) <= 10
    assert 1 <= len(b["idle_gaps"]) <= 10
    assert all(name.startswith("jit_fractal_sort:%") and " " not in name
               and s > 0 for name, s in b["device_ops"])
    assert sum(s for _, s in b["device_ops"]) <= reduced.busy_s * 1.0001
    labels = {label for label, _ in b["idle_gaps"]}
    assert labels <= {"between calls", harness.FETCH} | {
        n for n, _, _ in reduced.annotations}
    gaps = [s for _, s in b["idle_gaps"]]
    assert gaps == sorted(gaps, reverse=True)
