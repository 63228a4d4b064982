"""Each cell end to end at a tiny size on the CPU: set-up, window,
check and report, the control that must fail, and the faults of the
timed path that must each make ``correct`` false."""

import jax.numpy as jnp
import numpy as np
import pytest

import tiny  # noqa: F401  (puts the benchmark and the program on the path)
import exprs
import harness
from repro.query import Table

CELLS = ["paper_p16.bulk", "tpch_sf10.q18", "tpch_sf10.q1"]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_correct(cell):
    r = tiny.run(cell)
    assert r["correct"] is True
    assert r["attempted"] >= 1 and r["failed"] == 0
    assert {"rows_per_s", "latency_p95_ms", "setup_s"} <= set(r["metrics"])
    assert all(m["value"] > 0 for m in r["metrics"].values())
    assert list(r)[-1] == "checks"
    assert all(c["value"] <= c["limit"] for c in r["checks"].values())


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(cell):
    r = tiny.run(cell, control=True)
    assert r["correct"] is False
    assert any(c["value"] > c["limit"] for c in r["checks"].values())


def test_traced_run_keeps_its_trace(tmp_path):
    r = tiny.run("paper_p16.bulk", trace=1, keep_trace=str(tmp_path))
    assert r["correct"] is True
    assert {"busy_s", "window_s"} <= set(r["device"])
    assert set(r["breakdown"]) == {"device_ops", "idle_gaps"}
    assert list(tmp_path.glob("*.xplane.pb"))


def _sort_faults(orig):
    def half(keys, p):
        n = keys.shape[0] // 2
        return jnp.concatenate([orig(keys[:n], p), keys[n:]])

    return {
        "state_unchanged": lambda keys, p: keys,
        "half_left_out": half,
        "answer_altered": lambda keys, p: orig(keys, p).at[7].add(1),
    }


def _query_faults(orig):
    def half(table, by, aggs, codecs=None):
        n = table.num_rows // 2
        return orig(Table({c: table.column(c)[:n]
                           for c in table.column_names}), by, aggs,
                    codecs=codecs)

    def altered(column):
        def fault(table, by, aggs, codecs=None):
            g = orig(table, by, aggs, codecs=codecs)
            name = column(g, by, aggs)
            cols = {c: g.column(c) for c in g.column_names}
            v = np.array(cols[name])
            v[0] = v[0] + 1
            cols[name] = v
            return Table(cols)
        return fault

    return {
        "half_left_out": half,
        "answer_altered": altered(lambda g, by, aggs: next(iter(aggs))),
        "key_altered": altered(lambda g, by, aggs: by[-1]),
    }


FAULTS = ([(c, f) for c in CELLS[:1]
           for f in ("state_unchanged", "half_left_out", "answer_altered")]
          + [(c, f) for c in CELLS[1:]
             for f in ("half_left_out", "answer_altered", "key_altered")])


@pytest.mark.parametrize("cell,fault", FAULTS)
def test_fault_is_not_correct(cell, fault, monkeypatch):
    spec = tiny.spec(cell)
    entry = spec.entry
    name = "fractal_sort" if hasattr(entry, "fractal_sort") else "group_by"
    orig = getattr(entry, name)
    faults = (_sort_faults if name == "fractal_sort" else _query_faults)(orig)
    monkeypatch.setattr(entry, name, faults[fault])
    r = tiny.run(cell, cell_spec=spec)
    assert r["correct"] is False
    assert r["failed"] >= 1


def test_unknown_device_kind_raises():
    peaks = harness.load_spec("paper_p16.bulk").peaks
    assert harness.peaks_for(peaks, "TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="not in peaks.json"):
        harness.peaks_for(peaks, "TPU v99")


def test_nearest_rank():
    assert harness.nearest_rank([3.0], 0.95) == 3.0
    assert harness.nearest_rank([1.0, 2.0], 0.95) == 2.0
    assert harness.nearest_rank(list(range(1, 101)), 0.95) == 95


def test_keys_follow_the_seed():
    spec = tiny.spec("paper_p16.bulk")
    make = spec.config_module.generate
    a = [np.asarray(k) for k in make(spec.config, 2**33 + 1, 4096, 2)]
    b = [np.asarray(k) for k in make(spec.config, 2**33 + 1, 4096, 2)]
    c = [np.asarray(k) for k in make(spec.config, 2**33 + 2, 4096, 2)]
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not np.array_equal(a[0], c[0]) and not np.array_equal(a[0], a[1])
    assert all(k.dtype == np.int32 and 0 <= k.min() and k.max() < 2**16
               for k in a)


def test_query_table_is_filtered_in_setup():
    spec = tiny.spec("tpch_sf10.q1")
    cell = spec.entry.setup(spec.config, spec.config_module, spec.traffic, 5)
    cols = {n: c.values for n, c in cell.cols.items()}
    keep = cols["l_shipdate"] <= exprs.evaluate("date('1998-09-02')", {})
    assert cell.rows_per_call == int(keep.sum()) < len(keep)
    t = cell.table
    assert set(t.column_names) == {"l_returnflag", "l_linestatus",
                                   "l_quantity", "l_extendedprice",
                                   "l_discount", "disc_price", "charge"}
    price, disc = cols["l_extendedprice"][keep], cols["l_discount"][keep]
    np.testing.assert_array_equal(t.column("disc_price"), price * (1 - disc))
    assert isinstance(t.column("charge"), np.ndarray)
    assert not isinstance(t.column("l_returnflag"), np.ndarray)
