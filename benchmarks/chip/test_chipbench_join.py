"""The TPC-H Q3 cell (``tpch_sf10_join.q3``) end to end at a tiny size
on the CPU: set-up, window, check and report; the control and the faults
of the timed join path that must each make ``correct`` false; the
CUSTOMER and ORDERS rules of clause 4.2.3 beside ``tpch.py``'s LINEITEM;
and the reference against a row-by-row oracle."""

import copy
import json

import numpy as np
import pytest

import tiny
import harness
import joinref
from repro.query import Table

CELL = "tpch_sf10_join.q3"
SIZES = {"customer_rows": 1500, "orders_rows": 3000, "lineitem_rows": 11997,
         "part_rows": 2000}
TPCH = harness.load_module(harness.HERE / "configs" / "tpch.py")
JOIN = harness.load_module(harness.HERE / "configs" / "tpch_join.py")
CFG = {**json.loads((harness.HERE / "configs" / "tpch_sf10_join.json")
                    .read_text()), **SIZES}


def _spec() -> harness.Spec:
    """The cell's spec from BENCHMARK.json, its sizes cut."""
    import jax

    bench = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    (wl,) = [w for w in bench["workloads"] if w["name"] == CELL]
    s = harness.cell_spec(wl, bench)
    s.config = {**copy.deepcopy(s.config), **SIZES}
    s.peaks = {**s.peaks,
               jax.devices()[0].device_kind: s.peaks["TPU v5 lite"]}
    return s


def test_cell_runs_correct():
    r = tiny.run(CELL, cell_spec=_spec(), trace=1)
    assert r["correct"] is True
    assert r["attempted"] >= 1 and r["failed"] == 0
    assert list(r)[-1] == "checks"
    assert all(c["value"] <= c["limit"] for c in r["checks"].values())
    # the host merge brings sorted words and row ids back: ~8 B a row
    assert 4 < r["metrics"]["join.d2h_bytes_per_row"]["value"] < 16
    r = tiny.run(CELL, cell_spec=_spec())
    assert {"rows_per_s", "latency_p95_ms", "setup_s"} <= set(r["metrics"])


def test_control_is_not_correct():
    r = tiny.run(CELL, cell_spec=_spec(), control=True)
    assert r["correct"] is False
    assert r["checks"]["agg_rel_err"]["value"] > 1e-3


def _join_faults(orig):
    def half(left, right, on, codecs=None):
        return orig(left, right.head(right.num_rows // 2), on,
                    codecs=codecs)

    def altered(column):
        def fault(left, right, on, codecs=None):
            out = orig(left, right, on, codecs=codecs)
            cols = {c: out.column(c) for c in out.column_names}
            name = column(out, on)
            v = np.array(cols[name])
            v[0] = v[0] + 1
            cols[name] = v
            return Table(cols)
        return fault

    return {
        "half_left_out": half,
        "answer_altered": altered(lambda out, on: out.column_names[-1]),
        "key_altered": altered(lambda out, on: on),
    }


@pytest.mark.parametrize("fault", ["half_left_out", "answer_altered",
                                   "key_altered"])
def test_fault_is_not_correct(fault, monkeypatch):
    spec = _spec()
    entry = spec.entry
    monkeypatch.setattr(entry, "sort_merge_join",
                        _join_faults(entry.sort_merge_join)[fault])
    r = tiny.run(CELL, cell_spec=spec)
    assert r["correct"] is False
    assert r["failed"] >= 1


@pytest.fixture(scope="module")
def cols():
    return JOIN.generate(CFG, 2**31 + 17, [*CFG["columns"]])


def test_customer_rules(cols):
    key = cols["c_custkey"].values
    n = CFG["customer_rows"]
    np.testing.assert_array_equal(key, np.arange(1, n + 1))
    seg = cols["c_mktsegment"].values
    kinds = len(CFG["columns"]["c_mktsegment"]["values"])
    assert np.array_equal(np.bincount(seg, minlength=kinds),
                          np.full(kinds, n // kinds))
    assert key.max() < 2 ** CFG["columns"]["c_custkey"]["bits"]


def test_orders_rules_and_lines_agree(cols):
    okey, odate = cols["o_orderkey"].values, cols["o_orderdate"].values
    cust = cols["o_custkey"].values
    assert okey.size == CFG["orders_rows"]
    assert np.all(cust % 3 != 0)
    assert cust.min() >= 1 and cust.max() <= CFG["customer_rows"]
    # uniform over the 1,000 custkeys that have orders: most are used
    assert np.unique(cust).size > 900
    assert not np.any(cols["o_shippriority"].values)
    first = TPCH.days(CFG["start_date"])
    assert odate.min() >= first
    assert odate.max() <= TPCH.days(CFG["end_date"]) - 151
    assert odate.max() < 2 ** CFG["columns"]["o_orderdate"]["bits"]
    # the keys and dates tpch.py's lines inherit, order for order
    lines = TPCH._Lineitem(CFG, 2**31 + 17)
    lkey, ldate = lines.orderkey(), lines.orderdate()
    np.testing.assert_array_equal(cols["l_orderkey"].values, lkey)
    np.testing.assert_array_equal(okey, np.unique(lkey))
    at = np.searchsorted(okey, lkey)
    np.testing.assert_array_equal(odate[at], ldate)


def test_filters_keep_the_same_rows_on_every_seed():
    day = TPCH.days("1995-03-15")
    kept = set()
    for seed in (3, 2**33 + 5):
        c = JOIN.generate(CFG, seed, ["l_shipdate", "c_mktsegment"])
        kept.add((int(np.sum(c["l_shipdate"].values > day)),
                  int(np.sum(c["c_mktsegment"].values == 1))))
    first = TPCH.days(CFG["start_date"])
    last = TPCH.days(CFG["end_date"]) - 151
    assert kept == {(TPCH.expected_after(CFG["lineitem_rows"], first, last,
                                         day), CFG["customer_rows"] // 5)}


def _oracle(cols, segment, day):
    """Q3 by dictionaries, row by row."""
    v = {n: c.values for n, c in cols.items()}
    building = {int(k) for k, s in zip(v["c_custkey"], v["c_mktsegment"])
                if s == segment}
    orders = {int(k): (int(d), int(p)) for k, c, d, p in zip(
        v["o_orderkey"], v["o_custkey"], v["o_orderdate"],
        v["o_shippriority"]) if d < day and int(c) in building}
    price, disc = cols["l_extendedprice"].exact, cols["l_discount"].exact
    revenue = {}
    for i, (k, ship) in enumerate(zip(v["l_orderkey"], v["l_shipdate"])):
        if ship > day and int(k) in orders:
            revenue[int(k)] = revenue.get(int(k), 0) + int(
                price[i] * (100 - disc[i]))
    return {k: (orders[k], r) for k, r in revenue.items()}


def test_reference_matches_a_row_by_row_oracle(cols):
    query = {"segment": "BUILDING", "date": "1995-03-15", "limit": 10}
    want = _oracle(cols, 1, TPCH.days("1995-03-15"))
    got = joinref.run(cols, CFG, query)
    g = got["groups"]
    assert list(g["orderkey"]) == sorted(want)
    for i, k in enumerate(g["orderkey"]):
        (date, prio), rev = want[int(k)]
        assert (g["o_orderdate"][i], g["o_shippriority"][i]) == (date, prio)
        assert got["exact"].value[i] == rev and got["exact"].scale == 4
    ranked = sorted(want, key=lambda k: (-want[k][1], want[k][0][0], k))
    assert list(got["top"]["orderkey"]) == ranked[:10]
    semi = joinref.run(cols, CFG, query, control="semi_join")
    assert np.array_equal(semi["groups"]["orderkey"], g["orderkey"])
    assert np.all(semi["exact"].value <= got["exact"].value)
    assert np.any(semi["exact"].value < got["exact"].value)


def test_top_rows_trade_places_only_within_the_bound(cols):
    query = {"segment": "BUILDING", "date": "1995-03-15", "limit": 10}
    want = joinref.run(cols, CFG, query)
    exact = want["exact"]
    ok = joinref.compare(want, want, 1e-12)
    assert ok["keys_wrong"] == ok["result_rows_wrong"] == 0
    assert ok["agg_rel_err"] < 1e-15  # revenue / 10^4 in float64

    def swapped(i, j):
        top = {k: v.copy() for k, v in want["top"].items()}
        for v in top.values():
            v[[i, j]] = v[[j, i]]
        return {"groups": want["groups"], "top": top}

    # rows 0 and 1 differ in exact revenue: trading places is wrong
    first, second = exact.value[np.searchsorted(
        want["groups"]["orderkey"], want["top"]["orderkey"][:2])]
    assert first > second
    assert joinref.compare(swapped(0, 1), want, 1e-12)[
        "result_rows_wrong"] == 2
    # within a bound wider than their difference they may
    assert joinref.compare(swapped(0, 1), want, 1.0)[
        "result_rows_wrong"] == 0
    # a row twice is wrong whatever the bound
    twice = {k: v.copy() for k, v in want["top"].items()}
    for v in twice.values():
        v[1] = v[0]
    assert joinref.compare({"groups": want["groups"], "top": twice}, want,
                           1.0)["result_rows_wrong"] >= 1


def test_float32_revenue_fails_by_agg_rel_err_alone(cols):
    """The reference in float32, the precision below the configuration's
    float64, reads false by ``agg_rel_err`` and keeps every key: the
    limit lies between the program's reading and this one."""
    traffic = json.loads((harness.HERE / "traffic" / "q3.json").read_text())
    want = joinref.run(cols, CFG, traffic["query"])
    f32 = joinref.run(cols, CFG, traffic["query"], control="float32")
    got = joinref.compare(f32, want, traffic["tie_rel"])
    assert got["keys_wrong"] == 0
    assert got["agg_rel_err"] > 10 * traffic["limits"]["agg_rel_err"]
