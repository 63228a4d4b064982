"""The TPC-H generator's spec rules at a tiny scale, the expression
language of the query traffic, and the exact reference."""

import json

import numpy as np
import pytest

import tiny  # noqa: F401
import exprs
import harness
import queryref

CFG = {**json.load(open(harness.HERE / "configs" / "tpch_sf10.json")),
       "orders_rows": 3000, "lineitem_rows": 11997, "part_rows": 2000}
TPCH = harness.load_module(harness.HERE / "configs" / "tpch.py")
ALL = list(CFG["columns"])


@pytest.fixture(scope="module")
def cols():
    return TPCH.generate(CFG, 2**31 + 11, ALL)


def test_lines_per_order_and_sparse_keys(cols):
    key = cols["l_orderkey"].values
    assert key.size == CFG["lineitem_rows"]
    _, per_order = np.unique(key, return_counts=True)
    assert per_order.size == CFG["orders_rows"]
    assert per_order.min() >= 1 and per_order.max() <= 7
    # 8 keys used in every 32, up to SF * 6,000,000 * 4
    assert set(np.unique((key - 1) % 32)) <= set(range(8))
    assert key.max() <= CFG["orders_rows"] * 4
    # dbgen's SF 10 count: 15M orders of 1..7 lines, 59,986,052 lines
    counts = TPCH.lines_per_order_counts(15_000_000, 59_986_052, 7)
    assert counts.sum() == 15_000_000
    assert np.dot(np.arange(1, 8), counts) == 59_986_052


def test_value_ranges(cols):
    q = cols["l_quantity"]
    assert q.values.min() >= 1 and q.values.max() <= 50
    assert np.array_equal(q.values, q.exact)
    assert cols["l_discount"].exact.min() >= 0
    assert cols["l_discount"].exact.max() <= 10
    assert cols["l_tax"].exact.max() <= 8
    ext = cols["l_extendedprice"]
    assert ext.scale == 2
    assert np.array_equal(ext.values, ext.exact / 100.0)
    retail = ext.exact // q.exact
    assert np.all(ext.exact % q.exact == 0)
    assert retail.min() >= 90000 and retail.max() <= 90000 + 20000 + 99900


def test_flag_and_status_rules():
    t = TPCH._Lineitem(CFG, 5)
    ship, receipt = t.shipdate(), t.receiptdate()
    od = t.orderdate()
    current = TPCH.days(CFG["current_date"])
    assert np.all((ship - od >= 1) & (ship - od <= 121))
    assert np.all((receipt - ship >= 1) & (receipt - ship <= 30))
    assert od.min() >= TPCH.days(CFG["start_date"])
    assert od.max() <= TPCH.days(CFG["end_date"]) - 151
    flag, status = t.returnflag(), t.linestatus()
    late = receipt > current
    assert np.all(flag[late] == 1)                       # N
    assert set(np.unique(flag[~late])) == {0, 2}         # A or R
    assert np.array_equal(status, (ship > current).astype(np.int32))


def test_same_seed_same_data_and_held_counts():
    a = TPCH.generate(CFG, 7, ["l_shipdate", "l_quantity"])
    b = TPCH.generate(CFG, 7, ["l_quantity", "l_shipdate"])
    c = TPCH.generate(CFG, 8, ["l_shipdate"])
    assert np.array_equal(a["l_shipdate"].values, b["l_shipdate"].values)
    assert np.array_equal(a["l_quantity"].values, b["l_quantity"].values)
    assert not np.array_equal(a["l_shipdate"].values, c["l_shipdate"].values)
    cutoff = TPCH.days(CFG["held_count_dates"][0])
    after = [int((x["l_shipdate"].values > cutoff).sum()) for x in (a, c)]
    assert after[0] == after[1] > 0


def test_expressions():
    env = {"a": np.array([1, 2, 3]), "b": np.array([3, 2, 1])}
    assert list(exprs.evaluate("a * (1 - b) + 2", env)) == [0, 0, 2]
    assert list(exprs.evaluate("a <= b", env)) == [True, True, False]
    assert exprs.evaluate("date('1970-01-11') - 1", {}) == 9
    assert exprs.names("x * (1 - y) > date('1995-01-01')") == {"x", "y"}
    for bad in ("__import__('os')", "a.b", "a if b else a", "a < b < a",
                "a ** 2", "1.5 * a", "open('f')"):
        with pytest.raises(ValueError):
            exprs.parse(bad)


def test_reference_exact_against_fractions():
    from fractions import Fraction

    cols = TPCH.generate(CFG, 3, ["l_returnflag", "l_extendedprice",
                                  "l_discount", "l_tax"])
    q = {"by": ["l_returnflag"],
         "derive": {"charge": "l_extendedprice * (1 - l_discount)"
                              " * (1 + l_tax)"},
         "aggs": {"s": ["charge", "sum"], "n": [None, "count"]}}
    got = queryref.run(q, cols)
    flag = cols["l_returnflag"].values
    for g, key in enumerate(got["groups"]["l_returnflag"]):
        rows = np.flatnonzero(flag == key)
        want = sum(Fraction(int(e), 100) * (1 - Fraction(int(d), 100))
                   * (1 + Fraction(int(t), 100)) for e, d, t in zip(
                       cols["l_extendedprice"].exact[rows],
                       cols["l_discount"].exact[rows],
                       cols["l_tax"].exact[rows]))
        exact = got["exact"]["s"]
        assert Fraction(int(exact.value[g]), 10 ** exact.scale) == want
        assert got["groups"]["n"][g] == rows.size
    same = queryref.compare(got, got, q)
    assert same == {"keys_wrong": 0.0, "agg_rel_err": 0.0,
                    "result_rows_wrong": 0.0}
