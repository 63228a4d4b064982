"""Plain reference of TPC-H Q3 (clause 2.4.3) over the ``tpch_join``
columns, and the comparison that decides ``correct`` for it.

    select l_orderkey, sum(l_extendedprice * (1 - l_discount)) as revenue,
           o_orderdate, o_shippriority
    from customer, orders, lineitem
    where c_mktsegment = SEGMENT and c_custkey = o_custkey
      and l_orderkey = o_orderkey
      and o_orderdate < DATE and l_shipdate > DATE
    group by l_orderkey, o_orderdate, o_shippriority
    order by revenue desc, o_orderdate
    limit 10

The reference imports nothing of the program.  Joins are numpy lookup
arrays over the key domains (custkey -> qualifies, orderkey -> the
order's slot), revenue is exact decimal arithmetic
(``queryref.Dec``, ``queryref._exact_sum``), and the top rows are
ordered by (exact revenue desc, o_orderdate, l_orderkey).

``control="semi_join"`` keeps only the first matching line of each
order, a semi-join where Q3 asks for an inner join (the cell's
control).  ``control="float32"`` computes revenue in float32, the
precision below the one the configuration states: the reading that
bounds ``agg_rel_err`` from above.
"""

from __future__ import annotations

from typing import Dict, Mapping, Optional

import numpy as np

from queryref import Dec, _exact_sum, _f32_sum

KEYS = ("orderkey", "o_orderdate", "o_shippriority")


def _lookup(keys: np.ndarray, values: np.ndarray, size: int,
            fill) -> np.ndarray:
    """A dense array over the key domain [0, size): ``values`` at
    ``keys``, ``fill`` elsewhere."""
    table = np.full(size, fill, np.asarray(values).dtype)
    table[keys] = values
    return table


def run(cols: Mapping[str, object], cfg: Mapping, query: Mapping,
        control: Optional[str] = None) -> Dict[str, object]:
    """Q3's answer: ``{"groups": {column: values}, "top": {column:
    values}, "exact": Dec revenue of each group}``, groups in key order,
    revenue as float64."""
    v = {name: col.values for name, col in cols.items()}
    segment = cfg["columns"]["c_mktsegment"]["values"].index(
        query["segment"])
    day = int((np.datetime64(query["date"]) - np.datetime64("1970-01-01"))
              .astype(int))

    cust = v["c_custkey"].astype(np.int64)
    o_cust = v["o_custkey"].astype(np.int64)
    size = int(max(cust.max(), o_cust.max())) + 1
    in_segment = _lookup(cust, v["c_mktsegment"] == segment, size, False)
    orders = np.flatnonzero((v["o_orderdate"] < day) & in_segment[o_cust])

    o_key = v["o_orderkey"].astype(np.int64)
    l_key = v["l_orderkey"].astype(np.int64)
    size = int(max(o_key.max(), l_key.max())) + 1
    slot = _lookup(o_key[orders], np.arange(orders.size), size, -1)
    line_slot = slot[l_key]
    lines = np.flatnonzero((v["l_shipdate"] > day) & (line_slot >= 0))
    if control == "semi_join":
        _, first = np.unique(line_slot[lines], return_index=True)
        lines = lines[np.sort(first)]

    present = np.bincount(line_slot[lines], minlength=orders.size) > 0
    group = (np.cumsum(present) - 1)[line_slot[lines]]
    n_groups = int(present.sum())
    price, disc = cols["l_extendedprice"], cols["l_discount"]
    if control == "float32":
        volume = (price.values[lines].astype(np.float32)
                  * (np.float32(1) - disc.values[lines].astype(np.float32)))
        sums = _f32_sum(group, volume, n_groups).astype(np.float64)
        rank_by = -sums
    else:
        volume = Dec(price.exact[lines]
                     * (10 ** disc.scale - disc.exact[lines]),
                     price.scale + disc.scale)
        sums = Dec(_exact_sum(group, volume.value, n_groups), volume.scale)
        rank_by = -sums.value
    group_orders = orders[present]
    order = np.argsort(o_key[group_orders], kind="stable")
    group_orders = group_orders[order]
    revenue = sums[order]

    groups = {"orderkey": o_key[group_orders],
              "o_orderdate": v["o_orderdate"][group_orders].astype(np.int64),
              "o_shippriority":
                  v["o_shippriority"][group_orders].astype(np.int64),
              "revenue": (revenue.to_float() if isinstance(revenue, Dec)
                          else revenue)}
    rank = np.lexsort((groups["orderkey"], groups["o_orderdate"],
                       rank_by[order]))[: int(query["limit"])]
    return {"groups": groups,
            "top": {k: c[rank] for k, c in groups.items()},
            "exact": revenue}


def _rel(got: np.ndarray, exact: Dec) -> np.ndarray:
    """Relative error of float ``got`` against exact decimals."""
    ref = exact.value.astype(np.float64)
    diff = np.abs(np.asarray(got, np.float64) * 10.0 ** exact.scale - ref)
    rel = diff / np.maximum(np.abs(ref), np.finfo(np.float64).tiny)
    return np.where(diff == 0, 0.0, rel)


def compare(got: Mapping, want: Mapping, tie_rel: float) -> Dict[str, float]:
    """The numbers ``correct`` is decided on, for one answer.

    ``keys_wrong``: group rows whose (orderkey, o_orderdate,
    o_shippriority) differs from the reference's in the same place,
    plus any missing or extra group.  ``agg_rel_err``: the largest
    relative error of a revenue, over every group and every top row,
    against its exact value.  ``result_rows_wrong``: places of the top
    rows that hold another row than the reference's, or a row twice;
    a row may stand in another's place only where their exact revenues
    differ by at most ``tie_rel`` of the reference's, the float64
    rounding of the program's sums."""
    g, w = got["groups"], want["groups"]
    exact = want["exact"]
    n_got, n_want = len(np.asarray(g["orderkey"])), len(w["orderkey"])
    n = min(n_got, n_want)
    differs = np.zeros(n, bool)
    for k in KEYS:
        differs |= (np.asarray(g[k])[:n].astype(np.int64) != w[k][:n])
    keys_wrong = int(differs.sum()) + abs(n_got - n_want)
    errs = [_rel(np.asarray(g["revenue"])[:n], exact[np.arange(n)])]

    top_g, top_w = got["top"], want["top"]
    key_g = np.asarray(top_g["orderkey"]).astype(np.int64)
    at = np.minimum(np.searchsorted(w["orderkey"], key_g), max(n_want - 1, 0))
    known = np.zeros(key_g.size, bool)
    rev_g = np.zeros(key_g.size, np.int64)
    if n_want:
        known = w["orderkey"][at] == key_g
        for k in KEYS[1:]:
            known &= np.asarray(top_g[k]).astype(np.int64) == w[k][at]
        rev_g = exact.value[at]
    errs.append(_rel(np.asarray(top_g["revenue"])[known], exact[at[known]]))
    rev_w = exact.value[np.searchsorted(w["orderkey"], top_w["orderkey"])]
    wrong = key_g.size - np.unique(key_g).size
    for i in range(max(key_g.size, rev_w.size)):
        if i >= key_g.size or i >= rev_w.size or not known[i]:
            wrong += 1
        elif (key_g[i] != top_w["orderkey"][i]
              and abs(int(rev_g[i]) - int(rev_w[i]))
              > tie_rel * abs(int(rev_w[i]))):
            wrong += 1
    err = max((float(e.max()) for e in errs if e.size), default=0.0)
    if not np.isfinite(err):
        err = float("inf")
    return {"keys_wrong": float(keys_wrong), "agg_rel_err": err,
            "result_rows_wrong": float(wrong)}
