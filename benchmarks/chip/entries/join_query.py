"""Entry ``join_query``: one call answers TPC-H Q3 (clause 2.4.3) through
the program's ``repro.query`` operators, as a user of the library writes
it: ``sort_merge_join`` of CUSTOMER and ORDERS on custkey, a second
``sort_merge_join`` with LINEITEM on orderkey, ``group_by`` l_orderkey,
o_orderdate, o_shippriority with ``sum`` of the lines' revenue, and
``top_k`` by (revenue desc, o_orderdate).

The traffic's ``query`` gives Q3's parameters: ``segment``, ``date``
and ``limit``.  The program has no filter or projection operator, so
the three filters (c_mktsegment = SEGMENT, o_orderdate < DATE,
l_shipdate > DATE) and each line's l_extendedprice * (1 - l_discount)
are the caller's numpy code: set-up runs them once and builds the three
``Table``s, holding their columns as the program holds them (keys,
dates and codes as device arrays, decimals as float64 numpy arrays).
Join keys share a column name on both sides (``custkey``,
``orderkey``), and every key column is given ``UIntCodec`` of the width
its config states.  A call returns every group and the top rows.

Each call records the program's counter deltas in ``counters``, which
the per-layer metrics of this cell read.
"""

from __future__ import annotations

import time

import numpy as np

import jax
import jax.numpy as jnp

import joinref
from repro.obs import metrics
from repro.query import Table, UIntCodec, group_by, sort_merge_join, top_k

COLUMNS = ("c_custkey", "c_mktsegment", "o_orderkey", "o_custkey",
           "o_orderdate", "o_shippriority", "l_orderkey", "l_extendedprice",
           "l_discount", "l_shipdate")
GROUP = ["orderkey", "o_orderdate", "o_shippriority"]


def prepare(cols: dict, cfg: dict, query: dict):
    """Q3's three filtered tables (customer, orders, lineitem)."""
    v = {name: col.values for name, col in cols.items()}
    segment = cfg["columns"]["c_mktsegment"]["values"].index(
        query["segment"])
    day = int((np.datetime64(query["date"]) - np.datetime64("1970-01-01"))
              .astype(int))
    c = v["c_mktsegment"] == segment
    o = v["o_orderdate"] < day
    li = v["l_shipdate"] > day
    customer = Table({"custkey": jnp.asarray(v["c_custkey"][c])})
    orders = Table({"custkey": jnp.asarray(v["o_custkey"][o]),
                    "orderkey": jnp.asarray(v["o_orderkey"][o]),
                    "o_orderdate": jnp.asarray(v["o_orderdate"][o]),
                    "o_shippriority": jnp.asarray(v["o_shippriority"][o])})
    lineitem = Table({"orderkey": jnp.asarray(v["l_orderkey"][li]),
                      "volume": v["l_extendedprice"][li]
                      * (1 - v["l_discount"][li])})
    return customer, orders, lineitem


def answer(customer: Table, orders: Table, lineitem: Table, codecs: dict,
           limit: int) -> dict:
    """Q3 through the program: ``{"groups": {column: values}, "top":
    {column: values}}``."""
    placed = sort_merge_join(customer, orders, "custkey", codecs=codecs)
    lines = sort_merge_join(placed.select(GROUP), lineitem, "orderkey",
                            codecs=codecs)
    grouped = group_by(lines, GROUP, {"revenue": ("volume", "sum")},
                       codecs=codecs)
    top = top_k(grouped, [("revenue", "desc"), "o_orderdate"], limit,
                codecs=codecs)
    return {"groups": {n: grouped.column(n) for n in grouped.column_names},
            "top": {n: top.column(n) for n in top.column_names}}


class JoinCell:
    def __init__(self, cfg: dict, module, traffic: dict, seed: int):
        self.cfg = cfg
        self.query = traffic["query"]
        self.limit = int(self.query["limit"])
        self.tie_rel = float(traffic["tie_rel"])
        self.control_kind = traffic.get("control")
        self.cols = module.generate(cfg, seed, COLUMNS)
        t0 = time.perf_counter()
        self.tables = prepare(self.cols, cfg, self.query)
        specs = cfg["columns"]
        self.codecs = {
            "custkey": UIntCodec(bits=int(specs["o_custkey"]["bits"])),
            "orderkey": UIntCodec(bits=int(specs["o_orderkey"]["bits"])),
            "o_orderdate": UIntCodec(bits=int(specs["o_orderdate"]["bits"])),
            "o_shippriority":
                UIntCodec(bits=int(specs["o_shippriority"]["bits"]))}
        self.rows = sum(t.num_rows for t in self.tables)
        print(f"set-up: filters and revenue column "
              f"{time.perf_counter() - t0} s; the call reads "
              f"{[t.num_rows for t in self.tables]} rows", flush=True)
        self.sample = int(traffic.get("sample", 1))
        self.counters = []

    @property
    def rows_per_call(self) -> int:
        """The rows the call reads: the three filtered tables."""
        return self.rows

    def call(self, i: int) -> dict:
        before = metrics.snapshot()
        out = jax.block_until_ready(
            answer(*self.tables, self.codecs, self.limit))
        self.counters.append(metrics.snapshot_delta(before))
        return out

    def control(self, i: int) -> dict:
        return joinref.run(self.cols, self.cfg, self.query,
                           control=self.control_kind)

    def fetch(self, out: dict) -> dict:
        return {part: {n: np.asarray(v) for n, v in cols.items()}
                for part, cols in out.items() if part != "exact"}

    def release(self) -> None:
        self.tables = None

    def check(self, samples) -> list:
        """For each sampled call, ``joinref.compare``'s numbers."""
        want = joinref.run(self.cols, self.cfg, self.query)
        return [joinref.compare(got, want, self.tie_rel)
                for _, got in samples]


def setup(cfg: dict, module, traffic: dict, seed: int) -> JoinCell:
    cell = JoinCell(cfg, module, traffic, seed)
    jax.block_until_ready([t.column(n) for t in cell.tables
                           for n in t.column_names
                           if isinstance(t.column(n), jax.Array)])
    return cell
