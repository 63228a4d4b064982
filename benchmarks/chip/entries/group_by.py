"""Entry ``group_by``: one call answers one query through the program's
``repro.query.group_by`` over a ``repro.query.Table``, as a user of the
library writes it.

The traffic's ``query`` says what to answer: ``where`` (a filter),
``derive`` (new columns), ``by`` (the group keys), ``aggs`` (``out:
[column, sum|count]``), ``select`` (values computed from the
aggregates) and ``having`` (a filter on groups); see ``exprs`` for the
expressions.  The program has no filter or projection operator, so the
filter and the derived columns are the caller's numpy code: set-up runs
them once over the config's columns and builds the ``Table`` the
GROUP BY reads, holding its columns as the program holds them (ints and
codes as device arrays, decimals as float64 numpy arrays).  A call is
the GROUP BY and what follows it.  A key column whose config states
``bits`` (a dictionary code, a key of bounded domain) is given
``UIntCodec`` of that width.
"""

from __future__ import annotations

import time

import numpy as np

import jax
import jax.numpy as jnp

import exprs
import queryref
from repro.query import Table, UIntCodec, group_by


def _columns_read(query: dict) -> list:
    """The config columns ``query`` reads, in a fixed order."""
    made = set(query.get("derive", {}))
    read = set(query["by"])
    for col, _ in query["aggs"].values():
        if col is not None:
            read.add(col)
    for text in query.get("derive", {}).values():
        read |= exprs.names(text)
    if query.get("where"):
        read |= exprs.names(query["where"])
    return sorted(read - made)


def _host_if_mixed(f):
    """``f`` on numpy operands when either is numpy, so a float64 host
    column never meets a device array and drops to float32."""
    def op(*args):
        if any(isinstance(a, np.ndarray) for a in args):
            args = [np.asarray(a) if isinstance(a, jax.Array) else a
                    for a in args]
        return f(*args)
    return op


_OPS = {name: _host_if_mixed(f) for name, f in {
    "add": lambda a, b: a + b, "sub": lambda a, b: a - b,
    "mul": lambda a, b: a * b, "truediv": lambda a, b: a / b,
    "lt": lambda a, b: a < b, "le": lambda a, b: a <= b,
    "gt": lambda a, b: a > b, "ge": lambda a, b: a >= b,
    "eq": lambda a, b: a == b, "ne": lambda a, b: a != b}.items()}


def prepare(cols: dict, specs: dict, query: dict) -> Table:
    """The table the query's GROUP BY reads: the config's columns (host
    arrays) filtered by ``where`` and extended by ``derive`` in numpy,
    then held as the program holds them."""
    env = {name: col.values for name, col in cols.items()}
    if query.get("where"):
        keep = np.asarray(exprs.evaluate(query["where"], env))
        env = {name: v[keep] for name, v in env.items()}
    for name, text in query.get("derive", {}).items():
        env[name] = exprs.evaluate(text, env)
    used = list(query["by"]) + sorted(
        {col for col, _ in query["aggs"].values() if col is not None}
        - set(query["by"]))
    return Table({name: (env[name] if name in query.get("derive", {})
                         or specs[name]["type"] == "decimal"
                         else jnp.asarray(env[name]))
                  for name in used})


def answer(table: Table, query: dict, codecs: dict) -> dict:
    """The GROUP BY of ``query`` over ``table`` through the program, and
    what follows it: ``{"groups": {column: values}, "result": {column:
    numpy values}}``."""
    aggs = {name: (col, op) for name, (col, op) in query["aggs"].items()}
    grouped = group_by(table, list(query["by"]), aggs, codecs=codecs)
    groups = {n: grouped.column(n) for n in grouped.column_names}
    for name, text in query.get("select", {}).items():
        groups[name] = exprs.evaluate(text, groups, ops=_OPS)
    if query.get("having"):
        keep = np.asarray(exprs.evaluate(query["having"], groups, ops=_OPS))
        result = {n: np.asarray(v)[keep] for n, v in groups.items()}
    else:
        result = {n: np.asarray(v) for n, v in groups.items()}
    return {"groups": groups, "result": result}


class QueryCell:
    def __init__(self, cfg: dict, module, traffic: dict, seed: int):
        self.query = traffic["query"]
        self.control_kind = traffic.get("control")
        self.cols = module.generate(cfg, seed, _columns_read(self.query))
        self.specs = cfg["columns"]
        t0 = time.perf_counter()
        self.table = prepare(self.cols, self.specs, self.query)
        print(f"set-up: filter and derived columns {time.perf_counter() - t0}"
              f" s; the GROUP BY reads {self.table.num_rows} of "
              f"{len(next(iter(self.cols.values())).values)} rows",
              flush=True)
        self.codecs = {name: UIntCodec(bits=int(self.specs[name]["bits"]))
                       for name in self.query["by"]
                       if "bits" in self.specs[name]}
        key = self.query["by"][0]
        self.key_bytes = np.dtype(self.cols[key].values.dtype).itemsize
        self.sample = int(traffic.get("sample", 1))
        self.rows = self.table.num_rows

    @property
    def rows_per_call(self) -> int:
        """The rows the GROUP BY reads, those the filter kept."""
        return self.rows

    @property
    def min_bytes_per_row(self) -> int:
        """Key column read once and int32 row ids written once."""
        return self.key_bytes + 4

    def call(self, i: int) -> dict:
        out = answer(self.table, self.query, self.codecs)
        return jax.block_until_ready(out)

    def control(self, i: int) -> dict:
        return queryref.run(self.query, self.cols, control=self.control_kind)

    def fetch(self, out: dict) -> dict:
        return {part: {n: np.asarray(v) for n, v in cols.items()}
                for part, cols in out.items() if part != "exact"}

    def release(self) -> None:
        self.table = None

    def check(self, samples) -> list:
        """For each sampled call, ``queryref.compare``'s numbers."""
        want = queryref.run(self.query, self.cols)
        return [queryref.compare(got, want, self.query)
                for _, got in samples]


def setup(cfg: dict, module, traffic: dict, seed: int) -> QueryCell:
    cell = QueryCell(cfg, module, traffic, seed)
    jax.block_until_ready([c for c in (cell.table.column(n) for n in
                                       cell.table.column_names)
                           if isinstance(c, jax.Array)])
    return cell
