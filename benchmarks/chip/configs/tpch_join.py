"""TPC-H CUSTOMER and ORDERS columns made from a seed by the rules of
the TPC-H specification, clause 4.2.3, beside ``configs/tpch.py``'s
LINEITEM of the same seed.

``generate(cfg, seed, names)`` builds only the named columns, each a
``tpch.Column``.  LINEITEM columns are ``tpch.py``'s own.  ORDERS holds
one row per order of ``tpch.py``'s lines: O_ORDERKEY and O_ORDERDATE are
the key and the date those lines inherit, so the tables join as dbgen's
do.  Rules followed (clause 4.2.3): C_CUSTKEY unique in [1, SF *
150,000]; C_MKTSEGMENT one of the 5 segments; O_CUSTKEY uniform over
the custkeys not divisible by 3 (a third of the customers have no
orders); O_SHIPPRIORITY 0.  Where this departs from dbgen (segments in
equal counts) the config's ``assumed`` says so.
"""

from __future__ import annotations

from pathlib import Path
from typing import Dict, Iterable

import numpy as np

import harness

tpch = harness.load_module(Path(__file__).resolve().parent / "tpch.py")


def custkey_of(rank: np.ndarray) -> np.ndarray:
    """The ``rank``-th (from 0) custkey not divisible by 3: 1, 2, 4, 5,
    7, ..."""
    return 3 * (rank // 2) + rank % 2 + 1


class _Tables(tpch._Lineitem):
    """LINEITEM of ``tpch.py`` and the CUSTOMER and ORDERS rows it
    implies, lazily built for one seed."""

    def first_line(self) -> np.ndarray:
        """Index of each order's first line (every order has one)."""
        def build():
            o = self.order_of_line()
            return np.flatnonzero(np.r_[True, o[1:] != o[:-1]])
        return self._get("first_line", build)

    def o_orderkey(self) -> np.ndarray:
        return self.orderkey()[self.first_line()]

    def o_orderdate(self) -> np.ndarray:
        return self.orderdate()[self.first_line()]

    def o_custkey(self) -> np.ndarray:
        customers = int(self.cfg["customer_rows"])
        with_orders = customers - customers // 3
        rank = self.rng(200).integers(0, with_orders,
                                      int(self.cfg["orders_rows"]), np.int64)
        return custkey_of(rank)

    def o_shippriority(self) -> np.ndarray:
        return np.zeros(int(self.cfg["orders_rows"]), np.int32)

    def c_custkey(self) -> np.ndarray:
        return np.arange(1, int(self.cfg["customer_rows"]) + 1)

    def c_mktsegment(self) -> np.ndarray:
        n = int(self.cfg["customer_rows"])
        kinds = len(self.cfg["columns"]["c_mktsegment"]["values"])
        seg = np.arange(n) % kinds
        self.rng(201).shuffle(seg)
        return seg


_BUILD = {**tpch._BUILD,
          "o_orderkey": _Tables.o_orderkey,
          "o_custkey": _Tables.o_custkey,
          "o_orderdate": _Tables.o_orderdate,
          "o_shippriority": _Tables.o_shippriority,
          "c_custkey": _Tables.c_custkey,
          "c_mktsegment": _Tables.c_mktsegment}


def generate(cfg: dict, seed: int,
             names: Iterable[str]) -> Dict[str, "tpch.Column"]:
    """The named CUSTOMER, ORDERS and LINEITEM columns of ``seed``."""
    tables = _Tables(cfg, seed)
    out = {}
    for name in names:
        spec = cfg["columns"][name]
        raw = _BUILD[name](tables)
        if spec["type"] == "decimal":
            exact = raw.astype(np.int64)
            out[name] = tpch.Column(exact / 10.0 ** spec["scale"], exact,
                                    spec["scale"])
        else:
            out[name] = tpch.Column(raw.astype(np.int32))
    return out
