"""TPC-H LINEITEM columns made from a seed by the rules of the TPC-H
specification, clause 4.2.3.

``generate(cfg, seed, names)`` builds only the named columns, each from
its own random stream, so a column reads the same for a seed whatever
else a query asks for.  Every column is a :class:`Column`: the values as
the program holds them (int32 for keys, codes and dates; float64 for
decimals) and, for decimals, the exact unscaled integers the reference
computes with.

Rules followed (clause 4.2.3): sparse order keys, 8 used in every 32;
1 to 7 lines per order; O_ORDERDATE uniform in [STARTDATE, ENDDATE -
151 days]; L_SHIPDATE = O_ORDERDATE + [1, 121] days; L_RECEIPTDATE =
L_SHIPDATE + [1, 30] days; L_QUANTITY in [1, 50]; L_PARTKEY in [1, SF *
200,000]; L_EXTENDEDPRICE = L_QUANTITY * P_RETAILPRICE; L_DISCOUNT in
[0.00, 0.10]; L_TAX in [0.00, 0.08]; L_RETURNFLAG "R" or "A" at random
when L_RECEIPTDATE <= CURRENTDATE, else "N"; L_LINESTATUS "O" when
L_SHIPDATE > CURRENTDATE, else "F".  Where this departs from dbgen
(fixed row count, held counts after some dates) the config's
``assumed`` says so.
"""

from __future__ import annotations

import dataclasses
import datetime
from typing import Dict, Iterable, Optional

import numpy as np

EPOCH = datetime.date(1970, 1, 1)
MAX_SHIP_DELAY = 121
MAX_RECEIPT_DELAY = 30


def days(iso: str) -> int:
    """Days since 1970-01-01 of an ISO date."""
    return (datetime.date.fromisoformat(iso) - EPOCH).days


@dataclasses.dataclass
class Column:
    values: np.ndarray                 # as the program holds it
    exact: Optional[np.ndarray] = None  # unscaled int64, decimals only
    scale: int = 0


def lines_per_order_counts(orders: int, lines: int, most: int) -> np.ndarray:
    """How many orders have 1, 2, ..., ``most`` lines: as even as the
    totals allow, with ``orders`` orders holding ``lines`` lines."""
    if not orders <= lines <= most * orders:
        raise ValueError(f"{lines} lines cannot fill {orders} orders "
                         f"of 1..{most} lines")
    counts = np.full(most, orders // most, np.int64)
    counts[: orders % most] += 1
    diff = lines - int(np.dot(np.arange(1, most + 1), counts))
    # moving one order from 1 line to `most` lines adds most - 1 lines
    step = most - 1
    moves = abs(diff) // step
    src, dst = (0, most - 1) if diff > 0 else (most - 1, 0)
    counts[src] -= moves
    counts[dst] += moves
    rest = abs(diff) - moves * step
    if rest:  # one order moves by `rest` lines
        src = 0 if diff > 0 else most - 1
        counts[src] -= 1
        counts[src + (rest if diff > 0 else -rest)] += 1
    if counts.min() < 0:
        raise ValueError(f"no even split of {lines} lines over {orders} "
                         "orders")
    return counts


def expected_after(lines: int, first: int, last: int, cutoff: int) -> int:
    """Expected number of lines with ship date after ``cutoff`` when the
    order date is uniform on [first, last] and the delay on [1, 121]."""
    late = 0
    for od in range(first, last + 1):
        late += min(MAX_SHIP_DELAY, max(0, od + MAX_SHIP_DELAY - cutoff))
    return round(lines * late / ((last - first + 1) * MAX_SHIP_DELAY))


def _hold_after(ship: np.ndarray, od: np.ndarray, cutoff: int, target: int,
                rng: np.random.Generator) -> None:
    """Redraw the ship delay of just enough lines, chosen at random near
    ``cutoff``, that exactly ``target`` lines ship after it."""
    after = ship > cutoff
    excess = int(after.sum()) - target
    if excess > 0:
        pool = np.flatnonzero(after & (od < cutoff))
        pick = rng.choice(pool, excess, replace=False)
        ship[pick] = od[pick] + rng.integers(1, cutoff - od[pick] + 1)
    elif excess < 0:
        pool = np.flatnonzero(~after & (od + MAX_SHIP_DELAY > cutoff))
        pick = rng.choice(pool, -excess, replace=False)
        ship[pick] = od[pick] + rng.integers(cutoff - od[pick] + 1,
                                             MAX_SHIP_DELAY + 1)


class _Lineitem:
    """Lazily built LINEITEM columns of one seed."""

    def __init__(self, cfg: dict, seed: int):
        self.cfg = cfg
        self.seed = int(seed)
        self.n = int(cfg["lineitem_rows"])
        self._memo: Dict[str, np.ndarray] = {}

    def rng(self, stream: int) -> np.random.Generator:
        return np.random.default_rng([self.seed, stream])

    def _get(self, name, build):
        if name not in self._memo:
            self._memo[name] = build()
        return self._memo[name]

    def order_of_line(self) -> np.ndarray:
        def build():
            orders = int(self.cfg["orders_rows"])
            most = int(self.cfg["max_lines_per_order"])
            counts = lines_per_order_counts(orders, self.n, most)
            per_order = np.repeat(np.arange(1, most + 1, dtype=np.int32),
                                  counts)
            self.rng(1).shuffle(per_order)
            return np.repeat(np.arange(orders, dtype=np.int32), per_order)
        return self._get("order_of_line", build)

    def orderkey(self) -> np.ndarray:
        def build():
            i = self.order_of_line()
            return (i // 8) * 32 + (i % 8) + 1
        return self._get("orderkey", build)

    def orderdate(self) -> np.ndarray:
        def build():
            first = days(self.cfg["start_date"])
            last = days(self.cfg["end_date"]) - 151
            per_order = self.rng(2).integers(
                first, last + 1, int(self.cfg["orders_rows"]), np.int32)
            return per_order[self.order_of_line()]
        return self._get("orderdate", build)

    def shipdate(self) -> np.ndarray:
        def build():
            od = self.orderdate()
            ship = od + self.rng(3).integers(1, MAX_SHIP_DELAY + 1, self.n,
                                             np.int32)
            first = days(self.cfg["start_date"])
            last = days(self.cfg["end_date"]) - 151
            for k, iso in enumerate(self.cfg.get("held_count_dates", ())):
                cutoff = days(iso)
                _hold_after(ship, od, cutoff,
                            expected_after(self.n, first, last, cutoff),
                            self.rng(100 + k))
            return ship
        return self._get("shipdate", build)

    def receiptdate(self) -> np.ndarray:
        return self._get("receiptdate", lambda: self.shipdate() + self.rng(
            4).integers(1, MAX_RECEIPT_DELAY + 1, self.n, np.int32))

    def returnflag(self) -> np.ndarray:
        def build():
            values = self.cfg["columns"]["l_returnflag"]["values"]
            a, n_, r = (values.index(v) for v in ("A", "N", "R"))
            pick = np.where(self.rng(5).integers(0, 2, self.n, np.int32),
                            r, a).astype(np.int32)
            current = days(self.cfg["current_date"])
            return np.where(self.receiptdate() <= current, pick,
                            n_).astype(np.int32)
        return self._get("returnflag", build)

    def linestatus(self) -> np.ndarray:
        values = self.cfg["columns"]["l_linestatus"]["values"]
        f, o = values.index("F"), values.index("O")
        current = days(self.cfg["current_date"])
        return np.where(self.shipdate() > current, o, f).astype(np.int32)

    def quantity(self) -> np.ndarray:
        return self._get("quantity", lambda: self.rng(6).integers(
            1, 51, self.n, np.int32))

    def extendedprice_cents(self) -> np.ndarray:
        parts = int(self.cfg["part_rows"])
        pk = self.rng(7).integers(1, parts + 1, self.n, np.int64)
        retail = 90000 + (pk // 10) % 20001 + 100 * (pk % 1000)
        return self.quantity().astype(np.int64) * retail

    def discount_pct(self) -> np.ndarray:
        return self.rng(8).integers(0, 11, self.n, np.int32)

    def tax_pct(self) -> np.ndarray:
        return self.rng(9).integers(0, 9, self.n, np.int32)


_BUILD = {
    "l_orderkey": _Lineitem.orderkey,
    "l_quantity": _Lineitem.quantity,
    "l_extendedprice": _Lineitem.extendedprice_cents,
    "l_discount": _Lineitem.discount_pct,
    "l_tax": _Lineitem.tax_pct,
    "l_returnflag": _Lineitem.returnflag,
    "l_linestatus": _Lineitem.linestatus,
    "l_shipdate": _Lineitem.shipdate,
}


def generate(cfg: dict, seed: int, names: Iterable[str]) -> Dict[str, Column]:
    """The named LINEITEM columns of ``seed``."""
    table = _Lineitem(cfg, seed)
    out = {}
    for name in names:
        spec = cfg["columns"][name]
        raw = _BUILD[name](table)
        if spec["type"] == "decimal":
            exact = raw.astype(np.int64)
            out[name] = Column(exact / 10.0 ** spec["scale"], exact,
                               spec["scale"])
        else:
            out[name] = Column(raw.astype(np.int32))
    return out
