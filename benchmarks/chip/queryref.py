"""Plain reference of the group-by query traffic, and the comparison
that decides ``correct`` for it.

The reference imports nothing of the program.  It computes in exact
decimal arithmetic: a decimal is its unscaled int64 integers and a
scale, sums are exact (each value is split into a high and a low part
whose sums stay below 2**53), and only a division leaves the integers,
as one float64 operation on exact operands.

``control`` runs the same query with one guarantee broken, as a later
change might be tempted to: ``float32`` keeps the decimals and their
sums in float32, and ``drop_key_bit`` groups on the last key column
without its lowest bit.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Mapping, Optional

import numpy as np

import exprs

_SPLIT = 26  # low-part bits: n * 2**26 < 2**53 for n < 2**27 rows


@dataclasses.dataclass
class Dec:
    """Exact decimal: ``value / 10**scale``."""

    value: np.ndarray
    scale: int

    def at(self, scale: int) -> np.ndarray:
        return self.value * np.int64(10 ** (scale - self.scale))

    def __getitem__(self, idx) -> "Dec":
        return Dec(self.value[idx], self.scale)

    def to_float(self) -> np.ndarray:
        return self.value / 10.0 ** self.scale


def _aligned(f, dec_result=True):
    """``f`` on two decimals brought to the larger scale."""
    def op(a: Dec, b: Dec):
        s = max(a.scale, b.scale)
        r = f(a.at(s), b.at(s))
        return Dec(r, s) if dec_result else r
    return op


_DEC_OPS = {
    "add": _aligned(np.add),
    "sub": _aligned(np.subtract),
    "mul": lambda a, b: Dec(a.value * b.value, a.scale + b.scale),
    "truediv": lambda a, b: (a.value / b.value) * 10.0 ** (b.scale - a.scale),
    "neg": lambda a: Dec(-a.value, a.scale),
    "lt": _aligned(np.less, False),
    "le": _aligned(np.less_equal, False),
    "gt": _aligned(np.greater, False),
    "ge": _aligned(np.greater_equal, False),
    "eq": _aligned(np.equal, False),
    "ne": _aligned(np.not_equal, False),
}


def _const(v: int) -> Dec:
    return Dec(np.int64(v), 0)


def _exact_sum(inverse: np.ndarray, value: np.ndarray, groups: int):
    """Exact int64 per-group sums."""
    lo = value & ((1 << _SPLIT) - 1)
    hi = value >> _SPLIT
    lo_sum = np.bincount(inverse, lo.astype(np.float64), groups)
    hi_sum = np.bincount(inverse, hi.astype(np.float64), groups)
    return (hi_sum.astype(np.int64) << _SPLIT) + lo_sum.astype(np.int64)


def _f32_sum(inverse: np.ndarray, value: np.ndarray, groups: int):
    """Per-group sums accumulated in float32, rows in table order."""
    order = np.argsort(inverse, kind="stable")
    starts = np.searchsorted(inverse[order], np.arange(groups))
    return np.add.reduceat(value.astype(np.float32)[order], starts)


def _group_index(keys):
    """(sorted distinct key rows as columns, inverse) of int key columns."""
    code = np.zeros(keys[0].shape[0], np.int64)
    lows, spans = [], []
    for k in keys:
        lo = int(k.min()) if k.size else 0
        span = (int(k.max()) - lo + 1) if k.size else 1
        code = code * span + (k.astype(np.int64) - lo)
        lows.append(lo)
        spans.append(span)
    total = int(np.prod(spans, dtype=object))
    if total <= 4 * code.size + (1 << 20):
        present = np.bincount(code, minlength=total) > 0
        uniq = np.flatnonzero(present)
        lookup = np.cumsum(present) - 1
        inverse = lookup[code]
    else:
        uniq, inverse = np.unique(code, return_inverse=True)
    cols = []
    rest = uniq
    for lo, span in zip(reversed(lows), reversed(spans)):
        cols.append(rest % span + lo)
        rest = rest // span
    return cols[::-1], inverse


def _column_env(cols: Mapping[str, object], precision: str):
    """Reference values of the generated columns."""
    env = {}
    for name, col in cols.items():
        if col.exact is not None:
            env[name] = (Dec(col.exact, col.scale) if precision == "exact"
                         else col.exact.astype(np.float32)
                         / np.float32(10 ** col.scale))
        else:
            env[name] = (Dec(col.values.astype(np.int64), 0)
                         if precision == "exact" else col.values)
    return env


def run(query: Mapping, cols: Mapping[str, object],
        control: Optional[str] = None) -> Dict[str, Dict[str, np.ndarray]]:
    """The query's answer: ``{"groups": {column: values}, "result":
    {column: values}}``, group rows in key order, decimals as float64."""
    precision = "float32" if control == "float32" else "exact"
    env = _column_env(cols, precision)
    const = _const if precision == "exact" else (lambda v: v)
    ops = _DEC_OPS if precision == "exact" else None
    if query.get("where"):
        mask = exprs.evaluate(query["where"], env, const, ops)
        env = {k: v[mask] for k, v in env.items()}
    for name, text in query.get("derive", {}).items():
        env[name] = exprs.evaluate(text, env, const, ops)

    def as_int(v):
        return v.value if isinstance(v, Dec) else np.asarray(v, np.int64)

    keys = [as_int(env[name]) for name in query["by"]]
    if control == "drop_key_bit":
        keys[-1] = keys[-1] >> 1 << 1
    key_cols, inverse = _group_index(keys)
    groups = len(key_cols[0])
    out = dict(zip(query["by"], key_cols))
    for name, (col, op) in query["aggs"].items():
        if op == "count":
            out[name] = Dec(np.bincount(inverse, minlength=groups), 0) \
                if precision == "exact" else np.bincount(inverse,
                                                         minlength=groups)
        elif op == "sum":
            v = env[col]
            out[name] = (Dec(_exact_sum(inverse, v.value, groups), v.scale)
                         if isinstance(v, Dec)
                         else _f32_sum(inverse, v, groups))
        else:
            raise ValueError(f"the reference has no aggregate {op!r}")
    for name, text in query.get("select", {}).items():
        out[name] = exprs.evaluate(text, out, const, ops)
    keep = (exprs.evaluate(query["having"], out, const, ops)
            if query.get("having") else np.ones(groups, bool))
    as_float = {k: (v.to_float() if isinstance(v, Dec) else np.asarray(v))
                for k, v in out.items()}
    exact = {k: v for k, v in out.items() if isinstance(v, Dec)}
    return {"groups": as_float,
            "result": {k: v[keep] for k, v in as_float.items()},
            "exact": exact}


def compare(got: Mapping, want: Mapping, query: Mapping) -> Dict[str, float]:
    """The numbers ``correct`` is decided on, for one answer:
    ``keys_wrong`` group rows whose key differs (plus any missing or
    extra), ``agg_rel_err`` the largest relative error of an aggregate
    or selected value, and ``result_rows_wrong`` result rows in one
    answer but not the other."""
    by = list(query["by"])
    g, w = got["groups"], want["groups"]
    n_got, n_want = len(np.asarray(g[by[0]])), len(w[by[0]])
    n = min(n_got, n_want)
    differs = np.zeros(n, bool)
    for k in by:
        differs |= (np.asarray(g[k])[:n].astype(np.int64)
                    != np.asarray(w[k])[:n].astype(np.int64))
    keys_wrong = int(differs.sum()) + abs(n_got - n_want)
    err = 0.0
    for name in list(query["aggs"]) + list(query.get("select", {})):
        p = np.asarray(g[name], np.float64)[:n]
        if name in want.get("exact", {}):
            d = want["exact"][name]
            ref = d.value[:n].astype(np.float64)
            diff = np.abs(p * 10.0 ** d.scale - ref)
        else:
            ref = np.asarray(w[name], np.float64)[:n]
            diff = np.abs(p - ref)
        rel = diff / np.maximum(np.abs(ref), np.finfo(np.float64).tiny)
        rel = np.where(diff == 0, 0.0, rel)
        if rel.size:
            err = max(err, float(np.max(rel)))
    if not np.isfinite(err):
        err = float("inf")

    def rows(ans):
        return np.stack([np.asarray(ans["result"][k]).astype(np.int64)
                         for k in by], axis=1)

    both = np.concatenate([np.unique(rows(got), axis=0),
                           np.unique(rows(want), axis=0)])
    _, seen = np.unique(both, axis=0, return_counts=True)

    return {"keys_wrong": float(keys_wrong), "agg_rel_err": err,
            "result_rows_wrong": float(np.sum(seen == 1))}
