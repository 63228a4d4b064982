"""The small expression language of the query traffic files.

An expression is Python syntax limited to column names, whole-number
constants, ``date('YYYY-MM-DD')`` (days since 1970-01-01), unary minus,
``+ - * /`` and one comparison (``< <= > >= == !=``).  ``parse`` checks
it; ``evaluate`` folds it with the caller's operations, so the program
side runs it on arrays as the program holds them and the reference runs
it in exact decimal arithmetic.
"""

from __future__ import annotations

import ast
import datetime
import operator
from typing import Callable, Mapping, Set

_BINOPS = {ast.Add: operator.add, ast.Sub: operator.sub,
           ast.Mult: operator.mul, ast.Div: operator.truediv}
_CMPOPS = {ast.Lt: operator.lt, ast.LtE: operator.le, ast.Gt: operator.gt,
           ast.GtE: operator.ge, ast.Eq: operator.eq, ast.NotEq: operator.ne}


def _date(node: ast.Call) -> int:
    if (not isinstance(node.func, ast.Name) or node.func.id != "date"
            or len(node.args) != 1 or node.keywords
            or not isinstance(node.args[0], ast.Constant)
            or not isinstance(node.args[0].value, str)):
        raise ValueError(f"only date('YYYY-MM-DD') may be called: "
                         f"{ast.unparse(node)}")
    day = datetime.date.fromisoformat(node.args[0].value)
    return (day - datetime.date(1970, 1, 1)).days


def parse(text: str) -> ast.expr:
    """The checked syntax tree of ``text``."""
    tree = ast.parse(text, mode="eval").body
    for node in ast.walk(tree):
        ok = isinstance(node, (ast.BinOp, ast.UnaryOp, ast.Compare, ast.Name,
                               ast.Constant, ast.Call, ast.Load,
                               ast.USub, *_BINOPS, *_CMPOPS))
        if isinstance(node, ast.BinOp):
            ok = type(node.op) in _BINOPS
        if isinstance(node, ast.UnaryOp):
            ok = isinstance(node.op, ast.USub)
        if isinstance(node, ast.Compare):
            ok = len(node.ops) == 1 and type(node.ops[0]) in _CMPOPS
        if isinstance(node, ast.Constant):
            ok = isinstance(node.value, int) or isinstance(node.value, str)
        if isinstance(node, ast.Call):
            _date(node)
        if not ok:
            raise ValueError(f"not allowed in a query expression: "
                             f"{ast.unparse(node)!r} in {text!r}")
    return tree


def names(text: str) -> Set[str]:
    """The column names ``text`` reads."""
    return {n.id for n in ast.walk(parse(text))
            if isinstance(n, ast.Name) and n.id != "date"}


def evaluate(text: str, env: Mapping[str, object],
             const: Callable[[int], object] = lambda v: v,
             ops: Mapping[str, Callable] = None):
    """Fold ``text`` over ``env``.  ``const`` wraps a constant; ``ops``
    may replace an operation by its name (``add sub mul truediv lt le gt
    ge eq ne neg``)."""
    ops = dict(ops or {})

    def op(name, default):
        return ops.get(name, default)

    def fold(node):
        if isinstance(node, ast.Name):
            if node.id not in env:
                raise KeyError(f"no column {node.id!r} for {text!r}")
            return env[node.id]
        if isinstance(node, ast.Constant):
            return const(node.value)
        if isinstance(node, ast.Call):
            return const(_date(node))
        if isinstance(node, ast.UnaryOp):
            return op("neg", operator.neg)(fold(node.operand))
        if isinstance(node, ast.BinOp):
            f = _BINOPS[type(node.op)]
            return op(f.__name__, f)(fold(node.left), fold(node.right))
        f = _CMPOPS[type(node.ops[0])]
        return op(f.__name__, f)(fold(node.left), fold(node.comparators[0]))

    return fold(parse(text))
