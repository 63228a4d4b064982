"""Mesh construction.

*Functions*, not module-level constants — importing this module never
touches jax device state (the dry-run forces 512 host devices before any
jax initialization; tests and benches must keep seeing 1 device).
"""

from __future__ import annotations

import jax


def make_mesh(axis_shapes, axis_names, **kwargs):
    """``jax.make_mesh`` with every axis ``Auto``, the sharding mode this
    codebase is written for (jax's own default is ``Explicit``)."""
    kwargs.setdefault("axis_types",
                      (jax.sharding.AxisType.Auto,) * len(axis_names))
    return jax.make_mesh(axis_shapes, axis_names, **kwargs)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; multi_pod prepends a 2-pod axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over whatever devices exist (tests / examples)."""
    n = len(jax.devices())
    data = min(data, n)
    model = min(model, max(1, n // data))
    return make_mesh((data, model), ("data", "model"))
