"""Production mesh construction (deliverable (e), MULTI-POD DRY-RUN §1).

A FUNCTION, not a module-level constant: importing this module never
touches jax device state.
"""
from __future__ import annotations

import jax


def make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis Auto (sharding propagated by the
    compiler, not spelled out per op)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_test_mesh(shape=(1, 1), axes=("data", "model")):
    """Tiny mesh for CPU smoke tests (1 device)."""
    return make_mesh(shape, axes)
