"""Production mesh builders (assignment MULTI-POD DRY-RUN step 1).

A FUNCTION, not a module-level constant: importing this module never
touches jax device state.  Target: TPU v5e pods — 16x16 = 256 chips per
pod, 2 pods = 512 chips multi-pod.
"""
from __future__ import annotations

import jax


def _make_mesh(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_test_mesh(data: int = 2, model: int = 2):
    """Small mesh for CPU tests (requires >= data*model host devices)."""
    return _make_mesh((data, model), ("data", "model"))
