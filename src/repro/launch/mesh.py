"""Production mesh construction.

A FUNCTION, not a module-level constant: importing this module never touches
jax device state (the dry-run sets XLA_FLAGS before any jax import; tests
and benchmarks keep the single real CPU device).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _make_mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; multi_pod adds the 2-pod outer axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_mesh_for(devices: int, model_parallel: int = 1, pods: int = 1):
    """Small-scale helper for tests/examples (e.g. 8 fake devices)."""
    data = devices // (model_parallel * pods)
    assert data * model_parallel * pods == devices
    if pods > 1:
        return _make_mesh((pods, data, model_parallel),
                          ("pod", "data", "model"))
    return _make_mesh((data, model_parallel), ("data", "model"))
