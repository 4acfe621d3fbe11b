"""Production mesh construction (required deliverable (e), step 1).

Defined as FUNCTIONS so importing this module never touches jax device
state; the dry-run sets XLA_FLAGS before any jax import.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (data=16, model=16) = 256 chips. Multi-pod: x2 pods."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_test_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh for CPU multi-device tests (subprocess sets device count)."""
    return _mesh(shape, axes)


def batch_axes_of(mesh) -> tuple[str, ...]:
    names = tuple(mesh.axis_names)
    return tuple(a for a in names if a != "model")
