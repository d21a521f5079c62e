"""Production mesh builders. Functions, not module constants — importing this
module never touches jax device state (the dry-run must set XLA_FLAGS first)."""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes):
    # Auto axes: the logical-rule sharding constraints (parallel/sharding.py)
    # may only name Auto axes, and make_mesh defaults to Explicit
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips with a leading 'pod' axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_host_mesh():
    """Single-device mesh for CPU smoke tests (1x1, same axis names)."""
    return _mesh((1, 1), ("data", "model"))


def axis_size(mesh, name: str) -> int:
    return mesh.shape[name] if name in mesh.shape else 1
