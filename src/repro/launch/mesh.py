"""Production mesh definitions.

A FUNCTION, not a module-level constant — importing this module never
touches jax device state (the dry-run sets XLA_FLAGS before any jax call;
smoke tests see the single CPU device).
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; multi_pod adds the 2-pod outer axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def axis_sizes(mesh) -> dict:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def dp_axes(mesh) -> tuple:
    """The pure-data-parallel axes: ('pod','data') or ('data',)."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def all_axes(mesh) -> tuple:
    return tuple(mesh.axis_names)
