"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state; the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import and then calls this.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _make(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make(shape, axes)


def make_mesh(shape, axes):
    """Arbitrary mesh (tests, elastic re-meshing)."""
    return _make(tuple(shape), tuple(axes))


def describe(mesh) -> str:
    return " x ".join(f"{k}={v}" for k, v in mesh.shape.items()) + \
        f" ({mesh.devices.size} devices)"
