"""Mesh construction.

FUNCTIONS (not module-level constants) so importing this module never
touches jax device state.  The dry-run launcher sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import; smoke tests and benchmarks see the real devices.

Every mesh here has Auto axes: ``jax.make_mesh`` defaults to Explicit axes,
under which ``with_sharding_constraint`` rejects the partition specs the
sharding rules emit.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(axis_shapes, axis_names, *, devices=None):
    """``jax.make_mesh`` with Auto axes (optionally over given devices)."""
    return jax.make_mesh(axis_shapes, axis_names,
                         axis_types=(AxisType.Auto,) * len(axis_names),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh(model_axis: int = 1):
    """Tiny mesh over the actually-available devices (tests/examples)."""
    n = len(jax.devices())
    assert n % model_axis == 0
    return make_mesh((n // model_axis, model_axis), ("data", "model"))


def dp_axes(mesh) -> tuple[str, ...] | str:
    return ("pod", "data") if "pod" in mesh.axis_names else "data"
