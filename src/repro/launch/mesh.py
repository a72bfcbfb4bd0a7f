"""Production meshes.

Axis roles (DESIGN.md §5): 'pod' = across pods (DP), 'data' = DP within
a pod AND the expert-parallel axis, 'model' = TP AND the sequence-
parallel axis.  Defined as functions so importing this module never
touches jax device state (the dry-run sets the fake-device count before
any jax initialization).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes):
    # Auto axes: the model places arrays with sharding constraints and
    # leaves the rest to the partitioner (jax.make_mesh now defaults to
    # Explicit axes, under which such programs are refused)
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_test_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh for CPU multi-device unit tests (requires the caller to
    have set XLA_FLAGS=--xla_force_host_platform_device_count>=prod)."""
    return _mesh(shape, axes)
