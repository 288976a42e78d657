"""Production meshes.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state — the dry-run must set
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before jax init,
and smoke tests must keep seeing 1 device.
"""
from __future__ import annotations

from typing import Tuple

import jax


def make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis in Auto (GSPMD-propagated) mode."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def mesh_axes(mesh) -> Tuple[Tuple[str, ...], str]:
    """(data-parallel axes, tensor-parallel axis) for a production mesh."""
    names = mesh.axis_names
    if "pod" in names:
        return ("pod", "data"), "model"
    return ("data",), "model"


def make_test_mesh(shape=(2, 4), axes=("data", "model")):
    """Small mesh for unit tests (run under a host-device-count subprocess)."""
    return make_mesh(shape, axes)
