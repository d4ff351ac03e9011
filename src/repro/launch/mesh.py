"""Production mesh construction (defined as functions, never module-level
constants, so importing this module never touches jax device state)."""
from __future__ import annotations

import math

import jax
import numpy as np
from jax.sharding import AxisType, Mesh


def _mesh(devices, shape, axes) -> Mesh:
    return Mesh(np.asarray(devices).reshape(shape), axes,
                axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (16, 16) = 256 chips, axes (data, model).
    Multi-pod: (2, 16, 16) = 512 chips, axes (pod, data, model)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    ndev = math.prod(shape)
    devices = jax.devices()[:ndev]
    if len(devices) < ndev:
        raise RuntimeError(
            f"need {ndev} devices for the production mesh, have {len(devices)}; "
            "the dry-run driver must set XLA_FLAGS=--xla_force_host_platform_device_count=512 "
            "before any jax import")
    return _mesh(devices, shape, axes)


def make_test_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh over however many host devices tests forced."""
    return _mesh(jax.devices()[:math.prod(shape)], shape, axes)


def data_axes(mesh) -> tuple:
    """The pure-DP axes of a mesh (pod+data)."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def all_axes(mesh) -> tuple:
    return tuple(mesh.axis_names)
