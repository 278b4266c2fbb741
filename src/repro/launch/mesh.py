"""Mesh construction (functions only — importing this module never touches
jax device state; jax locks the device count on first backend init).

Every mesh is built here with *Auto* axis types: the model code places
arrays through ``with_sharding_constraint`` and lets GSPMD propagate the
rest, which Explicit axes (``jax.make_mesh``'s default in jax 0.9)
reject at the first gather whose output sharding is not spelled out."""
from __future__ import annotations

import jax

from repro.distributed.sharding import MeshInfo


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """Assigned production meshes: 16x16 single pod (256 v5e chips) or
    2x16x16 multi-pod (512 chips).  The 'pod' axis is pure DP; its gradient
    all-reduce crosses the slow inter-pod links (see grad compression)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh_info(*, multi_pod: bool = False) -> MeshInfo:
    return MeshInfo(make_production_mesh(multi_pod=multi_pod))


def small_mesh_info(shape=(2, 2), axes=("data", "model")) -> MeshInfo:
    """Tiny mesh for CI-scale multi-device tests (run under
    --xla_force_host_platform_device_count)."""
    return MeshInfo(make_mesh(shape, axes))
