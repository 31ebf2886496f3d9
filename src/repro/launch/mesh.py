"""Production meshes.

Single pod: (data=16, model=16) — 256 chips (TPU v5e pod).
Multi-pod:  (pod=2, data=16, model=16) — 512 chips; the ``pod`` axis
joins batch data-parallelism (DP hierarchy: inter-pod DCN-ish axis
outermost, so its collectives are the rarest/most overlappable).

Functions, not module constants — importing this module never touches
jax device state."""

from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "make_smoke_mesh", "mesh_chips"]


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_smoke_mesh(data: int = 2, model: int = 4):
    """Small mesh for CPU distributed tests (needs
    xla_force_host_platform_device_count ≥ data·model)."""
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


def mesh_chips(mesh) -> int:
    return int(mesh.devices.size)
