"""Production mesh construction.

Kept as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state — the dry-run must set
XLA_FLAGS=--xla_force_host_platform_device_count BEFORE any jax init.

Production target: TPU v5e, 16x16 = 256 chips per pod; multi-pod = 2 pods
(512 chips) with the "pod" axis joining the FSDP/data dimension (DCN-ish
outer axis in a real deployment; here just the outer mesh axis).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

from repro.sharding.rules import MeshAxes


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def mesh_axes(*, multi_pod: bool = False) -> MeshAxes:
    if multi_pod:
        return MeshAxes(fsdp=("pod", "data"), model="model",
                        fsdp_size=32, model_size=16)
    return MeshAxes(fsdp=("data",), model="model", fsdp_size=16, model_size=16)


def make_smoke_mesh(data: int = 2, model: int = 2):
    """Tiny mesh for CPU multi-device tests (requires forced host devices)."""
    return jax.make_mesh(
        (data, model), ("data", "model"), axis_types=(AxisType.Auto,) * 2
    )
