"""Production mesh definitions (TPU v5e target).

Defined as FUNCTIONS so importing this module never touches jax device
state (jax locks the device count on first backend init; the dry-run sets
XLA_FLAGS before any import).
"""
from __future__ import annotations

import jax

# Hardware constants used by the roofline model (TPU v5e)
PEAK_FLOPS_BF16 = 197e12  # per chip
HBM_BW = 819e9  # bytes/s per chip
ICI_BW = 50e9  # bytes/s per link

SINGLE_POD_SHAPE = (16, 16)  # 256 chips
MULTI_POD_SHAPE = (2, 16, 16)  # 2 pods x 256 chips


def _mk(shape, axes):
    auto = (jax.sharding.AxisType.Auto,) * len(axes)
    return jax.make_mesh(shape, axes, axis_types=auto)


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = MULTI_POD_SHAPE if multi_pod else SINGLE_POD_SHAPE
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mk(shape, axes)


def make_debug_mesh(data: int = 2, model: int = 2, pod: int | None = None) -> jax.sharding.Mesh:
    """Small mesh for CPU multi-device tests (XLA_FLAGS host device count)."""
    if pod is None:
        return _mk((data, model), ("data", "model"))
    return _mk((pod, data, model), ("pod", "data", "model"))


def data_axes(mesh: jax.sharding.Mesh) -> tuple[str, ...]:
    """Axes that shard the batch: ('pod','data') on multi-pod else ('data',)."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def has_pod_axis(mesh: jax.sharding.Mesh) -> bool:
    return "pod" in mesh.axis_names
