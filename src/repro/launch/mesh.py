"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (never a module-level constant) so
importing this module never touches jax device state. The dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import; everything else sees the real device count.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def _mk(shape, axes) -> Mesh:
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """16x16 (256 chips / pod); multi_pod adds a leading 2-pod axis (512)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mk(shape, axes)


def make_host_mesh(*, model_parallel: int = 1) -> Mesh:
    """Mesh over whatever devices this process actually has (tests/examples)."""
    n = jax.device_count()
    assert n % model_parallel == 0, (n, model_parallel)
    return _mk((n // model_parallel, model_parallel), ("data", "model"))


def mesh_chip_count(mesh: Mesh) -> int:
    return int(mesh.devices.size)
