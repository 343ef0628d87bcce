"""Production mesh construction (dry-run target: TPU v5e pods).

Defined as FUNCTIONS so importing this module never touches jax device
state (device count is locked at first jax init — see dryrun.py which
sets XLA_FLAGS before any import).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips when multi_pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_local_mesh():
    """Whatever this host offers (tests/examples: 1 CPU device)."""
    n = len(jax.devices())
    return jax.make_mesh((n, 1), ("data", "model"),
                         axis_types=(AxisType.Auto, AxisType.Auto))


def mesh_devices(mesh) -> int:
    return mesh.devices.size
