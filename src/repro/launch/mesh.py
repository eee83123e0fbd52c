"""Production meshes (TPU v5e pods; placeholder host devices for dry-runs).

A FUNCTION, not a module constant — importing this module never touches jax
device state (the dry-run must set XLA_FLAGS before the first jax init).
"""
from __future__ import annotations

import jax

# TPU v5e hardware constants (per chip) — used by the roofline
PEAK_FLOPS_BF16 = 197e12      # FLOP/s
HBM_BW = 819e9                # B/s
ICI_BW = 50e9                 # B/s per link


def _make_mesh(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh(data: int = 1):
    """Whatever this host has (1 device on CPU) as (data, model=n/data)."""
    n = len(jax.devices())
    if data < 1 or n % data:
        raise ValueError(f"data={data} must divide the {n} devices")
    return _make_mesh((data, n // data), ("data", "model"))


def mesh_devices(mesh) -> int:
    import numpy as np
    return int(np.prod([mesh.shape[a] for a in mesh.axis_names]))
