"""Production mesh definitions (TPU v5e).

single-pod: (data=16, model=16)  = 256 chips
multi-pod:  (pod=2, data=16, model=16) = 512 chips

A FUNCTION, not a module-level constant: importing this module never touches
jax device state (the dry-run sets XLA_FLAGS before first jax init).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _make_mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh():
    """1-device mesh for smoke tests / examples on CPU."""
    return _make_mesh((1, 1), ("data", "model"))


def make_client_mesh(data: int):
    """data×1 mesh over the first ``data`` devices: clients shard over
    ``data`` (the chips of a four-chip host, or N forced CPU devices under
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N``)."""
    return _make_mesh((data, 1), ("data", "model"))


# TPU v5e hardware constants for the roofline (per chip / per link)
PEAK_FLOPS_BF16 = 197e12        # FLOP/s
HBM_BW = 819e9                  # bytes/s
ICI_LINK_BW = 50e9              # bytes/s per link
