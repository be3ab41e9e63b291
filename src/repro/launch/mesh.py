"""Production mesh definitions (TPU v5e pods).

``make_production_mesh`` is a FUNCTION (not a module-level constant) so that
importing this module never touches jax device state — the dry-run must set
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` *before* first jax
initialization (see launch/dryrun.py), and smoke tests must keep seeing one
device.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "HW"]


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = 1
    for s in shape:
        n *= s
    devices = jax.devices()[:n]       # single-pod uses 256 of the 512 hosts
    import numpy as np
    return jax.sharding.Mesh(
        np.asarray(devices).reshape(shape), axes,
        axis_types=(AxisType.Auto,) * len(axes))


class HW:
    """TPU v5e hardware constants for the roofline (per chip)."""

    PEAK_BF16_FLOPS = 197e12       # FLOP/s
    HBM_BW = 819e9                 # bytes/s
    ICI_BW = 50e9                  # bytes/s per link
    HBM_BYTES = 16e9               # capacity
