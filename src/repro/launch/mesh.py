"""Production meshes. TPU v5e: 16x16 = 256 chips per pod; 2 pods = 512.

``make_production_mesh`` is a FUNCTION (never a module constant) so importing
this module touches no jax device state — required because the dry-run pins
the host-device count before first jax init.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(model_axis: int = 1):
    """(data, model) mesh over every local device: the chips of one
    TPU host, or the CPU backend's (possibly fake) devices."""
    n = jax.device_count()
    assert n % model_axis == 0
    return jax.make_mesh((n // model_axis, model_axis), ("data", "model"),
                         axis_types=(AxisType.Auto, AxisType.Auto))


# Hardware constants (TPU v5e), used by the roofline analysis.
PEAK_FLOPS_BF16 = 197e12       # per chip
HBM_BW = 819e9                 # bytes/s per chip
ICI_BW = 50e9                  # bytes/s per link
