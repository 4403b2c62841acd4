"""Production mesh construction (assignment MULTI-POD DRY-RUN §1).

A FUNCTION, not a module-level constant: importing this module never
touches jax device state (device count is locked at first jax init, and
only dryrun.py sets the 512-device host-platform override).
"""

from __future__ import annotations

import jax

__all__ = ["make_production_mesh", "make_mesh_by_name", "HW"]


def _auto_mesh(shape, axes):
    """``jax.make_mesh`` with ``Auto`` axes: JAX 0.9 defaults to
    ``Explicit`` axes, which ``with_sharding_constraint`` in
    ``launch/steps.py`` refuses."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_mesh_by_name(name: str):
    if name == "single":
        return make_production_mesh(multi_pod=False)
    if name == "multi":
        return make_production_mesh(multi_pod=True)
    if name == "host":  # whatever this process actually has (tests)
        n = len(jax.devices())
        return _auto_mesh((1, n), ("data", "model"))
    raise ValueError(name)


class HW:
    """TPU v5e per-chip roofline constants (assignment §ROOFLINE)."""

    PEAK_BF16_FLOPS = 197e12       # FLOP/s
    PEAK_INT8_OPS = 394e12         # int8 MXU ~2x bf16
    HBM_BW = 819e9                 # bytes/s
    ICI_BW = 50e9                  # bytes/s per link
    HBM_BYTES = 16 * 2**30
