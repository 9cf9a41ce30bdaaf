"""Production mesh builders.

Functions, not module-level constants — importing this module never touches
jax device state (required: smoke tests must see 1 device; only
``dryrun.py`` forces 512 host devices).
"""
from __future__ import annotations

import jax


def _gspmd_mesh(shape, axes):
    """A mesh whose axes the partitioner resolves (``AxisType.Auto``):
    ``sharding.constrain`` places hints for GSPMD, which the explicit
    axes ``jax.make_mesh`` defaults to would turn into assertions."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """TPU v5e: 16×16 = 256 chips/pod; 2 pods = 512 chips via DCN."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _gspmd_mesh(shape, axes)


def make_local_mesh():
    """Single-device mesh for CPU smoke/integration runs."""
    return _gspmd_mesh((1, 1), ("data", "model"))


# Hardware constants (TPU v5e) for the roofline model — see EXPERIMENTS.md.
PEAK_FLOPS_BF16 = 197e12     # per chip
HBM_BW = 819e9               # bytes/s per chip
ICI_BW = 50e9                # bytes/s per link
