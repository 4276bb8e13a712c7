"""Production mesh builders.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state; the dry-run sets XLA_FLAGS before any jax import.

Every axis is Auto: the step builders pin shardings with
``with_sharding_constraint``, which only Auto axes accept.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with every axis Auto (its default is Explicit)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(model: int = 1):
    """Tiny mesh over whatever devices exist (tests / examples)."""
    n = len(jax.devices())
    data = n // model
    if data * model != n:
        data, model = n, 1
    return make_mesh((data, model), ("data", "model"))


# TPU v5e hardware constants used by the roofline analysis
PEAK_FLOPS_BF16 = 197e12        # per chip
HBM_BW = 819e9                  # bytes/s per chip
ICI_BW = 50e9                   # bytes/s per link
