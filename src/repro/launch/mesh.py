"""Production mesh construction.

Single pod:  (16, 16)      axes ('data', 'model')   — 256 chips (v5e pod)
Multi pod:   (2, 16, 16)   axes ('pod', 'data', 'model') — 512 chips

A FUNCTION, not a module constant, so importing never touches jax device
state (smoke tests must keep seeing 1 device).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    # the model code places activations with sharding hints (GSPMD), so
    # every axis is Auto; jax.make_mesh defaults to Explicit axes, under
    # which a gather from the vocab-sharded embedding is refused
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_local_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many (possibly forced-host) devices exist —
    used by distributed correctness tests."""
    return _auto_mesh((data, model), ("data", "model"))


# TPU v5e hardware constants (roofline denominators)
PEAK_FLOPS_BF16 = 197e12        # per chip
HBM_BW = 819e9                  # bytes/s per chip
ICI_BW = 50e9                   # bytes/s per link (~per chip, one direction)
HBM_BYTES = 16 * 2 ** 30        # 16 GiB per chip
