"""Mesh construction (assignment-mandated shapes).

``make_production_mesh`` is a FUNCTION — importing this module never touches
jax device state. Single pod: 16×16 = 256 chips ("data", "model");
multi-pod: 2×16×16 = 512 chips ("pod", "data", "model") — the "pod" axis is
the DCN dimension.
"""
from __future__ import annotations

import math

import jax


def _mk(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(
        jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mk(shape, axes)


def make_local_mesh(model_parallel: int = 1):
    """Mesh over whatever devices exist (CPU smoke runs, small slices)."""
    n = len(jax.devices())
    mp = math.gcd(model_parallel, n)
    return _mk((n // mp, mp), ("data", "model"))


# --- TPU v5e hardware constants (roofline, per assignment) -----------------
PEAK_FLOPS_BF16 = 197e12        # FLOP/s per chip
HBM_BW = 819e9                  # B/s per chip
ICI_BW = 50e9                   # B/s per link
