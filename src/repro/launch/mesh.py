"""Production mesh construction.

A FUNCTION, not a module constant: importing this module never touches JAX
device state (the dry-run sets XLA_FLAGS before any jax import; smoke
tests keep the single real device).

Axes are ``Auto``: the model code places activations with
``with_sharding_constraint``, which ``jax.make_mesh``'s default
``Explicit`` axes turn into an assertion instead of a placement.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips/pod ("data", "model"); 2 pods adds a "pod" axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_debug_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh for subprocess tests with a few fake host devices."""
    return _auto_mesh(shape, axes)


# TPU v5e single-chip peak numbers used by the roofline analysis.
PEAK_FLOPS_BF16 = 197e12        # FLOP/s
HBM_BW = 819e9                  # B/s
ICI_BW = 50e9                   # B/s per link
