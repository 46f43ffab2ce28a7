"""Production mesh definitions (TPU v5e numbers).

Defined as FUNCTIONS so importing this module never touches jax device
state — the dry-run sets XLA_FLAGS before any jax initialization.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

# Hardware constants used by the roofline analysis (TPU v5e).
PEAK_FLOPS_BF16 = 197e12        # FLOP/s per chip
HBM_BW = 819e9                  # bytes/s per chip
ICI_BW = 50e9                   # bytes/s per link

SINGLE_POD = (16, 16)           # 256 chips
MULTI_POD = (2, 16, 16)         # 2 pods × 256 chips


def make_mesh(shape, axes, devices=None):
    """The repo's one mesh constructor, over ``devices`` (default: all of
    ``jax.devices()``). Every axis is ``Auto``: the fed sync's
    ``with_sharding_constraint`` calls are layout hints the partitioner
    must honour (``jax.make_mesh`` now defaults to ``Explicit`` axes, under
    which such a constraint is an assert and the sync cannot run)."""
    axes = tuple(axes)
    return jax.make_mesh(tuple(shape), axes,
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = MULTI_POD if multi_pod else SINGLE_POD
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_debug_mesh(n_data: int = 4, n_model: int = 2):
    """Small mesh for tests (requires >= n_data*n_model host devices)."""
    return make_mesh((n_data, n_model), ("data", "model"))


def chips(mesh) -> int:
    n = 1
    for s in mesh.devices.shape:
        n *= s
    return n
