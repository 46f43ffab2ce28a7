"""Pallas TPU kernel: 2-bit packing/unpacking of ternary codes (§3.3).

The wire format behind Eq. (8)'s 16× upload reduction: four {-1,0,+1}
codes per byte. Pack reads an int8 (R, 512) tile and writes a uint8
(R, 128) tile — the output stays lane-aligned (128 lanes) so the packed
buffer feeds collectives directly. Unpack is the inverse.

Shifts are implemented as multiplies/divides by powers of two: VPU-safe,
and exact for the 2-bit fields.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128
PACK = 4
BLOCK_ROWS = 256


def _pack_kernel(t_ref, out_ref):
    t = t_ref[...]                                     # (R, 512) int8
    r = t.shape[0]
    codes = (t.astype(jnp.int32) + 1).reshape(r, LANES, PACK)
    byte = (codes[..., 0]
            + codes[..., 1] * 4
            + codes[..., 2] * 16
            + codes[..., 3] * 64)
    out_ref[...] = byte.astype(jnp.uint8)              # (R, 128)


def _unpack_kernel(b_ref, out_ref):
    b = b_ref[...].astype(jnp.int32)                   # (R, 128)
    r = b.shape[0]
    f0 = b % 4
    f1 = (b // 4) % 4
    f2 = (b // 16) % 4
    f3 = (b // 64) % 4
    codes = jnp.stack([f0, f1, f2, f3], axis=-1)       # (R, 128, 4)
    out_ref[...] = (codes - 1).astype(jnp.int8).reshape(r, LANES * PACK)


@functools.partial(jax.jit, static_argnames=("interpret", "block_rows"))
def pack2bit_2d(t, *, interpret: bool, block_rows: int = BLOCK_ROWS):
    """t int8 (R, 512), R % block_rows == 0 → uint8 (R, 128).

    Group layout matches ref.pack2bit_ref: four consecutive codes → 1 byte.
    """
    rows = t.shape[0]
    grid = (rows // block_rows,)
    return pl.pallas_call(
        _pack_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((block_rows, LANES * PACK), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((block_rows, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, LANES), jnp.uint8),
        interpret=interpret,
    )(t)


@functools.partial(jax.jit, static_argnames=("interpret", "block_rows"))
def unpack2bit_2d(b, *, interpret: bool, block_rows: int = BLOCK_ROWS):
    """b uint8 (R, 128) → int8 (R, 512)."""
    rows = b.shape[0]
    grid = (rows // block_rows,)
    return pl.pallas_call(
        _unpack_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((block_rows, LANES), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((block_rows, LANES * PACK), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, LANES * PACK), jnp.int8),
        interpret=interpret,
    )(b)
