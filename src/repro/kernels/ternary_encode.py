"""Pallas TPU kernel: Eq. (5) ternarization of parameter evolution.

This op runs over *every model parameter every round* — the per-round
compute hot-spot of the FedPC protocol (everything else in a round is the
local training itself). On TPU it is a pure VPU elementwise pass; the win
over the unfused jnp version is fusing threshold + sign + compare into one
VMEM-resident pass (4 HBM reads + 1 write per element → exactly 3 reads +
1 int8 write, no intermediates).

Layout: flat parameter vectors are viewed as (rows, 128) — lane-aligned —
and tiled (BLOCK_ROWS, 128) per grid step, 8-sublane aligned.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
BLOCK_ROWS = 256          # (256, 128) fp32 tile = 128 KiB / input → fits VMEM


def _kernel(q_ref, p1_ref, p2_ref, beta_ref, out_ref):
    q = q_ref[...].astype(jnp.float32)
    p1 = p1_ref[...].astype(jnp.float32)
    p2 = p2_ref[...].astype(jnp.float32)
    beta = beta_ref[0]
    step = p1 - p2
    delta = q - p1
    significant = jnp.abs(delta) >= beta * jnp.abs(step)
    out_ref[...] = jnp.where(
        significant, jnp.sign(delta * step), 0.0).astype(jnp.int8)


def _kernel_round1(q_ref, p0_ref, alpha_ref, out_ref):
    d = q_ref[...].astype(jnp.float32) - p0_ref[...].astype(jnp.float32)
    alpha = alpha_ref[0]
    out_ref[...] = ((d > alpha).astype(jnp.int8)
                    - (d < -alpha).astype(jnp.int8))


@functools.partial(jax.jit, static_argnames=("interpret", "block_rows"))
def ternary_encode_2d(q, p1, p2, beta, *, interpret: bool,
                      block_rows: int = BLOCK_ROWS):
    """q/p1/p2 (R, 128) with R % block_rows == 0 → int8 (R, 128)."""
    rows = q.shape[0]
    grid = (rows // block_rows,)
    spec = pl.BlockSpec((block_rows, LANES), lambda i: (i, 0))
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[spec, spec, spec,
                  pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, jnp.int8),
        interpret=interpret,
    )(q, p1, p2, jnp.asarray([beta], jnp.float32))


@functools.partial(jax.jit, static_argnames=("interpret", "block_rows"))
def ternary_encode_round1_2d(q, p0, alpha, *, interpret: bool,
                             block_rows: int = BLOCK_ROWS):
    rows = q.shape[0]
    grid = (rows // block_rows,)
    spec = pl.BlockSpec((block_rows, LANES), lambda i: (i, 0))
    return pl.pallas_call(
        _kernel_round1,
        grid=grid,
        in_specs=[spec, spec, pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, jnp.int8),
        interpret=interpret,
    )(q, p0, jnp.asarray([alpha], jnp.float32))
