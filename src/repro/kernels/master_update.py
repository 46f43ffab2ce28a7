"""Pallas TPU kernel: fused Eq. (3) master update (t > 1).

    P^t = Q_{k*} − (Σ_k w_k T_k) ⊙ (P^{t-1} − P^{t-2}),   w_k = p_k β_k, w_{k*}=0

Fuses the worker-axis reduction of int8 ternary codes with the history-step
multiply and the subtraction — one VMEM pass instead of materializing the
(N, M) float promotion and a separate elementwise chain in HBM.

Layout: M is viewed as (rows, 128); the grid tiles rows; the full worker
axis N (≤ 16 fed slices) rides along inside the tile: block (N, R, 128)
int8 = N·R·128 bytes — at N=16, R=256 that is 512 KiB, well inside VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
BLOCK_ROWS = 256


def _kernel(q_ref, t_ref, w_ref, p1_ref, p2_ref, out_ref):
    q = q_ref[...].astype(jnp.float32)            # (R, 128)
    coeff = jnp.zeros(q.shape, jnp.float32)       # Σ_k w_k T_k, w in SMEM
    for k in range(t_ref.shape[0]):
        coeff = coeff + w_ref[k] * t_ref[k].astype(jnp.float32)
    step = p1_ref[...].astype(jnp.float32) - p2_ref[...].astype(jnp.float32)
    out_ref[...] = (q - coeff * step).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret", "block_rows"))
def master_update_2d(q_pilot, tern, w, p1, p2, *, interpret: bool,
                     block_rows: int = BLOCK_ROWS):
    """q_pilot/p1/p2 (R, 128); tern (N, R, 128) int8; w (N,) fp32 (masked).

    R % block_rows == 0. Returns (R, 128) in q_pilot.dtype.
    """
    n, rows, _ = tern.shape
    grid = (rows // block_rows,)
    spec2d = pl.BlockSpec((block_rows, LANES), lambda i: (i, 0))
    spec3d = pl.BlockSpec((n, block_rows, LANES), lambda i: (0, i, 0))
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[spec2d, spec3d, pl.BlockSpec(memory_space=pltpu.SMEM),
                  spec2d, spec2d],
        out_specs=spec2d,
        out_shape=jax.ShapeDtypeStruct(q_pilot.shape, q_pilot.dtype),
        interpret=interpret,
    )(q_pilot, tern, w, p1, p2)
