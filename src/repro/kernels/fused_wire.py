"""Fused Pallas TPU kernels for the FedPC flat wire path.

Two kernels cover the whole per-round plaintext wire cost over the
``FlatParams`` buffer (``repro.core.flat``):

``ternary_pack_stacked_2d`` — the worker uplink, all N workers in ONE
launch over a (N, R, 512) stack. Fuses Eq. (4)/(5) ternarization *directly*
into the §3.3 2-bit packed wire format: float history views in, uint8
(N, R, 128) packed codes out. No int8 code tensor — 4× the wire size —
ever reaches HBM: codes live only in VMEM registers. The round index ``t``
rides as a scalar operand, so a traced ``t`` selects the Eq. (4)/(5)
branch in-register and one compiled kernel serves every round; a single
worker's uplink is the same kernel at N = 1. The grid is **rows-major
with the worker axis minor**: for one row block the kernel steps through
consecutive worker blocks, so the shared ``p1``/``p2`` history blocks keep
the same block index across those steps and are fetched once per row
block instead of once per (worker, row) step. The Eq. (5) threshold may be
a per-worker ``(N,)`` beta vector (heterogeneous beta_k) read from SMEM at
the grid's worker index.

``packed_master_update_2d`` — master downlink side of Eq. (3). Consumes the
*packed* uint8 codes of all N workers on a 2-D ``(rows, workers)`` grid and
**accumulates** the weighted ternary sum into a revisited output block: the
output's block index ignores the (minor) worker axis, so it stays resident
in VMEM while the grid walks the workers, collecting Σ_k w_k T_k in place;
the final worker step folds in the Eq. (3) combine (q − coeff·mult). VMEM
per step is O(block) — independent of N — so federations scale past the
paper's 10 nodes without growing the tile. The 2-bit decode replicates
each byte over its four lanes (an MXU matmul, the inverse of the pack) and
shifts each lane's field out in-register — no (N, R, 128, 4) intermediate
— with the per-worker ``w[k]`` multiply folded straight into the decoded
field. Worker contributions are
accumulated strictly sequentially (k = 0..N−1), so the result is **bitwise
invariant across every (block_rows, block_workers) plan** — autotuning can
never change the math (``kernels.ref.packed_master_accum_ref`` is the
order-exact oracle). Both round branches of Eq. (3) (t == 1 uses
``alpha0``, t > 1 uses P^{t-1} − P^{t-2}) are computed from scalar operands
so the round index may be traced.

Layout: the flat (rows, 128) buffer is viewed as (rows/4, 512) so that the
four *consecutive* codes forming each wire byte sit in the last axis —
exactly the §3.3 / ``core.packing.pack2bit`` byte order. Gathering four
adjacent lanes into one byte is a lane compaction the TPU's vector unit
cannot express (no lane-splitting reshape, no lane-strided load), so the
pack runs on the MXU as a matmul against a 0/4^e selection matrix — exact,
since fields, weights and byte values (≤ 170) are all integers that bf16
holds exactly and the MXU accumulates in f32.

Scalars (``t``, thresholds) and per-worker vectors (beta_k, w_k) live in
SMEM and are read at the grid's worker index; blocks span whole row tiles,
so every block obeys the chip's (8, 128) tiling. The same kernels run in
the Pallas interpreter on the CPU (``tune.use_interpreter`` decides).

Block sizes: callers normally leave ``block_rows``/``block_workers`` to the
``repro.kernels.tune`` autotuner (via the ``ops`` wrappers); the module
defaults here are the TPU-shaped fallbacks.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
PACK = 4
BLOCK_ROWS = 64            # (64, 512) fp32 tile = 128 KiB per input
BLOCK_WORKERS = 1          # one worker per step → master VMEM is O(block)

# Whole-array SMEM operand: scalars and per-worker vectors, indexed in-kernel.
SMEM = pl.BlockSpec(memory_space=pltpu.SMEM)


def _codes_any(q, p1, p2, t, beta, alpha1):
    """Round-branch select on a (possibly traced) round index: Eq. (4) at
    t <= 1 (p1 slot holds P^0), Eq. (5) after. Both branches share the
    ``q - p1`` evolution and are in-register VPU ops, so evaluating both
    costs no HBM traffic."""
    delta = q - p1
    step = p1 - p2
    c5 = jnp.where(jnp.abs(delta) >= beta * jnp.abs(step),
                   jnp.sign(delta * step), 0.0)
    c4 = ((delta > alpha1).astype(jnp.float32)
          - (delta < -alpha1).astype(jnp.float32))
    return jnp.where(t <= 1.0, c4, c5)


def _pack_tile(codes):
    """(R, 512) float codes → (R, 128) uint8, 4 consecutive codes/byte.

    byte[j] = Σ_e 4^e·(code[4j+e] + 1): a (512, 128) selection matrix with
    4^e at row 4j+e of column j turns the lane compaction into one MXU
    matmul. Every operand is a small integer (exact in bf16) and the f32
    accumulation of at most 170 is exact, so this is bit-identical to
    shift-and-or packing on every backend.
    """
    r = jax.lax.broadcasted_iota(jnp.int32, (LANES * PACK, LANES), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (LANES * PACK, LANES), 1)
    sel = jnp.where(jax.lax.shift_right_logical(r, 2) == j,
                    jax.lax.shift_left(1, 2 * (r & 3)), 0)
    byte = jnp.dot((codes + 1.0).astype(jnp.bfloat16),
                   sel.astype(jnp.bfloat16),
                   preferred_element_type=jnp.float32)
    return byte.astype(jnp.int32).astype(jnp.uint8)


def _fields(b):
    """(R, 128) packed bytes → (R, 512) int32 biased 2-bit fields {0, 1, 2}.

    The inverse lane expansion also runs on the MXU: a 0/1 matrix
    replicates byte j over lanes 4j..4j+3 (exact — bytes ≤ 255 are bf16
    integers), then each lane shifts out its own field by its lane index.
    """
    j = jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES * PACK), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES * PACK), 1)
    rep_sel = (jax.lax.shift_right_logical(c, 2) == j).astype(jnp.bfloat16)
    rep = jnp.dot(b.astype(jnp.int32).astype(jnp.bfloat16), rep_sel,
                  preferred_element_type=jnp.float32).astype(jnp.int32)
    lane = jax.lax.broadcasted_iota(jnp.int32, rep.shape, 1)
    return jax.lax.shift_right_logical(rep, 2 * (lane & 3)) & 3


def _weighted_decode(b, w):
    """(R, 128) packed byte + scalar w → (R, 512) float32 ``w · code``,
    with the ``w`` multiply folded into the de-bias (``w·field − w`` =
    ``w·(field − 1)``) so the bare {-1, 0, 1} code tensor never
    materializes."""
    return _fields(b).astype(jnp.float32) * w - w


def _stacked_kernel(q_ref, p1_ref, p2_ref, beta_ref, scal_ref, out_ref):
    """One (block_workers, block_rows) step of the stacked uplink: each
    resident worker ternarizes against the shared history block with its
    own beta_k (SMEM, at the grid's worker index) and packs in-register."""
    bw = q_ref.shape[0]
    w0 = pl.program_id(1) * bw
    t, alpha1 = scal_ref[0], scal_ref[1]
    p1 = p1_ref[...].astype(jnp.float32)               # shared history block
    p2 = p2_ref[...].astype(jnp.float32)
    for j in range(bw):
        q = q_ref[j].astype(jnp.float32)
        out_ref[j] = _pack_tile(
            _codes_any(q, p1, p2, t, beta_ref[w0 + j], alpha1))


def _master_accum_kernel(q_ref, pk_ref, w_ref, p1_ref, p2_ref, scal_ref,
                         out_ref):
    """One (row block, worker block) step of the accumulating master.

    The output block is revisited across the (minor) worker axis: step
    k == 0 zeroes it, every step folds its workers' weighted codes in
    strictly ascending order, and the last worker step applies Eq. (3).
    """
    k = pl.program_id(1)
    bw = pk_ref.shape[0]

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros(out_ref.shape, out_ref.dtype)

    acc = out_ref[...].astype(jnp.float32)
    for j in range(bw):                                # sequential: bitwise
        acc = acc + _weighted_decode(pk_ref[j], w_ref[k * bw + j])
    out_ref[...] = acc.astype(out_ref.dtype)

    @pl.when(k == pl.num_programs(1) - 1)
    def _combine():
        t, alpha0 = scal_ref[0], scal_ref[1]
        step = (p1_ref[...].astype(jnp.float32)
                - p2_ref[...].astype(jnp.float32))
        mult = jnp.where(t <= 1.0, alpha0, step)       # Eq. (3) branches
        coeff = out_ref[...].astype(jnp.float32)
        q = q_ref[...].astype(jnp.float32)
        out_ref[...] = (q - coeff * mult).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret", "block_rows",
                                             "block_workers"))
def ternary_pack_stacked_2d(q, p1, p2, t, beta, alpha1, *, interpret: bool,
                            block_rows: int = BLOCK_ROWS,
                            block_workers: int = BLOCK_WORKERS):
    """Batched uplink: all N workers' wire buffers from ONE launch.

    q (N, R, 512) — every worker's history view; p1/p2 (R, 512) — the shared
    public history passed once, not stacked N times (P^0 in the p1 slot at
    t <= 1). Grid is (R/block_rows, N/block_workers) — **rows-major, worker
    minor**, so the history blocks keep their block index across the
    consecutive worker steps of one row block and are re-fetched per *row
    block*, not per (worker, row) step. Every plan packs
    bitwise-identically (the math is elementwise).

    ``t`` may be traced; ``beta`` is either one scalar (shared threshold) or
    a ``(N,)`` vector of per-worker beta_k. Returns (N, R, 128) uint8.
    """
    n, rows, _ = q.shape
    betas = jnp.broadcast_to(jnp.asarray(beta, jnp.float32).reshape(-1), (n,))
    scal = jnp.stack([jnp.asarray(t, jnp.float32),
                      jnp.asarray(alpha1, jnp.float32)])
    grid = (rows // block_rows, n // block_workers)
    q_spec = pl.BlockSpec((block_workers, block_rows, LANES * PACK),
                          lambda i, k: (k, i, 0))
    h_spec = pl.BlockSpec((block_rows, LANES * PACK), lambda i, k: (i, 0))
    out_spec = pl.BlockSpec((block_workers, block_rows, LANES),
                            lambda i, k: (k, i, 0))
    return pl.pallas_call(
        _stacked_kernel,
        grid=grid,
        in_specs=[q_spec, h_spec, h_spec, SMEM, SMEM],
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct((n, rows, LANES), jnp.uint8),
        interpret=interpret,
    )(q, p1, p2, betas, scal)


@functools.partial(jax.jit, static_argnames=("interpret", "block_rows",
                                             "block_workers"))
def packed_master_update_2d(q_pilot, packed, w, p1, p2, t, alpha0, *,
                            interpret: bool,
                            block_rows: int = BLOCK_ROWS,
                            block_workers: int = BLOCK_WORKERS):
    """Fused Eq. (3) over packed wire codes, grid-accumulated over workers.

    q_pilot/p1/p2 (R, 512) float; packed (N, R, 128) uint8 — every worker's
    §3.3 wire buffer, pilot row masked by ``w``; w (N,) masked p_k·beta_k at
    t > 1 / p_k at t == 1; ``t`` may be traced. Returns (R, 512) in
    q_pilot.dtype.

    The 2-D (rows, workers) grid iterates workers minor and the output
    block's index ignores the worker axis, so the Σ_k w_k T_k accumulator
    *is* the resident output block: VMEM per step is
    ``(3 float + 1 out) · block_rows·512·4B + block_workers·block_rows·128B``
    — independent of N at the default ``block_workers = 1``, which is what
    lets N = 64+ federations run without growing the tile. Workers
    accumulate strictly sequentially regardless of the (block_rows,
    block_workers) plan, so every plan is bitwise-identical to
    ``kernels.ref.packed_master_accum_ref``.
    """
    n, rows, _ = packed.shape
    scal = jnp.stack([jnp.asarray(t, jnp.float32),
                      jnp.asarray(alpha0, jnp.float32)])
    grid = (rows // block_rows, n // block_workers)
    spec_f = pl.BlockSpec((block_rows, LANES * PACK), lambda i, k: (i, 0))
    spec_pk = pl.BlockSpec((block_workers, block_rows, LANES),
                           lambda i, k: (k, i, 0))
    return pl.pallas_call(
        _master_accum_kernel,
        grid=grid,
        in_specs=[spec_f, spec_pk, SMEM, spec_f, spec_f, SMEM],
        out_specs=spec_f,
        out_shape=jax.ShapeDtypeStruct(q_pilot.shape, q_pilot.dtype),
        interpret=interpret,
    )(q_pilot, packed, w.astype(jnp.float32), p1, p2, scal)
