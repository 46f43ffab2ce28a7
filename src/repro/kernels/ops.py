"""Public jit'd wrappers over the Pallas kernels.

Handles: flat (or pytree) → padded (rows, 128) layout, interpret-mode
selection (``tune.use_interpreter``: the Pallas interpreter on the CPU
platform only, compiled Mosaic kernels everywhere else), block-plan selection
(callers that leave ``block_rows``/``block_workers`` unset get the
``repro.kernels.tune`` plan for their shape and backend), and un-padding.
These are drop-in replacements for the core/* reference functions and are
what the distributed sync uses when ``use_kernels=True``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import fused_wire as fw
from repro.kernels import masked_wire as mw
from repro.kernels import pack2bit as pk
from repro.kernels import master_update as mu
from repro.kernels import partial_sum as ps
from repro.kernels import ternary_encode as te
from repro.kernels import tune
from repro.telemetry import profile as tprof
from repro.utils import round_up

LANES = 128


def _to_2d(x: jax.Array, row_multiple: int, lane_multiple: int = LANES):
    """Flatten + zero-pad to (rows, lane_multiple), rows % row_multiple == 0."""
    flat = x.reshape(-1)
    n = flat.shape[0]
    per_row = lane_multiple
    rows = round_up(max(-(-n // per_row), 1), row_multiple)
    padded = rows * per_row
    flat = jnp.pad(flat, (0, padded - n))
    return flat.reshape(rows, per_row), n


# Canonical gcd-snapping lives in tune (one implementation; its docstring
# carries the alignment argument).
_block_rows_for = tune.fit_block_rows


def _stacked_plan(kind: str, rows: int, n: int, block_rows: int | None,
                  block_workers: int | None, interpret: bool) -> tuple[int,
                                                                       int]:
    """Resolve a worker-batched kernel's (block_rows, block_workers): any
    axis the caller left as None comes from the tuner table / heuristic;
    explicit requests are snapped to legal tilings (divisors)."""
    tuned_br, tuned_bw = tune.lookup(kind, rows, n, interpret=interpret)
    br = _block_rows_for(rows, block_rows or tuned_br)
    bw = tune.fit_block_workers(n, block_workers or tuned_bw)
    return br, bw


def ternary_encode(q, p1, p2, beta: float, interpret: bool | None = None):
    """Eq. (5) over an arbitrary-shape array; returns int8 of q.shape."""
    interpret = tune.use_interpreter(interpret)
    q2, n = _to_2d(q, 8)
    p12, _ = _to_2d(p1, 8)
    p22, _ = _to_2d(p2, 8)
    br = _block_rows_for(q2.shape[0], te.BLOCK_ROWS)
    out = te.ternary_encode_2d(q2, p12, p22, beta, interpret=interpret,
                               block_rows=br)
    return out.reshape(-1)[:n].reshape(q.shape)


def ternary_encode_round1(q, p0, alpha: float, interpret: bool | None = None):
    interpret = tune.use_interpreter(interpret)
    q2, n = _to_2d(q, 8)
    p02, _ = _to_2d(p0, 8)
    br = _block_rows_for(q2.shape[0], te.BLOCK_ROWS)
    out = te.ternary_encode_round1_2d(q2, p02, alpha, interpret=interpret,
                                      block_rows=br)
    return out.reshape(-1)[:n].reshape(q.shape)


def pack2bit(t, interpret: bool | None = None):
    """int8 codes any shape → uint8 (ceil(n/4),) flat packed buffer."""
    interpret = tune.use_interpreter(interpret)
    t2, n = _to_2d(t, 8, LANES * pk.PACK)
    br = _block_rows_for(t2.shape[0], pk.BLOCK_ROWS)
    out = pk.pack2bit_2d(t2, interpret=interpret, block_rows=br)
    n_bytes = -(-n // pk.PACK)
    return out.reshape(-1)[:n_bytes]


def unpack2bit(b, n: int, interpret: bool | None = None):
    """uint8 packed buffer → int8 (n,) codes."""
    interpret = tune.use_interpreter(interpret)
    b2, nb = _to_2d(b, 8, LANES)
    br = _block_rows_for(b2.shape[0], pk.BLOCK_ROWS)
    out = pk.unpack2bit_2d(b2, interpret=interpret, block_rows=br)
    return out.reshape(-1)[:n]


def _uplink_one(q4, p14, p24, t, beta, alpha1, *, interpret: bool,
                block_rows: int | None):
    """One worker's uplink over (R, 512) views: the stacked kernel at N=1,
    under the ``uplink`` plan kind."""
    r4 = q4.shape[0]
    br, _ = _stacked_plan("uplink", r4, 1, block_rows, 1, interpret)
    with tprof.kernel_scope("uplink", r4, 1, interpret):
        return fw.ternary_pack_stacked_2d(
            q4[None], p14, p24, t, beta, alpha1, interpret=interpret,
            block_rows=br, block_workers=1)[0]


def ternary_pack(q, p1, p2, beta: float, interpret: bool | None = None):
    """Fused Eq. (5) → §3.3 uplink over an arbitrary-shape array.

    Equals ``pack2bit(ternary_encode(q, p1, p2, beta))`` in one launch with
    no int8 intermediate. Returns uint8 (ceil(n/4),) packed wire bytes.
    """
    interpret = tune.use_interpreter(interpret)
    q2, n = _to_2d(q, 8, LANES * fw.PACK)
    p12, _ = _to_2d(p1, 8, LANES * fw.PACK)
    p22, _ = _to_2d(p2, 8, LANES * fw.PACK)
    out = _uplink_one(q2, p12, p22, 2, beta, 0.0, interpret=interpret,
                      block_rows=None)
    n_bytes = -(-n // fw.PACK)
    return out.reshape(-1)[:n_bytes]


def ternary_pack_round1(q, p0, alpha: float, interpret: bool | None = None):
    """Round-1 (Eq. (4)) variant of :func:`ternary_pack`."""
    interpret = tune.use_interpreter(interpret)
    q2, n = _to_2d(q, 8, LANES * fw.PACK)
    p02, _ = _to_2d(p0, 8, LANES * fw.PACK)
    out = _uplink_one(q2, p02, p02, 1, 0.0, alpha, interpret=interpret,
                      block_rows=None)
    n_bytes = -(-n // fw.PACK)
    return out.reshape(-1)[:n_bytes]


def flat_ternary_pack(buf_q, buf_p1, buf_p2, *, t: int, beta: float,
                      alpha1: float, interpret: bool | None = None,
                      block_rows: int | None = None):
    """Fused uplink over FlatParams buffers: (rows, 128) → (rows//4, 128).

    ``t`` is the 1-based round index: round 1 uses the Eq. (4) threshold
    ``alpha1`` against ``buf_p1`` (= P^0), later rounds Eq. (5) with
    ``beta`` against the (P^{t-1}, P^{t-2}) history. ``t`` and ``beta``
    may be traced (the branch is selected in-register), so this also
    serves jit'd round loops such as the distributed sync body — e.g. with
    this fed instance's own beta_k gathered from a per-worker vector.
    """
    interpret = tune.use_interpreter(interpret)
    r4 = buf_q.shape[0] // fw.PACK
    wide = LANES * fw.PACK
    return _uplink_one(buf_q.reshape(r4, wide), buf_p1.reshape(r4, wide),
                       buf_p2.reshape(r4, wide), t, beta, alpha1,
                       interpret=interpret, block_rows=block_rows)


def flat_ternary_pack_stacked(bufs_q, buf_p1, buf_p2, *, t, beta,
                              alpha1: float, interpret: bool | None = None,
                              block_rows: int | None = None,
                              block_workers: int | None = None):
    """Batched uplink: (N, rows, 128) worker buffers → (N, rows//4, 128)
    packed wire buffers in ONE kernel launch.

    The shared public history ``buf_p1``/``buf_p2`` is passed once, not
    stacked N times; the rows-major grid re-reads it once per row block,
    not once per worker. ``t`` may be traced (scalar-operand branch
    select); ``beta`` is a shared scalar or a per-worker ``(N,)`` vector of
    beta_k. ``block_rows``/``block_workers`` default to the tuned plan for
    (rows, N, backend) — see ``repro.kernels.tune``.
    """
    interpret = tune.use_interpreter(interpret)
    n, rows, _ = bufs_q.shape
    r4 = rows // fw.PACK
    wide = LANES * fw.PACK
    br, bw = _stacked_plan("uplink_stacked", r4, n, block_rows,
                           block_workers, interpret)
    with tprof.kernel_scope("uplink_stacked", r4, n, interpret):
        return fw.ternary_pack_stacked_2d(
            bufs_q.reshape(n, r4, wide), buf_p1.reshape(r4, wide),
            buf_p2.reshape(r4, wide), t, beta, alpha1,
            interpret=interpret, block_rows=br, block_workers=bw)


def flat_master_update(buf_q_pilot, packed_stacked, w, buf_p1, buf_p2, *,
                       t, alpha0: float, interpret: bool | None = None,
                       block_rows: int | None = None,
                       block_workers: int | None = None):
    """Fused Eq. (3) over the packed wire buffers of all N workers.

    buf_* (rows, 128) float; packed_stacked (N, rows//4, 128) uint8; w (N,)
    masked per-worker coefficients (pilot zeroed). ``t`` may be traced.
    Returns the new global buffer, (rows, 128) in buf_q_pilot.dtype.

    The kernel walks a (rows, workers) grid accumulating into the resident
    output block, so its VMEM is O(block) — independent of N — and the
    result is bitwise-identical under every (block_rows, block_workers)
    plan (strictly sequential worker accumulation; the oracle is
    ``ref.packed_master_accum_ref``). Block sizes default to the tuned
    plan for (rows, N, backend).
    """
    interpret = tune.use_interpreter(interpret)
    rows = buf_q_pilot.shape[0]
    n = packed_stacked.shape[0]
    r4 = rows // fw.PACK
    wide = LANES * fw.PACK
    br, bw = _stacked_plan("master", r4, n, block_rows, block_workers,
                           interpret)
    with tprof.kernel_scope("master", r4, n, interpret):
        out = fw.packed_master_update_2d(
            buf_q_pilot.reshape(r4, wide), packed_stacked,
            w.astype(jnp.float32), buf_p1.reshape(r4, wide),
            buf_p2.reshape(r4, wide), t, alpha0,
            interpret=interpret, block_rows=br, block_workers=bw)
    return out.reshape(rows, LANES)


def flat_ternary_pack_masked(bufs_q, buf_p1, buf_p2, *, t, beta,
                             alpha1: float, wq, pair_keys, pair_signs,
                             rr_keys, rr_threshold: int = 0,
                             word_bits: int = 32, use_masks: bool = True,
                             interpret: bool | None = None,
                             block_rows: int | None = None,
                             block_workers: int | None = None):
    """Masked (secure-agg) uplink over FlatParams buffers: (N, rows, 128)
    float -> (N, rows//4, 512) wire words (uint16 at ``word_bits=16``,
    else uint32) in ONE launch.

    ``wq`` (N,) uint32 fixed-point Eq. (3) weights; ``pair_keys`` (N, L)
    uint32 / ``pair_signs`` (N, L) int32 the per-pair counter keys and
    participation-folded signs (``privacy.masking.pair_stream_keys`` /
    ``pair_signs``); ``rr_keys`` (N,) uint32 per-worker RR keys;
    ``rr_threshold`` the STATIC uint16 flip threshold (0 = DP off);
    ``use_masks`` static (False = unmasked debug wire — no streams are
    generated at all). The mask/RR planes are generated INSIDE the kernel
    from these keys; no (N, rows, 512) tensor ever reaches HBM. ``t`` may
    be traced; ``beta`` a scalar or per-worker (N,) vector. Block plans
    resolve through the ``kernels.tune`` table (kind ``uplink_masked16`` /
    ``uplink_masked`` by modulus, chaining down to the ``uplink_stacked``
    plan when untuned) — every plan produces identical bits.
    """
    interpret = tune.use_interpreter(interpret)
    n, rows, _ = bufs_q.shape
    r4 = rows // fw.PACK
    wide = LANES * fw.PACK
    kind = "uplink_masked16" if word_bits == 16 else "uplink_masked"
    br, bw = _stacked_plan(kind, r4, n, block_rows, block_workers,
                           interpret)
    with tprof.kernel_scope(kind, r4, n, interpret):
        return mw.ternary_pack_masked_2d(
            bufs_q.reshape(n, r4, wide), buf_p1.reshape(r4, wide),
            buf_p2.reshape(r4, wide), t, beta, alpha1, wq, pair_keys,
            pair_signs, rr_keys, rr_threshold=int(rr_threshold),
            word_bits=word_bits, use_masks=use_masks, interpret=interpret,
            block_rows=br, block_workers=bw)


def flat_masked_master_update(buf_q_pilot, masked, sum_wq, buf_p1, buf_p2,
                              *, t, alpha0: float, scale_mult: float,
                              interpret: bool | None = None,
                              block_rows: int | None = None,
                              block_workers: int | None = None):
    """Sum-then-unmask Eq. (3) over the masked wire words.

    buf_* (rows, 128) float; masked (N, rows//4, 512) uint16 or uint32
    (the dtype picks the modulus); ``sum_wq`` the public scalar sum of the
    fixed-point weights; ``scale_mult`` the fixed-point descale with the
    RR unbias folded in. ``t`` may be traced. Returns the new global
    buffer, (rows, 128) in buf_q_pilot.dtype — bitwise invariant under
    every block plan (modular accumulation is order-free; the oracle is
    ``repro.privacy.ref.masked_master_ref``).
    """
    interpret = tune.use_interpreter(interpret)
    rows = buf_q_pilot.shape[0]
    n = masked.shape[0]
    r4 = rows // fw.PACK
    wide = LANES * fw.PACK
    kind = ("master_masked16" if masked.dtype == jnp.uint16
            else "master_masked")
    br, bw = _stacked_plan(kind, r4, n, block_rows, block_workers,
                           interpret)
    with tprof.kernel_scope(kind, r4, n, interpret):
        out = mw.masked_master_update_2d(
            buf_q_pilot.reshape(r4, wide), masked, sum_wq,
            buf_p1.reshape(r4, wide), buf_p2.reshape(r4, wide), t, alpha0,
            scale_mult, interpret=interpret, block_rows=br,
            block_workers=bw)
    return out.reshape(rows, LANES)


def flat_mask_repair(words, pair_keys, pair_coeff, *,
                     interpret: bool | None = None,
                     block_rows: int | None = None):
    """Dropout repair over one masked-word slab (kernel view): add
    ``sum_p coeff[p] * stream(keys[p])`` mod 2**modulus_bits to a
    (rows//4, 512) wire-word buffer in one launch.

    ``pair_keys``/``pair_coeff`` come from
    ``privacy.recovery.repair_coefficients`` — coefficients are nonzero
    only for dead-live pairs, and zero-coefficient streams are skipped
    in-kernel, so a fault-free round's repair is a near-no-op. Plans
    resolve under kind ``mask_repair16``/``mask_repair`` (by dtype) and
    chain down to the ``uplink`` row plan when untuned; every plan
    produces identical bits (modular addition is order-free).
    """
    interpret = tune.use_interpreter(interpret)
    r4 = words.shape[0]
    kind = "mask_repair16" if words.dtype == jnp.uint16 else "mask_repair"
    tuned_br, _ = tune.lookup(kind, r4, 1, interpret=interpret)
    br = _block_rows_for(r4, block_rows or tuned_br)
    with tprof.kernel_scope(kind, r4, 1, interpret):
        return mw.mask_repair_2d(words, pair_keys, pair_coeff,
                                 interpret=interpret, block_rows=br)


def flat_partial_sum(packed, wq, *, fanout: int, word_bits: int = 32,
                     interpret: bool | None = None,
                     block_rows: int | None = None,
                     block_groups: int | None = None):
    """Leaf-level tree sub-aggregate over the packed wire: (C, rows//4,
    128) uint8 children + (C,) fixed-point weights -> (ceil(C/fanout),
    rows//4, 512) word partials, one launch per level.

    The ragged last sibling group (C not a multiple of ``fanout``) is
    padded with zero bytes and zero weight — an exact identity (0·field ==
    0 mod 2**word_bits). Block plans resolve through the tune table under
    kind ``partial_sum`` keyed by (rows, fanout, backend); every plan
    produces identical bits (modular accumulation is order-free).
    """
    interpret = tune.use_interpreter(interpret)
    c, r4, _ = packed.shape
    g = -(-c // fanout)
    # Plans are keyed by fanout (the per-node working set); the group-axis
    # block is fitted to this level's width, not to fanout.
    tuned_br, tuned_bg = tune.lookup("partial_sum", r4, fanout,
                                     interpret=interpret)
    br = _block_rows_for(r4, block_rows or tuned_br)
    bg = tune.fit_block_workers(g, block_groups or tuned_bg)
    pad = g * fanout - c
    wq = jnp.asarray(wq, jnp.uint32)
    if pad:
        packed = jnp.pad(packed, ((0, pad), (0, 0), (0, 0)))
        wq = jnp.pad(wq, (0, pad))
    with tprof.kernel_scope("partial_sum", r4, fanout, interpret):
        return ps.partial_sum_2d(packed, wq, fanout=fanout,
                                 word_bits=word_bits, interpret=interpret,
                                 block_rows=br, block_groups=bg)


def flat_masked_partial_sum(words, keys, signs, *, fanout: int,
                            sibling: int, use_masks: bool = True,
                            interpret: bool | None = None,
                            block_rows: int | None = None,
                            block_groups: int | None = None):
    """Interior tree sub-aggregate over word partials: (C, rows//4, 512)
    children -> (ceil(C/fanout), rows//4, 512) parents in the same wire
    dtype, each parent's own sibling-scoped net mask added in-kernel from
    the level's (G, G) ``keys``/``signs`` matrices.

    Zero-word padding of the ragged last group is an exact identity.
    Plans resolve under kind ``partial_sum_masked16``/``partial_sum_masked``
    (by dtype) keyed by (rows, fanout, backend), chaining down to the
    plain ``partial_sum`` plan when untuned.
    """
    interpret = tune.use_interpreter(interpret)
    c, r4, _ = words.shape
    g = -(-c // fanout)
    kind = ("partial_sum_masked16" if words.dtype == jnp.uint16
            else "partial_sum_masked")
    tuned_br, tuned_bg = tune.lookup(kind, r4, fanout, interpret=interpret)
    br = _block_rows_for(r4, block_rows or tuned_br)
    bg = tune.fit_block_workers(g, block_groups or tuned_bg)
    pad = g * fanout - c
    if pad:
        words = jnp.pad(words, ((0, pad), (0, 0), (0, 0)))
    with tprof.kernel_scope(kind, r4, fanout, interpret):
        return ps.masked_partial_sum_2d(
            words, keys, signs, fanout=fanout, sibling=sibling,
            use_masks=use_masks, interpret=interpret, block_rows=br,
            block_groups=bg)


def master_update(q_pilot, tern_stacked, w, p1, p2,
                  interpret: bool | None = None):
    """Fused Eq. (3), t>1. tern_stacked (N, *shape) int8; w (N,) masked.

    Returns array of q_pilot.shape/dtype.
    """
    interpret = tune.use_interpreter(interpret)
    n_workers = tern_stacked.shape[0]
    q2, n = _to_2d(q_pilot, 8)
    p12, _ = _to_2d(p1, 8)
    p22, _ = _to_2d(p2, 8)
    rows = q2.shape[0]
    # Pad/reshape all N workers in ONE traced op (the worker axis rides
    # along), not a Python loop of N per-worker _to_2d + stack.
    flat = tern_stacked.reshape(n_workers, -1)
    t2 = jnp.pad(flat, ((0, 0), (0, rows * LANES - flat.shape[1]))
                 ).reshape(n_workers, rows, LANES)
    br = _block_rows_for(rows, mu.BLOCK_ROWS)
    out = mu.master_update_2d(q2, t2, w.astype(jnp.float32), p12, p22,
                              interpret=interpret, block_rows=br)
    return out.reshape(-1)[:n].reshape(q_pilot.shape)
