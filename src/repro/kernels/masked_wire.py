"""Fused Pallas kernels for the secure-aggregation (masked) wire path.

Two kernels mirror the plaintext pair of ``fused_wire`` and keep the round
at exactly two launches when privacy is on:

``ternary_pack_masked_2d`` — the masked uplink. Fuses Eq. (4)/(5)
ternarization -> bias to fields {0, 1, 2} -> 3-ary randomized response
(local DP, threshold 0 = off) -> fixed-point weighting by the public
per-worker ``W_k`` -> pairwise-mask addition, all in-register: float
history views in, uint16/uint32 masked words out. The plaintext code NEVER
exists outside VMEM registers — what reaches HBM (and then the wire) is
already masked.

The pairwise mask and RR streams are generated INSIDE the kernel from the
counter PRNG of ``repro.privacy.masking``: the launch consumes only the
tiny per-pair key matrix ``(N, L)``, the antisymmetric sign matrix (with
participation folded in) and the ``(N,)`` RR key vector — all in SMEM —
never an ``(N, rows, 512)`` mask tensor. Each tile hashes its absolute
element counters once (``mix32(e)``, or ``mix32(e >> 1)`` at the 16-bit
modulus, where one 32-bit stream word feeds two adjacent lanes) and reuses
that hash across every pair stream of the tile (only the ``+ key``
finalizer differs per stream). At 16 bits each lane takes the low or high
half of its stream word by its parity — a lane-local shift, since the
chip's vector unit has neither 16-bit arithmetic nor lane interleaving.
All arithmetic runs in 32-bit lanes; words are truncated to the modulus
only when stored. Whenever the whole cohort is resident, each unordered
pair's stream is evaluated ONCE and ±accumulated into both endpoints —
n(n-1)/2 stream expansions instead of n^2.

``masked_master_update_2d`` — the sum-then-unmask master. Walks the same
2-D (rows, workers) grid as ``packed_master_update_2d``, accumulating the
masked words into a uint32 VMEM scratch accumulator that persists across
the (minor) worker axis. Because the accumulation is modular (mod 2**32,
whose low 16 bits are the mod-2**16 sum), the pairwise masks cancel EXACTLY
once all workers are folded — the master never observes an individual
worker's ternary directions, only the sum — and the result is bitwise
invariant under every block plan *and* every reduction order. The last
worker step de-biases in the integer domain (subtract the public
``sum_k W_k``), reinterprets the residue as the wire-width SIGNED int
(exact by the ``sum w_k <= 1`` + fixpoint-headroom bound), descales by the
fixed-point multiplier (with the RR unbias folded in), and applies the
Eq. (3) combine.

``mask_repair_2d`` — dropout repair: regenerates the uncancelled pair
streams of post-uplink deaths and folds them into one masked-word slab.

Wire cost: one word per parameter — 8x the 2-bit plaintext wire at the
16-bit modulus (16x at 32). The overhead is benchmarked per modulus in
``benchmarks/kernels_bench.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.fused_wire import SMEM, _codes_any
from repro.privacy import masking as pvm
from repro.telemetry import profile as tprof
from repro.privacy.dp import rr_fields

LANES = 128
PACK = 4
BLOCK_ROWS = 64
BLOCK_WORKERS = 1


def _tile_hash(base_u32, rows: int, width: int, word_bits: int):
    """The shared counter hash of one (rows, width) tile whose first
    element sits at absolute flat index ``base_u32`` (tiles always span
    full rows, so the flat index is ``base + r*width + c``): ``mix32(e)``
    per element at 32 bits, ``mix32(e >> 1)`` at 16 — adjacent lane pairs
    share one stream word (``base`` and ``width`` are even)."""
    r = jax.lax.broadcasted_iota(jnp.uint32, (rows, width), 0)
    c = jax.lax.broadcasted_iota(jnp.uint32, (rows, width), 1)
    e = base_u32 + r * jnp.uint32(width) + c
    if word_bits == 16:
        e = jax.lax.shift_right_logical(e, jnp.uint32(1))
    return pvm.mix32(e)


def _stream(key, hashed, word_bits: int):
    """One stream over a tile as int32 values for the ± net accumulation:
    the word's bit pattern at 32 bits, its low (even lane) or high (odd
    lane) 16-bit half at 16 — mod-2**word_bits congruent either way."""
    u = pvm.mask_stream(key, hashed)
    if word_bits == 16:
        lane = jax.lax.broadcasted_iota(jnp.uint32, hashed.shape, 1)
        half = (lane & jnp.uint32(1)) * jnp.uint32(16)
        u = jax.lax.shift_right_logical(u, half) & jnp.uint32(0xFFFF)
    return jax.lax.bitcast_convert_type(u, jnp.int32)


def _to_wire(acc, word_bits: int):
    """A 32-bit accumulator tile -> the wire word (truncate mod 2**16)."""
    if word_bits == 16:
        return (jax.lax.bitcast_convert_type(acc, jnp.uint32)
                & jnp.uint32(0xFFFF)).astype(jnp.uint16)
    return jax.lax.bitcast_convert_type(acc, jnp.uint32)


def _net_words(keys_ref, signs_ref, hashed, *, w0, bw: int, cohort: int,
               word_bits: int, sibling: int | None = None):
    """Net pairwise mask of each of the ``bw`` resident nodes ``w0 + j``
    over one tile, as int32 accumulators. ``keys_ref``/``signs_ref`` are
    the row-major flattened (cohort, cohort) key and sign matrices in
    SMEM. When the whole cohort is resident, each unordered pair expands
    once and ±folds into both endpoints (pairs in different ``sibling``
    groups are structurally sign-zero and skipped statically); otherwise
    each node folds its row of the key matrix (self/inactive pairs
    sign-zeroed — ``w0`` is traced, so they cannot be pruned)."""
    zeros = functools.partial(jnp.zeros, hashed.shape, jnp.int32)
    if bw == cohort:
        nets = [zeros() for _ in range(bw)]
        for i in range(bw):
            for j in range(i + 1, bw):
                if sibling is not None and i // sibling != j // sibling:
                    continue
                p = i * cohort + j
                sv = signs_ref[p] * _stream(keys_ref[p], hashed, word_bits)
                nets[i] = nets[i] + sv
                nets[j] = nets[j] - sv
        return nets
    nets = []
    for j in range(bw):
        row = (w0 + j) * cohort
        acc = zeros()
        for l in range(cohort):
            acc = acc + signs_ref[row + l] * _stream(keys_ref[row + l],
                                                     hashed, word_bits)
        nets.append(acc)
    return nets


def _masked_pack_kernel(q_ref, p1_ref, p2_ref, beta_ref, wq_ref, keys_ref,
                        signs_ref, rrk_ref, scal_ref, out_ref, *,
                        cohort: int, word_bits: int, use_masks: bool,
                        rr_threshold: int):
    """Masked-uplink tile: ternarize -> RR -> weight -> in-register mask
    streams -> truncate to the wire word. ``cohort`` is the total worker
    count L of the key matrix (== N in the simulator's stacked call, the
    fed size in the distributed N=1 slab call)."""
    bw, br, wide = q_ref.shape
    base = (jnp.asarray(pl.program_id(0), jnp.uint32)
            * jnp.uint32(br * wide))
    w0 = pl.program_id(1) * bw
    t, alpha1 = scal_ref[0], scal_ref[1]
    p1 = p1_ref[...].astype(jnp.float32)
    p2 = p2_ref[...].astype(jnp.float32)
    nets = None
    if use_masks:
        nets = _net_words(keys_ref, signs_ref,
                          _tile_hash(base, br, wide, word_bits), w0=w0,
                          bw=bw, cohort=cohort, word_bits=word_bits)
    h_rr = _tile_hash(base, br, wide, 32) if rr_threshold else None
    for j in range(bw):
        w = w0 + j
        code = _codes_any(q_ref[j].astype(jnp.float32), p1, p2, t,
                          beta_ref[w], alpha1)
        field = (code + 1.0).astype(jnp.int32).astype(jnp.uint32)
        if rr_threshold:                               # RR: full words
            field = rr_fields(field, pvm.mask_stream(rrk_ref[w], h_rr),
                              jnp.uint32(rr_threshold))
        acc = wq_ref[w] * field                        # mod 2**32
        if use_masks:
            acc = acc + jax.lax.bitcast_convert_type(nets[j], jnp.uint32)
        out_ref[j] = _to_wire(acc, word_bits)


def _masked_master_kernel(q_ref, y_ref, p1_ref, p2_ref, scal_ref, sumw_ref,
                          out_ref, acc_ref, *, word_bits: int):
    """One (row block, worker block) step of the sum-then-unmask master.

    ``acc_ref`` is a uint32 VMEM scratch tile that persists across the
    (minor) worker axis: step k == 0 zeroes it, every step folds its
    workers mod 2**32, the last step unmasks — integer de-bias, signed
    reinterpretation at the wire width, fixed-point descale — and writes
    the Eq. (3) combine into ``out_ref``.
    """
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros(acc_ref.shape, acc_ref.dtype)

    acc = acc_ref[...]
    for j in range(y_ref.shape[0]):       # modular: order can't change bits
        acc = acc + y_ref[j].astype(jnp.uint32)
    acc_ref[...] = acc

    @pl.when(k == pl.num_programs(1) - 1)
    def _combine():
        t, alpha0, smult = scal_ref[0], scal_ref[1], scal_ref[2]
        ci = jax.lax.bitcast_convert_type(acc_ref[...] - sumw_ref[0],
                                          jnp.int32)
        if word_bits == 16:               # sign-extend the low 16 bits
            ci = jax.lax.shift_right_arithmetic(
                jax.lax.shift_left(ci, jnp.int32(16)), jnp.int32(16))
        coeff = ci.astype(jnp.float32) * smult
        step = (p1_ref[...].astype(jnp.float32)
                - p2_ref[...].astype(jnp.float32))
        mult = jnp.where(t <= 1.0, alpha0, step)
        q = q_ref[...].astype(jnp.float32)
        out_ref[...] = (q - coeff * mult).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("rr_threshold", "word_bits",
                                             "use_masks", "interpret",
                                             "block_rows", "block_workers"))
def ternary_pack_masked_2d(q, p1, p2, t, beta, alpha1, wq, pair_keys,
                           pair_signs, rr_keys, *, rr_threshold: int = 0,
                           word_bits: int = 32, use_masks: bool = True,
                           interpret: bool,
                           block_rows: int = BLOCK_ROWS,
                           block_workers: int = BLOCK_WORKERS):
    """Masked uplink: all N workers' secure-agg wire words from ONE launch.

    q (N, R, 512) float history views; p1/p2 (R, 512) shared public
    history; ``beta`` a scalar or (N,) per-worker Eq. (5) threshold; wq
    (N,) uint32 fixed-point Eq. (3) weights (public); ``pair_keys``
    (N, L) uint32 pair stream keys (``masking.pair_stream_keys`` rows —
    L = cohort size, == N here or the fed size for a 1-row slab call);
    ``pair_signs`` (N, L) int32 antisymmetric signs with participation
    folded in; ``rr_keys`` (N,) uint32 per-worker RR stream keys;
    ``rr_threshold`` the STATIC uint16 flip threshold (0 = DP off — the
    RR stream is never generated); ``use_masks`` static (False skips mask
    generation entirely — the unmasked debug wire). ``t`` may be traced.
    Returns (N, R, 512) in the wire dtype (uint16 at ``word_bits=16``,
    else uint32) — already masked when it first touches HBM.
    """
    n, rows, wide = q.shape
    cohort = pair_keys.shape[1]
    out_dtype = jnp.uint16 if word_bits == 16 else jnp.uint32
    kind = "uplink_masked16" if word_bits == 16 else "uplink_masked"
    betas = jnp.broadcast_to(jnp.asarray(beta, jnp.float32).reshape(-1), (n,))
    scal = jnp.stack([jnp.asarray(t, jnp.float32),
                      jnp.asarray(alpha1, jnp.float32)])
    keys = jnp.asarray(pair_keys, jnp.uint32).reshape(-1)
    signs = jnp.asarray(pair_signs, jnp.int32).reshape(-1)
    q_spec = pl.BlockSpec((block_workers, block_rows, wide),
                          lambda i, k: (k, i, 0))
    h_spec = pl.BlockSpec((block_rows, wide), lambda i, k: (i, 0))
    with tprof.kernel_scope(kind, rows, n, interpret):
        return pl.pallas_call(
            functools.partial(_masked_pack_kernel, cohort=cohort,
                              word_bits=word_bits, use_masks=use_masks,
                              rr_threshold=rr_threshold),
            grid=(rows // block_rows, n // block_workers),
            in_specs=[q_spec, h_spec, h_spec] + [SMEM] * 6,
            out_specs=q_spec,
            out_shape=jax.ShapeDtypeStruct((n, rows, wide), out_dtype),
            interpret=interpret,
        )(q, p1, p2, betas, jnp.asarray(wq, jnp.uint32).reshape(n), keys,
          signs, jnp.asarray(rr_keys, jnp.uint32).reshape(n), scal)


@functools.partial(jax.jit, static_argnames=("interpret", "block_rows",
                                             "block_workers"))
def masked_master_update_2d(q_pilot, masked, sum_wq, p1, p2, t, alpha0,
                            scale_mult, *, interpret: bool,
                            block_rows: int = BLOCK_ROWS,
                            block_workers: int = BLOCK_WORKERS):
    """Sum-then-unmask Eq. (3) over masked wire words.

    q_pilot/p1/p2 (R, 512) float; masked (N, R, 512) uint16 or uint32 (the
    wire dtype picks the modulus); ``sum_wq`` the public scalar
    ``sum_k W_k`` (uint32 — only its residue mod the modulus matters);
    ``scale_mult`` the fixed-point descale with the RR unbias folded in;
    ``t`` may be traced. Returns (R, 512) in q_pilot.dtype. Bitwise
    invariant under every (block_rows, block_workers) plan — modular
    accumulation is order-free.
    """
    n, rows, wide = masked.shape
    word_bits = 16 if masked.dtype == jnp.uint16 else 32
    scal = jnp.stack([jnp.asarray(t, jnp.float32),
                      jnp.asarray(alpha0, jnp.float32),
                      jnp.asarray(scale_mult, jnp.float32)])
    sumw = jnp.asarray(sum_wq, jnp.uint32).reshape(1)
    kind = "master_masked16" if word_bits == 16 else "master_masked"
    spec_f = pl.BlockSpec((block_rows, wide), lambda i, k: (i, 0))
    spec_y = pl.BlockSpec((block_workers, block_rows, wide),
                          lambda i, k: (k, i, 0))
    with tprof.kernel_scope(kind, rows, n, interpret):
        return pl.pallas_call(
            functools.partial(_masked_master_kernel, word_bits=word_bits),
            grid=(rows // block_rows, n // block_workers),
            in_specs=[spec_f, spec_y, spec_f, spec_f, SMEM, SMEM],
            out_specs=spec_f,
            out_shape=jax.ShapeDtypeStruct(q_pilot.shape, q_pilot.dtype),
            scratch_shapes=[pltpu.VMEM((block_rows, wide), jnp.uint32)],
            interpret=interpret,
        )(q_pilot, masked, p1, p2, scal, sumw)


def _mask_repair_kernel(y_ref, keys_ref, coeff_ref, out_ref, *,
                        n_pairs: int, word_bits: int):
    """Dropout-repair tile: fold ``coeff[p] * stream(keys[p])`` for every
    repair pair into a (rows, wide) slab of masked wire words, mod
    2**word_bits. The accumulation STARTS from the slab's own words, so the
    repaired output is one pass — no separate residue tensor.
    Zero-coefficient pairs (the common case: coeff is nonzero only for
    dead-live pairs) skip their stream expansion via ``lax.cond``."""
    br, wide = y_ref.shape
    base = (jnp.asarray(pl.program_id(0), jnp.uint32)
            * jnp.uint32(br * wide))
    h = _tile_hash(base, br, wide, word_bits)
    acc0 = jax.lax.bitcast_convert_type(y_ref[...].astype(jnp.uint32),
                                        jnp.int32)

    def fold(p, acc):
        c = coeff_ref[p]
        return jax.lax.cond(
            c == 0, lambda a: a,
            lambda a: a + c * _stream(keys_ref[p], h, word_bits), acc)

    out_ref[...] = _to_wire(jax.lax.fori_loop(0, n_pairs, fold, acc0),
                            word_bits)


@functools.partial(jax.jit, static_argnames=("interpret", "block_rows"))
def mask_repair_2d(y, pair_keys, pair_coeff, *, interpret: bool,
                   block_rows: int = BLOCK_ROWS):
    """Repair a masked-word slab after post-uplink deaths, in one launch.

    ``y`` (R, 512) wire words (uint16/uint32 picks the modulus);
    ``pair_keys`` (P,) uint32 stream keys and ``pair_coeff`` (P,) int32
    coefficients from ``privacy.recovery.repair_coefficients`` — the term
    ``sum_p coeff[p] * stream(keys[p])`` is added mod 2**modulus_bits.
    The stream geometry (flat element index ``r * 512 + c``, halved at the
    16-bit modulus) is exactly the uplink kernel's, so a dead worker's
    regenerated words are bitwise the ones it committed. Bitwise invariant
    under ``block_rows`` (modular addition; each tile hashes its own
    absolute counter range).
    """
    rows, wide = y.shape
    n_pairs = int(pair_keys.shape[0])
    if n_pairs == 0:
        return y
    word_bits = 16 if y.dtype == jnp.uint16 else 32
    kind = "mask_repair16" if word_bits == 16 else "mask_repair"
    spec = pl.BlockSpec((block_rows, wide), lambda i: (i, 0))
    with tprof.kernel_scope(kind, rows, 1, interpret):
        return pl.pallas_call(
            functools.partial(_mask_repair_kernel, n_pairs=n_pairs,
                              word_bits=word_bits),
            grid=(rows // block_rows,),
            in_specs=[spec, SMEM, SMEM],
            out_specs=spec,
            out_shape=jax.ShapeDtypeStruct(y.shape, y.dtype),
            interpret=interpret,
        )(y, jnp.asarray(pair_keys, jnp.uint32),
          jnp.asarray(pair_coeff, jnp.int32))
