"""Fused sub-aggregate kernels for hierarchical (tree) aggregation.

An internal tree node folds its (at most ``fanout``) children into one
*partial* accumulator block on the INTEGER wire — fixed-point-weighted
fields summed mod 2**word_bits — WITHOUT de-biasing or descaling: the
subtraction of the public ΣW_k and the fixed-point descale happen exactly
once, at the root (``masked_master_update_2d``). Modular accumulation is
order-free, so any tree shape produces bitwise the flat master's result.

``partial_sum_2d`` — the leaf-level sub-aggregate over the PLAIN packed
wire: decodes each child's §3.3 2-bit codes in-register (the
``fused_wire`` register decode, minus the de-bias) to fields {0, 1, 2},
weights by the public fixed-point ``W_c``, and sums children per sibling
group mod 2**word_bits. One launch turns (C, R, 128) packed uint8 leaves
into (C/fanout, R, 512) word partials.

``masked_partial_sum_2d`` — the interior sub-aggregate over masked (or
plain integer) word partials: sums each sibling group's children mod
2**word_bits and adds the EMITTING node's own net pairwise mask,
regenerated in-register from the level's (G, G) counter-key matrix (the
``masked_wire`` stream idiom — shared tile hash, pair dedup whenever the
whole level is resident, 32-bit lanes truncated to the wire word on
store).
The children's masks — scoped to exactly this sibling group by
``masking.tree_pair_signs`` — cancel inside the group sum; the node's own
mask keeps the partial masked while it crosses the next tree edge, and
cancels one level up. Every tree edge therefore carries masked words;
nothing is unmasked below the root.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.fused_wire import SMEM, _fields
from repro.kernels.masked_wire import _net_words, _tile_hash, _to_wire
from repro.telemetry import profile as tprof

LANES = 128
PACK = 4
BLOCK_ROWS = 64
BLOCK_GROUPS = 1


def _weighted_fields(b, w):
    """One child's packed (br, 128) uint8 codes -> (br, 512) uint32
    fixed-point-weighted fields ``W_c * field`` (mod 2**32). The register
    2-bit decode of ``fused_wire._weighted_decode`` minus the de-bias:
    fields stay biased {0, 1, 2} so the ΣW_k subtraction can happen once,
    at the root."""
    return w * _fields(b).astype(jnp.uint32)


def _partial_sum_kernel(pk_ref, wq_ref, out_ref, *, fanout: int,
                        word_bits: int):
    """One (row block, group block) tile: each resident sibling group's
    packed children decode + weight + modular sum (32-bit lanes, truncated
    to the wire word on store). Children's weights come from SMEM at the
    grid's group index."""
    cb = pk_ref.shape[0]
    c0 = pl.program_id(1) * cb
    for k in range(cb // fanout):
        acc = None
        for j in range(fanout):
            c = k * fanout + j
            wf = _weighted_fields(pk_ref[c], wq_ref[c0 + c])
            acc = wf if acc is None else acc + wf
        out_ref[k] = _to_wire(acc, word_bits)


def _masked_partial_kernel(y_ref, keys_ref, signs_ref, out_ref, *,
                           fanout: int, word_bits: int, use_masks: bool,
                           sibling: int, g_total: int):
    """One tile of the interior sub-aggregate: sum each sibling group's
    children words mod 2**word_bits, then add each emitting node's net
    mask from the level's counter keys (``use_masks=False`` — the plain
    integer tree wire, or an all-dropped level — skips stream generation
    entirely)."""
    cb, br, wide = y_ref.shape
    bg = cb // fanout
    sums = []
    for k in range(bg):
        acc = y_ref[k * fanout].astype(jnp.uint32)
        for j in range(1, fanout):        # modular: order can't change bits
            acc = acc + y_ref[k * fanout + j].astype(jnp.uint32)
        sums.append(acc)
    if use_masks and g_total >= 2:
        base = (jnp.asarray(pl.program_id(0), jnp.uint32)
                * jnp.uint32(br * wide))
        nets = _net_words(keys_ref, signs_ref,
                          _tile_hash(base, br, wide, word_bits),
                          w0=pl.program_id(1) * bg, bw=bg, cohort=g_total,
                          word_bits=word_bits, sibling=sibling)
        sums = [s + jax.lax.bitcast_convert_type(m, jnp.uint32)
                for s, m in zip(sums, nets)]
    for k in range(bg):
        out_ref[k] = _to_wire(sums[k], word_bits)


@functools.partial(jax.jit, static_argnames=("fanout", "word_bits",
                                             "interpret", "block_rows",
                                             "block_groups"))
def partial_sum_2d(packed, wq, *, fanout: int, word_bits: int = 32,
                   interpret: bool, block_rows: int = BLOCK_ROWS,
                   block_groups: int = BLOCK_GROUPS):
    """Leaf-level sub-aggregate: (C, R, 128) packed uint8 + (C,) public
    fixed-point weights -> (C/fanout, R, 512) word partials, one launch.

    ``C`` must be a multiple of ``fanout`` (the ``ops`` wrapper pads the
    ragged last group with zero bytes and zero weight — an exact identity:
    0 * field == 0). Each output row g is ``Σ_{c in group g} W_c·field_c``
    mod 2**word_bits — no de-bias, no descale. Bitwise invariant under
    every (block_rows, block_groups) plan.
    """
    c, rows, _ = packed.shape
    if c % fanout:
        raise ValueError(f"children count {c} not a multiple of fanout "
                         f"{fanout} — pad before the kernel")
    g = c // fanout
    wide = LANES * PACK
    out_dtype = jnp.uint16 if word_bits == 16 else jnp.uint32
    pk_spec = pl.BlockSpec((block_groups * fanout, block_rows, LANES),
                           lambda i, k: (k, i, 0))
    out_spec = pl.BlockSpec((block_groups, block_rows, wide),
                            lambda i, k: (k, i, 0))
    with tprof.kernel_scope("partial_sum", rows, fanout, interpret):
        return pl.pallas_call(
            functools.partial(_partial_sum_kernel, fanout=fanout,
                              word_bits=word_bits),
            grid=(rows // block_rows, g // block_groups),
            in_specs=[pk_spec, SMEM],
            out_specs=out_spec,
            out_shape=jax.ShapeDtypeStruct((g, rows, wide), out_dtype),
            interpret=interpret,
        )(packed, jnp.asarray(wq, jnp.uint32).reshape(c))


@functools.partial(jax.jit, static_argnames=("fanout", "sibling",
                                             "use_masks", "interpret",
                                             "block_rows", "block_groups"))
def masked_partial_sum_2d(words, keys, signs, *, fanout: int, sibling: int,
                          use_masks: bool = True, interpret: bool,
                          block_rows: int = BLOCK_ROWS,
                          block_groups: int = BLOCK_GROUPS):
    """Interior sub-aggregate: (C, R, 512) child word partials -> (C/fanout,
    R, 512) parent partials in the same wire dtype (modulus from dtype).

    ``keys``/``signs`` are the (G, G) pair stream-key / scoped sign
    matrices of the EMITTING level's nodes (``masking.pair_stream_keys``
    at the level seed, ``masking.tree_pair_signs`` at ``sibling``): each
    output adds its node's net mask so the partial crossing the next tree
    edge stays masked; the children's own masks cancel inside the group
    sum. ``C`` must be a multiple of ``fanout`` (zero-word padding is an
    exact identity). ``sibling`` is the static sibling-group size of the
    emitting level (``fanout`` below the last level, the whole level at
    it). ``t`` dependence rides inside ``keys``. Bitwise invariant under
    every plan.
    """
    c, rows, wide = words.shape
    if c % fanout:
        raise ValueError(f"children count {c} not a multiple of fanout "
                         f"{fanout} — pad before the kernel")
    g = c // fanout
    word_bits = 16 if words.dtype == jnp.uint16 else 32
    kind = ("partial_sum_masked16" if word_bits == 16
            else "partial_sum_masked")
    y_spec = pl.BlockSpec((block_groups * fanout, block_rows, wide),
                          lambda i, k: (k, i, 0))
    out_spec = pl.BlockSpec((block_groups, block_rows, wide),
                            lambda i, k: (k, i, 0))
    with tprof.kernel_scope(kind, rows, fanout, interpret):
        return pl.pallas_call(
            functools.partial(_masked_partial_kernel, fanout=fanout,
                              word_bits=word_bits, use_masks=use_masks,
                              sibling=sibling, g_total=keys.shape[0]),
            grid=(rows // block_rows, g // block_groups),
            in_specs=[y_spec, SMEM, SMEM],
            out_specs=out_spec,
            out_shape=jax.ShapeDtypeStruct((g, rows, wide), words.dtype),
            interpret=interpret,
        )(words, jnp.asarray(keys, jnp.uint32).reshape(-1),
          jnp.asarray(signs, jnp.int32).reshape(-1))
