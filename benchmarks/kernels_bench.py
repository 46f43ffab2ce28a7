"""Kernel micro-bench: latency of the FedPC round ops (interpret mode on
CPU — correctness-weighted; TPU timings come from real hardware) and the
equivalent jnp reference, plus fused-vs-unfused flat wire path timings
emitted to BENCH_kernels.json so the perf trajectory is tracked across PRs.

NOTE on CPU numbers: interpret mode executes one Python step per grid tile,
so wall time measures launch overhead, not HBM traffic — the fused win there
shows up as HALF the grid steps (one kernel instead of two) rather than
bandwidth. The no-int8-intermediate property is asserted structurally in
tests/test_flat_wire.py via jaxpr inspection.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit
from repro.core import flat as fl
from repro.core import protocol as proto
from repro.core.tree import TreeSpec
from repro.fed import rounds as rd
from repro.kernels import fused_wire as fw
from repro.kernels import ops, ref, tune
from repro.kernels import pack2bit as pk
from repro.kernels import ternary_encode as te
from repro.utils import HOST_SYNC_PRIMITIVES, jaxpr_primitive_counts

M = 1 << 20            # 1M params
N_WORKERS = 8
BENCH_JSON = os.path.join(os.path.dirname(__file__), "..",
                          "BENCH_kernels.json")
BENCH_SMOKE_JSON = os.path.join(os.path.dirname(__file__), "..",
                                "BENCH_kernels_smoke.json")


def _bench(fn, *args, reps=3):
    """Best-of-reps wall time (us). Min, not mean: on a shared machine the
    distribution is one-sided (interference only adds time), so the minimum
    is the noise-robust estimator of true cost — applied uniformly to both
    sides of every comparison."""
    fn(*args)  # compile/warm
    best = float("inf")
    for _ in range(max(reps, 2)):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best * 1e6


def _wire_inputs(m: int, key=0):
    k = jax.random.PRNGKey(key)
    q = jax.random.normal(k, (m,))
    p1 = jax.random.normal(jax.random.fold_in(k, 1), (m,))
    p2 = jax.random.normal(jax.random.fold_in(k, 2), (m,))
    return q, p1, p2


def _fused_vs_unfused(m: int, reps: int) -> dict:
    """Flat wire path at m params: old two-kernel uplink vs ternary_pack,
    old loop-and-stack master vs packed_master_update.

    Block sizes come from the ``kernels.tune`` plan for this (shape,
    backend) — on cpu-interpret that is the fewest-step plan (every grid
    step pays the interpreter's full block machinery), on TPU the
    VMEM-sized tiles. Nothing is hand-pinned per size any more.
    """
    q, p1, p2 = _wire_inputs(m)
    rows = m // 128
    r4 = rows // 4
    br4 = tune.lookup("uplink", r4, interpret=True)[0]
    br = br4 * 4
    q2, p12, p22 = (x.reshape(rows, 128) for x in (q, p1, p2))
    q4, p14, p24 = (x.reshape(r4, 512) for x in (q, p1, p2))

    def unfused():
        codes = te.ternary_encode_2d(q2, p12, p22, 0.2, interpret=True,
                                     block_rows=br)
        return pk.pack2bit_2d(codes.reshape(r4, 512), interpret=True,
                              block_rows=br4)

    def fused():
        return fw.ternary_pack_stacked_2d(
            q4[None], p14, p24, 2, 0.2, 0.0, interpret=True,
            block_rows=br4, block_workers=1)[0]

    np.testing.assert_array_equal(np.asarray(unfused()), np.asarray(fused()))
    up_unfused = _bench(unfused, reps=reps)
    up_fused = _bench(fused, reps=reps)

    # master side: N workers' wire buffers
    tern = jax.random.randint(jax.random.PRNGKey(9), (N_WORKERS, m),
                              -1, 2).astype(jnp.int8)
    w = jnp.full((N_WORKERS,), 0.02)
    packed = jnp.stack([ops.pack2bit(tern[k], interpret=True)
                        for k in range(N_WORKERS)]).reshape(
                            N_WORKERS, r4, 128)

    def master_unfused():
        # the old path: stacked-pad + int8 promotion inside master_update_2d
        return ops.master_update(q, tern, w, p1, p2, interpret=True)

    def master_fused():
        return ops.flat_master_update(q2, packed, w, p12, p22, t=3,
                                      alpha0=0.01, interpret=True)

    got = np.asarray(master_fused()).reshape(-1)
    want = np.asarray(master_unfused())
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    ms_unfused = _bench(master_unfused, reps=reps)
    ms_fused = _bench(master_fused, reps=reps)

    return {
        "params": m,
        "uplink_unfused_us": up_unfused,
        "uplink_fused_us": up_fused,
        "uplink_speedup": up_unfused / up_fused,
        "uplink_launches": {"unfused": 2, "fused": 1},
        "uplink_block_rows": br4,
        "master_unfused_us": ms_unfused,
        "master_fused_us": ms_fused,
        "master_speedup": ms_unfused / ms_fused,
        "n_workers": N_WORKERS,
        "mode": "cpu-interpret",
    }


def _batched_uplink(m: int, n_workers: int, reps: int,
                    autotune: bool = True) -> dict:
    """Simulator uplink at m params × N workers: a per-worker loop of N
    fused traced-t launches (both real drivers trace the round index, so
    this is the launch the loop alternative would actually dispatch) vs ONE
    stacked launch at its autotuned (block_rows, block_workers) plan.

    The stacked win on cpu-interpret comes from touching every operand
    once (the interpreter pays per-step block machinery ∝ operand bytes,
    and the loop re-reads the shared history N times); on TPU the same
    rows-major plan turns that into one history fetch per row block. All
    plans pack bitwise-identically."""
    rows = m // 128
    r4 = rows // 4
    k = jax.random.PRNGKey(11)
    bufs_q = jax.random.normal(k, (n_workers, rows, 128))
    p1 = jax.random.normal(jax.random.fold_in(k, 1), (rows, 128))
    p2 = jax.random.normal(jax.random.fold_in(k, 2), (rows, 128))
    if autotune:
        tune.autotune_stacked(r4, n_workers, interpret=True, reps=1)
    plan = tune.lookup("uplink_stacked", r4, n_workers, interpret=True)

    def loop():
        return jnp.stack([ops.flat_ternary_pack(
            bufs_q[i], p1, p2, t=3, beta=0.2, alpha1=0.01,
            interpret=True) for i in range(n_workers)])

    def stacked():
        return ops.flat_ternary_pack_stacked(
            bufs_q, p1, p2, t=3, beta=0.2, alpha1=0.01, interpret=True)

    np.testing.assert_array_equal(np.asarray(loop()).reshape(n_workers, r4,
                                                             128),
                                  np.asarray(stacked()))
    us_loop = _bench(loop, reps=reps)
    us_stacked = _bench(stacked, reps=reps)
    return {
        "params": m,
        "n_workers": n_workers,
        "uplink_loop_us": us_loop,
        "uplink_stacked_us": us_stacked,
        "stacked_speedup": us_loop / us_stacked,
        "launches": {"loop": n_workers, "stacked": 1},
        "plan": {"block_rows": plan[0], "block_workers": plan[1]},
        "mode": "cpu-interpret",
    }


def _worker_scaling(m: int, n_list: tuple, reps: int) -> list:
    """Federation-size sweep: tuned stacked-uplink + accumulating-master
    latency at N workers, with the §3.3 wire payload and the master
    kernel's per-tile VMEM model (new: O(block), constant in N; old
    pre-accumulation kernel: linear in N — the term that capped federation
    size)."""
    rows = m // 128
    r4 = rows // 4
    out = []
    for n in n_list:
        k = jax.random.PRNGKey(n)
        bufs_q = jax.random.normal(k, (n, rows, 128))
        p1 = jax.random.normal(jax.random.fold_in(k, 1), (rows, 128))
        p2 = jax.random.normal(jax.random.fold_in(k, 2), (rows, 128))
        w = jnp.full((n,), 1.0 / max(n - 1, 1)).at[0].set(0.0)
        tune.autotune_stacked(r4, n, interpret=True, reps=1)
        tune.autotune_master(r4, n, interpret=True, reps=1)

        def uplink():
            return ops.flat_ternary_pack_stacked(
                bufs_q, p1, p2, t=3, beta=0.2, alpha1=0.01, interpret=True)

        packed = uplink()

        def master():
            return ops.flat_master_update(
                bufs_q[0], packed, w, p1, p2, t=3, alpha0=0.01,
                interpret=True)

        us_up = _bench(uplink, reps=reps)
        us_ms = _bench(master, reps=reps)
        # VMEM model at the compiled-backend (TPU) plan: the accumulating
        # master's tile is independent of N; the old kernel blocked the
        # full worker axis, so its tile grew linearly with N.
        tpu_plan = tune.default_plan("master", r4, n, "tpu")
        vmem_new = tune.master_vmem_tile_bytes(tpu_plan["block_rows"],
                                               tpu_plan["block_workers"])
        vmem_old = tune.master_vmem_tile_bytes_preaccum(
            tpu_plan["block_rows"], n)
        out.append({
            "params": m,
            "n_workers": n,
            "uplink_stacked_us": us_up,
            "master_us": us_ms,
            "wire_bytes_per_round": n * r4 * 128,   # uint8 uplink payload
            "master_vmem_tile_bytes": vmem_new,     # constant in N
            "master_vmem_tile_bytes_preaccum": vmem_old,  # linear in N
            "mode": "cpu-interpret",
        })
    return out


def _tree_scaling(m: int, n_list: tuple, fanout: int, reps: int) -> list:
    """Cohort-scale sweep of hierarchical fan-in aggregation: a full plain
    round through the tree (packed leaves → fixed-point level partials →
    root sum-and-descale, ``n_levels + 2`` launches) vs the flat two-launch
    round, at each N.

    The tree rides the integer wire, so its result is invariant to tree
    shape — the parity assert against the flat float master is bounded only
    by Eq. (3) weight quantization at ``TREE_PLAIN_FIXPOINT_BITS``. The
    structural claims are asserted on the jaxpr before timing: launch count
    grows with DEPTH (log_fanout N), not N, and zero host syncs.

    Byte columns come from the analytic Eq. (8) models at all three wires
    (plaintext 2-bit, masked-16, masked-32): the link INTO the root carries
    ``w_L <= fanout`` partials instead of the flat master's N-1 uplinks, and
    the root's grid/VMEM tile is O(fanout), not O(N)."""
    rows = m // 128
    r4 = rows // 4
    ts = TreeSpec(fanout=fanout)
    # one timed sweep fills the partial_sum plan for (r4, fanout) — the
    # table is keyed by fanout, not level width, so every level shares it
    tune.autotune_partial_sum(r4, fanout, fanout * fanout, interpret=True,
                              reps=1)
    out = []
    for n in n_list:
        levels = ts.n_levels(n)
        widths = ts.level_widths(n)
        k = jax.random.PRNGKey(100 + n)
        bufs_q = jax.random.normal(k, (n, rows, 128))
        p1 = jax.random.normal(jax.random.fold_in(k, 1), (rows, 128))
        p2 = jax.random.normal(jax.random.fold_in(k, 2), (rows, 128))
        w = jnp.full((n,), 1.0 / max(n - 1, 1)).at[0].set(0.0)
        if n <= 64:
            # at larger N the cpu-interpret default (single step) is already
            # the plan the sweep would pick; skip the expensive timing
            tune.autotune_stacked(r4, n, interpret=True, reps=1)
            tune.autotune_master(r4, n, interpret=True, reps=1)
        wire_flat = rd.WirePath(rd.WireConfig(), interpret=True)
        wire_tree = rd.WirePath(rd.WireConfig(), interpret=True, tree=ts)

        def flat():
            return wire_flat.round_from_stacked(bufs_q, 0, w, p1, p2,
                                                t=3)[0]

        def tree():
            return wire_tree.round_from_stacked(bufs_q, 0, w, p1, p2,
                                                t=3)[0]

        np.testing.assert_allclose(np.asarray(tree()), np.asarray(flat()),
                                   rtol=1e-4, atol=1e-4)
        counts_tree = jaxpr_primitive_counts(tree)
        counts_flat = jaxpr_primitive_counts(flat)
        assert counts_tree.get("pallas_call") == levels + 2, counts_tree
        assert counts_flat.get("pallas_call") == 2, counts_flat
        host_syncs = sum(counts_tree.get(p, 0)
                         for p in HOST_SYNC_PRIMITIVES)
        assert host_syncs == 0, counts_tree

        us_flat = _bench(flat, reps=reps)
        us_tree = _bench(tree, reps=reps)

        mb = m * 4.0                       # float32 model bytes
        w_last = widths[-1]
        tpu_root = tune.default_plan("master", r4, w_last, "tpu")
        tpu_flat = tune.default_plan("master", r4, n, "tpu")
        out.append({
            "params": m,
            "n_workers": n,
            "fanout": fanout,
            "levels": levels,
            "level_widths": widths,
            "flat_round_us": us_flat,
            "tree_round_us": us_tree,
            "launches": {"flat": 2, "tree": levels + 2},
            "host_syncs": 0,
            # the root sums w_L <= fanout partials, not N-1 uplinks — its
            # worker-axis grid and VMEM tile stop growing with cohort size
            "root_fan_in": {"flat": n, "tree": w_last},
            "root_link_reduction": (n - 1) / max(w_last, 1),
            # bytes over the link INTO the root per round (the flat
            # master's ingress bottleneck), masked-16 wire: N-1 word
            # buffers flat vs the last level's w_L partials on the tree
            "flat_root_link16_bytes": (n - 1) * mb * 16 / 32,
            "tree_root_link16_bytes": w_last * mb * 16 / 32,
            "root_vmem_tile_bytes": tune.master_vmem_tile_bytes(
                tpu_root["block_rows"], tpu_root["block_workers"]),
            "flat_master_vmem_tile_bytes": tune.master_vmem_tile_bytes(
                tpu_flat["block_rows"], tpu_flat["block_workers"]),
            "flat_plain_bytes": proto.fedpc_bytes_per_round(mb, n),
            "tree_plain_bytes": proto.fedpc_tree_bytes_per_round(
                mb, n, fanout),
            "flat_masked16_bytes": proto.fedpc_masked_bytes_per_round(
                mb, n, 16),
            "tree_masked16_bytes": proto.fedpc_tree_bytes_per_round(
                mb, n, fanout, word_bits=16),
            "flat_masked32_bytes": proto.fedpc_masked_bytes_per_round(
                mb, n, 32),
            "tree_masked32_bytes": proto.fedpc_tree_bytes_per_round(
                mb, n, fanout, word_bits=32),
            "fedavg_bytes": proto.fedavg_bytes_per_round(mb, n),
            "mode": "cpu-interpret",
        })
    return out


def _masked_wire(m: int, n_workers: int, reps: int) -> list:
    """Secure-aggregation wire overhead at m params x N workers, at BOTH
    wire moduli (2**16 default / 2**32 conservative): the masked uplink
    (ternarize -> RR -> fixed-point weight -> pairwise mask, one modular
    word out per parameter) vs the plaintext 2-bit stacked uplink, and the
    sum-then-unmask master vs the accumulating plaintext master — both at
    their autotuned plans. Mask and RR streams are generated IN-KERNEL
    from per-pair/per-worker counter keys, so no (N, rows, 128) mask
    tensor exists in HBM and no host-side incidence matmul runs per round
    — asserted structurally on the uplink jaxpr before timing. The
    wire-byte price per modulus is recorded so the trade is a number, not
    a vibe: 16-bit words are 8x the 2-bit codes (half the 32-bit path's
    fp32-FedAvg-sized uplinks)."""
    from repro.privacy import (pair_signs, pair_stream_keys,
                               quantize_weights, rr_stream_keys)
    rows = m // 128
    r4 = rows // 4
    k = jax.random.PRNGKey(23)
    bufs_q = jax.random.normal(k, (n_workers, rows, 128))
    p1 = jax.random.normal(jax.random.fold_in(k, 1), (rows, 128))
    p2 = jax.random.normal(jax.random.fold_in(k, 2), (rows, 128))
    w = jnp.full((n_workers,), 1.0 / max(n_workers - 1, 1)).at[0].set(0.0)
    keys = pair_stream_keys(0, n_workers, 3)
    signs = pair_signs(n_workers)
    rrk = rr_stream_keys(1, 3, n_workers)
    tune.autotune_stacked(r4, n_workers, interpret=True, reps=1)
    tune.autotune_master(r4, n_workers, interpret=True, reps=1)

    def uplink_plain():
        return ops.flat_ternary_pack_stacked(
            bufs_q, p1, p2, t=3, beta=0.2, alpha1=0.01, interpret=True)

    packed = uplink_plain()

    def master_plain():
        return ops.flat_master_update(bufs_q[0], packed, w, p1, p2, t=3,
                                      alpha0=0.01, interpret=True)

    us_up_plain = _bench(uplink_plain, reps=reps)
    us_ms_plain = _bench(master_plain, reps=reps)

    out = []
    for wb in (16, 32):
        fb = 14 if wb == 16 else 24
        wq = quantize_weights(w, fb)
        tune.autotune_masked_uplink(r4, n_workers, interpret=True, reps=1,
                                    word_bits=wb)
        tune.autotune_masked_master(r4, n_workers, interpret=True, reps=1,
                                    word_bits=wb)
        kind = "uplink_masked16" if wb == 16 else "uplink_masked"
        plan = tune.lookup(kind, r4, n_workers, interpret=True)

        def uplink_masked():
            return ops.flat_ternary_pack_masked(
                bufs_q, p1, p2, t=3, beta=0.2, alpha1=0.01, wq=wq,
                pair_keys=keys, pair_signs=signs, rr_keys=rrk,
                rr_threshold=0, word_bits=wb, interpret=True)

        # structural guarantee before timing: ONE launch whose only
        # unsigned operands are the tiny O(N^2) counter keys — the mask
        # streams never round-trip through HBM and no threefry PRNG runs
        counts = jaxpr_primitive_counts(uplink_masked)
        assert counts.get("pallas_call") == 1, counts
        assert not any("threefry" in p for p in counts), counts
        from repro.utils import iter_jaxpr_eqns
        jaxpr = jax.make_jaxpr(uplink_masked)()
        [eqn] = [e for e in iter_jaxpr_eqns(jaxpr.jaxpr, into_pallas=False)
                 if e.primitive.name == "pallas_call"]
        for v in eqn.invars:
            if np.issubdtype(v.aval.dtype, np.unsignedinteger):
                assert int(np.prod(v.aval.shape)) <= n_workers * n_workers, (
                    v.aval, "mask tensor operand leaked into the uplink")

        y = uplink_masked()

        def master_masked():
            return ops.flat_masked_master_update(
                bufs_q[0], y, jnp.sum(wq), p1, p2, t=3, alpha0=0.01,
                scale_mult=2.0 ** -fb, interpret=True)

        # correctness rides along: masked == plain up to weight
        # quantization (coarser at fb=14, hence the looser 16-bit bound)
        np.testing.assert_allclose(
            np.asarray(master_masked()), np.asarray(master_plain()),
            rtol=1e-5 if wb == 32 else 1e-3,
            atol=1e-5 if wb == 32 else 2e-3)
        us_up_masked = _bench(uplink_masked, reps=reps)
        us_ms_masked = _bench(master_masked, reps=reps)
        out.append({
            "params": m,
            "n_workers": n_workers,
            "modulus_bits": wb,
            "uplink_plain_us": us_up_plain,
            "uplink_masked_us": us_up_masked,
            "masked_uplink_overhead": us_up_masked / us_up_plain,
            "master_plain_us": us_ms_plain,
            "master_masked_us": us_ms_masked,
            "masked_master_overhead": us_ms_masked / us_ms_plain,
            "wire_bytes_plain": n_workers * r4 * 128,           # 2-bit codes
            "wire_bytes_masked": n_workers * r4 * 512 * (wb // 8),
            "plan": {"block_rows": plan[0], "block_workers": plan[1]},
            "launches": {"uplink": 1, "master": 1},
            "mode": "cpu-interpret",
        })
    return out


def _dropout_recovery(m: int, n_list: tuple, reps: int) -> list:
    """Dropout-recovery price at m params x N workers vs dropout rate.

    Times the fused ``mask_repair_2d`` launch that subtracts the dead
    workers' mask residue from the aggregated slab (rate 0 exercises the
    in-kernel zero-coefficient skip — a fault-free round's repair is a
    near-no-op) and records the analytic control-plane wire overhead:
    per-round Shamir dealing (every worker shares its key row with its
    siblings) plus per-death reconstruction traffic — so the robustness
    premium is a number next to the masked-wire numbers it rides on."""
    from repro.core import protocol as proto
    from repro.privacy import masking as pvm
    from repro.privacy import recovery as pvr
    rows = m // 128
    r4 = rows // 4
    thr = 2
    k = jax.random.PRNGKey(31)
    out = []
    for n in n_list:
        keys_mat = pvm.pair_stream_keys(0, n, 3)
        signs = pvm.pair_signs(n)
        i_idx, j_idx = pvr.repair_pair_index(n)
        dealing = proto.recovery_dealing_bytes_per_round(n)
        for rate in (0.0, 1.0 / n, 0.10):
            n_dead = int(round(rate * n))
            alive = np.ones(n)
            alive[:n_dead] = 0.0
            ae, de = pvr.effective_masks(None, jnp.asarray(alive), thr,
                                         None, n)
            for wb in (16, 32):
                kf, cf = pvr.repair_coefficients(keys_mat, signs, ae, de,
                                                 i_idx, j_idx)
                word = jnp.uint16 if wb == 16 else jnp.uint32
                y = jax.random.bits(k, (r4, 512), jnp.uint32).astype(word)
                tune.autotune_mask_repair(r4, len(i_idx), interpret=True,
                                          reps=1, word_bits=wb)

                def repair():
                    return ops.flat_mask_repair(y, kf, cf, interpret=True)

                us = _bench(repair, reps=reps)
                recon = proto.recovery_reconstruction_bytes(
                    n_dead, thr, n_workers=n)
                out.append({
                    "params": m,
                    "n_workers": n,
                    "modulus_bits": wb,
                    "dropout": round(rate, 4),
                    "n_dead": n_dead,
                    "repair_pairs": int(len(i_idx)),
                    "active_pairs": int(np.sum(np.asarray(cf) != 0)),
                    "repair_us": us,
                    "dealing_bytes_per_round": dealing,
                    "reconstruction_bytes": recon,
                    "recovery_bytes_total": dealing + recon,
                    "mode": "cpu-interpret",
                })
    return out


def _scan_rounds_bench(m: int, n_workers: int, rounds: int,
                       reps: int) -> dict:
    """Multi-round FedPC: a Python loop re-dispatching ONE jitted round body
    (local models + round_step — what the real Python driver compiles) vs
    the same body under a single jitted lax.scan (the scan driver). Both
    sides jit identical work, so the delta is pure per-round dispatch +
    host-return overhead.

    The structural win is asserted at jaxpr level before timing: the scan
    program contains exactly TWO pallas_call eqns total (uplink + master,
    amortized over every round by the scan) and ZERO host-sync primitives —
    the Python loop re-dispatches both launches and returns control to the
    host every round.

    NOTE on CPU wall time: since the tuned single-step wire plans landed,
    the jitted round body is ~4x faster, which leaves the interpret-mode
    scan's fixed carry overhead (the pallas while_loop buffers threaded
    through the lax.scan carry) as the visible cost — the scan can time
    BELOW 1x here. The claim that matters (one dispatch, zero per-round
    host syncs) is the asserted structure; wall-clock wins are a compiled-
    TPU property.
    """
    rows = m // 128
    wire = rd.WirePath(rd.WireConfig(), interpret=True,
                       block_rows=rows // fl.PACK)
    key = jax.random.PRNGKey(17)
    buf = jax.random.normal(key, (rows, 128))
    state = rd.RoundState(
        buf_p1=buf, buf_p2=0.9 * buf,
        prev_costs=jnp.ones((n_workers,)),
        round=jnp.asarray(3, jnp.int32))
    deltas = 0.01 * jax.random.normal(
        jax.random.fold_in(key, 1), (rounds, n_workers, rows, 128))
    sizes = jnp.linspace(50.0, 200.0, n_workers)

    def worker_fn(wc, gbuf, t):
        d = jnp.take(deltas, t - 3, axis=0)
        costs = 1.0 / (t.astype(jnp.float32)
                       + jnp.arange(n_workers, dtype=jnp.float32) + 1.0)
        return wc, gbuf[None] + d, costs

    def scan_fn(st):
        st, _, infos = rd.scan_rounds(wire, st, worker_fn, 0, rounds, sizes)
        return st, infos["k_star"]

    counts = jaxpr_primitive_counts(scan_fn, state)
    assert counts.get("pallas_call") == 2, counts
    host_syncs = sum(counts.get(p, 0) for p in HOST_SYNC_PRIMITIVES)
    assert host_syncs == 0, counts

    scan_jit = jax.jit(scan_fn)

    def round_body(st):
        _, bufs, costs = worker_fn(0, st.buf_p1, st.round)
        st, _, _ = wire.round_step(st, bufs, costs, sizes)
        return st

    body_jit = jax.jit(round_body)

    def loop():
        st = state
        for _ in range(rounds):
            st = body_jit(st)
        return st.buf_p1

    def scan():
        st, _ = scan_jit(state)
        return st.buf_p1

    np.testing.assert_array_equal(np.asarray(loop()), np.asarray(scan()))
    us_loop = _bench(loop, reps=reps)
    us_scan = _bench(scan, reps=reps)
    return {
        "params": m,
        "n_workers": n_workers,
        "rounds": rounds,
        "loop_us": us_loop,
        "scan_us": us_scan,
        "scan_speedup": us_loop / us_scan,
        "pallas_calls_in_scan_program": counts.get("pallas_call"),
        "host_sync_primitives_in_scan_program": host_syncs,
        "mode": "cpu-interpret",
    }


_SYNC_BENCH_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json, sys, time
import jax, jax.numpy as jnp
from repro.core import flat as fl
from repro.fed.distributed import build_fed_sync, fed_state_init

m = int(sys.argv[1])
reps = int(sys.argv[2])
from repro.launch.mesh import make_mesh
mesh = make_mesh((4, 2), ("data", "model"))
F, MOD = 4, 2
params = {"w": jax.random.normal(jax.random.PRNGKey(0), (m,))}
sizes = jnp.linspace(50.0, 200.0, F)
costs = jnp.linspace(0.9, 0.5, F)
params_F = jax.tree_util.tree_map(
    lambda x: jnp.stack([x + 0.05 * (i + 1) for i in range(F)]), params)
state = fed_state_init(params, F)
state["round"] = jnp.asarray(3, jnp.int32)
state["params_prev"] = jax.tree_util.tree_map(lambda x: x + 0.01, params)
state["prev_costs"] = jnp.ones((F,))

out = {"params": m, "fed": F, "model": MOD, "mode": "cpu-interpret"}
with jax.set_mesh(mesh):
    for strat in ("fedpc_packed", "fedpc_reduce"):
        for shard in (True, False):
            layout = fl.layout_of(params, shards=MOD if shard else 1)
            # single interpret tile per device (see kernels_bench NOTE)
            sync = jax.jit(build_fed_sync(
                None, mesh, "data", strat, shard_wire=shard,
                wire_block_rows=layout.shard_rows // fl.PACK))
            new_params, _ = sync(params_F, costs, sizes, state)   # compile
            jax.block_until_ready(new_params)
            t0 = time.time()
            for _ in range(reps):
                new_params, _ = sync(params_F, costs, sizes, state)
                jax.block_until_ready(new_params)
            us = (time.time() - t0) / reps * 1e6
            key = f"{strat}_{'sharded' if shard else 'replicated'}"
            out[key + "_us"] = us
            if strat == "fedpc_packed":
                # uint8 §3.3 payload each device contributes to the fed
                # all_gather per round
                out[key + "_wire_bytes_per_device"] = (
                    layout.packed_shard_rows * fl.LANES)
print("SYNC " + json.dumps(out))
"""


def _sharded_sync(m: int, reps: int) -> dict | None:
    """Sharded vs replicated fed sync on an 8-host-device subprocess mesh
    (4 fed × 2 model): wall time per jitted sync + per-device wire bytes.

    CPU only: the child needs JAX while this process already holds it, and
    on a chip host the parent owns the chip — a JAX child there fails or
    hangs, so the phase is refused instead."""
    if jax.default_backend() != "cpu":
        emit("sync_bench_skipped", 0.0,
             f"host-device subprocess mesh is CPU-only "
             f"(platform {jax.default_backend()})")
        return None
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env = dict(os.environ, PYTHONPATH=src)
    proc = subprocess.run(
        [sys.executable, "-c", _SYNC_BENCH_SCRIPT, str(m), str(reps)],
        env=env, capture_output=True, text=True, timeout=900)
    if proc.returncode != 0:
        emit("sync_bench_failed", 0.0, proc.stderr[-200:].replace("\n", " "))
        return None
    line = [l for l in proc.stdout.splitlines() if l.startswith("SYNC ")][-1]
    return json.loads(line[len("SYNC "):])


def run(smoke: bool = False) -> dict:
    # --smoke: tiny sizes for CI — exercises every bench path in seconds
    # and does NOT overwrite BENCH_kernels.json (whose numbers are real).
    # Smoke reps are high (cheap at 16K params) so the best-of-reps
    # estimator stays stable under CI-runner load — the regression gate
    # compares these numbers across runs.
    m0 = (1 << 14) if smoke else M
    q, p1, p2 = _wire_inputs(m0)
    tern = jax.random.randint(jax.random.fold_in(jax.random.PRNGKey(0), 3),
                              (N_WORKERS, m0), -1, 2).astype(jnp.int8)
    w = jnp.full((N_WORKERS,), 0.02)

    tag0 = f"{m0 // (1 << 20)}M" if m0 >= (1 << 20) else f"{m0 // 1024}K"
    us = _bench(lambda: ops.ternary_encode(q, p1, p2, 0.2, interpret=True))
    us_ref = _bench(lambda: jax.jit(
        lambda a, b, c: ref.ternary_encode_ref(a, b, c, 0.2))(q, p1, p2))
    emit(f"kernel_ternary_encode_{tag0}", us, f"ref_jnp={us_ref:.0f}us")

    t = ops.ternary_encode(q, p1, p2, 0.2, interpret=True)
    us = _bench(lambda: ops.pack2bit(t, interpret=True))
    us_ref = _bench(jax.jit(ref.pack2bit_ref), t.reshape(-1, 4).reshape(-1))
    emit(f"kernel_pack2bit_{tag0}", us,
         f"ref_jnp={us_ref:.0f}us bytes_out={m0 // 4}")

    us = _bench(lambda: ops.master_update(q, tern, w, p1, p2, interpret=True))
    us_ref = _bench(jax.jit(ref.master_update_ref), q, tern, w, p1, p2)
    emit(f"kernel_master_update_{tag0}_8w", us, f"ref_jnp={us_ref:.0f}us")

    # correctness spot check rides along
    out = ops.master_update(q, tern, w, p1, p2, interpret=True)
    want = ref.master_update_ref(q, tern, w, p1, p2)
    err = float(jnp.max(jnp.abs(out - want)))
    emit("kernel_master_update_maxerr", 0.0, f"{err:.2e}")

    # ---- fused flat wire path vs the old composition, 1M and 16M --------
    sizes = (((1 << 14), 6),) if smoke else ((1 << 20, 3), (1 << 24, 1))
    results = []
    uplink_results = []
    for m, reps in sizes:
        tag = (f"{m // (1 << 20)}M" if m >= (1 << 20) else f"{m // 1024}K")
        r = _fused_vs_unfused(m, reps)
        results.append(r)
        emit(f"fused_uplink_{tag}", r["uplink_fused_us"],
             f"unfused={r['uplink_unfused_us']:.0f}us "
             f"speedup={r['uplink_speedup']:.2f}x launches=1v2")
        emit(f"fused_master_{tag}_{N_WORKERS}w", r["master_fused_us"],
             f"unfused={r['master_unfused_us']:.0f}us "
             f"speedup={r['master_speedup']:.2f}x")

        # ---- batched N-worker uplink: loop of N launches vs ONE ---------
        b = _batched_uplink(m, N_WORKERS, reps)
        uplink_results.append(b)
        emit(f"batched_uplink_{tag}_{N_WORKERS}w", b["uplink_stacked_us"],
             f"loop={b['uplink_loop_us']:.0f}us "
             f"speedup={b['stacked_speedup']:.2f}x launches=1v{N_WORKERS} "
             f"plan={b['plan']['block_rows']}x{b['plan']['block_workers']}")

    # ---- federation-size sweep: latency + wire bytes + master VMEM ------
    ws_m = (1 << 14) if smoke else (1 << 18)
    ws_n = (4, 8) if smoke else (8, 32, 64)
    ws_tag = (f"{ws_m // (1 << 20)}M" if ws_m >= (1 << 20)
              else f"{ws_m // 1024}K")
    scaling_results = _worker_scaling(ws_m, ws_n, max(r for _, r in sizes))
    for s in scaling_results:
        emit(f"worker_scaling_{ws_tag}_{s['n_workers']}w",
             s["uplink_stacked_us"],
             f"master={s['master_us']:.0f}us "
             f"wire={s['wire_bytes_per_round']}B "
             f"master_vmem_tile={s['master_vmem_tile_bytes']}B "
             f"(preaccum={s['master_vmem_tile_bytes_preaccum']}B)")

    # ---- hierarchical tree aggregation: cohort-scale sweep --------------
    tr_m = (1 << 14) if smoke else (1 << 18)
    tr_n = (4, 8) if smoke else (16, 64, 256)
    tr_fanout = 2 if smoke else 4
    tr_tag = (f"{tr_m // (1 << 20)}M" if tr_m >= (1 << 20)
              else f"{tr_m // 1024}K")
    tree_results = _tree_scaling(tr_m, tr_n, tr_fanout, 1)
    for s in tree_results:
        emit(f"tree_scaling_{tr_tag}_{s['n_workers']}w_f{s['fanout']}",
             s["tree_round_us"],
             f"flat={s['flat_round_us']:.0f}us levels={s['levels']} "
             f"launches={s['launches']['tree']}v2 "
             f"root_fanin={s['root_fan_in']['tree']}v"
             f"{s['root_fan_in']['flat']} "
             f"root_vmem={s['root_vmem_tile_bytes']}B "
             f"m16_wire={s['tree_masked16_bytes']:.3g}B "
             f"(flat {s['flat_masked16_bytes']:.3g}B)")

    # ---- secure-aggregation wire: masked vs plaintext kernels -----------
    mk_m = (1 << 14) if smoke else (1 << 20)
    mk_tag = (f"{mk_m // (1 << 20)}M" if mk_m >= (1 << 20)
              else f"{mk_m // 1024}K")
    masked_results = _masked_wire(mk_m, N_WORKERS, max(r for _, r in sizes))
    for s in masked_results:
        mb = s["modulus_bits"]
        emit(f"masked_uplink_{mk_tag}_{s['n_workers']}w_m{mb}",
             s["uplink_masked_us"],
             f"plain={s['uplink_plain_us']:.0f}us "
             f"overhead={s['masked_uplink_overhead']:.2f}x "
             f"wire={s['wire_bytes_masked']}B "
             f"(plain {s['wire_bytes_plain']}B)")
        emit(f"masked_master_{mk_tag}_{s['n_workers']}w_m{mb}",
             s["master_masked_us"],
             f"plain={s['master_plain_us']:.0f}us "
             f"overhead={s['masked_master_overhead']:.2f}x")

    # ---- dropout recovery: repair latency + control-plane bytes ---------
    dr_m = (1 << 14) if smoke else (1 << 18)
    dr_n = (4, 8) if smoke else (16, 64)
    dr_tag = (f"{dr_m // (1 << 20)}M" if dr_m >= (1 << 20)
              else f"{dr_m // 1024}K")
    recovery_results = _dropout_recovery(dr_m, dr_n, 1 if not smoke else 3)
    for s in recovery_results:
        if s["modulus_bits"] != 16:
            continue                       # one emit per (n, rate) is enough
        emit(f"dropout_recovery_{dr_tag}_{s['n_workers']}w"
             f"_d{s['dropout']}",
             s["repair_us"],
             f"dead={s['n_dead']} pairs={s['active_pairs']}/"
             f"{s['repair_pairs']} dealing={s['dealing_bytes_per_round']:.0f}B "
             f"recon={s['reconstruction_bytes']:.0f}B")

    # ---- multi-round scan driver vs per-round Python loop ---------------
    scan_results = []
    scan_sizes = (((1 << 14), 4, 4),) if smoke else ((1 << 20, 4, 3),)
    for m, n_rounds, reps in scan_sizes:
        tag = (f"{m // (1 << 20)}M" if m >= (1 << 20) else f"{m // 1024}K")
        sc = _scan_rounds_bench(m, 4, n_rounds, reps)
        scan_results.append(sc)
        emit(f"scan_rounds_{tag}_{n_rounds}r", sc["scan_us"],
             f"loop={sc['loop_us']:.0f}us "
             f"speedup={sc['scan_speedup']:.2f}x "
             f"launches_in_program=2 host_syncs=0")

    # ---- sharded vs replicated fed sync (8-device subprocess mesh) ------
    sync_results = []
    for m, reps in sizes:
        tag = (f"{m // (1 << 20)}M" if m >= (1 << 20) else f"{m // 1024}K")
        s = _sharded_sync(m, reps)
        if s is None:
            continue
        sync_results.append(s)
        for strat in ("fedpc_packed", "fedpc_reduce"):
            sh = s[f"{strat}_sharded_us"]
            rp = s[f"{strat}_replicated_us"]
            emit(f"sync_{strat}_{tag}", sh,
                 f"replicated={rp:.0f}us speedup={rp / sh:.2f}x "
                 f"mesh={s['fed']}x{s['model']}")
        emit(f"sync_wire_bytes_{tag}",
             float(s["fedpc_packed_sharded_wire_bytes_per_device"]),
             f"replicated={s['fedpc_packed_replicated_wire_bytes_per_device']}"
             f" ({s['model']}x fewer per device)")

    payload = {"bench": "fedpc_flat_wire_kernels",
               "backend": jax.default_backend(),
               "results": results,
               "batched_uplink": uplink_results,
               "worker_scaling": scaling_results,
               "tree_scaling": tree_results,
               "masked_wire": masked_results,
               "dropout_recovery": recovery_results,
               "scan_rounds": scan_results,
               "sharded_sync": sync_results}
    if smoke:
        # tiny-size smoke numbers land in their own JSON — committed as the
        # CI regression-gate baseline (benchmarks/check_bench_regression.py
        # fails the build on >25% slowdown of any entry) and uploaded as an
        # artifact; BENCH_kernels.json keeps only real-size runs.
        with open(BENCH_SMOKE_JSON, "w") as f:
            json.dump(payload, f, indent=2)
        emit("bench_kernels_smoke_json", 0.0,
             os.path.abspath(BENCH_SMOKE_JSON))
    else:
        with open(BENCH_JSON, "w") as f:
            json.dump(payload, f, indent=2)
        emit("bench_kernels_json", 0.0, os.path.abspath(BENCH_JSON))
    return payload


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes for CI; writes BENCH_kernels_smoke.json "
                         "(artifact) instead of BENCH_kernels.json")
    ap.add_argument("--trace", metavar="PATH", default=None,
                    help="stream every autotune sweep's timed plans to a "
                         "telemetry JSONL trace at PATH (one plan event per "
                         "candidate — BENCH_kernels.json provenance)")
    cli = ap.parse_args()
    from contextlib import ExitStack

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    from repro.kernels import tune as _tune
    from repro.telemetry import trace as _tmt
    with ExitStack() as stack:
        if cli.trace:
            writer = stack.enter_context(
                _tmt.TraceWriter(cli.trace, source="kernels_bench"))
            _tune.set_trace_writer(_tmt.plan_emitter(writer.emit))
            stack.callback(_tune.set_trace_writer, None)
        run(smoke=cli.smoke)
