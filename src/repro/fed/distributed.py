"""FedPC on the TPU mesh: fed workers = slices of a mesh axis.

Mapping (DESIGN.md §2): each federated worker owns one index of the fed
mesh axis ('data' on a single pod → up to 16 workers; 'pod' across pods).
Within a worker slice the model is tensor-sharded over 'model'.

The round sync flattens the whole model pytree into ONE padded
``FlatParams`` buffer (``repro.core.flat``) and runs a single 2-D
``shard_map`` over (fed, model): the buffer's rows are *sharded over the
model axis* (``layout_of(..., shards=M)``), so every device owns a
``(rows/M, 128)`` slab, runs the fused wire kernels on that slab only, and
the fed-axis collectives move ``1/M`` of the buffer per device instead of a
replicated copy. The protocol math itself lives in ``repro.fed.rounds``
(:class:`~repro.fed.rounds.WirePath`) — shared verbatim with the simulator —
and this module only decides which bytes move between its steps:

  fedpc:        all_gather(int8 ternary)           — faithful Eq. (3)-(5)
  fedpc_packed: all_gather(uint8 2-bit codes)      — beyond-paper: the
                paper packs for TCP; we pack *before the collective* so ICI
                moves 4× fewer bytes than int8 (16× fewer than fp32)
  fedpc_reduce: psum_scatter + all_gather(f16 Σ w_k T_k) — Eq. (3) needs
                only the weighted sum; the RS+AG pair is the bandwidth-
                optimal all-reduce and caps the payload regardless of N
  fedavg:       psum(weighted params)              — baseline all-reduce

Pilot weights travel as a masked psum over the fed axis (the mesh analogue
of the star-topology upload+broadcast; see EXPERIMENTS.md for the honest
star-vs-all-reduce byte comparison).

Every (fed) shard_map instance runs the *same* master math on public
inputs, so the update stays consistent without a physical master — the
master of the paper is replicated control flow here (replicated over fed,
sharded over model).
"""
from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import flat as fl
from repro.core.goodness import select_pilot as _select_pilot
from repro.core.tree import TreeSpec
from repro.fed import rounds as rd
from repro.kernels import ops
from repro.models.model import Model
from repro.privacy import audit as pv_audit
from repro.privacy import dp as pdp
from repro.privacy import masking as pvm
from repro.privacy import recovery as pvr
from repro.privacy.spec import PrivacySpec
from repro.telemetry import record as tmr
from repro.utils import PyTree

from repro.sharding.specs import param_specs, wire_specs


# ---------------------------------------------------------------------------
# Sync strategies (shard_map bodies over (fed, model), on flat buffer slabs)
# ---------------------------------------------------------------------------

def _tree_butterfly_reduce(y, *, spec, tree, idx, t, fed_axis, n_fed,
                           m_idx, pmask):
    """Tree-shaped masked all-reduce as XOR recursive doubling.

    Level l folds aligned sibling groups of ``fanout`` nodes with
    ``fanout``-spanning ppermute hops (group masks cancel in the modular
    sum — leaf signs are sibling-scoped), then only the group
    representatives (``idx % fanout**l == 0``) carry on: each adds its OWN
    level-salted sibling-scoped node mask (``net_mask_slab`` over the
    ``tree_level_seed`` stream) and every non-representative zeroes out, so
    the words on every subsequent hop stay masked and nothing is
    double-counted. After the last level a butterfly over the w_L
    representatives completes the root sum (their level-L masks cancel
    there), and an additive down-broadcast returns the identical public
    masked total to every instance — at each hop exactly one endpoint is
    nonzero, so addition is a broadcast. Modular addition is order-free:
    the result is bitwise equal to the flat psum of the flat-signed wire.

    Per level the number of information-bearing payloads drops fanout×
    (non-representatives ship all-zero slabs — SPMD cannot skip a
    permute, a physical tree runtime simply would not send them).
    """
    f = tree.fanout
    L = tree.n_levels(n_fed)
    seed = spec.mask_seed if spec.masking_on else 0
    act = None if pmask is None else jnp.asarray(pmask, jnp.float32)
    contrib = y

    def hop(x, d):
        perm = [(i, i ^ d) for i in range(n_fed)]
        return x + jax.lax.ppermute(x, fed_axis, perm=perm)

    for lvl in range(1, L + 1):
        for d in (f ** (lvl - 1) * (1 << k) for k in range(f.bit_length() - 1)):
            contrib = hop(contrib, d)
        if act is not None:
            act = pvm.tree_activity(act, f)
        stride = f ** lvl
        node = idx // stride
        w_l = n_fed // stride
        sib_l = f if lvl < L else w_l
        if spec.masking_on and w_l >= 2:
            slab = pvm.net_mask_slab(
                pvm.tree_level_seed(seed, lvl), node, w_l, t, y.shape,
                m_idx, word_bits=spec.modulus_bits,
                signs_row=pvm.tree_pair_signs_row(node, w_l, sib_l,
                                                  participation=act))
            contrib = contrib + slab
        is_rep = (idx % stride) == 0
        contrib = jnp.where(is_rep, contrib, jnp.zeros_like(contrib))
    d = f ** L
    while d < n_fed:            # root: fold the w_L last-level partials
        contrib = hop(contrib, d)
        d *= 2
    d = 1
    while d < f ** L:           # down-broadcast the public masked total
        contrib = hop(contrib, d)
        d *= 2
    return contrib


def _sync_body(q_buf, p_prev, p_prev2, *, wire: rd.WirePath, k_star, w,
               t, fed_axis, n_fed, mode, betas=None, model_axis=None,
               pmask=None, tree=None, alive=None):
    """One (fed, model) device's slice of the round sync — a thin driver
    over :class:`repro.fed.rounds.WirePath`.

    q_buf: (1, sr, 128) this worker's slab of its flattened weights;
    p_prev/p_prev2: (sr, 128) slabs of the public history (replicated over
    fed, sharded over model). ``betas`` is an optional (F,) per-worker
    beta_k vector (replicated): each fed instance ternarizes its own slab
    with its own threshold. Returns the (sr, 128) slab of the new global
    flat model (identical on every fed instance).
    """
    idx = jax.lax.axis_index(fed_axis)
    q = q_buf[0]
    beta_k = None if betas is None else jnp.take(betas, idx)
    # pilot upload+broadcast == masked all-reduce over the fed axis
    q_pilot = jax.lax.psum(jnp.where(idx == k_star, q, 0.0), fed_axis)
    wf = w.astype(jnp.float32)                    # (F,) masked Eq.(3) weights

    if mode == "masked":
        # Secure-aggregation wire: this instance masks its own fixed-point
        # weighted fields in-kernel (the uplink kernel regenerates only
        # this worker's row of pair streams from the (F,) key row — no
        # mask tensor exists in HBM, unlike the full F(F-1)/2 set the
        # simulator's oracle materializes), the fed collective sums mod
        # 2**modulus_bits (masks cancel EXACTLY, and modular addition is
        # order-free, so psum_scatter+all_gather is bit-identical to a
        # plain psum and to the replicated path), and every instance
        # unmasks the identical public sum. At the 16-bit modulus the
        # collective moves native uint16 words — HALF the bytes of the
        # uint32 wire for the same topology.
        spec = wire.privacy
        sr = q.shape[0]
        m_idx = (jax.lax.axis_index(model_axis) if model_axis is not None
                 else jnp.int32(0))
        wq = pvm.quantize_weights(wf, spec.fixpoint_bits)
        seed = spec.mask_seed if spec.masking_on else 0
        keys_row = pvm.pair_stream_keys_row(seed, idx, n_fed, t, m_idx)
        if tree is not None:        # leaf masks cancel within sibling groups
            signs_row = pvm.tree_pair_signs_row(idx, n_fed, tree.fanout,
                                                participation=pmask)
        else:
            signs_row = pvm.pair_signs_row(idx, n_fed, participation=pmask)
        rr_key = pdp.rr_stream_key(spec.dp_seed, t, idx, m_idx)
        y = wire.uplink_masked_slab(q, p_prev, p_prev2, t=t,
                                    wq_own=jnp.take(wq, idx),
                                    keys_row=keys_row,
                                    signs_row=signs_row, rr_key=rr_key,
                                    beta=beta_k)
        alive_eff = dead_eff = None
        if alive is not None:
            # Dropout recovery (repro.privacy.recovery): the uplink above
            # is what this worker COMMITTED; a post-fault death zeroes its
            # slab before the collective (nothing arrives from a dead
            # worker), its W_k leaves the de-bias, and the survivors'
            # uncancelled pair masks toward the dead are repaired on the
            # reduced total below — identically on every instance.
            alive_eff, dead_eff = pvr.effective_masks(
                pmask, alive, spec.recovery_threshold,
                tree.fanout if tree is not None else None, n_fed)
            y = jnp.where(jnp.take(alive_eff, idx) > 0, y,
                          jnp.zeros_like(y))
            wq = jnp.where(alive_eff > 0, wq, jnp.zeros_like(wq))
        if tree is not None:
            s = _tree_butterfly_reduce(y, spec=spec, tree=tree, idx=idx,
                                       t=t, fed_axis=fed_axis,
                                       n_fed=n_fed, m_idx=m_idx,
                                       pmask=pmask)
        elif y.shape[0] % n_fed == 0:
            part = jax.lax.psum_scatter(y, fed_axis, scatter_dimension=0,
                                        tiled=True)
            s = jax.lax.all_gather(part, fed_axis, axis=0, tiled=True)
        else:                       # slab rows not divisible by F
            s = jax.lax.psum(y, fed_axis)
        if alive is not None and spec.masking_on:
            i_idx, j_idx = pvr.repair_pair_index(
                n_fed, tree.fanout if tree is not None else None)
            keys_mat = pvm.pair_stream_keys(seed, n_fed, t, m_idx)
            if tree is not None:
                signs_mat = pvm.tree_pair_signs(n_fed, tree.fanout,
                                                participation=pmask)
            else:
                signs_mat = pvm.pair_signs(n_fed, participation=pmask)
            kf, cf = pvr.repair_coefficients(keys_mat, signs_mat,
                                             alive_eff, dead_eff,
                                             i_idx, j_idx)
            s = ops.flat_mask_repair(s, kf, cf, interpret=wire.interpret,
                                     block_rows=wire.block_rows)
        sw = jnp.sum(wq)
        if spec.modulus_bits == 16:
            sw = (sw & jnp.uint32(0xFFFF)).astype(jnp.uint16)
            ci = jax.lax.bitcast_convert_type(s - sw, jnp.int16)
        else:
            ci = jax.lax.bitcast_convert_type(s - sw, jnp.int32)
        coeff = ci.astype(jnp.float32) * jnp.float32(spec.scale_mult)
        return wire.combine(q_pilot, coeff.reshape(sr, fl.LANES), p_prev,
                            p_prev2, t)

    if mode == "packed":
        # Fused uplink on the slab → uint8 §3.3 codes on the wire → fused
        # master over the gathered stack (in-register decode, Eq. (3)).
        pk = wire.uplink(q, p_prev, p_prev2, t=t, beta=beta_k)
        pk_all = jax.lax.all_gather(pk, fed_axis)     # (F, sr/4, 128)
        return wire.master(q_pilot, pk_all, wf, p_prev, p_prev2, t=t)

    tern = wire.codes(q, p_prev, p_prev2, t, beta=beta_k)  # int8 (sr, 128)
    if mode == "reduce":
        # Beyond-paper: Eq. (3) needs only Σ_k w_k T_k — reduce in-network
        # instead of gathering N ternary slabs. psum_scatter + all_gather is
        # the bandwidth-optimal all-reduce decomposition: each fed hop moves
        # sr/F rows, and the payload stays flat in N (the gather grows
        # linearly). Every instance ends with the same sum so the
        # replicated-master math is unchanged.
        w_me = jnp.take(wf, idx)
        # f16 on the wire (bf16 triggers an XLA-CPU AllReducePromotion
        # crash in this container; on TPU use bf16 — same byte count)
        contrib = (w_me * tern.astype(jnp.float32)).astype(jnp.float16)
        if contrib.shape[0] % n_fed == 0:
            part = jax.lax.psum_scatter(contrib, fed_axis,
                                        scatter_dimension=0, tiled=True)
            coeff = jax.lax.all_gather(part, fed_axis, axis=0,
                                       tiled=True).astype(jnp.float32)
        else:                       # slab rows not divisible by F: plain psum
            coeff = jax.lax.psum(contrib, fed_axis).astype(jnp.float32)
    else:
        tern_all = jax.lax.all_gather(tern, fed_axis)  # (F, sr, 128) int8
        coeff = jnp.tensordot(wf, tern_all.astype(jnp.float32), axes=1)

    return wire.combine(q_pilot, coeff, p_prev, p_prev2, t)


def build_fed_sync(model: Model, mesh: Mesh, fed_axis: str = "data",
                   strategy: str = "fedpc", alpha0: float = 0.01,
                   beta: float = 0.2, alpha1: float = 0.01, *,
                   model_axis: str = "model", shard_wire: bool = True,
                   wire_block_rows: int | None = None,
                   wire_block_workers: int | None = None,
                   betas=None, privacy: PrivacySpec | None = None,
                   renorm_shares: bool = False,
                   tree: TreeSpec | None = None,
                   faults=None, ledger=None) -> Callable:
    """Returns sync(params_F, costs, sizes, state, mask=None) ->
    (new_global_params, aux).

    params_F leaves are stacked (F, ...) over the fed axis; state carries
    the public history (params, params_prev — replicated) plus per-round
    costs (F,) and the 1-based round index.

    ``betas`` is an optional (F,) per-worker beta_k vector — each fed
    instance ternarizes with its own threshold and Eq. (3) weights carry
    p_k·beta_k. ``mask`` (optional (F,) 0/1, passed per call) is a
    partial-participation round: non-sampled workers are excluded from
    pilot selection, contribute zero Eq. (3) weight, and keep their
    previous cost in the carried state.

    With ``shard_wire=True`` (default) and a ``model_axis`` in the mesh, the
    flat wire buffers are sharded over the model axis: per-device wire
    memory and fed-collective payload are ``rows/M``. ``shard_wire=False``
    keeps the replicated wire path (used by the parity tests and meshes
    without a model axis — both paths produce identical global params).

    ``wire_block_rows``/``wire_block_workers`` pin the wire-kernel tiling on
    each device's slab (master VMEM per tile stays O(block) regardless of
    F); left as None they resolve through the ``kernels.tune`` table —
    tiling never changes bits.

    An active ``privacy`` spec puts the fedpc strategies on the masked
    secure-aggregation wire: each instance uploads masked fixed-point
    words mod ``2**privacy.modulus_bits`` (uint16 by default — half the
    collective bytes of the uint32 wire), the fed collective is the
    bandwidth-optimal psum_scatter+all_gather over the native wire word
    (modular addition is order-free,
    so mask cancellation — and bitwise parity with the replicated path —
    survives ANY reduction topology), and the master never sees a worker's
    plaintext codes. With ``privacy.enforce`` the traced sync program is
    audited against the §4.2 leakage policy on first call (shape-only
    trace) and the passing audit recorded in ``ledger`` when given.
    ``renorm_shares`` selects the renormalized-share Eq. (3) variant under
    partial participation.

    ``tree`` (masked wire only) replaces the flat fed all-reduce with the
    tree-shaped XOR-butterfly of :func:`_tree_butterfly_reduce`: sibling
    groups of ``tree.fanout`` fold level by level, per-level node masks
    keep every hop's payload masked, and the link into the root carries
    w_L ≤ fanout partials instead of F — bitwise identical to the flat
    path. Requires power-of-two ``fanout`` and fed axis size.

    ``faults`` attaches a deterministic :class:`repro.fed.faults.FaultPlan`:
    each round realizes per-worker fault codes from ``state["round"]`` and
    excludes faulted workers from pilot selection and the aggregate. On the
    masked wire the committed uplinks of dead workers are dropped and their
    residual pair masks repaired post-reduce (identically on every
    instance) — requires ``privacy.recovery_threshold``; a sibling group
    below it degrades to an exact-zero subtree.
    """
    F = mesh.shape[fed_axis]
    M = mesh.shape.get(model_axis, 1) if shard_wire else 1
    m_axis = model_axis if M > 1 else None
    wcfg = rd.WireConfig(alpha0=alpha0, beta=beta, alpha1=alpha1)
    betas_arr = None if betas is None else jnp.asarray(betas, jnp.float32)
    masked_wire = privacy is not None and privacy.active
    if masked_wire and strategy == "fedavg":
        # Silently running FedAvg's full-precision psum while the caller
        # believes secure aggregation is on would be the worst failure
        # mode a privacy layer can have.
        raise ValueError("privacy (secure-agg / DP wire) requires a fedpc "
                         "strategy; strategy='fedavg' moves full-precision "
                         "params over the fed axis")
    if tree is not None:
        # The XOR-butterfly tree reduce needs aligned power-of-two sibling
        # groups and full levels over the fed axis, and the masked wire
        # (partials crossing tree edges must be masked words).
        if not masked_wire:
            raise ValueError("tree aggregation on the mesh requires an "
                             "active privacy spec — every tree edge must "
                             "carry masked words")
        if tree.fanout & (tree.fanout - 1):
            raise ValueError(f"mesh tree fanout must be a power of two, "
                             f"got {tree.fanout}")
        if F & (F - 1):
            raise ValueError(f"mesh tree reduce needs a power-of-two fed "
                             f"axis, got {F}")
        if F % (tree.fanout ** tree.n_levels(F)):
            raise ValueError(
                f"fed axis ({F}) must hold whole sibling groups at every "
                f"level: not divisible by fanout**levels "
                f"({tree.fanout}**{tree.n_levels(F)})")
    fault_plan = faults if faults is not None and faults.active else None
    if (fault_plan is not None and masked_wire
            and privacy.recovery_threshold is None):
        raise ValueError(
            "fault injection on the masked wire requires "
            "privacy.recovery_threshold (the Shamir t of the "
            "dropout-recovery dealing) to be set")
    audit_state = {"done": False}

    def sync(params_F: PyTree, costs: jax.Array, sizes: jax.Array,
             state: dict, mask: jax.Array | None = None
             ) -> tuple[PyTree, dict]:
        t = state["round"]
        codes = dead_eff = None
        av = None
        if fault_plan is not None:
            codes = fault_plan.codes(t, F)
            av = (codes == tmr.FAULT_NONE).astype(jnp.float32)
        if av is None:
            sel_mask = mask
        elif masked_wire:
            # Survivors of a below-threshold sibling group contribute an
            # exact-zero subtree — exclude them from pilot selection and
            # the cost carry along with the dead (the threshold and fault
            # set are public, so every instance computes the same split).
            sel_mask, dead_eff = pvr.effective_masks(
                mask, av, privacy.recovery_threshold,
                tree.fanout if tree is not None else None, F)
        elif mask is None:
            sel_mask = av
        else:
            sel_mask = jnp.asarray(mask, jnp.float32) * av
        k_star, scores = _select_pilot(costs, state["prev_costs"], sizes, t,
                                       sel_mask)
        p_shares = sizes.astype(jnp.float32) / jnp.sum(sizes)

        if strategy == "fedavg":
            # C-fraction FedAvg: average over the sampled (and surviving)
            # workers only, shares renormalized over that set (>= 1
            # participant by construction).
            if sel_mask is None:
                wts = p_shares
            else:
                wm = p_shares * jnp.asarray(sel_mask, jnp.float32)
                wts = wm / jnp.sum(wm)

            def avg(x):
                wb = wts.reshape((-1,) + (1,) * (x.ndim - 1))
                return jnp.sum(x.astype(jnp.float32) * wb, axis=0).astype(x.dtype)
            new_params = jax.tree_util.tree_map(avg, params_F)
        else:
            # Flat wire path: the whole pytree becomes one padded buffer per
            # worker (rows padded to M aligned slabs), so the sync is a
            # single shard_map over (fed, model) — one fed collective per
            # round, not one per leaf, each moving rows/M per device.
            layout = fl.layout_of(state["params"], shards=M)
            wire = rd.WirePath(wcfg, block_rows=wire_block_rows,
                               block_workers=wire_block_workers,
                               privacy=privacy if masked_wire else None,
                               renorm_shares=renorm_shares)
            # Masked wire: weights were committed BEFORE faults realized
            # (pre-fault participation); dead rows drop downstream and the
            # de-bias reweights by the surviving ΣW_k. Plain wire: faults
            # fold straight into the weights — survivors-only exactly.
            w = wire.weights(p_shares, k_star, t, betas=betas_arr,
                             mask=(mask if masked_wire else sel_mask))
            q_flat_F = fl.flatten_stacked(params_F, layout)
            p1_flat = fl.flatten_tree(state["params"], layout)
            p2_flat = fl.flatten_tree(state["params_prev"], layout)
            if M > 1:
                # Materialize the flat buffers on a sharding whose row axis
                # is NOT split before handing them to the shard_map: XLA's
                # SPMD partitioner (observed on CPU, jax 0.4) miscompiles
                # the concat+pad+reshape of flatten when its output is
                # resharded along the concat-derived row axis in the same
                # fusion — values arrive strided. The constraint forces a
                # clean boundary; the model-axis reshard then happens at
                # shard_map entry. Workers stay sharded over fed (no
                # cross-fed gather), history is replicated as it already is
                # semantically.
                q_flat_F = jax.lax.with_sharding_constraint(
                    q_flat_F, NamedSharding(mesh, P(fed_axis, None, None)))
                p1_flat, p2_flat = (
                    jax.lax.with_sharding_constraint(
                        x, NamedSharding(mesh, P(None, None)))
                    for x in (p1_flat, p2_flat))

            mode = ("masked" if masked_wire else
                    {"fedpc_packed": "packed",
                     "fedpc_reduce": "reduce"}.get(strategy, "gather"))
            body = partial(
                _sync_body, wire=wire, k_star=k_star, w=w, t=t,
                fed_axis=fed_axis, n_fed=F, betas=betas_arr,
                model_axis=m_axis, pmask=mask, mode=mode, tree=tree,
                alive=(av if masked_wire else None))

            specs = wire_specs(fed_axis, m_axis)
            # Manual over EVERY mesh axis: a Mosaic kernel cannot be
            # auto-partitioned, even over a size-1 axis. Axes the wire does
            # not shard hold replicated slabs, so each of their devices
            # runs the identical body.
            sharded_sync = jax.shard_map(
                body, mesh=mesh,
                in_specs=(specs["stacked"], specs["history"],
                          specs["history"]),
                out_specs=specs["out"],
                axis_names=frozenset(mesh.axis_names), check_vma=False)
            if (masked_wire and privacy.enforce
                    and not audit_state["done"]):
                # §4.2 enforcement hook: audit what actually crosses the
                # fed axis in this round's traced program (shape-only
                # trace — runs once, works under an outer jit too).
                report = pv_audit.check_fed_collectives(
                    sharded_sync, q_flat_F, p1_flat, p2_flat,
                    n_fed=F, masked=True)
                audit_state["done"] = True
                if ledger is not None:
                    ledger.record_audit("build_fed_sync", report)
            new_flat = sharded_sync(q_flat_F, p1_flat, p2_flat)
            new_params = fl.unflatten_tree(new_flat, layout)

        costs_eff = costs.astype(jnp.float32)
        if sel_mask is not None:  # non-participants / faulted: carry prev
            costs_eff = jnp.where(jnp.asarray(sel_mask) > 0, costs_eff,
                                  state["prev_costs"])
        new_state = {
            "params": new_params,
            "params_prev": state["params"],
            "prev_costs": costs_eff,
            "round": t + 1,
        }
        # The same device-resident round record the simulator drivers
        # emit — the mesh runtime's per-round observability rides aux (all
        # scalars; fetch-when-you-want, nothing syncs here).
        rec = tmr.build_round_record(
            t=t, k_star=k_star, n=F, costs=costs, sizes=sizes, mask=mask,
            codes=codes, sel_mask=sel_mask, dead_eff=dead_eff,
            modulus_bits=privacy.modulus_bits if masked_wire else 0,
            fanout=tree.fanout if tree is not None else 0,
            levels=tree.n_levels(F) if tree is not None else 0)
        aux = {"k_star": k_star, "goodness": scores, "telemetry": rec}
        return new_params, {"state": new_state, **aux}

    return sync


# ---------------------------------------------------------------------------
# Full federated step: local training (vmap over fed axis) + sync
# ---------------------------------------------------------------------------

def build_fed_step(model: Model, mesh: Mesh, fed_axis: str = "data",
                   strategy: str = "fedpc", local_steps: int = 1,
                   lr: float = 0.01, betas=None,
                   privacy: PrivacySpec | None = None,
                   renorm_shares: bool = False, faults=None,
                   ledger=None) -> Callable:
    """fed_step(state, opt_states_F, batch_F, sizes, mask=None) ->
       (state', opt_states_F', metrics)

    batch_F: pytree with leaves (F, local_steps, B_local, ...) — each fed
    worker's private micro-batches for this round. Worker k trains
    ``local_steps`` steps from the shared global params (its private
    optimizer state persists), reports its final loss as the round cost.
    ``betas``/``mask`` as in :func:`build_fed_sync` (under SPMD every
    worker still computes when masked — the mask drops its contribution
    from the aggregate, the federated semantics of a skipped round), and
    so are ``privacy``/``renorm_shares``/``ledger`` — the secure-agg wire
    is reachable from the end-to-end driver, not only from the raw sync.
    """
    sync = build_fed_sync(model, mesh, fed_axis, strategy, betas=betas,
                          privacy=privacy, renorm_shares=renorm_shares,
                          faults=faults, ledger=ledger)

    def local_train(params, opt_state, batches):
        def step(carry, b):
            p, os = carry
            p, os, m = model.train_step(p, os, b, lr)
            return (p, os), m["loss"]
        (p, os), losses = jax.lax.scan(step, (params, opt_state), batches)
        return p, os, losses[-1]

    def fed_step(state: dict, opt_states_F: PyTree, batch_F: PyTree,
                 sizes: jax.Array, mask: jax.Array | None = None):
        params_F, opt_F, costs = jax.vmap(
            local_train, in_axes=(None, 0, 0))(
                state["params"], opt_states_F, batch_F)
        if mask is not None:    # a skipped worker's private state is frozen
            opt_F = jax.tree_util.tree_map(
                lambda new, old: jnp.where(
                    (mask > 0).reshape((-1,) + (1,) * (new.ndim - 1)),
                    new, old),
                opt_F, opt_states_F)
        new_params, aux = sync(params_F, costs, sizes, state, mask)
        metrics = {"cost_mean": jnp.mean(costs), "k_star": aux["k_star"]}
        return aux["state"], opt_F, metrics

    return fed_step


def fed_state_init(params: PyTree, n_fed: int) -> dict:
    return {
        "params": params,
        "params_prev": jax.tree_util.tree_map(jnp.zeros_like, params),
        "prev_costs": jnp.full((n_fed,), jnp.inf, jnp.float32),
        "round": jnp.asarray(1, jnp.int32),
    }


def fed_shardings(model: Model, mesh: Mesh, fed_axis: str,
                  params: PyTree) -> dict:
    """NamedShardings for the fed-step arguments."""
    pspecs = param_specs(params, mesh)

    def prepend_fed(spec: P) -> P:
        return P(fed_axis, *spec)

    stacked = jax.tree_util.tree_map(prepend_fed, pspecs)
    return {
        "params": jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), pspecs),
        "params_F": jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), stacked),
    }
