"""The FedPC round core — Algorithm 1 as a pure, device-resident recurrence.

The paper's round is one pure function of public state: score goodness
(Eq. (1)) → pick the pilot → ternarize/pack everyone's evolution
(Eq. (4)/(5), §3.3) → master update (Eq. (3)). This module expresses it
exactly that way:

* :class:`WirePath` owns the *math*: ternarize (Eq. (4)/(5)) → pack (§3.3)
  → aggregate (the masked Σ_k w_k β_k T_k) → master update (Eq. (3)), over
  the flat ``(rows, 128)`` buffers of ``repro.core.flat``. Fused Pallas
  kernels where the data layout allows, jnp reference semantics (``codes``
  / ``combine``) for runtimes that move their own bytes between the steps.
* :class:`RoundState` is the *whole* public inter-round state as one pytree:
  the history buffers P^{t-1}/P^{t-2}, last-round costs, and the round
  counter. It is a valid ``lax.scan`` carry and serializes through
  ``repro.checkpoint`` (:func:`save_round_state` / :func:`load_round_state`).
* :func:`WirePath.round_step` is the recurrence itself —
  ``(state, bufs_q, costs, sizes) -> (state', new_buf, info)`` — fully
  traceable: pilot selection stays on device (``k_star`` is never pulled to
  the host; the pilot buffer is gathered with a dynamic index), the batched
  uplink and the fused master update are the round's only two kernel
  launches, and both scenario axes ride along as optional operands: a
  per-round participation ``mask`` (sampled workers only) and a per-worker
  ``betas`` vector (heterogeneous beta_k on the wire).
* :func:`scan_rounds` drives many rounds as ONE ``lax.scan`` over
  ``round_step`` — zero per-round device→host transfers; the pilot history
  and per-round costs come back stacked in ``infos`` for a single post-scan
  fetch (ledger backfill).

Both runtimes are thin drivers over this core:

* ``repro.fed.simulator`` — in-process workers; ``run_fedpc`` steps
  ``round_step`` per round (workers are stateful Python), ``run_fedpc_scan``
  runs the whole federation under ``lax.scan``.
* ``repro.fed.distributed.build_fed_sync`` — workers are slices of a mesh
  axis; the shard_map body calls the same :class:`WirePath` methods on its
  local slab and moves bytes with collectives between them.

:class:`RoundEngine` remains as the thin stateful wrapper the per-round
drivers use (it holds the history buffers and calls the pure core).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.core import flat as fl
from repro.core.goodness import select_pilot
from repro.core.ternary import ternarize, ternarize_round1
from repro.core.tree import TreeSpec
from repro.kernels import ops
from repro.privacy import dp as pdp
from repro.privacy import masking as pvm
from repro.privacy import recovery as pvr
from repro.privacy.accountant import PrivacyAccountant
from repro.privacy.spec import PrivacySpec
from repro.telemetry import record as tmr
from repro.utils import PyTree

#: The plain (no-privacy) tree rides the integer wire so that float
#: non-associativity cannot break tree == flat bitwise parity: leaves are
#: weighted with fixed-point Eq. (3) coefficients at these parameters and
#: every tree edge carries modular uint32 words; the single root launch
#: de-biases by the public ΣW_k and descales by 2**-TREE_PLAIN_FIXPOINT_BITS.
TREE_PLAIN_WORD_BITS = 32
TREE_PLAIN_FIXPOINT_BITS = 24


@dataclass(frozen=True)
class WireConfig:
    """The three public protocol scalars of the FedPC wire path."""
    alpha0: float = 0.01      # Eq. (3) round-1 master step
    beta: float = 0.2         # Eq. (5) significance threshold
    alpha1: float = 0.01      # Eq. (4) round-1 threshold

    @classmethod
    def from_fedpc(cls, cfg) -> "WireConfig":
        """Lift the wire scalars out of a ``core.fedpc.FedPCConfig``."""
        return cls(alpha0=cfg.alpha0, beta=cfg.beta, alpha1=cfg.alpha_round1)


class RoundState(NamedTuple):
    """Device-resident inter-round federation state — one pure pytree.

    Everything Algorithm 1 carries between rounds, and nothing else: the
    two-step public history needed by Eq. (3)/(5), the previous costs needed
    by Eq. (1), and the 1-based index of the round about to run. Being a
    flat pytree of arrays makes it a ``lax.scan`` carry, a jit donation
    target, and a checkpointable object all at once.
    """
    buf_p1: jax.Array      # (rows, 128) — P^{t-1}
    buf_p2: jax.Array      # (rows, 128) — P^{t-2}
    prev_costs: jax.Array  # (N,) — C_k^{t-1}, +inf before round 1
    round: jax.Array       # scalar int32, 1-based round about to run
    accountant: Any = None  # PrivacyAccountant when the DP wire is on
    telemetry: Any = None   # TelemetryCarry — cumulative round counters


def init_round_state(init_params: PyTree, n_workers: int,
                     layout: fl.FlatLayout | None = None, *,
                     privacy: PrivacySpec | None = None,
                     telemetry: bool = True) -> RoundState:
    """Fresh :class:`RoundState` at round 1 (P^{t-2} = 0, costs = +inf).

    With a DP-enabled ``privacy`` spec the state carries a zeroed
    :class:`~repro.privacy.accountant.PrivacyAccountant` — four device
    scalars that ride the scan carry and the checkpoint alongside the
    history buffers. ``telemetry`` (default on) seeds a zeroed
    :class:`~repro.telemetry.record.TelemetryCarry` the same way, so the
    cumulative round counters checkpoint and resume with the federation.
    """
    layout = layout or fl.layout_of(init_params)
    buf_p1 = fl.flatten_tree(init_params, layout)
    return RoundState(
        buf_p1=buf_p1,
        buf_p2=jnp.zeros_like(buf_p1),
        prev_costs=jnp.full((n_workers,), jnp.inf, jnp.float32),
        round=jnp.asarray(1, jnp.int32),
        accountant=(PrivacyAccountant.zero()
                    if privacy is not None and privacy.dp_on else None),
        telemetry=tmr.TelemetryCarry.zero() if telemetry else None,
    )


def save_round_state(directory: str, state: RoundState,
                     metadata: dict | None = None) -> str:
    """Serialize a :class:`RoundState` through ``repro.checkpoint``.

    The (single, intentional) host sync here reads ``state.round`` for the
    checkpoint step — checkpointing is already an I/O barrier.
    """
    from repro.checkpoint import save_checkpoint
    meta = {"kind": "fedpc_round_state", **(metadata or {})}
    return save_checkpoint(directory, state._asdict(), int(state.round),
                           metadata=meta)


def load_round_state(directory: str, like: RoundState,
                     step: int | None = None) -> tuple[RoundState, dict]:
    """Restore a :class:`RoundState` saved by :func:`save_round_state`.

    ``like`` supplies the expected structure/shapes (strict-checked by the
    checkpoint layer) — e.g. ``init_round_state(params, n)``.
    """
    from repro.checkpoint import load_checkpoint
    tree, manifest = load_checkpoint(directory, like._asdict(), step)
    return RoundState(**tree), manifest


def participation_mask(key: jax.Array, n_workers: int,
                       fraction: float) -> jax.Array:
    """One round's FedAvg-style C-fraction mask: a traceable (N,) float32
    0/1 vector with ``max(1, round(C·N))`` uniformly sampled workers."""
    m = max(1, int(round(fraction * n_workers)))
    perm = jax.random.permutation(key, n_workers)
    return (perm < m).astype(jnp.float32)


def participation_masks(key: jax.Array, n_rounds: int, n_workers: int,
                        fraction: float, start_round: int = 1) -> jax.Array:
    """(n_rounds, N) masks — the per-round ``xs`` of :func:`scan_rounds`.
    Pre-generating them (rather than sampling inside the scan body) lets a
    Python-loop driver and the scan driver consume identical schedules.
    Each row is keyed by its ABSOLUTE round index (``start_round + i``), so
    a run resumed at round t draws exactly the rows an uninterrupted run
    would have used for rounds t, t+1, ..."""
    return jnp.stack([
        participation_mask(jax.random.fold_in(key, start_round + i),
                           n_workers, fraction)
        for i in range(n_rounds)])


@dataclass(frozen=True)
class WirePath:
    """Ternarize → pack → aggregate → master-update over flat buffers.

    Buffers (any ``(rows, 128)`` slab of a ``FlatLayout``) are passed to
    each method explicitly, so one WirePath serves full buffers and model
    shards alike. ``interpret=None`` defers to the backend (Python
    interpret on CPU, compiled on TPU); ``block_rows=None`` uses the
    kernels' VMEM-sized default tile.

    ``cfg.beta`` is the shared default threshold; every method that touches
    Eq. (5) or the Eq. (3) coefficients accepts an optional per-worker
    override (``beta=`` a traced scalar for single-worker slabs, ``betas=``
    a ``(N,)`` vector for stacked/aggregate forms).

    ``block_rows``/``block_workers`` pin the kernel tiling of the batched
    uplink and the accumulating master; left as None they resolve per
    (shape, N, backend) through the ``repro.kernels.tune`` table. Tiling
    never changes results — the master accumulates workers in a fixed
    sequential order, so every plan is bitwise-identical.

    ``privacy`` switches the round onto the secure-aggregation / local-DP
    wire (``repro.privacy``): the uplink becomes masked fixed-point words
    (``ternary_pack_masked_2d``) and the master a sum-then-unmask launch
    (``masked_master_update_2d``) — still two launches, still zero host
    syncs, and the master never sees an individual worker's ternary
    directions. ``renorm_shares`` enables the renormalized-share variant
    of Eq. (3) under partial participation: the data shares p_k are
    renormalized over the sampled set (mirroring the C-fraction FedAvg
    fix) instead of keeping the paper's global shares.

    ``tree`` switches the aggregation onto a hierarchical fan-in tree
    (:class:`repro.core.tree.TreeSpec`): instead of the master folding all
    N uplinks in one launch, each level folds sibling groups of ``fanout``
    children into one partial with a fused sub-aggregate kernel, and the
    root runs the master update over the last level's w_L ≤ fanout
    partials — master VMEM and grid are O(fanout), not O(N), and a round
    costs ``levels + 2`` launches. De-bias (−ΣW_k) and fixed-point descale
    happen exactly ONCE, at the root, over the public global ΣW_k, so the
    tree is bitwise identical to the flat path (modular accumulation is
    order-free). On the masked wire, pairwise mask streams are scoped per
    sibling group (leaf masks cancel inside the level-1 partial; each
    interior node adds its own level-salted sibling-scoped mask in-kernel)
    so every tree edge still carries masked words. Without privacy the
    tree rides the unmasked integer wire at ``TREE_PLAIN_WORD_BITS`` /
    ``TREE_PLAIN_FIXPOINT_BITS`` — identical bits to the flat integer
    comparator; vs the float flat master it differs only by the
    fixed-point weight quantization.

    ``faults`` attaches a deterministic failure schedule
    (:class:`repro.fed.faults.FaultPlan`): each round realizes per-worker
    fault codes from the plan's counter stream and excludes faulted workers
    from pilot selection and the aggregate. On the plain wire faults simply
    fold into the Eq. (3) weights (survivors-only, exactly); on the masked
    wire the uplink was already committed when a post-uplink death is
    observed, so the dead rows are dropped from the modular sum, the root
    de-bias reweights by the surviving ΣW_k, and the survivors' uncancelled
    pairwise masks toward the dead are repaired in one fused
    ``mask_repair_2d`` launch from the recovered pair streams
    (``repro.privacy.recovery`` — requires ``privacy.recovery_threshold``).
    A sibling group left with fewer than ``recovery_threshold`` survivors
    degrades to an exact-zero subtree instead of aborting.
    """
    cfg: WireConfig = WireConfig()
    interpret: bool | None = None
    block_rows: int | None = None
    block_workers: int | None = None
    privacy: PrivacySpec | None = None
    renorm_shares: bool = False
    tree: TreeSpec | None = None
    faults: Any = None

    # -- elementwise protocol math (jnp semantics, traced round index) ------

    def codes(self, q: jax.Array, p1: jax.Array, p2: jax.Array,
              t, *, beta=None) -> jax.Array:
        """Eq. (4) at t <= 1 (``p1`` holds P^0), Eq. (5) after; int8 codes
        of ``q.shape``. Works on any slab/shape — it is elementwise.
        ``beta`` (scalar, may be traced) overrides the shared threshold."""
        beta = self.cfg.beta if beta is None else beta
        t1 = ternarize_round1(q, p1, self.cfg.alpha1)
        tt = ternarize(q, p1, p2, beta)
        return jnp.where(jnp.asarray(t) <= 1, t1, tt)

    def combine(self, q_pilot: jax.Array, coeff: jax.Array, p1: jax.Array,
                p2: jax.Array, t) -> jax.Array:
        """Eq. (3) given the aggregated ``coeff = Σ_k w_k T_k``: round 1
        steps by ``alpha0``, later rounds by the history step P^{t-1}-P^{t-2}."""
        step = (p1 - p2).astype(jnp.float32)
        r1 = q_pilot - self.cfg.alpha0 * coeff
        rt = q_pilot - coeff * step
        return jnp.where(jnp.asarray(t) <= 1, r1, rt)

    def weights(self, p_shares: jax.Array, k_star, t, *, betas=None,
                mask=None) -> jax.Array:
        """Masked per-worker Eq. (3) coefficients: p_k at round 1 (the
        alpha0 rule), p_k·beta_k after; the pilot's entry is zeroed.

        ``betas`` is an optional (N,) per-worker beta_k vector (defaults to
        the shared ``cfg.beta``); ``mask`` an optional (N,) participation
        mask — non-participants contribute exactly ±0.0 to the reduce, the
        same mechanism that already masks the pilot. By default shares are
        NOT renormalized over the sampled set: p_k = S_k/S stays the
        paper's global data share, so a round's update magnitude scales
        with how much data actually reported; with ``renorm_shares`` the
        shares are renormalized over the sampled workers (the C-fraction
        FedAvg convention), keeping the update magnitude constant across
        rounds regardless of who reported."""
        n = p_shares.shape[0]
        if self.renorm_shares and mask is not None:
            pm = p_shares.astype(jnp.float32) * jnp.asarray(mask,
                                                            jnp.float32)
            p_shares = pm / jnp.maximum(jnp.sum(pm), 1e-12)
        not_pilot = (jnp.arange(n) != k_star).astype(jnp.float32)
        if betas is None:
            scale = jnp.where(jnp.asarray(t) <= 1, 1.0, self.cfg.beta)
        else:
            betas = jnp.asarray(betas, jnp.float32)
            scale = jnp.where(jnp.asarray(t) <= 1, jnp.ones_like(betas),
                              betas)
        w = not_pilot * p_shares.astype(jnp.float32) * scale
        if mask is not None:
            w = w * jnp.asarray(mask, jnp.float32)
        return w

    # -- fused kernel path over (rows, 128) slabs ---------------------------

    def uplink(self, buf_q: jax.Array, buf_p1: jax.Array, buf_p2: jax.Array,
               *, t, beta=None) -> jax.Array:
        """One worker's §3.3 wire buffer: (rows, 128) → (rows//4, 128)
        uint8, one launch, no int8 intermediate. ``t`` (and an optional
        per-worker ``beta``) may be traced — the distributed sync's
        per-slab uplink."""
        beta = self.cfg.beta if beta is None else beta
        return ops.flat_ternary_pack(
            buf_q, buf_p1, buf_p2, t=t, beta=beta,
            alpha1=self.cfg.alpha1, interpret=self.interpret,
            block_rows=self.block_rows)

    def uplink_stacked(self, bufs_q: jax.Array, buf_p1: jax.Array,
                       buf_p2: jax.Array, *, t, betas=None) -> jax.Array:
        """All N workers' wire buffers in ONE launch: (N, rows, 128) →
        (N, rows//4, 128) uint8 — the batched uplink (rows-major grid, the
        shared history block is fetched once per row block, not once per
        worker). ``betas`` is an optional (N,) per-worker beta_k vector."""
        beta = self.cfg.beta if betas is None else betas
        return ops.flat_ternary_pack_stacked(
            bufs_q, buf_p1, buf_p2, t=t, beta=beta,
            alpha1=self.cfg.alpha1, interpret=self.interpret,
            block_rows=self.block_rows, block_workers=self.block_workers)

    def master(self, buf_pilot: jax.Array, packed: jax.Array, w: jax.Array,
               buf_p1: jax.Array, buf_p2: jax.Array, *, t) -> jax.Array:
        """Fused Eq. (3) over packed wire codes: register-only 2-bit decode
        (w folded into the de-bias) grid-accumulated over the worker axis
        into the resident output block — one launch, VMEM independent of N.
        ``t`` may be traced."""
        return ops.flat_master_update(
            buf_pilot, packed, w, buf_p1, buf_p2, t=t,
            alpha0=self.cfg.alpha0, interpret=self.interpret,
            block_rows=self.block_rows, block_workers=self.block_workers)

    # -- secure-aggregation / local-DP wire (repro.privacy) -----------------

    def uplink_masked(self, bufs_q: jax.Array, buf_p1: jax.Array,
                      buf_p2: jax.Array, *, t, w: jax.Array, betas=None,
                      pmask=None) -> tuple[jax.Array, jax.Array]:
        """All N workers' masked secure-agg wire words in ONE launch.

        Derives the round's (N, N) pairwise stream-key and sign matrices
        (counter chains keyed by the — possibly traced — absolute round
        ``t``, participation folded into the signs) and the (N,) RR key
        vector, quantizes the public Eq. (3) weights ``w`` to fixed point,
        and runs the fused masked uplink: the mask/RR planes are generated
        INSIDE the kernel from those keys — codes exist only in kernel
        registers, no mask tensor ever exists in HBM, and what HBM sees is
        masked ``spec.word_dtype`` words. ``pmask`` is the public
        participation mask (pairs are active only between sampled
        workers). Returns ``(masked_words, wq)``.
        """
        spec = self.privacy
        n = bufs_q.shape[0]
        wq = pvm.quantize_weights(w, spec.fixpoint_bits)
        keys = pvm.pair_stream_keys(
            spec.mask_seed if spec.masking_on else 0, n, t)
        if self.tree is not None:
            # Leaf masks are scoped to sibling groups so they cancel inside
            # the level-1 partial, not only at the root.
            signs = pvm.tree_pair_signs(n, self.tree.fanout,
                                        participation=pmask)
        else:
            signs = pvm.pair_signs(n, participation=pmask)
        rrk = pdp.rr_stream_keys(spec.dp_seed, t, n)
        beta = self.cfg.beta if betas is None else betas
        y = ops.flat_ternary_pack_masked(
            bufs_q, buf_p1, buf_p2, t=t, beta=beta,
            alpha1=self.cfg.alpha1, wq=wq, pair_keys=keys,
            pair_signs=signs, rr_keys=rrk,
            rr_threshold=spec.rr_threshold,
            word_bits=spec.modulus_bits, use_masks=spec.masking_on,
            interpret=self.interpret, block_rows=self.block_rows,
            block_workers=self.block_workers)
        return y, wq

    def uplink_masked_slab(self, buf_q: jax.Array, buf_p1: jax.Array,
                           buf_p2: jax.Array, *, t, wq_own, keys_row,
                           signs_row, rr_key, beta=None) -> jax.Array:
        """One worker's masked wire words over a single (sr, 128) slab —
        the distributed per-instance form (the stacked kernel at N = 1).
        ``wq_own`` is this worker's fixed-point weight (traced scalar);
        ``keys_row``/``signs_row`` its (n_fed,) row of the pairwise
        key/sign matrices (``masking.pair_stream_keys_row`` at a traced
        worker index); ``rr_key`` its uint32 RR stream key. The mask/RR
        streams are generated inside the kernel. Returns (sr//4, 512) in
        ``spec.word_dtype``.
        """
        spec = self.privacy
        beta = self.cfg.beta if beta is None else beta
        y = ops.flat_ternary_pack_masked(
            buf_q[None], buf_p1, buf_p2, t=t, beta=beta,
            alpha1=self.cfg.alpha1, wq=jnp.reshape(wq_own, (1,)),
            pair_keys=jnp.reshape(keys_row, (1, -1)),
            pair_signs=jnp.reshape(signs_row, (1, -1)),
            rr_keys=jnp.reshape(rr_key, (1,)),
            rr_threshold=spec.rr_threshold,
            word_bits=spec.modulus_bits, use_masks=spec.masking_on,
            interpret=self.interpret, block_rows=self.block_rows,
            block_workers=self.block_workers)
        return y[0]

    def master_masked(self, buf_pilot: jax.Array, masked: jax.Array,
                      wq: jax.Array, buf_p1: jax.Array, buf_p2: jax.Array,
                      *, t) -> jax.Array:
        """Sum-then-unmask Eq. (3): modular sum of the masked words (masks
        cancel exactly), integer de-bias by the public ``sum_k W_k``,
        fixed-point descale with the RR unbias folded in, combine."""
        spec = self.privacy
        return ops.flat_masked_master_update(
            buf_pilot, masked, jnp.sum(wq), buf_p1, buf_p2, t=t,
            alpha0=self.cfg.alpha0, scale_mult=spec.scale_mult,
            interpret=self.interpret, block_rows=self.block_rows,
            block_workers=self.block_workers)

    def _tree_fold_masked(self, y: jax.Array, *, t, pmask=None) -> jax.Array:
        """Fold the N masked leaf uplinks level by level down to the last
        level's w_L partials — one fused sub-aggregate launch per level.

        Level l's nodes each sum their ``fanout`` children (whose
        sibling-scoped masks cancel in the modular sum) and add their OWN
        net mask from the level-salted stream
        (``tree_level_seed(mask_seed, l)``), scoped to level-l sibling
        groups — so the words crossing every tree edge stay masked, and all
        masks have cancelled exactly when the root sums the last level.
        ``pmask`` participation folds upward: a node is active iff any
        descendant leaf is, and masks only pair active nodes."""
        spec, ts = self.privacy, self.tree
        n = y.shape[0]
        widths = ts.level_widths(n)
        act = None if pmask is None else jnp.asarray(pmask, jnp.float32)
        cur = y
        for lvl in range(1, len(widths)):
            g = widths[lvl]
            sib = ts.sibling_size(lvl, n)
            if act is not None:
                act = pvm.tree_activity(act, ts.fanout)
            if spec.masking_on:
                keys = pvm.pair_stream_keys(
                    pvm.tree_level_seed(spec.mask_seed, lvl), g, t)
            else:
                keys = jnp.zeros((g, g), jnp.uint32)
            signs = pvm.tree_pair_signs(g, sib, participation=act)
            cur = ops.flat_masked_partial_sum(
                cur, keys, signs, fanout=ts.fanout, sibling=sib,
                use_masks=spec.masking_on, interpret=self.interpret,
                block_rows=self.block_rows,
                block_groups=self.block_workers)
        return cur

    def _tree_round_plain(self, bufs_q: jax.Array, k_star, w: jax.Array,
                          buf_p1: jax.Array, buf_p2: jax.Array, *, t,
                          betas=None) -> tuple[jax.Array, jax.Array]:
        """The no-privacy tree round: packed §3.3 leaves → fixed-point
        weighted level-1 partials → unmasked interior folds → one root
        sum-and-descale. Rides the integer wire (uint32 words, Eq. (3)
        weights quantized at ``TREE_PLAIN_FIXPOINT_BITS``) so the result is
        invariant to tree shape — bitwise equal to the flat integer path
        for every fanout, ragged groups included."""
        ts = self.tree
        n = bufs_q.shape[0]
        packed = self.uplink_stacked(bufs_q, buf_p1, buf_p2, t=t,
                                     betas=betas)
        wq = pvm.quantize_weights(w, TREE_PLAIN_FIXPOINT_BITS)
        cur = ops.flat_partial_sum(
            packed, wq, fanout=ts.fanout, word_bits=TREE_PLAIN_WORD_BITS,
            interpret=self.interpret, block_rows=self.block_rows,
            block_groups=self.block_workers)
        widths = ts.level_widths(n)
        for lvl in range(2, len(widths)):
            g = widths[lvl]
            sib = ts.sibling_size(lvl, n)
            cur = ops.flat_masked_partial_sum(
                cur, jnp.zeros((g, g), jnp.uint32),
                jnp.zeros((g, g), jnp.int32), fanout=ts.fanout,
                sibling=sib, use_masks=False, interpret=self.interpret,
                block_rows=self.block_rows,
                block_groups=self.block_workers)
        buf_pilot = jnp.take(bufs_q, k_star, axis=0)
        new_buf = ops.flat_masked_master_update(
            buf_pilot, cur, jnp.sum(wq), buf_p1, buf_p2, t=t,
            alpha0=self.cfg.alpha0,
            scale_mult=2.0 ** -TREE_PLAIN_FIXPOINT_BITS,
            interpret=self.interpret, block_rows=self.block_rows,
            block_workers=self.block_workers)
        return new_buf, packed

    def aggregate_masked(self, bufs_q: jax.Array, buf_p1: jax.Array,
                         buf_p2: jax.Array, *, t, w: jax.Array, betas=None,
                         pmask=None, alive=None
                         ) -> tuple[jax.Array, jax.Array, jax.Array]:
        """Everything of a masked round before the root unmask: the masked
        uplink, the post-fault drop of dead rows, the tree folds and the
        dropout repair. Returns ``(y_top, wq, y)`` — the root's word
        partials (their modular sum is the recovered aggregate
        ``sum_k W_k field_k`` over the survivors, masks cancelled), the
        surviving fixed-point weights, and the committed leaf uplinks."""
        spec = self.privacy
        y, wq = self.uplink_masked(bufs_q, buf_p1, buf_p2, t=t, w=w,
                                   betas=betas, pmask=pmask)
        repair = None
        if alive is not None:
            if spec.recovery_threshold is None:
                raise ValueError(
                    "fault injection on the privacy wire requires "
                    "privacy.recovery_threshold (the Shamir t of the "
                    "dropout-recovery dealing) to be set")
            n = bufs_q.shape[0]
            gsz = self.tree.fanout if self.tree is not None else None
            alive_eff, dead_eff = pvr.effective_masks(
                pmask, alive, spec.recovery_threshold, gsz, n)
            # Post-uplink deaths: each dead row leaves the modular sum
            # (its weighted fields AND its own net mask), taking its W_k
            # out of the de-bias; what remains is the survivors'
            # uncancelled masks toward the dead, repaired below.
            y = jnp.where(alive_eff[:, None, None] > 0, y,
                          jnp.zeros_like(y))
            wq = jnp.where(alive_eff > 0, wq, jnp.zeros_like(wq))
            if spec.masking_on:
                i_idx, j_idx = pvr.repair_pair_index(n, gsz)
                keys = pvm.pair_stream_keys(spec.mask_seed, n, t)
                if self.tree is not None:
                    signs = pvm.tree_pair_signs(n, self.tree.fanout,
                                                participation=pmask)
                else:
                    signs = pvm.pair_signs(n, participation=pmask)
                repair = pvr.repair_coefficients(
                    keys, signs, alive_eff, dead_eff, i_idx, j_idx)
        y_top = (self._tree_fold_masked(y, t=t, pmask=pmask)
                 if self.tree is not None else y)
        if repair is not None:
            # Modular sums commute, so leaf-level residue rides the tree
            # unchanged and ONE fused launch at the root repairs every
            # surviving-toward-dead stream. The repair lands in a static
            # row: even a zeroed dead row still participates in the
            # master's modular sum.
            y_top = y_top.at[0].set(ops.flat_mask_repair(
                y_top[0], repair[0], repair[1],
                interpret=self.interpret, block_rows=self.block_rows))
        return y_top, wq, y

    def round_from_stacked(self, bufs_q: jax.Array, k_star, w: jax.Array,
                           buf_p1: jax.Array, buf_p2: jax.Array, *, t,
                           betas=None, pmask=None, alive=None
                           ) -> tuple[jax.Array, jax.Array]:
        """A full round over stacked worker buffers: batched uplink + fused
        master — exactly two kernel launches regardless of N.

        The pilot's row is packed like everyone else's and masked out of
        Eq. (3) by ``w[k_star] == 0`` (bitwise identical to zero-filling it:
        0·T contributes exactly ±0.0 to the reduce) — the same mechanism
        drops non-participating workers when ``w`` carries a mask.

        ``k_star`` may be traced: the pilot buffer is gathered with a
        dynamic index, no host sync. With an active :class:`PrivacySpec`
        the round takes the masked wire instead (same launch count; the
        wire buffer is uint32 masked words). ``pmask`` is the public
        participation mask, consumed only by the masked wire's pairwise
        mask derivation. ``alive`` is the post-fault (N,) survival mask of
        the privacy wire's dropout-recovery path: dead rows leave the
        modular sum, the de-bias reweights by the surviving ΣW_k and the
        residual masks are repaired at the root (see :class:`WirePath`
        docstring). Returns ``(new_global_buf, wire_buffer)`` — the wire
        buffers ride along for byte accounting / ledger purposes.
        """
        if self.privacy is not None and self.privacy.active:
            y_top, wq, y = self.aggregate_masked(
                bufs_q, buf_p1, buf_p2, t=t, w=w, betas=betas, pmask=pmask,
                alive=alive)
            buf_pilot = jnp.take(bufs_q, k_star, axis=0)
            new_buf = self.master_masked(buf_pilot, y_top, wq, buf_p1,
                                         buf_p2, t=t)
            return new_buf, y
        if self.tree is not None:
            return self._tree_round_plain(bufs_q, k_star, w, buf_p1,
                                          buf_p2, t=t, betas=betas)
        packed = self.uplink_stacked(bufs_q, buf_p1, buf_p2, t=t,
                                     betas=betas)
        buf_pilot = jnp.take(bufs_q, k_star, axis=0)
        new_buf = self.master(buf_pilot, packed, w, buf_p1, buf_p2, t=t)
        return new_buf, packed

    # -- the pure recurrence ------------------------------------------------

    def round_step(self, state: RoundState, bufs_q: jax.Array,
                   costs: jax.Array, sizes: jax.Array, *, betas=None,
                   mask=None) -> tuple[RoundState, jax.Array, dict]:
        """Algorithm 1, one full round, as a pure traced function.

        ``state`` — inter-round carry; ``bufs_q`` (N, rows, 128) — every
        worker's flattened local model; ``costs``/``sizes`` (N,). Optional
        ``betas`` (N,) per-worker beta_k and ``mask`` (N,) participation
        (non-participants: excluded from pilot selection, zero Eq. (3)
        weight, previous cost carried forward — their ``bufs_q`` row may be
        anything, conventionally the current global buffer).

        With a :class:`~repro.fed.faults.FaultPlan` attached, the round
        additionally realizes its per-worker fault codes from
        ``state.round`` (so ``scan_rounds`` needs no extra operand) and
        excludes faulted workers exactly like non-participants — on the
        masked wire via the post-uplink dropout-recovery path.

        Returns ``(state', new_global_buf, info)`` with ``info`` holding the
        on-device round records (``k_star``, ``goodness``, ``costs``, plus
        ``alive`` when faults are active) that a driver fetches ONCE after
        all rounds to backfill ledger and pilot history. Exactly two kernel
        launches (plus one repair launch on post-fault masked rounds); zero
        host syncs.
        """
        t = state.round
        sizes = jnp.asarray(sizes, jnp.float32)
        costs = jnp.asarray(costs, jnp.float32)
        n = sizes.shape[0]
        av = codes = dead_eff = None
        masked_wire = self.privacy is not None and self.privacy.active
        if self.faults is not None and self.faults.active:
            codes = self.faults.codes(t, n)
            av = (codes == tmr.FAULT_NONE).astype(jnp.float32)
        if av is None:
            sel_mask = mask
        elif masked_wire:
            # A sibling group below the recovery threshold degrades to an
            # exact-zero subtree, so its SURVIVORS contribute nothing
            # either — the master (which knows the fault set and the
            # public threshold) excludes them from pilot selection and the
            # cost carry exactly like the dead.
            if self.privacy.recovery_threshold is None:
                raise ValueError(
                    "fault injection on the privacy wire requires "
                    "privacy.recovery_threshold (the Shamir t of the "
                    "dropout-recovery dealing) to be set")
            sel_mask, dead_eff = pvr.effective_masks(
                mask, av, self.privacy.recovery_threshold,
                self.tree.fanout if self.tree is not None else None,
                n)
        elif mask is None:
            sel_mask = av
        else:
            sel_mask = jnp.asarray(mask, jnp.float32) * av
        k_star, scores = select_pilot(costs, state.prev_costs, sizes, t,
                                      sel_mask)
        p_shares = sizes / jnp.sum(sizes)
        # The masked wire commits Eq. (3) weights BEFORE faults realize —
        # the uplink is already on the wire when a post-uplink death is
        # observed — so dead rows are excluded downstream and the de-bias
        # reweights by the surviving ΣW_k. The plain wire has no such
        # commitment: faults fold straight into the weights, which IS the
        # survivors-only aggregate.
        w_mask = mask if masked_wire else sel_mask
        w = self.weights(p_shares, k_star, t, betas=betas, mask=w_mask)
        new_buf, _wire = self.round_from_stacked(
            bufs_q, k_star, w, state.buf_p1, state.buf_p2, t=t, betas=betas,
            pmask=mask, alive=(av if masked_wire else None))
        if sel_mask is None:
            costs_eff = costs
        else:   # non-participants / faulted workers did not report a cost
            costs_eff = jnp.where(jnp.asarray(sel_mask) > 0, costs,
                                  state.prev_costs)
        accountant = state.accountant
        if (accountant is not None and self.privacy is not None
                and self.privacy.dp_on):
            accountant = accountant.add(self.privacy.eps_round)
        # The round's device-resident telemetry record: jnp reductions over
        # operands computed above — no extra launches, no host syncs. The
        # record rides info (stacked by the scan for the one post-run
        # fetch); the cumulative carry rides the state like the accountant.
        rec = tmr.build_round_record(
            t=t, k_star=k_star, n=n, costs=costs, sizes=sizes, mask=mask,
            codes=codes, sel_mask=sel_mask, dead_eff=dead_eff,
            modulus_bits=self.privacy.modulus_bits if masked_wire else 0,
            fanout=self.tree.fanout if self.tree is not None else 0,
            levels=(self.tree.n_levels(n) if self.tree is not None else 0))
        telemetry = state.telemetry
        if telemetry is not None:
            telemetry = telemetry.add(rec)
        new_state = RoundState(buf_p1=new_buf, buf_p2=state.buf_p1,
                               prev_costs=costs_eff, round=t + 1,
                               accountant=accountant, telemetry=telemetry)
        info = {"k_star": k_star, "goodness": scores, "costs": costs_eff,
                "telemetry": rec}
        if mask is not None:
            info["mask"] = jnp.asarray(mask, jnp.float32)
        if av is not None:
            info["alive"] = av
        return new_state, new_buf, info


WorkerFn = Callable[[Any, jax.Array, jax.Array],
                    tuple[Any, jax.Array, jax.Array]]


def scan_rounds(wire: WirePath, state: RoundState, worker_fn: WorkerFn,
                worker_carry: Any, n_rounds: int, sizes: jax.Array, *,
                betas=None, masks=None, participation: float | None = None,
                participation_key: jax.Array | None = None
                ) -> tuple[RoundState, Any, dict]:
    """Many rounds of Algorithm 1 as ONE ``lax.scan`` over ``round_step``.

    ``worker_fn(worker_carry, global_buf, t) -> (worker_carry, bufs_q,
    costs)`` produces the round's local models — it is traced into the scan
    body, so it must be pure (private optimizer states etc. live in
    ``worker_carry``). ``masks`` is an optional (n_rounds, N) participation
    schedule (see :func:`participation_masks`); ``betas`` an optional (N,)
    per-worker beta_k vector.

    Alternatively the participation mask can be sampled INSIDE the scan
    body — pass ``participation`` (the C fraction) and a
    ``participation_key``: each round draws
    ``participation_mask(fold_in(key, t), N, C)`` with the ABSOLUTE round
    index ``t`` from the carry, so no (n_rounds, N) host-side schedule is
    materialized (cross-device scale) and a resumed run draws exactly the
    rows an uninterrupted run would — bit-identical to the precomputed
    :func:`participation_masks` schedule from the same key. The sampled
    masks come back in ``infos["mask"]`` for ledger backfill.

    The scan body costs exactly two kernel launches and performs zero
    device→host transfers; ``infos`` comes back with per-round stacked
    ``k_star`` / ``goodness`` / ``costs`` for one post-scan fetch. XLA
    double-buffers the carry, so the history buffers are reused in place
    across rounds (jit the caller with ``donate_argnums`` on ``state`` to
    extend that to the initial buffers).
    """
    sizes = jnp.asarray(sizes, jnp.float32)
    n_workers = sizes.shape[0]
    if participation is not None:
        if masks is not None:
            raise ValueError("pass a precomputed mask schedule OR in-scan "
                             "participation sampling, not both")
        if participation_key is None:
            raise ValueError("in-scan participation sampling needs a "
                             "participation_key")
        if not 0.0 < participation <= 1.0:
            raise ValueError(
                f"participation must be in (0, 1], got {participation}")

    def body(carry, x):
        st, wc = carry
        mask = x
        if participation is not None:
            mask = participation_mask(
                jax.random.fold_in(participation_key, st.round),
                n_workers, participation)
        wc, bufs_q, costs = worker_fn(wc, st.buf_p1, st.round)
        st, _new_buf, info = wire.round_step(st, bufs_q, costs, sizes,
                                             betas=betas, mask=mask)
        return (st, wc), info

    (state, worker_carry), infos = jax.lax.scan(
        body, (state, worker_carry), masks, length=n_rounds)
    return state, worker_carry, infos


class RoundEngine:
    """Carries the public history across rounds and drives :class:`WirePath`.

    The per-round drivers' protocol work reduces to::

        bufs_q = engine.flatten_locals(locals_)           # stack worker trees
        new_params = engine.run_round(bufs_q, k_star, p_shares, t)

    which is two kernel launches + one unflatten. The history rotation
    (P^{t-1}, P^{t-2}) ← (P^t, P^{t-1}) happens inside ``run_round``. This
    is a thin stateful wrapper over the pure core — jit-able multi-round
    drivers should carry a :class:`RoundState` through
    :meth:`WirePath.round_step` / :func:`scan_rounds` instead.
    """

    def __init__(self, init_params: PyTree, cfg: WireConfig | None = None,
                 *, shards: int = 1, interpret: bool | None = None,
                 block_rows: int | None = None,
                 block_workers: int | None = None):
        self.layout = fl.layout_of(init_params, shards=shards)
        self.wire = WirePath(cfg or WireConfig(), interpret=interpret,
                             block_rows=block_rows,
                             block_workers=block_workers)
        self.buf_p1 = fl.flatten_tree(init_params, self.layout)   # P^{t-1}
        self.buf_p2 = jnp.zeros_like(self.buf_p1)                 # P^{t-2}

    def flatten_locals(self, locals_: list[PyTree]) -> jax.Array:
        """Stack N worker pytrees into the (N, rows, 128) uplink input."""
        stacked = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs), *locals_)
        return fl.flatten_stacked(stacked, self.layout)

    def run_round(self, bufs_q: jax.Array, k_star, p_shares: jax.Array,
                  t, *, betas=None, mask=None) -> PyTree:
        """Alg. 1 lines 5-8 for one round; returns the new global pytree and
        advances the engine's history. ``k_star`` may be traced."""
        w = self.wire.weights(p_shares, k_star, t, betas=betas, mask=mask)
        new_buf, _packed = self.wire.round_from_stacked(
            bufs_q, k_star, w, self.buf_p1, self.buf_p2, t=t, betas=betas)
        self.buf_p1, self.buf_p2 = new_buf, self.buf_p1
        return fl.unflatten_tree(new_buf, self.layout)
