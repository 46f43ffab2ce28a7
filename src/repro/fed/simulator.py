"""Single-process federated simulator — the paper's experimental testbed.

Drives FedPC, FedAvg and Phong et al. over N in-process workers with private
data shards and private hyper-parameters, with exact Eq. (8) byte accounting
and the §4.2 information-flow ledger enforced on every round.

Two FedPC drivers share the pure round core (``repro.fed.rounds``):

* :meth:`FedSimulator.run_fedpc` — workers are stateful Python objects, so
  rounds step in a Python loop, but the protocol is device-resident: pilot
  selection is traced (``k_star`` never syncs to the host mid-run), worker
  costs stay device scalars, and the ledger / pilot history are backfilled
  from ONE post-loop fetch. The only per-round host syncs left are the
  opt-in worker-side evasion defence (``evade_streak`` — inherently a host
  behaviour: workers compare their history to decide what to report) and
  ``eval_every``.
* :meth:`FedSimulator.run_fedpc_scan` — the jitted multi-round path: every
  worker's batch schedule is pre-drawn on the host, then ALL rounds run as
  one ``lax.scan`` over ``WirePath.round_step`` — two kernel launches per
  round, zero per-round device→host transfers.

Both drivers support the two scenario axes of the round core: FedAvg-style
C-fraction **partial participation** (sampled workers only; the same
pre-generated mask schedule feeds both drivers) and **heterogeneous
per-worker beta_k** on the wire.

This is what the paper-table benchmarks (Tables 2–4, Figs 4/6) run on; the
TPU-mesh counterpart with the same math as collectives is
``repro.fed.distributed``.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from functools import partial
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import baselines as bl
from repro.core import fedpc as fp
from repro.core import flat as fl
from repro.core import protocol as proto
from repro.core.privacy import LeakageLedger
from repro.fed import faults as ft
from repro.fed import rounds as rd
from repro.fed.worker import Worker
from repro.privacy import audit as pv_audit
from repro.telemetry import trace as tmt
from repro.utils import PyTree


@dataclass
class SimResult:
    algorithm: str
    params: PyTree
    costs: list = field(default_factory=list)          # per-round mean cost
    pilot_history: list = field(default_factory=list)  # FedPC only
    eval_history: list = field(default_factory=list)
    round_state: Optional[rd.RoundState] = None        # FedPC resume handle
    # The FedPC drivers' byte accounting lives in the telemetry rollup (the
    # device-recorded counts pushed through core.protocol and cross-checked
    # in build_trace); bytes_per_round / recovery_bytes_per_round are thin
    # views over it. The baseline drivers (fedavg/phong/centralized) have
    # no traced round program and append into the backing lists directly.
    telemetry: Optional[tmt.TraceSummary] = None
    _bytes: list = field(default_factory=list)
    _recovery_bytes: list = field(default_factory=list)

    @property
    def bytes_per_round(self) -> list:
        if self.telemetry is not None:
            return self.telemetry.bytes_per_round
        return self._bytes

    @property
    def recovery_bytes_per_round(self) -> list:
        # Dropout-recovery control-plane bytes (share dealing +
        # reconstruction), accounted SEPARATELY from the data-plane bytes.
        if self.telemetry is not None:
            return self.telemetry.recovery_bytes_per_round
        return self._recovery_bytes

    @property
    def total_bytes(self) -> float:
        return float(np.sum(self.bytes_per_round)
                     + np.sum(self.recovery_bytes_per_round))


class ScanProgram(NamedTuple):
    """One compiled multi-round scan program and its compile seconds."""
    compiled: Any
    compile_s: float


def _should_donate() -> bool:
    """Donate the RoundState buffers into the jitted step where the backend
    honours donation (CPU silently copies and warns, so skip it there)."""
    return jax.default_backend() != "cpu"


def _own_state(state: rd.RoundState, was_caller_supplied: bool
               ) -> rd.RoundState:
    """Copy a caller-supplied resume state before it enters a donating jit —
    the caller keeps a valid handle (e.g. for save_round_state or a second
    driver run from the same checkpoint)."""
    if was_caller_supplied and _should_donate():
        return jax.tree_util.tree_map(jnp.copy, state)
    return state


class FedSimulator:
    def __init__(self, workers: list[Worker], init_params: PyTree,
                 fed_cfg: Optional[fp.FedPCConfig] = None,
                 eval_fn: Optional[Callable[[PyTree], float]] = None,
                 evade_streak: int = 0):
        self.workers = workers
        self.init_params = init_params
        self.n = len(workers)
        self.fed_cfg = fed_cfg or fp.FedPCConfig(n_workers=self.n)
        self.sizes = np.array([w.loader.n for w in workers], np.float32)
        self.eval_fn = eval_fn
        self.ledger = LeakageLedger()
        self.evade_streak = evade_streak  # 0 = defence off
        # Compiled run_fedpc_scan programs by (rounds, wire, has-masks,
        # has-betas): reused by every later run of that shape (a resumed
        # run does not recompile); their HLO is what a chip check inspects.
        self.scan_programs: dict[tuple, ScanProgram] = {}

    # ------------------------------------------------------------------
    # FedPC shared plumbing
    # ------------------------------------------------------------------
    def _resolve_scenario(self, participation, betas, rounds, seed, t0):
        """(masks host (R,N) float or None, betas device (N,) or None).

        Masks are keyed by ABSOLUTE round (``t0`` onward), so a resumed run
        draws the same schedule an uninterrupted run would for those rounds.
        """
        cfg = self.fed_cfg
        frac = cfg.participation if participation is None else participation
        if not 0.0 < frac <= 1.0:
            raise ValueError(f"participation must be in (0, 1], got {frac}")
        masks = None
        if frac < 1.0:
            masks = np.asarray(rd.participation_masks(
                jax.random.PRNGKey(seed), rounds, self.n, frac,
                start_round=t0))
        if betas is not None:
            betas_arr = jnp.asarray(betas, jnp.float32)
        elif cfg.betas is not None:
            betas_arr = cfg.beta_vector
        else:
            # Workers that drew a private beta_k (make_worker_configs'
            # beta_menu sets WorkerConfig.beta; None = no draw) put them on
            # the wire, with cfg.beta filling any gaps; an undrawn fleet
            # stays on the shared-scalar path so cfg.beta remains the
            # single knob (bitwise-identical to before).
            wb = [w.cfg.beta for w in self.workers]
            betas_arr = (jnp.asarray(
                [cfg.beta if b is None else b for b in wb], jnp.float32)
                if any(b is not None for b in wb) else None)
        return masks, betas_arr

    def _wire_path(self, wire_block_rows, wire_block_workers) -> rd.WirePath:
        """The round's WirePath with the config's privacy/renorm axes."""
        cfg = self.fed_cfg
        return rd.WirePath(rd.WireConfig.from_fedpc(cfg),
                           block_rows=wire_block_rows,
                           block_workers=wire_block_workers,
                           privacy=cfg.privacy,
                           renorm_shares=cfg.renorm_shares,
                           tree=cfg.tree,
                           faults=cfg.faults)

    def _fault_codes(self, t0: int, n_rounds: int) -> np.ndarray | None:
        """(R, N) host copy of the fault schedule, or None without a plan.
        The plan is a pure function of (seed, round, worker), so the host
        recomputes it — no extra device→host traffic."""
        plan = self.fed_cfg.faults
        if plan is None or not plan.active:
            return None
        return np.stack([np.asarray(plan.codes(t0 + i, self.n))
                         for i in range(n_rounds)])

    def _fault_split(self, row: np.ndarray, codes: np.ndarray
                     ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """(live_eff, dead, recoverable) boolean views of one round (the
        masked wire's viability rule): survivors in VIABLE sibling groups
        (>= recovery_threshold survivors after a death — the others
        degrade to zero subtrees), sampled faulted workers, and the
        subset of the dead whose seeds CAN be reconstructed (dead in a
        viable group)."""
        pm = row > 0
        live = pm & (codes == ft.FAULT_NONE)
        dead = pm & (codes != ft.FAULT_NONE)
        spec = self.fed_cfg.privacy
        thr = spec.recovery_threshold if spec is not None else None
        g = (self.fed_cfg.tree.fanout if self.fed_cfg.tree is not None
             else self.n)
        ng = -(-self.n // g)
        pad = ng * g - self.n
        lp = np.pad(live, (0, pad)).reshape(ng, g)
        dp = np.pad(dead, (0, pad)).reshape(ng, g)
        viable = (dp.sum(1) == 0) | (lp.sum(1) >= (thr or np.inf))
        v = np.repeat(viable, g)[:self.n]
        return live & v, dead, dead & v

    def _enforce_privacy(self, runtime: str, wire: rd.WirePath,
                         state: rd.RoundState, betas_arr,
                         has_mask: bool) -> None:
        """§4.2 enforcement hook: audit the traced round program (against
        ShapeDtypeStructs, no real data) before any round runs. A policy
        violation raises LeakageError here; the passing audit is recorded
        in the ledger."""
        spec = self.fed_cfg.privacy
        if spec is None or not spec.enforce:
            return
        bufs = jax.ShapeDtypeStruct((self.n,) + state.buf_p1.shape,
                                    jnp.float32)
        costs = jax.ShapeDtypeStruct((self.n,), jnp.float32)
        # The mask spec must flow through check_round_program's kwargs —
        # that is what as_specs/make_jaxpr convert to tracers; baking it
        # into the partial would leave a raw ShapeDtypeStruct inside the
        # traced program.
        mask_kw = ({"mask": jax.ShapeDtypeStruct((self.n,), jnp.float32)}
                   if has_mask else {})
        report = pv_audit.check_round_program(
            partial(wire.round_step, betas=betas_arr),
            state, bufs, costs, jnp.asarray(self.sizes),
            n_workers=self.n, masked=spec.active, **mask_kw)
        self.ledger.record_audit(runtime, report)

    def _backfill_ledger(self, t0: int, pilots: np.ndarray,
                         masks: np.ndarray | None) -> None:
        """Record each round's uplink events after the fact — the ledger is
        host metadata, so it is reconstructed from the single post-run fetch
        of the on-device pilot history (§4.2 invariants unchanged). On the
        masked wire the master receives mod-2^modulus masked words, never
        the per-worker 2-bit codes — the ledger records what crossed."""
        spec = self.fed_cfg.privacy
        code_kind = ("masked_words" if spec is not None and spec.active
                     else "packed_ternary")
        codes_mat = self._fault_codes(t0, len(pilots))
        recovery_on = (codes_mat is not None and spec is not None
                       and spec.masking_on
                       and spec.recovery_threshold is not None)
        for i, k_star in enumerate(pilots):
            t = t0 + i
            row = (np.ones(self.n) if masks is None
                   else np.asarray(masks[i]))
            # A pre-uplink death sends NOTHING this round; post-uplink
            # deaths and stragglers already committed their cost + words.
            sent = row > 0
            if codes_mat is not None:
                sent = sent & (codes_mat[i] != ft.DROP_BEFORE)
            if recovery_on:
                _, _, recoverable = self._fault_split(row, codes_mat[i])
                for k in range(self.n):
                    if row[k]:   # share dealing precedes the round's faults
                        self.ledger.record(k, t, "seed_shares", False)
                for k in np.flatnonzero(recoverable):
                    self.ledger.record(int(k), t, "mask_recovery", False)
            for k in range(self.n):
                if sent[k]:
                    self.ledger.record(k, t, "cost", False)
            self.ledger.record(int(k_star), t, "pilot_params", True)
            for k in range(self.n):
                if sent[k] and k != int(k_star):
                    self.ledger.record(k, t, code_kind, False)

    def _finish_fedpc(self, res: SimResult, state: rd.RoundState,
                      layout: fl.FlatLayout, t0: int,
                      k_stars: list, raw_costs: list,
                      masks: np.ndarray | None, model_bytes: int,
                      ledger_done: bool, records=None,
                      driver: str = "run_fedpc",
                      check_costs: bool = True) -> SimResult:
        """The ONE post-run device→host fetch: pilot history, costs and the
        stacked telemetry records come back together; ledger, byte
        accounting and trace assembly are host work.

        The host recomputes every round's participation/fault/byte model
        from its own schedules (the legacy ledger math) and
        ``telemetry.trace.build_trace`` cross-checks the device-recorded
        counts and the derived bytes against it — any divergence raises
        ``TelemetryMismatch`` instead of returning a wrong ledger.
        """
        pilots = np.asarray(jnp.stack(k_stars))
        costs_mat = np.asarray(jnp.stack(raw_costs))        # (R, N)
        if not ledger_done:
            self._backfill_ledger(t0, pilots, masks)
        spec = self.fed_cfg.privacy
        masked_wire = spec is not None and spec.active
        codes_mat = self._fault_codes(t0, len(pilots))
        host_rounds: list[dict] = []
        for i in range(len(pilots)):
            row = np.ones(self.n) if masks is None else masks[i]
            # The reported round cost averages only workers whose report
            # the master USED: sampled, not faulted, and (masked wire) in
            # a viable sibling group. (The scan driver's costs_mat carries
            # prev-round values for the excluded, the Python driver their
            # never-delivered local measurements — both are masked out
            # here, keeping the drivers bitwise.)
            n_recoverable = 0
            if codes_mat is None:
                eff = row
            elif masked_wire:
                live_eff, _, recoverable = self._fault_split(
                    row, codes_mat[i])
                eff = row * live_eff
                n_recoverable = int(recoverable.sum())
            else:
                eff = row * (codes_mat[i] == ft.FAULT_NONE)
            if np.sum(eff) == 0:   # every report lost: cost track carries
                res.costs.append(res.costs[-1] if res.costs
                                 else float("inf"))
            else:
                vals = np.where(eff > 0, costs_mat[i], 0.0)
                res.costs.append(float(np.average(
                    vals, weights=self.sizes * eff)))
            res.pilot_history.append(int(pilots[i]))
            n_part = int(np.sum(row > 0))
            if self.fed_cfg.tree is not None:
                wire_bytes = proto.fedpc_tree_bytes_per_round(
                    model_bytes, n_part, self.fed_cfg.tree.fanout,
                    levels=self.fed_cfg.tree.levels,
                    word_bits=spec.modulus_bits if masked_wire else None)
            elif masked_wire:
                wire_bytes = proto.fedpc_masked_bytes_per_round(
                    model_bytes, n_part, word_bits=spec.modulus_bits)
            else:
                wire_bytes = proto.fedpc_bytes_per_round(
                    model_bytes, n_part)
            rec_bytes = 0.0
            if codes_mat is not None:
                codes = codes_mat[i]
                # pre-uplink deaths never spent their uplink bytes
                n_pre = int(np.sum((row > 0) & (codes == ft.DROP_BEFORE)))
                leaf_bits = (float(spec.modulus_bits) if masked_wire
                             else 2.0)
                wire_bytes -= model_bytes * n_pre * leaf_bits / 32.0
                if (spec is not None and spec.masking_on
                        and spec.recovery_threshold is not None):
                    g = (self.fed_cfg.tree.fanout
                         if self.fed_cfg.tree is not None else None)
                    _, _, recoverable = self._fault_split(row, codes)
                    rec_bytes = (
                        proto.recovery_dealing_bytes_per_round(self.n, g)
                        + proto.recovery_reconstruction_bytes(
                            int(recoverable.sum()),
                            spec.recovery_threshold, g,
                            n_workers=self.n))
            host_rounds.append({
                "row": row > 0,
                "codes": None if codes_mat is None else codes_mat[i],
                "used": np.asarray(eff) > 0,
                "n_recoverable": n_recoverable,
                "pilot": int(pilots[i]), "cost": res.costs[-1],
                "wire_bytes": wire_bytes, "recovery_bytes": rec_bytes})
        if records is not None:
            tree = self.fed_cfg.tree
            meta = tmt.trace_meta(
                source="fed_simulator", algorithm="fedpc", driver=driver,
                n_workers=self.n, t0=t0, rounds=len(pilots),
                model_bytes=model_bytes,
                wire="masked" if masked_wire else "plain",
                masking=bool(spec is not None and spec.masking_on),
                modulus_bits=spec.modulus_bits if masked_wire else 0,
                fanout=tree.fanout if tree is not None else 0,
                levels=(tree.levels or 0) if tree is not None else 0,
                recovery_threshold=((spec.recovery_threshold or 0)
                                    if spec is not None else 0),
                faults_active=codes_mat is not None)
            recs_host = jax.tree_util.tree_map(np.asarray, records)
            res.telemetry = tmt.build_trace(meta, recs_host, host_rounds,
                                            check_costs=check_costs)
        else:       # telemetry disabled on the carry: legacy byte lists
            for h in host_rounds:
                res._bytes.append(h["wire_bytes"])
                res._recovery_bytes.append(h["recovery_bytes"])
        res.params = fl.unflatten_tree(state.buf_p1, layout)
        res.round_state = state
        return res

    # ------------------------------------------------------------------
    # FedPC (Algorithms 1 & 2) — Python-loop driver, stateful workers
    # ------------------------------------------------------------------
    def run_fedpc(self, rounds: int, eval_every: int = 0, *,
                  participation: Optional[float] = None,
                  betas=None, participation_seed: int = 0,
                  state: Optional[rd.RoundState] = None,
                  wire_block_rows: Optional[int] = None,
                  wire_block_workers: Optional[int] = None) -> SimResult:
        """Run ``rounds`` rounds (resuming from ``state`` if given).

        Per round: workers train locally (device costs), one traced
        ``round_step`` does pilot selection + batched uplink + fused master
        update (two kernel launches). Pilot history and costs stay on
        device until the end of the run. ``wire_block_rows`` /
        ``wire_block_workers`` pin the wire-kernel tiling (default: the
        ``kernels.tune`` plan for this shape — tiling never changes bits).
        """
        cfg = self.fed_cfg
        wire = self._wire_path(wire_block_rows, wire_block_workers)
        layout = fl.layout_of(self.init_params)
        resumed = state is not None
        if state is None:
            state = rd.init_round_state(self.init_params, self.n, layout,
                                        privacy=cfg.privacy)
        state = _own_state(state, resumed)
        t0 = int(state.round)                 # one setup-time sync
        masks, betas_arr = self._resolve_scenario(
            participation, betas, rounds, participation_seed, t0)
        if self.evade_streak and masks is not None:
            raise ValueError("evasion defence + partial participation is "
                             "not supported in one run")
        model_bytes = proto.model_size_bytes(self.init_params)
        params = fl.unflatten_tree(state.buf_p1, layout)
        res = SimResult("fedpc", params)
        sizes = jnp.asarray(self.sizes)
        self._enforce_privacy("run_fedpc", wire, state, betas_arr,
                              has_mask=masks is not None)

        step = jax.jit(
            partial(wire.round_step, betas=betas_arr),
            donate_argnums=(0,) if _should_donate() else ())
        # The defence's reported-cost memory: on resume, state.prev_costs
        # holds exactly the last reported costs (a fresh state holds the
        # same +inf this used to start from).
        prev_costs_rep = (list(np.asarray(state.prev_costs))
                          if self.evade_streak else [np.inf] * self.n)
        k_stars: list = []
        raw_costs: list = []
        recs: list = []

        for i in range(rounds):
            t = t0 + i
            row = None if masks is None else masks[i]
            # --- workers train locally (parallel in the real system) ---
            locals_, costs = [], []
            for k, w in enumerate(self.workers):
                if row is None or row[k]:
                    q, c = w.train_round_device(params)
                else:       # not sampled: nothing trains, nothing uploads
                    q, c = params, 0.0
                locals_.append(q)
                costs.append(jnp.asarray(c, jnp.float32))

            # --- worker-side evasion defence (§4.2 discussion): inherently
            # a host behaviour — each worker inspects its own pilot history
            # to decide what to report, so this path syncs k* per round ---
            rep_costs = list(costs)
            if self.evade_streak:
                for k in range(self.n):
                    if (self.ledger.consecutive_pilot_streak(k)
                            >= self.evade_streak):
                        rep_costs[k] = prev_costs_rep[k]  # goodness → 0

            stacked = jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs), *locals_)
            bufs_q = fl.flatten_stacked(stacked, layout)
            costs_arr = jnp.stack(
                [jnp.asarray(c, jnp.float32) for c in rep_costs])
            mask_dev = None if row is None else jnp.asarray(row)
            state, new_buf, info = step(state, bufs_q, costs_arr, sizes,
                                        mask=mask_dev)
            params = fl.unflatten_tree(new_buf, layout)
            k_stars.append(info["k_star"])
            raw_costs.append(jnp.stack(costs))   # reported costs, un-evaded
            recs.append(info["telemetry"])       # device scalars, no sync
            prev_costs_rep = rep_costs

            if self.evade_streak:     # defence needs the ledger live
                k_host = int(info["k_star"])
                self._backfill_ledger(t, np.asarray([k_host]), None)
            if eval_every and self.eval_fn and (t - t0 + 1) % eval_every == 0:
                res.eval_history.append((t, self.eval_fn(params)))

        # Stack the per-round records like the scan would — the trace is
        # driver-invariant (pinned bitwise by tests/test_telemetry.py).
        records = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *recs)
        # With the evasion defence the device averaged the REPORTED costs
        # (what the master acted on) while res.costs tracks the measured
        # ones — the cost cross-check is meaningless there by design.
        return self._finish_fedpc(res, state, layout, t0, k_stars,
                                  raw_costs, masks, model_bytes,
                                  ledger_done=bool(self.evade_streak),
                                  records=records, driver="run_fedpc",
                                  check_costs=not bool(self.evade_streak))

    # ------------------------------------------------------------------
    # FedPC — scan driver: ALL rounds inside one jitted lax.scan
    # ------------------------------------------------------------------
    def run_fedpc_scan(self, rounds: int, *,
                       participation: Optional[float] = None,
                       betas=None, participation_seed: int = 0,
                       state: Optional[rd.RoundState] = None,
                       wire_block_rows: Optional[int] = None,
                       wire_block_workers: Optional[int] = None) -> SimResult:
        """The device-resident multi-round driver.

        Every worker's batch schedule for all ``rounds`` is pre-drawn on the
        host (consuming each loader's rng exactly as the Python driver
        would, skipped rounds included), then local training + the round
        protocol run as ONE ``lax.scan`` over ``WirePath.round_step``: two
        kernel launches per round, zero per-round device→host transfers.
        Ledger and pilot history are backfilled from a single post-scan
        fetch. Bitwise-identical to :meth:`run_fedpc` on the same fresh
        simulator state.

        Requires jit-able workers: every loader's shard size must be a
        multiple of its batch size (no ragged last batch). The evasion
        defence (per-round host behaviour) is not available here.
        """
        if self.evade_streak:
            raise ValueError("evade_streak requires the Python-loop driver "
                             "(per-round host behaviour)")
        cfg = self.fed_cfg
        wire = self._wire_path(wire_block_rows, wire_block_workers)
        layout = fl.layout_of(self.init_params)
        resumed = state is not None
        if state is None:
            state = rd.init_round_state(self.init_params, self.n, layout,
                                        privacy=cfg.privacy)
        state = _own_state(state, resumed)
        t0 = int(state.round)                 # one setup-time sync
        masks, betas_arr = self._resolve_scenario(
            participation, betas, rounds, participation_seed, t0)
        model_bytes = proto.model_size_bytes(self.init_params)
        params0 = fl.unflatten_tree(state.buf_p1, layout)
        res = SimResult("fedpc", params0)
        self._enforce_privacy("run_fedpc_scan", wire, state, betas_arr,
                              has_mask=masks is not None)

        # --- pre-draw every worker's batch schedule (host) --------------
        # Only the sample INDICES are pre-drawn — (rounds, steps, bs) int32
        # per worker; the shard itself lives on device once and the scan
        # body gathers batches from it, so device memory stays
        # O(shard + rounds·steps·bs·4B) instead of O(rounds · shard).
        shards, index_schedules, steps_per_round = [], [], []
        for k, w in enumerate(self.workers):
            if not w.uniform_batches:
                raise ValueError(
                    f"worker {k}: scan driver needs batch_size "
                    f"({w.loader.batch_size}) to divide the shard size "
                    f"({w.loader.n}) — no ragged last batch under scan")
            steps = w.cfg.local_epochs * w.loader.steps_per_epoch()
            steps_per_round.append(steps)
            rows = []
            for i in range(rounds):
                if masks is None or masks[i, k]:
                    rows.append(np.stack(
                        [sel for _ in range(w.cfg.local_epochs)
                         for sel in w.loader.epoch_indices()]))
                else:       # skipped round: loader rng untouched; the
                    # gathered batch is masked out of all state anyway
                    rows.append(np.zeros((steps, w.loader.batch_size),
                                         np.int64))
            index_schedules.append(jnp.asarray(np.stack(rows), jnp.int32))
            shards.append(tuple(jnp.asarray(a) for a in w.loader.arrays))
            if w.opt_state is None:
                w.opt_state = w.opt.init(params0)

        worker_carry = tuple(
            (w.opt_state, jnp.asarray(w.step, jnp.int32))
            for w in self.workers)
        masks_dev = None if masks is None else jnp.asarray(masks)
        args = (state, worker_carry, tuple(index_schedules), tuple(shards),
                masks_dev, jnp.asarray(self.sizes), betas_arr,
                jnp.asarray(t0, jnp.int32))
        key = (rounds, wire, masks is None, betas_arr is None)
        prog = self.scan_programs.get(key)
        if prog is None:
            t_c = time.perf_counter()
            # The history state and the workers' optimizer carry are both
            # replaced by the program's outputs: donate them.
            compiled = jax.jit(
                partial(self._scan_body, wire, layout, rounds),
                donate_argnums=(0, 1) if _should_donate() else ()
            ).lower(*args).compile()
            prog = ScanProgram(compiled, time.perf_counter() - t_c)
            self.scan_programs[key] = prog
        state, worker_carry, infos = prog.compiled(*args)

        # write back worker state (host bookkeeping, once)
        for k, w in enumerate(self.workers):
            w.opt_state = worker_carry[k][0]
            part = rounds if masks is None else int(np.sum(masks[:, k] > 0))
            w.step += steps_per_round[k] * part

        k_stars = list(infos["k_star"])
        raw_costs = list(infos["costs"])
        return self._finish_fedpc(res, state, layout, t0, k_stars,
                                  raw_costs, masks, model_bytes,
                                  ledger_done=False,
                                  records=infos["telemetry"],
                                  driver="run_fedpc_scan")

    def _scan_body(self, wire: rd.WirePath, layout: fl.FlatLayout,
                   rounds: int, state, worker_carry, index_schedules,
                   shards, masks_dev, sizes, betas_arr, t0):
        """The whole multi-round program: ``rounds`` rounds of local
        training + ``round_step`` as one ``lax.scan``. Every per-run operand
        (schedules, shards, masks, the starting round) is an argument, so
        one compiled program serves every run of the same shape — a resumed
        run does not recompile."""
        def worker_fn(wc, buf, t):
            params = fl.unflatten_tree(buf, layout)
            r = t - t0                        # row into the schedules
            m_row = (None if masks_dev is None
                     else jnp.take(masks_dev, r, axis=0))
            new_wc, bufs, costs = [], [], []
            for k, w in enumerate(self.workers):
                opt_state, step0 = wc[k]
                idx = jnp.take(index_schedules[k], r, axis=0)  # (steps, bs)
                bk = tuple(
                    jnp.take(a, idx.reshape(-1), axis=0).reshape(
                        idx.shape + a.shape[1:])
                    for a in shards[k])
                # The same recurrence train_round_device jits standalone —
                # traced here inside the round body (bitwise-identical).
                pk, osk, sk, cost_k = w.scan_train(params, opt_state,
                                                   step0, bk)
                buf_k = fl.flatten_tree(pk, layout)
                if m_row is not None:         # skipped: state frozen
                    m = m_row[k] > 0
                    buf_k = jnp.where(m, buf_k, buf)
                    osk = jax.tree_util.tree_map(
                        lambda a, b: jnp.where(m, a, b), osk, opt_state)
                    sk = jnp.where(m, sk, step0)
                    cost_k = jnp.where(m, cost_k, 0.0)
                new_wc.append((osk, sk))
                bufs.append(buf_k)
                costs.append(cost_k)
            return tuple(new_wc), jnp.stack(bufs), jnp.stack(costs)

        return rd.scan_rounds(wire, state, worker_fn, worker_carry, rounds,
                              sizes, betas=betas_arr, masks=masks_dev)

    # ------------------------------------------------------------------
    # FedAvg baseline
    # ------------------------------------------------------------------
    def run_fedavg(self, rounds: int, eval_every: int = 0) -> SimResult:
        params = self.init_params
        model_bytes = proto.model_size_bytes(self.init_params)
        res = SimResult("fedavg", params)
        for t in range(1, rounds + 1):
            locals_, costs = [], []
            for w in self.workers:
                q, c = w.train_round(params)
                locals_.append(q)
                costs.append(c)
            params = bl.fedavg_aggregate(locals_, self.sizes)
            res.costs.append(float(np.average(costs, weights=self.sizes)))
            res.bytes_per_round.append(proto.fedavg_bytes_per_round(
                model_bytes, self.n))
            if eval_every and self.eval_fn and t % eval_every == 0:
                res.eval_history.append((t, self.eval_fn(params)))
        res.params = params
        return res

    # ------------------------------------------------------------------
    # Phong et al. baseline (sequential weight transmission)
    # ------------------------------------------------------------------
    def run_phong(self, rounds: int, eval_every: int = 0) -> SimResult:
        params = self.init_params
        model_bytes = proto.model_size_bytes(self.init_params)
        res = SimResult("phong", params)
        for t in range(1, rounds + 1):
            costs = []
            for w in self.workers:          # model travels worker→worker
                params, c = w.train_round(params)
                costs.append(c)
            res.costs.append(float(np.mean(costs)))
            res.bytes_per_round.append(proto.phong_bytes_per_round(
                model_bytes, self.n))
            if eval_every and self.eval_fn and t % eval_every == 0:
                res.eval_history.append((t, self.eval_fn(params)))
        res.params = params
        return res

    # ------------------------------------------------------------------
    # Centralized upper bound (Table 1)
    # ------------------------------------------------------------------
    def run_centralized(self, rounds: int, central_worker: Worker,
                        eval_every: int = 0) -> SimResult:
        params = self.init_params
        res = SimResult("centralized", params)
        for t in range(1, rounds + 1):
            params, c = central_worker.train_round(params)
            res.costs.append(c)
            res.bytes_per_round.append(0.0)
            if eval_every and self.eval_fn and t % eval_every == 0:
                res.eval_history.append((t, self.eval_fn(params)))
        res.params = params
        return res
