"""One-chip cells: the federation simulator driven as a user drives it.

Set-up assembles the simulator as ``repro.launch.train.build_simulation``
does (worker configs, one ``BatchIterator`` per worker, ``Worker``,
``FedSimulator``), with the benchmark's own tokens, weights and worker
hyper-parameters. It then makes the workload's ``setup_calls`` calls of
``FedSimulator.run_fedpc_scan(rounds_per_call, state=previous)``: the
first compiles, later ones are served by the same program. Those calls
are the first steps of the very object the window drives; what the check
needs from them (costs, pilots, optimizer-state and parameter-change
norms) is recorded as they happen, and the reference replays them after
the window.
"""
from __future__ import annotations

import gc
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench.harness import gen, spec
from bench.harness.cells import arch_config, check_param_tree, diff_norms
from bench.harness.check import compare
from bench.reference import fedpc as ref_fedpc


def _say(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def _opt_norms(opt_state) -> dict:
    kinds = {k: v for k, v in opt_state._asdict().items() if k != "count"}
    return {f"{kind}/{key}": v
            for kind, tree in kinds.items()
            for key, v in ref_fedpc.leaf_norms(tree).items()}


class SimCell:
    def __init__(self, wl: dict, cfg: dict, seed: int):
        self.wl, self.cfg, self.seed = wl, cfg, seed
        self.arch = cfg["arch"]
        self.ref = spec.reference(cfg["reference"])
        self.spec = self.ref.param_spec(self.arch)
        self.rounds = wl["rounds_per_call"]
        self.check_rounds = wl["check_rounds"]
        n = len(wl["workers"])
        self.shards = gen.token_shards(seed, n, wl["sequences_per_worker"],
                                       wl["seq_len"], self.arch["vocab"],
                                       wl["token_successors"])
        seeds = gen.loader_seeds(seed, n)
        steps_epoch = -(-wl["sequences_per_worker"] // wl["batch_size"])
        self.workers = [dict(w, batch_size=wl["batch_size"],
                             lr_decay=wl["lr_decay"],
                             lr_decay_every=max(
                                 wl["lr_decay_every_epochs"] * steps_epoch,
                                 1),
                             loader_seed=seeds[k])
                        for k, w in enumerate(wl["workers"])]
        self.tokens_per_call = self.rounds * sum(
            w["local_epochs"] * steps_epoch * wl["batch_size"]
            * wl["seq_len"] for w in self.workers)
        self.prog = {"costs": [], "pilots": [], "opt_norms": None,
                     "change_norms": None}
        self.rounds_done = 0
        self.window_bytes: list[float] = []

    # -- the system under test ------------------------------------------
    def wire_dict(self) -> dict:
        w = dict(self.wl["wire"])
        priv = self.wl.get("privacy")
        w["masked"] = priv is not None
        if priv is not None:
            w["fixpoint_bits"] = priv["fixpoint_bits"]
            w["recovery_threshold"] = priv.get("recovery_threshold")
        tree = self.wl.get("tree")
        w["fanout"] = tree["fanout"] if tree else None
        faults = self.wl.get("faults")
        if faults:
            w["fault_seed"] = faults["seed"]
            w["fault_p_after"] = faults["drop_after_uplink"]
        return w

    def build(self):
        from repro.core.fedpc import FedPCConfig
        from repro.core.tree import TreeSpec
        from repro.data.pipeline import BatchIterator
        from repro.fed.faults import FaultPlan
        from repro.fed.simulator import FedSimulator
        from repro.fed.worker import Worker, WorkerConfig
        from repro.models import build_model
        from repro.privacy.spec import PrivacySpec

        model = build_model(arch_config(self.arch))
        check_param_tree(model, self.spec)
        self.params0 = gen.make_weights(self.spec, self.seed)
        loss_fn = jax.jit(jax.value_and_grad(
            lambda p, b: model.loss(p, {"tokens": jnp.asarray(b[0])}),
            has_aux=True))
        fleet = []
        for k, w in enumerate(self.workers):
            wc = WorkerConfig(worker_id=k, batch_size=w["batch_size"],
                              lr0=w["lr0"], lr_decay=w["lr_decay"],
                              lr_decay_every=w["lr_decay_every"],
                              local_epochs=w["local_epochs"],
                              optimizer=w["optimizer"], seed=k)
            fleet.append(Worker(cfg=wc, loader=BatchIterator(
                (self.shards[k],), w["batch_size"], seed=w["loader_seed"]),
                loss_and_grad=loss_fn))
        wd = self.wire_dict()
        priv = self.wl.get("privacy")
        fed_cfg = FedPCConfig(
            n_workers=len(fleet), alpha0=wd["alpha0"], beta=wd["beta"],
            alpha_round1=wd["alpha1"],
            privacy=(PrivacySpec(modulus_bits=priv["modulus_bits"],
                                 fixpoint_bits=priv["fixpoint_bits"],
                                 recovery_threshold=priv.get(
                                     "recovery_threshold"))
                     if priv else None),
            tree=TreeSpec(fanout=wd["fanout"]) if wd["fanout"] else None,
            faults=(FaultPlan(seed=wd["fault_seed"],
                              drop_after_uplink=wd["fault_p_after"])
                    if wd.get("fault_p_after") else None))
        self.sim = FedSimulator(fleet, self.params0, fed_cfg=fed_cfg)
        self.state = None

    def call(self, record: bool = False):
        """One call of the timed path; returns the result, blocked on."""
        res = self.sim.run_fedpc_scan(self.rounds, state=self.state)
        jax.block_until_ready((res.round_state, res.params))
        self.state = res.round_state
        self.rounds_done += self.rounds
        if record:
            self._record(res)
        else:
            self.window_bytes.extend(
                np.add(res.bytes_per_round, res.recovery_bytes_per_round))
        return res

    def _record(self, res):
        if self.rounds_done <= self.check_rounds:
            self.prog["costs"].extend(res.costs)
            self.prog["pilots"].extend(res.pilot_history)
        if self.rounds_done == self.rounds:
            self.prog["opt_norms"] = [_opt_norms(w.opt_state)
                                      for w in self.sim.workers]
        if self.rounds_done == self.check_rounds:
            self.prog["change_norms"] = diff_norms(res.params, self.params0)

    def setup(self):
        t0 = time.perf_counter()
        self.build()
        _say(f"build {time.perf_counter() - t0:.3f} s")
        for _ in range(self.wl["setup_calls"]):
            t0 = time.perf_counter()
            del_res = self.call(record=True)
            del del_res
            _say(f"set-up call {time.perf_counter() - t0:.3f} s")
        if self.rounds_done < self.check_rounds:
            raise RuntimeError("set-up calls end before the checked rounds")

    def window(self, seconds: float) -> dict:
        self.window_bytes = []
        ends = []
        t0 = time.perf_counter()
        while True:
            res = self.call()
            del res
            ends.append(time.perf_counter())
            if ends[-1] - t0 >= seconds:
                break
        elapsed = ends[-1] - t0
        calls = len(ends)
        return {"seconds": elapsed, "calls": calls,
                "tokens": calls * self.tokens_per_call,
                "rounds": calls * self.rounds,
                "call_s": np.diff([t0] + ends).tolist()}

    def program_counters(self) -> dict:
        """``wire_bytes_per_round``: mean bytes a round of the window's
        calls put on the wire, as the simulator's own ledger counts them
        (data plane plus recovery); absent before the window."""
        if not self.window_bytes:
            return {}
        return {"wire_bytes_per_round": float(np.mean(self.window_bytes))}

    def hlo_texts(self) -> list:
        """The HLO texts of the round programs compiled so far."""
        return [p.compiled.as_text()
                for p in self.sim.scan_programs.values()]

    def release(self):
        """Free the program's device state before the reference runs."""
        self.sim = self.state = self.params0 = None
        gc.collect()
        jax.clear_caches()
        gc.collect()

    # -- the check -------------------------------------------------------
    def reference(self, limits: dict, dtype=None, precision="highest",
                  loss_override=None, pilots="program", drop_wire=False):
        """Replay the checked rounds with the plain reference (or, with
        ``dtype=bfloat16``, the control); returns its readings. ``pilots``
        is ``"program"`` (follow the program's pilots where they are
        acceptable), ``None`` (its own choice) or a list."""
        dtype = dtype or jnp.float32
        arch = self.arch
        loss = loss_override or (lambda p, t: self.ref.loss(p, t, arch))
        with jax.default_matmul_precision(precision):
            params0 = gen.make_weights(self.spec, self.seed)
            out = ref_fedpc.replay(
                loss_fn=loss, params0=params0, shards=self.shards,
                workers=self.workers, wire=self.wire_dict(),
                rounds=self.check_rounds, opt_round=self.rounds,
                program_pilots=(self.prog["pilots"] if pilots == "program"
                                else pilots),
                tol=limits["loss_gap"], dtype=dtype, drop_wire=drop_wire)
            out["change_norms"] = diff_norms(out.pop("params"), params0)
        return out

    def check(self, limits: dict) -> dict:
        return compare(self.prog, self.reference(limits))


Cell = SimCell
