"""Cross-chip cells: the federated step on a mesh, one worker per chip.

Set-up builds the model with the workload's optimizer, a (fed = F,
model = 1) mesh over the cell's chips (``repro.launch.mesh.make_mesh``)
and ``repro.fed.distributed.build_fed_step`` with the workload's strategy,
rate and local steps, jitted with the public state replicated and each
worker's optimizer state and batches on its own chip, as a cross-silo user
runs it, and compiles it once: every call of set-up and window runs that
one program, with the state and optimizer states donated. A call is one
round: every worker trains its local epochs from the global model on its
own shard, in the order its loader draws, and the sync moves the ternary
codes and the pilot's weights between the chips.

``build_fed_step`` gives every worker the same optimizer, rate and local
steps, trains at a fixed rate, reports a worker's last local loss as its
cost and runs the sync at ``build_fed_sync``'s own ``alpha0``, ``beta``
and ``alpha1``: the workload states those values, and the reference
replays them.

The set-up calls are the first rounds of the object the window drives;
what the check needs from them (costs, pilots, optimizer-state and
parameter-change norms) is recorded as they happen, and the reference
replays them after the window.
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

FED_AXIS = "data"


def _say(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


@jax.jit
def _worker_norms(xs):
    """Each stacked ``(F, ...)`` leaf's per-worker L2 norm, ``(F,)``."""
    return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)),
                             axis=tuple(range(1, x.ndim)))) for x in xs]


class MeshCell:
    def __init__(self, wl: dict, cfg: dict, seed: int):
        self.wl, self.cfg, self.seed = wl, cfg, seed
        self.arch = cfg["arch"]
        self.ref = spec.reference(cfg["reference"])
        self.spec = self.ref.param_spec(self.arch)
        self.rounds = wl["rounds_per_call"]
        self.check_rounds = wl["check_rounds"]
        if self.rounds != 1:
            raise ValueError("a call of build_fed_step is one round")
        if len({tuple(sorted(w.items())) for w in wl["workers"]}) != 1:
            raise ValueError("build_fed_step gives every worker the same "
                             "optimizer, rate and local epochs")
        n, seqs, bs = (len(wl["workers"]), wl["sequences_per_worker"],
                       wl["batch_size"])
        if seqs % bs:
            raise ValueError("a worker's rows must fill whole batches")
        if n != wl["chips"]:
            raise ValueError("one worker per chip")
        self.n = n
        self.worker = wl["workers"][0]
        self.local_steps = self.worker["local_epochs"] * seqs // bs
        self.shards = gen.token_shards(seed, n, seqs, wl["seq_len"],
                                       self.arch["vocab"],
                                       wl["token_successors"])
        seeds = gen.loader_seeds(seed, n)
        # one fixed rate: no decay
        self.workers = [dict(w, batch_size=bs, lr_decay=1.0,
                             lr_decay_every=1, loader_seed=seeds[k])
                        for k, w in enumerate(wl["workers"])]
        self.loaders = [np.random.default_rng(s) for s in seeds]
        self.tokens_per_call = n * self.local_steps * bs * wl["seq_len"]
        self.prog = {"costs": [], "pilots": [], "opt_norms": None,
                     "change_norms": None}
        self.rounds_done = 0

    # -- the system under test ------------------------------------------
    def wire_dict(self) -> dict:
        return dict(self.wl["wire"], masked=False, fanout=None)

    def _tokens(self) -> np.ndarray:
        """The next round's ``(F, local steps, batch, seq_len)`` tokens:
        each worker's rows in the order its loader draws, epoch by epoch
        (the reference draws the same orders)."""
        seqs = self.wl["sequences_per_worker"]
        out = []
        for shard, rng in zip(self.shards, self.loaders):
            order = np.concatenate([rng.permutation(seqs) for _ in
                                    range(self.worker["local_epochs"])])
            out.append(shard[order].reshape(self.local_steps,
                                            self.wl["batch_size"], -1))
        return np.stack(out)

    def build(self):
        from jax.sharding import NamedSharding, PartitionSpec as P

        from repro.fed.distributed import build_fed_step, fed_state_init
        from repro.launch.mesh import make_mesh
        from repro.models import build_model
        from repro.optim import optimizers

        devices = jax.devices()[:self.n]
        if len(devices) < self.n:
            raise RuntimeError(f"the cell needs {self.n} devices")
        self.mesh = make_mesh((self.n, 1), (FED_AXIS, "model"),
                              devices=devices)
        model = build_model(arch_config(self.arch),
                            optimizer=optimizers.get(self.worker["optimizer"]))
        check_param_tree(model, self.spec)
        self.rep = NamedSharding(self.mesh, P())
        self.per_fed = NamedSharding(self.mesh, P(FED_AXIS))
        n = self.n
        params = jax.device_put(gen.make_weights(self.spec, self.seed),
                                self.rep)
        self.state = jax.jit(lambda p: fed_state_init(p, n),
                             out_shardings=self.rep)(params)
        self.opt = jax.jit(lambda p: jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x, (n,) + x.shape),
            model.optimizer.init(p)), out_shardings=self.per_fed)(params)
        del params
        self.sizes = jax.device_put(
            jnp.full((n,), float(self.wl["sequences_per_worker"])), self.rep)
        step = jax.jit(
            build_fed_step(model, self.mesh, FED_AXIS, self.wl["strategy"],
                           local_steps=self.local_steps,
                           lr=self.worker["lr0"]),
            in_shardings=(self.rep, self.per_fed, self.per_fed, self.rep),
            out_shardings=(self.rep, self.per_fed, self.rep),
            donate_argnums=(0, 1))
        batch = {"tokens": jax.ShapeDtypeStruct(
            (n, self.local_steps, self.wl["batch_size"], self.wl["seq_len"]),
            jnp.int32, sharding=self.per_fed)}
        with jax.set_mesh(self.mesh):
            self.step = step.lower(self.state, self.opt, batch,
                                   self.sizes).compile()

    def call(self, record: bool = False):
        """One round of the timed path; returns its metrics, blocked on."""
        batch = {"tokens": jax.device_put(self._tokens(), self.per_fed)}
        with jax.set_mesh(self.mesh):
            self.state, self.opt, metrics = self.step(
                self.state, self.opt, batch, self.sizes)
        jax.block_until_ready((self.state, self.opt, metrics))
        self.rounds_done += 1
        if record:
            self._record(metrics)
        return metrics

    def _record(self, metrics):
        if self.rounds_done <= self.check_rounds:
            self.prog["costs"].append(float(metrics["cost_mean"]))
            self.prog["pilots"].append(int(metrics["k_star"]))
        if self.rounds_done == self.rounds:
            self.prog["opt_norms"] = self._opt_norms()
        if self.rounds_done == self.check_rounds:
            params0 = jax.device_put(gen.make_weights(self.spec, self.seed),
                                     self.rep)
            self.prog["change_norms"] = diff_norms(self.state["params"],
                                                   params0)

    def _opt_norms(self) -> list:
        """Per worker, ``{kind/leaf: norm}`` of its optimizer state."""
        kinds = {k: v for k, v in self.opt._asdict().items() if k != "count"}
        out = [{} for _ in range(self.n)]
        for kind, tree in kinds.items():
            flat = jax.tree_util.tree_flatten_with_path(tree)[0]
            vals = _worker_norms([x for _, x in flat])
            for (path, _), v in zip(flat, vals):
                key = f"{kind}/{ref_fedpc.path_str(path)}"
                for k, x in enumerate(np.asarray(v)):
                    out[k][key] = float(x)
        return out

    def setup(self):
        t0 = time.perf_counter()
        self.build()
        _say(f"build {time.perf_counter() - t0:.3f} s")
        for _ in range(self.wl["setup_calls"]):
            t0 = time.perf_counter()
            self.call(record=True)
            _say(f"set-up call {time.perf_counter() - t0:.3f} s")
        if self.rounds_done < self.check_rounds:
            raise RuntimeError("set-up calls end before the checked rounds")

    def window(self, seconds: float) -> dict:
        ends = []
        t0 = time.perf_counter()
        while True:
            self.call()
            ends.append(time.perf_counter())
            if ends[-1] - t0 >= seconds:
                break
        calls = len(ends)
        return {"seconds": ends[-1] - t0, "calls": calls,
                "tokens": calls * self.tokens_per_call, "rounds": calls,
                "call_s": np.diff([t0] + ends).tolist()}

    def program_counters(self) -> dict:
        """None: ``build_fed_step`` returns no byte count."""
        return {}

    def hlo_texts(self) -> list:
        return [self.step.as_text()]

    def release(self):
        """Free the program's device state before the reference runs."""
        self.step = self.state = self.opt = self.sizes = None
        gc.collect()
        jax.clear_caches()
        gc.collect()

    # -- the check -------------------------------------------------------
    def reference(self, limits: dict, dtype=None, precision="highest",
                  loss_override=None, pilots="program", drop_wire=False):
        """Replay the checked rounds with the plain reference (or, with
        ``dtype=bfloat16``, the control); as ``SimCell.reference``."""
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
                tol=limits["loss_gap"], dtype=dtype, drop_wire=drop_wire,
                cost="last")
            out["change_norms"] = diff_norms(out.pop("params"), params0)
        return out

    def check(self, limits: dict) -> dict:
        return compare(self.prog, self.reference(limits))


Cell = MeshCell
