"""Worker-side local training (Algorithm 2, line 1).

Each worker owns: a private data shard, private hyper-parameters (batch
size, learning rate + decay, local epochs, optimizer) — exactly the private
information Theorem 2's privacy argument relies on.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.data.pipeline import BatchIterator
from repro.optim import optimizers as opt_mod
from repro.optim.schedules import step_decay
from repro.utils import PyTree

LR_MENU = (0.01,)                 # paper: initial lr 0.01 for everyone
EPOCH_MENU = (1, 2)               # local epochs per round
OPT_MENU = ("momentum", "adam", "sgd")
BETA_MENU = (0.1, 0.2, 0.3)       # heterogeneous per-worker beta_k choices


@dataclass
class WorkerConfig:
    worker_id: int
    batch_size: int
    lr0: float = 0.01
    lr_decay: float = 0.5
    lr_decay_every: int = 1000     # derived from local dataset size (paper)
    local_epochs: int = 1
    optimizer: str = "momentum"
    seed: int = 0
    # Private Eq. (5) significance threshold beta_k; None = no private draw
    # (the federation's shared beta applies). Set by beta_menu draws.
    beta: float | None = None


def make_worker_configs(n_workers: int, shard_sizes: list[int],
                        seed: int = 0,
                        batch_menu=(128, 64, 32),
                        beta_menu=None) -> list[WorkerConfig]:
    """Draw private hyper-parameters per worker, following §5.1: batch size
    from a menu, lr 0.01 with size-dependent step decay, 1–2 local epochs,
    momentum or adam. ``beta_menu`` (e.g. ``BETA_MENU``) additionally draws
    a per-worker significance threshold beta_k — the heterogeneous-wire
    regime; without it workers carry no private beta (the federation's
    shared beta applies) and the draws stay byte-identical to before."""
    rng = np.random.default_rng(seed)
    cfgs = []
    for k in range(n_workers):
        bs = int(rng.choice(batch_menu))
        bs = min(bs, max(shard_sizes[k], 1))
        steps_per_epoch = max(shard_sizes[k] // bs, 1)
        cfgs.append(WorkerConfig(
            worker_id=k,
            batch_size=bs,
            lr0=0.01,
            lr_decay=0.5,
            lr_decay_every=max(10 * steps_per_epoch, 1),
            local_epochs=int(rng.choice(EPOCH_MENU)),
            optimizer=str(rng.choice(OPT_MENU[:2])),
            seed=seed * 1000 + k,
            beta=(float(rng.choice(beta_menu)) if beta_menu is not None
                  else None),
        ))
    return cfgs


@dataclass
class Worker:
    """Stateful in-process worker for the simulator (the paper's testbed)."""
    cfg: WorkerConfig
    loader: BatchIterator
    loss_and_grad: Callable            # (params, batch) -> ((loss, aux), grads)
    opt: opt_mod.Optimizer = field(init=False)
    opt_state: Optional[PyTree] = None
    step: int = 0

    def __post_init__(self):
        self.opt = opt_mod.get(self.cfg.optimizer)
        self.lr_fn = step_decay(self.cfg.lr0, self.cfg.lr_decay,
                                self.cfg.lr_decay_every)
        self._scan_train_jit = None    # lazily-built jit of scan_train

    @property
    def uniform_batches(self) -> bool:
        """True when every batch of an epoch has the same shape — the
        condition for stacking a round's batches into one scan."""
        return self.loader.n % self.loader.batch_size == 0

    def train_round(self, params: PyTree) -> tuple[PyTree, float]:
        """Run `local_epochs` epochs from the given global params; return
        (local_params Q_k, cost C_k). Optimizer state is private and persists
        across rounds (fresh momentum for new params would also be valid —
        the paper leaves this to the worker).

        The single ``float(...)`` here is the round's only device→host sync.
        """
        params, cost = self.train_round_device(params)
        return params, float(cost)

    def scan_train(self, params: PyTree, opt_state: PyTree, step: jax.Array,
                   batches: tuple) -> tuple[PyTree, PyTree, jax.Array,
                                            jax.Array]:
        """One round of local training as a pure ``lax.scan`` over stacked
        batches (tuple of (steps, batch, ...) arrays).

        This is THE local-training recurrence: ``train_round_device`` jits
        it standalone, and the simulator's multi-round scan driver traces it
        inside its round body — XLA compiles the same computation either
        way, which is what makes the two drivers bitwise-identical.
        Returns (params, opt_state, step, mean cost).
        """
        def bstep(carry, batch):
            p, os, s, tot = carry
            lr = self.lr_fn(s)
            (loss, _aux), grads = self.loss_and_grad(p, batch)
            with jax.named_scope("fed/train/optimizer"):
                updates, os = self.opt.update(grads, os, p, lr)
                p = opt_mod.apply_updates(p, updates)
            return (p, os, s + 1, tot + loss), None

        n_steps = jax.tree_util.tree_leaves(batches)[0].shape[0]
        (params, opt_state, step, tot), _ = jax.lax.scan(
            bstep, (params, opt_state, step, jnp.zeros((), jnp.float32)),
            batches)
        return params, opt_state, step, tot / max(n_steps, 1)

    def stack_round_batches(self) -> tuple:
        """Draw one round's batch schedule from the loader and stack it into
        the (steps, batch, ...) arrays ``scan_train`` consumes."""
        bs = [b for _ in range(self.cfg.local_epochs)
              for b in self.loader.epoch()]
        return tuple(np.stack([b[j] for b in bs])
                     for j in range(len(bs[0])))

    def train_round_device(self, params: PyTree) -> tuple[PyTree, jax.Array]:
        """`train_round` without the host sync: the cost comes back as a
        device scalar and the whole round is ONE jitted dispatch
        (``scan_train`` over the round's stacked batches) when the shard
        size permits stacking; ragged shards fall back to the eager
        per-batch loop (still zero host syncs — the loss accumulates
        on-device)."""
        if self.opt_state is None:
            self.opt_state = self.opt.init(params)
        if self.uniform_batches:
            if self._scan_train_jit is None:
                self._scan_train_jit = jax.jit(self.scan_train)
            batches = self.stack_round_batches()
            n_steps = batches[0].shape[0]
            params, self.opt_state, _, cost = self._scan_train_jit(
                params, self.opt_state, jnp.asarray(self.step, jnp.int32),
                batches)
            self.step += n_steps
            return params, cost
        total_loss = jnp.zeros((), jnp.float32)
        n_batches = 0
        for _ in range(self.cfg.local_epochs):
            for batch in self.loader.epoch():
                lr = self.lr_fn(self.step)
                (loss, _aux), grads = self.loss_and_grad(params, batch)
                updates, self.opt_state = self.opt.update(
                    grads, self.opt_state, params, lr)
                params = opt_mod.apply_updates(params, updates)
                total_loss = total_loss + loss
                n_batches += 1
                self.step += 1
        return params, total_loss / max(n_batches, 1)
