"""Plain replay of FedPC rounds: local training, Eq. (1)/(3)/(4)/(5).

A round ``t`` (1-based) from the global model ``P1 = P^{t-1}`` with
history ``P2 = P^{t-2}`` (zeros before round 2):

1. every worker trains ``local_epochs`` epochs of its shard from ``P1``
   with its own optimizer, whose state it keeps between rounds; its cost
   ``C_k`` is the mean loss of its local steps (the simulator's), or the
   loss of its last local step (``build_fed_step``'s);
2. the pilot ``k*`` maximises the goodness of Eq. (1): ``S_k / C_k`` in
   round 1, ``S_k (C_k^{t-1} - C_k)`` after, among the workers whose
   report is used;
3. every other worker's ternary code of Eq. (4) (round 1: the sign of
   ``Q - P1`` beyond ``alpha1``) or Eq. (5) (after: where ``|Q - P1| >=
   beta |P1 - P2|``, the sign of ``(Q - P1)(P1 - P2)``);
4. Eq. (3): ``P^t = Q_{k*} - alpha0 sum_k w_k T_k`` in round 1 and
   ``P^t = Q_{k*} - (P1 - P2) sum_k w_k T_k`` after, with ``w_k = p_k``
   in round 1 and ``p_k beta`` after, ``p_k`` the data share, ``w_{k*} =
   0``.

Secure aggregation changes only the arithmetic of the sum, which the
masks leave exact: each weight is fixed point, ``W_k = round(w_k 2^b)``,
and the master recovers ``sum_k W_k T_k`` over the survivors, then scales
by ``2^-b``. A worker that dies after its uplink is left out of the sum
and of pilot selection, and so is every member of a sibling group of the
aggregation tree that keeps fewer survivors than the recovery threshold;
workers left out carry their previous cost. The fault schedule is a copy
of the repository's counter-hash stream (lowbias32), keyed by the
workload's fault seed.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import optim

# lowbias32 counter stream, the fault domain and salts
_FAULT_DOMAIN = 0x94D049BB
_SALT_STREAM = 0x85EBCA6B
_SALT_ROUND = 0xC2B2AE35
_SALT_SHARD = 0x27D4EB2F


def _mix32(x: np.ndarray) -> np.ndarray:
    x = np.asarray(x, np.uint32)
    x = x ^ (x >> np.uint32(16))
    x = x * np.uint32(0x7FEB352D)
    x = x ^ (x >> np.uint32(15))
    x = x * np.uint32(0x846CA68B)
    return x ^ (x >> np.uint32(16))


def fault_alive(seed: int, t: int, n: int, p_drop_after: float
                ) -> np.ndarray:
    """(n,) bool: the workers that did not die after their uplink."""
    with np.errstate(over="ignore"):
        k = _mix32(np.full(n, seed, np.uint32) ^ np.uint32(_FAULT_DOMAIN))
        k = _mix32(k + np.arange(n, dtype=np.uint32)
                   * np.uint32(_SALT_STREAM))
        k = _mix32(k + np.uint32(t) * np.uint32(_SALT_ROUND))
        k = _mix32(k + np.uint32(0) * np.uint32(_SALT_SHARD))
    r = k.astype(np.float32) * np.float32(2.0 ** -32)
    return ~(r < np.float32(p_drop_after))


def used_workers(alive: np.ndarray, threshold: int | None,
                 fanout: int | None) -> np.ndarray:
    """Survivors in sibling groups that can still be recovered."""
    n = alive.shape[0]
    g = fanout or n
    used = alive.copy()
    for s in range(0, n, g):
        grp = slice(s, min(s + g, n))
        deaths = int((~alive[grp]).sum())
        if deaths and (threshold is None or alive[grp].sum() < threshold):
            used[grp] = False
    return used


def _goodness(c, cp, s, t):
    if t <= 1 or not np.isfinite(cp):
        return s / max(c, 1e-12)
    return s * (cp - c)


def pilot_choices(costs, prev, sizes, t, used, tol):
    """(argmax, set of acceptable pilots): any used worker whose goodness
    can still be the largest when every cost moves by up to ``tol`` of
    itself (a near tie is not a fault of either side)."""
    n = len(costs)
    g = np.array([_goodness(costs[k], prev[k], sizes[k], t) if used[k]
                  else -np.inf for k in range(n)])
    best = int(np.argmax(g))

    def bound(k, hi):
        c, cp, s = costs[k], prev[k], sizes[k]
        dc = tol * abs(c)
        dp = tol * abs(cp) if np.isfinite(cp) else 0.0
        if t <= 1 or not np.isfinite(cp):
            return s / max(c - dc if hi else c + dc, 1e-12)
        return s * ((cp + dp) - (c - dc)) if hi else s * ((cp - dp)
                                                          - (c + dc))
    if not used.any():      # every report lost: argmax of all -inf
        return best, {best}
    ok = set()
    for k in range(n):
        if not used[k]:
            continue
        others = [bound(j, False) for j in range(n) if j != k and used[j]]
        if not others or bound(k, True) >= max(others):
            ok.add(k)
    return best, ok


@partial(jax.jit, static_argnames=("first",))
def _codes(q, p1, p2, beta, alpha1, first: bool):
    def one(qq, a, b):
        d = qq - a
        if first:
            return ((d > alpha1).astype(jnp.int8)
                    - (d < -alpha1).astype(jnp.int8))
        step = a - b
        return jnp.where(jnp.abs(d) >= beta * jnp.abs(step),
                         jnp.sign(d * step), 0).astype(jnp.int8)
    return jax.tree_util.tree_map(one, q, p1, p2)


@partial(jax.jit, static_argnames=("first",))
def _master(q_pilot, codes, w, p1, p2, alpha0, first: bool):
    def one(qp, a, b, *cs):
        coeff = sum(w[k] * c.astype(qp.dtype) for k, c in enumerate(cs))
        return qp - (alpha0 if first else (a - b)) * coeff
    return jax.tree_util.tree_map(one, q_pilot, p1, p2, *codes)


@jax.jit
def _norms(xs):
    return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))) for x in xs]


def leaf_norms(tree) -> dict:
    """``{path: float32 L2 norm}`` of every leaf."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    vals = _norms([x for _, x in flat])
    return {path_str(p): float(v) for (p, _), v in zip(flat, vals)}


def path_str(path) -> str:
    parts = []
    for k in path:
        for attr in ("key", "name", "idx"):
            if hasattr(k, attr):
                parts.append(str(getattr(k, attr)))
                break
    return "/".join(parts)


def replay(*, loss_fn, params0, shards, workers, wire: dict, rounds: int,
           opt_round: int, program_pilots, tol: float, dtype=jnp.float32,
           drop_wire: bool = False, cost: str = "mean"):
    """Replay ``rounds`` rounds. ``workers[k]`` has ``optimizer``,
    ``local_epochs``, ``batch_size``, ``lr0``, ``lr_decay``,
    ``lr_decay_every`` and ``loader_seed``; ``shards[k]`` is its (n_k, S)
    token array. Where the program's pilot is among the acceptable ones
    (``pilot_choices``), the replay follows it, so one near tie does not
    send the two runs down different paths; with ``program_pilots=None``
    it follows its own choice. ``drop_wire`` leaves every worker's codes
    out of Eq. (3), a fault the check has to catch. ``cost`` is
    ``"mean"`` or ``"last"``: a worker's cost is the mean of its local
    steps' losses, or its last step's.

    Returns ``costs`` and ``pilot_ok`` per round, the optimizer-state leaf
    norms of every worker after round ``opt_round``, the leaf norms of the
    first gradient, and the global parameters after the last round."""
    tm = jax.tree_util.tree_map
    n = len(workers)
    sizes = np.array([s.shape[0] for s in shards], np.float64)
    shares = sizes / sizes.sum()
    params = tm(lambda x: x.astype(dtype), params0)
    p2 = tm(jnp.zeros_like, params)
    steppers = {}
    for name in {w["optimizer"] for w in workers}:
        def stepper(p, st, toks, lr, name=name):
            l, g = jax.value_and_grad(loss_fn)(p, toks)
            p, st = optim.update(name, g, st, p, lr.astype(dtype))
            return p, st, l, g
        steppers[name] = jax.jit(stepper)
    states = [optim.init(w["optimizer"], params) for w in workers]
    steps = [0] * n
    rngs = [np.random.default_rng(w["loader_seed"]) for w in workers]
    prev = np.full(n, np.inf)
    out = {"costs": [], "pilot_ok": [], "pilots": [], "opt_norms": None,
           "first_grad_norms": {}}
    masked = wire.get("masked", False)
    for t in range(1, rounds + 1):
        locals_, costs = [], np.zeros(n)
        for k, w in enumerate(workers):
            q, losses = params, []
            for _ in range(w["local_epochs"]):
                order = rngs[k].permutation(shards[k].shape[0])
                for s in range(0, len(order), w["batch_size"]):
                    toks = jnp.asarray(shards[k][order[s:s + w["batch_size"]]])
                    lr = optim.step_decay_lr(w["lr0"], w["lr_decay"],
                                             w["lr_decay_every"], steps[k])
                    q, states[k], l, g = steppers[w["optimizer"]](
                        q, states[k], toks, jnp.float32(lr))
                    if t == 1 and not losses:
                        for key, v in leaf_norms(g).items():
                            out["first_grad_norms"][key] = max(
                                v, out["first_grad_norms"].get(key, 0.0))
                    del g
                    losses.append(l)
                    steps[k] += 1
            costs[k] = (float(losses[-1]) if cost == "last"
                        else float(np.mean([float(x) for x in losses])))
            locals_.append(q)
        alive = np.ones(n, bool)
        if wire.get("fault_p_after"):
            alive = fault_alive(wire["fault_seed"], t, n,
                                wire["fault_p_after"])
        used = (used_workers(alive, wire.get("recovery_threshold"),
                             wire.get("fanout")) if masked else alive)
        best, ok = pilot_choices(costs, prev, sizes, t, used, tol)
        prog = best if program_pilots is None else program_pilots[t - 1]
        k_star = prog if prog in ok else best
        out["pilot_ok"].append(prog in ok)
        out["pilots"].append(k_star)
        first = t <= 1
        wts = np.where(np.arange(n) != k_star, shares, 0.0) * (
            1.0 if first else wire["beta"])
        codes = [_codes(q, params, p2, wire["beta"], wire["alpha1"],
                        first=first) for q in locals_]
        if masked:
            scale = float(1 << wire["fixpoint_bits"])
            wq = np.round(wts.astype(np.float32) * np.float32(scale))
            wts = np.where(used, wq, 0.0) / scale
        else:
            wts = np.where(used, wts, 0.0)
        if drop_wire:
            wts = np.zeros_like(wts)
        new = _master(locals_[k_star], codes,
                      jnp.asarray(wts, dtype), params, p2,
                      jnp.asarray(wire["alpha0"], dtype), first=first)
        p2, params = params, new
        rep = np.where(used, costs, 0.0)
        if used.sum():
            out["costs"].append(float(np.average(rep, weights=sizes * used)))
        else:
            out["costs"].append(out["costs"][-1] if out["costs"]
                                else float("inf"))
        prev = np.where(used, costs, prev)
        del locals_, codes, new
        if t == opt_round:
            out["opt_norms"] = [
                {f"{kind}/{key}": v
                 for kind in ("velocity", "mu", "nu") if kind in st
                 for key, v in leaf_norms(st[kind]).items()}
                for st in states]
    out["params"] = params
    return out
