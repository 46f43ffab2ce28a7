"""The numbers that decide ``correct``, each against its own limit.

For the first checked rounds of a training cell, program against the
plain reference:

* ``loss_gap``: the largest relative gap of a round's reported cost;
* ``opt_state_gap``: after the first call, for each worker and each kind
  of optimizer state (momentum velocity, Adam's first and second moment),
  the worst leaf's gap between the program's norm and the reference's,
  over the larger of that leaf's reference norm and the median leaf's;
  the state is what the optimizer made of the gradients, so this is the
  gradient as the optimizer got it;
* ``opt_state_median_gap``: the same leaf gaps, their median over the
  leaves of each worker's state of each kind in place of their worst, the
  largest such median: steady where the worst leaf is one small leaf's
  round-off;
* ``param_change_gap``: the same measure for the global parameters' change
  from the initial weights after the checked rounds;
* ``param_change_median_gap``: the median of those leaf gaps: steady where
  the worst leaf's change turns on round-off, one leaf ill-conditioned;
* ``pilot_mismatch``: rounds whose pilot the reference could not have
  chosen with every cost within ``loss_gap``'s limit (exact: limit 0).

Leaves whose first gradient in the reference is under a thousandth of the
median leaf's are left out of the two norm gaps: they move by round-off.
"""
from __future__ import annotations

import numpy as np

NOUGHT = 1e-3


def _included(first_grad: dict) -> set:
    med = float(np.median(list(first_grad.values())))
    return {k for k, v in first_grad.items() if v >= NOUGHT * med}


def norm_gaps(prog: dict, ref: dict, keep) -> dict:
    """``{leaf: gap}``: each kept leaf's gap between the two norms over the
    larger of its reference norm and the median leaf's."""
    keys = [k for k in ref if keep(k)]
    if not keys:
        return {}
    med = float(np.median([ref[k] for k in keys]))
    return {k: abs(prog[k] - ref[k]) / max(ref[k], med, 1e-30)
            for k in keys}


def compare(prog: dict, ref: dict) -> dict:
    inc = _included(ref["first_grad_norms"])
    loss = max(abs(p - r) / abs(r) for p, r in zip(prog["costs"],
                                                    ref["costs"]))
    opt = opt_med = 0.0
    for pk, rk in zip(prog["opt_norms"], ref["opt_norms"]):
        for kind in ("velocity", "mu", "nu"):
            gaps = list(norm_gaps(
                pk, rk, lambda k, kind=kind: k.startswith(kind + "/")
                and k.split("/", 1)[1] in inc).values())
            if gaps:
                opt = max(opt, max(gaps))
                opt_med = max(opt_med, float(np.median(gaps)))
    change_gaps = list(norm_gaps(prog["change_norms"], ref["change_norms"],
                                 lambda k: k in inc).values())
    change = max(change_gaps, default=0.0)
    change_med = float(np.median(change_gaps)) if change_gaps else 0.0
    return {"loss_gap": float(loss), "opt_state_gap": float(opt),
            "opt_state_median_gap": opt_med,
            "param_change_gap": float(change),
            "param_change_median_gap": change_med,
            "pilot_mismatch": float(sum(not ok for ok in ref["pilot_ok"]))}


def explain(prog: dict, ref: dict) -> dict:
    """Where each norm gap comes from: per optimizer-state kind and for the
    parameters' change, the worst leaf with both norms; the loss gap of
    every round."""
    inc = _included(ref["first_grad_norms"])
    out = {"loss_gap_by_round": [abs(p - r) / abs(r) for p, r in
                                 zip(prog["costs"], ref["costs"])]}
    for kind in ("velocity", "mu", "nu"):
        worst = None
        for w, (pk, rk) in enumerate(zip(prog["opt_norms"],
                                         ref["opt_norms"])):
            gaps = norm_gaps(pk, rk, lambda k: k.startswith(kind + "/")
                             and k.split("/", 1)[1] in inc)
            for k, g in gaps.items():
                if worst is None or g > worst[0]:
                    worst = (g, w, k, pk[k], rk[k])
        if worst:
            out[f"opt_state_gap.{kind}"] = worst
    gaps = norm_gaps(prog["change_norms"], ref["change_norms"],
                     lambda k: k in inc)
    k = max(gaps, key=gaps.get)
    out["param_change_gap"] = (gaps[k], k, prog["change_norms"][k],
                               ref["change_norms"][k])
    out["param_change_gaps"] = dict(sorted(gaps.items(),
                                           key=lambda kv: -kv[1]))
    return out


def verdict(numbers: dict, limits: dict) -> tuple[bool, list]:
    """(correct, [(name, value, limit)]): every number at or under its
    limit, and none missing or not finite."""
    rows, ok = [], True
    for name, lim in limits.items():
        v = numbers.get(name, float("nan"))
        rows.append((name, v, lim))
        ok &= bool(np.isfinite(v) and v <= lim)
    return ok, rows
