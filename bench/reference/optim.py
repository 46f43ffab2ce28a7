"""Plain heavy-ball momentum and Adam, and the step-decay learning rate.

Momentum: ``v <- 0.9 v + g``, ``p <- p - lr v``. Adam (Kingma & Ba, 2015):
``m <- 0.9 m + 0.1 g``, ``s <- 0.999 s + 0.001 g^2``, ``p <- p - lr
m_hat / (sqrt(s_hat) + 1e-8)`` with bias-corrected ``m_hat``, ``s_hat``.
States are dicts keyed ``velocity`` or ``mu``/``nu``; they hold the dtype
of the parameters.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

MOMENTUM = 0.9
B1, B2, EPS = 0.9, 0.999, 1e-8


def init(name: str, params):
    zeros = lambda: jax.tree_util.tree_map(jnp.zeros_like, params)
    if name == "momentum":
        return {"velocity": zeros()}
    if name == "adam":
        return {"mu": zeros(), "nu": zeros(), "count": jnp.zeros((), jnp.int32)}
    raise ValueError(f"no reference optimizer {name!r}")


def update(name: str, grads, state, params, lr):
    tm = jax.tree_util.tree_map
    if name == "momentum":
        vel = tm(lambda v, g: MOMENTUM * v + g, state["velocity"], grads)
        return tm(lambda p, v: p - lr * v, params, vel), {"velocity": vel}
    count = state["count"] + 1
    mu = tm(lambda m, g: B1 * m + (1 - B1) * g, state["mu"], grads)
    nu = tm(lambda s, g: B2 * s + (1 - B2) * g * g, state["nu"], grads)
    c = count.astype(jnp.float32)
    scale_m = (1.0 / (1.0 - B1 ** c))
    scale_s = (1.0 / (1.0 - B2 ** c))
    new = tm(lambda p, m, s: p - lr * (m * scale_m.astype(p.dtype)) / (
        jnp.sqrt(s * scale_s.astype(p.dtype)) + EPS), params, mu, nu)
    return new, {"mu": mu, "nu": nu, "count": count}


def step_decay_lr(lr0: float, decay: float, every: int, step: int) -> float:
    return lr0 * decay ** (step // every)
