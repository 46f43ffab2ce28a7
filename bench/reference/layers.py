"""Plain pieces shared by the model references.

Every function computes in the dtype of its inputs and never upcasts, so
the same code is the float32 reference and, fed bfloat16 weights, the
bfloat16 control.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp


@dataclass(frozen=True)
class P:
    """One parameter of a reference: its shape and how it is drawn.

    ``init`` is ``("normal", std)``, ``("const", value)`` or
    ``("segments", ((value, count), ...))`` along the last axis."""
    shape: tuple
    init: tuple


def dense(d_in: int, d_out: int, lead: tuple = ()) -> P:
    """A fan-in scaled weight."""
    return P(lead + (d_in, d_out), ("normal", d_in ** -0.5))


def rms_norm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * scale


def silu(x):
    return x * jax.nn.sigmoid(x)


def next_token_xent(logits, tokens):
    """Mean cross-entropy of ``tokens[:, 1:]`` under ``logits[:, :-1]``."""
    lg = logits[:, :-1]
    labels = tokens[:, 1:]
    mx = jnp.max(lg, axis=-1, keepdims=True)
    lse = jnp.log(jnp.sum(jnp.exp(lg - mx), axis=-1)) + mx[..., 0]
    gold = jnp.take_along_axis(lg, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - gold)


def unit(params: dict, u: int) -> dict:
    """The ``u``-th layer period of a stacked ``units`` tree."""
    return jax.tree_util.tree_map(lambda a: a[u], params)
