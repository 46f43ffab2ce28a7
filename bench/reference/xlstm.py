"""Plain reference of the xLSTM language model's training loss.

Follows the mLSTM and sLSTM cell equations of arXiv:2405.04517 (Beck et
al., 2024), with the block layout the configuration file states: each
block is ``x + cell(RMSNorm(x))``, mLSTM and sLSTM blocks alternate, a
final RMSNorm and an untied output head. The mLSTM runs in the paper's
*parallel* form (its eq. 19-27: a causal log-gate matrix with a row-wise
stabilizer), an independent route to the same function as a recurrent
implementation. The sLSTM is a time recurrence with exponential input and
forget gates and the stabilizer state ``m``. Parameters are a nested dict
whose keys name each weight; no cache, no batching tricks, no kernels.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.harness import counts
from bench.reference.layers import (P, dense, next_token_xent, rms_norm,
                                    silu, unit)


def _widths(arch: dict) -> tuple[int, int, int, int]:
    d = arch["d_model"]
    h = arch["n_heads"]
    di = int(arch["lstm_proj_factor"] * d) // h * h
    return d, h, di, di // h


def param_spec(arch: dict) -> dict:
    d, h, di, _ = _widths(arch)
    v = arch["vocab"]
    lead = (arch["n_layers"] // len(arch["pattern"]),)
    blocks = {}
    for j, (mixer, _ffn) in enumerate(arch["pattern"]):
        if mixer == "mlstm":
            cell = {
                "in_proj": dense(d, 2 * di, lead),
                "wq": dense(di, di, lead),
                "wk": dense(di, di, lead),
                "wv": dense(di, di, lead),
                "gates_w": dense(di, 2 * h, lead),
                # input-gate bias 0, forget-gate bias 3 (forget gate open)
                "gates_b": P(lead + (2 * h,),
                             ("segments", ((0.0, h), (3.0, h)))),
                "norm": P(lead + (di,), ("const", 1.0)),
                "out_proj": dense(di, d, lead),
            }
        elif mixer == "slstm":
            cell = {
                "gates_w": dense(d, 4 * d, lead),
                "r_gates_w": P(lead + (d, 4 * d), ("normal", 1.0 / d)),
                "gates_b": P(lead + (4 * d,), (
                    "segments", ((0.0, d), (3.0, d), (0.0, 2 * d)))),
                "out_proj": dense(d, d, lead),
            }
        else:
            raise ValueError(f"xlstm reference has no {mixer!r} block")
        blocks[f"b{j}"] = {"norm1": P(lead + (d,), ("const", 1.0)),
                           "mixer": cell}
    spec = {"embed": P((v, d), ("normal", 0.02)),
            "norm_f": P((d,), ("const", 1.0)),
            "units": blocks}
    if not arch.get("tie_embeddings", False):
        spec["lm_head"] = dense(d, v)
    return spec


def mlstm(p: dict, x, heads: int, eps: float):
    """mLSTM cell, parallel form. x (B, S, D) -> (B, S, D)."""
    b, s, _ = x.shape
    xm, z = jnp.split(x @ p["in_proj"], 2, axis=-1)
    di = xm.shape[-1]
    dh = di // heads
    split = lambda a: a.reshape(b, s, heads, dh).transpose(0, 2, 1, 3)
    q = split(xm @ p["wq"])                                   # (B,H,S,dh)
    k = split(xm @ p["wk"]) / jnp.sqrt(jnp.asarray(dh, x.dtype))
    v = split(xm @ p["wv"])
    gates = xm @ p["gates_w"] + p["gates_b"]                  # (B,S,2H)
    log_i = gates[..., :heads].transpose(0, 2, 1)             # (B,H,S)
    log_f = jax.nn.log_sigmoid(gates[..., heads:]).transpose(0, 2, 1)
    cum_f = jnp.cumsum(log_f, axis=-1)
    # D[t, j] = sum_{j < r <= t} log f_r + log i_j, for j <= t
    dmat = cum_f[..., :, None] - cum_f[..., None, :] + log_i[..., None, :]
    causal = jnp.tril(jnp.ones((s, s), bool))
    dmat = jnp.where(causal, dmat, -jnp.inf)
    m = jnp.max(dmat, axis=-1, keepdims=True)                 # stabilizer
    c = (q @ k.transpose(0, 1, 3, 2)) * jnp.exp(dmat - m)
    den = jnp.maximum(jnp.abs(jnp.sum(c, axis=-1, keepdims=True)),
                      jnp.exp(-m))
    h = (c @ v) / den                                         # (B,H,S,dh)
    h = h.transpose(0, 2, 1, 3).reshape(b, s, di)
    h = rms_norm(h, p["norm"], eps) * silu(z)
    return h @ p["out_proj"]


def slstm(p: dict, x):
    """sLSTM cell, a recurrence over time. x (B, S, D) -> (B, S, D)."""
    b, _, d = x.shape
    xg = x @ p["gates_w"]                                     # (B,S,4D)

    def step(carry, xg_t):
        c, n, h, m = carry
        raw = xg_t + h @ p["r_gates_w"] + p["gates_b"]
        log_i, log_f = raw[:, :d], raw[:, d:2 * d]            # exp gates
        z, o = raw[:, 2 * d:3 * d], raw[:, 3 * d:]
        m_new = jnp.maximum(log_f + m, log_i)
        i_g = jnp.exp(log_i - m_new)
        f_g = jnp.exp(log_f + m - m_new)
        c = f_g * c + i_g * jnp.tanh(z)
        n = jnp.maximum(f_g * n + i_g, jnp.exp(-m_new))
        h = jax.nn.sigmoid(o) * c / n
        return (c, n, h, m_new), h

    zeros = jnp.zeros((b, d), x.dtype)
    init = (zeros, jnp.ones((b, d), x.dtype), zeros, zeros)
    _, hs = jax.lax.scan(step, init, jnp.moveaxis(xg, 1, 0))
    return jnp.moveaxis(hs, 0, 1) @ p["out_proj"]


def loss(params: dict, tokens, arch: dict):
    """Mean next-token cross-entropy of ``tokens`` (B, S)."""
    eps = arch["norm_eps"]
    x = params["embed"][tokens]
    n_units = arch["n_layers"] // len(arch["pattern"])
    for u in range(n_units):
        up = unit(params["units"], u)
        for j, (mixer, _ffn) in enumerate(arch["pattern"]):
            bp = up[f"b{j}"]
            h = rms_norm(x, bp["norm1"], eps)
            if mixer == "mlstm":
                h = mlstm(bp["mixer"], h, arch["n_heads"], eps)
            else:
                h = slstm(bp["mixer"], h)
            x = x + h
    x = rms_norm(x, params["norm_f"], eps)
    head = (params["embed"].T if arch.get("tie_embeddings", False)
            else params["lm_head"])
    return next_token_xent(x @ head, tokens)


def train_flops_per_token(arch: dict, seq_len: int) -> float:
    """Model FLOPs of one training token (``bench/harness/counts.py``)."""
    d, h = arch["d_model"], arch["n_heads"]
    per_unit = sum(counts.mlstm_flops(d, h, arch["lstm_proj_factor"])
                   if mixer == "mlstm" else counts.slstm_flops(d)
                   for mixer, _ffn in arch["pattern"])
    n_units = arch["n_layers"] // len(arch["pattern"])
    return counts.BACKWARD * (n_units * per_unit
                              + counts.head_flops(d, arch["vocab"]))
