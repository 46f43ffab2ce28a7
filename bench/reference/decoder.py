"""Plain reference of a GQA decoder's training loss (Phi-4-mini family).

Pre-RMSNorm blocks of causal grouped-query attention with rotary position
embeddings (rotate-half pairs, base ``rope_theta``) and a SwiGLU MLP; a
final RMSNorm and, with ``tie_embeddings``, the embedding matrix as the
output head. Every step is written out: heads split, keys and values
repeated per query group, a dense causal softmax. No cache, no kernels.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.harness import counts
from bench.reference.layers import (P, dense, next_token_xent, rms_norm,
                                    silu, unit)


def param_spec(arch: dict) -> dict:
    d, v = arch["d_model"], arch["vocab"]
    hq, hk, dh, f = (arch["n_heads"], arch["n_kv_heads"], arch["head_dim"],
                     arch["d_ff"])
    lead = (arch["n_layers"] // len(arch["pattern"]),)
    blocks = {}
    for j, (mixer, ffn) in enumerate(arch["pattern"]):
        if mixer != "attn" or ffn != "mlp":
            raise ValueError(f"decoder reference has no ({mixer}, {ffn})")
        blocks[f"b{j}"] = {
            "norm1": P(lead + (d,), ("const", 1.0)),
            "mixer": {"wq": dense(d, hq * dh, lead),
                      "wk": dense(d, hk * dh, lead),
                      "wv": dense(d, hk * dh, lead),
                      "wo": dense(hq * dh, d, lead)},
            "norm2": P(lead + (d,), ("const", 1.0)),
            "ffn": {"w_gate": dense(d, f, lead),
                    "w_up": dense(d, f, lead),
                    "w_down": dense(f, d, lead)},
        }
    spec = {"embed": P((v, d), ("normal", 0.02)),
            "norm_f": P((d,), ("const", 1.0)),
            "units": blocks}
    if not arch.get("tie_embeddings", False):
        spec["lm_head"] = dense(d, v)
    return spec


def rope(x, theta: float):
    """x (B, S, H, dh): rotate the pairs (x[:half], x[half:]) by position."""
    s, dh = x.shape[1], x.shape[-1]
    half = dh // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv     # (S, half)
    cos = jnp.cos(ang).astype(x.dtype)[None, :, None, :]
    sin = jnp.sin(ang).astype(x.dtype)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(p: dict, x, arch: dict):
    b, s, _ = x.shape
    hq, hk, dh = arch["n_heads"], arch["n_kv_heads"], arch["head_dim"]
    q = rope((x @ p["wq"]).reshape(b, s, hq, dh), arch["rope_theta"])
    k = rope((x @ p["wk"]).reshape(b, s, hk, dh), arch["rope_theta"])
    v = (x @ p["wv"]).reshape(b, s, hk, dh)
    k = jnp.repeat(k, hq // hk, axis=2)        # query head h reads kv h//g
    v = jnp.repeat(v, hq // hk, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(
        jnp.asarray(dh, x.dtype))
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", w, v).reshape(b, s, hq * dh)
    return out @ p["wo"]


def swiglu(p: dict, x):
    return (silu(x @ p["w_gate"]) * (x @ p["w_up"])) @ p["w_down"]


def loss(params: dict, tokens, arch: dict):
    """Mean next-token cross-entropy of ``tokens`` (B, S)."""
    eps = arch["norm_eps"]
    x = params["embed"][tokens]
    n_units = arch["n_layers"] // len(arch["pattern"])
    for u in range(n_units):
        up = unit(params["units"], u)
        for j in range(len(arch["pattern"])):
            bp = up[f"b{j}"]
            x = x + attention(bp["mixer"], rms_norm(x, bp["norm1"], eps),
                              arch)
            x = x + swiglu(bp["ffn"], rms_norm(x, bp["norm2"], eps))
    x = rms_norm(x, params["norm_f"], eps)
    head = (params["embed"].T if arch.get("tie_embeddings", False)
            else params["lm_head"])
    return next_token_xent(x @ head, tokens)


def train_flops_per_token(arch: dict, seq_len: int) -> float:
    """Model FLOPs of one training token (``bench/harness/counts.py``)."""
    d = arch["d_model"]
    per_block = (counts.attention_flops(d, arch["n_heads"],
                                        arch["n_kv_heads"], arch["head_dim"],
                                        seq_len)
                 + counts.swiglu_flops(d, arch["d_ff"]))
    n_units = arch["n_layers"] // len(arch["pattern"])
    return counts.BACKWARD * (n_units * len(arch["pattern"]) * per_block
                              + counts.head_flops(d, arch["vocab"]))
