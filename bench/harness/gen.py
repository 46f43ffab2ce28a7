"""Inputs and weights from ``--seed``: the same seed gives the same bits.

Every random stream is keyed by ``(seed, purpose)`` through numpy's
``SeedSequence``, so seeds beyond 32 bits are fine and streams for tokens,
weights and loaders never overlap. The seed never changes a shape, a
worker's hyper-parameters or the fault schedule: those come from the
workload file, so every seed runs the same compiled programs.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

PURPOSE = {"weights": 1, "tokens": 2, "loaders": 3}


def seed_sequence(seed: int, purpose: str) -> np.random.SeedSequence:
    return np.random.SeedSequence([int(seed), PURPOSE[purpose]])


def key_words(seed: int, purpose: str) -> np.ndarray:
    """Two uint32 words: the raw data of a threefry key."""
    return seed_sequence(seed, purpose).generate_state(2, np.uint32)


def loader_seeds(seed: int, n: int) -> list[int]:
    ss = seed_sequence(seed, "loaders")
    return [int(x) for x in ss.generate_state(n, np.uint32)]


def token_shards(seed: int, n_workers: int, seqs: int, seq_len: int,
                 vocab: int, successors: int) -> list[np.ndarray]:
    """Each worker's ``(seqs, seq_len)`` int32 shard: walks on a sparse
    random successor graph (every token has ``successors`` possible next
    tokens), so the data has structure a model can learn. Vectorised over
    all sequences; one numpy step per position."""
    rng = np.random.default_rng(seed_sequence(seed, "tokens"))
    table = rng.integers(0, vocab, size=(vocab, successors), dtype=np.int64)
    n = n_workers * seqs
    toks = np.empty((n, seq_len), np.int64)
    toks[:, 0] = rng.integers(0, vocab, size=n)
    picks = rng.integers(0, successors, size=(n, seq_len))
    for s in range(1, seq_len):
        toks[:, s] = table[toks[:, s - 1], picks[:, s]]
    toks = toks.astype(np.int32)
    return [toks[k * seqs:(k + 1) * seqs] for k in range(n_workers)]


def make_weights(spec: dict, seed: int):
    """Weights for a reference ``param_spec`` tree, made on the default
    device by ONE jitted call (float32). Leaf ``i`` in tree order draws
    from ``fold_in(key, i)``; the key is an argument, so every seed runs
    the same compiled program."""
    leaves, treedef = jax.tree_util.tree_flatten(spec)

    def build(words):
        key = jax.random.wrap_key_data(words)
        out = []
        for i, leaf in enumerate(leaves):
            shape, init = leaf.shape, leaf.init
            kind = init[0]
            if kind == "normal":
                x = init[1] * jax.random.normal(jax.random.fold_in(key, i),
                                                shape, jnp.float32)
            elif kind == "const":
                x = jnp.full(shape, init[1], jnp.float32)
            elif kind == "segments":
                # (value, count) runs along the last axis, e.g. gate biases
                row = jnp.concatenate([jnp.full((c,), v, jnp.float32)
                                       for v, c in init[1]])
                x = jnp.broadcast_to(row, shape)
            else:
                raise ValueError(f"unknown init {init!r}")
            out.append(x)
        return jax.tree_util.tree_unflatten(treedef, out)

    words = jnp.asarray(key_words(seed, "weights"))
    return jax.jit(build)(words)


def spec_shapes(spec: dict):
    """The ``param_spec`` tree as ``ShapeDtypeStruct`` leaves."""
    return jax.tree_util.tree_map(
        lambda leaf: jax.ShapeDtypeStruct(leaf.shape, jnp.float32), spec)
