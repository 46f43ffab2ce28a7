"""What every driver's cell takes from a configuration and hands to the
check: the program's model configuration, and per-leaf norms of the
program's trees, each in one jitted call."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.reference import fedpc as ref_fedpc


def arch_config(arch: dict):
    """The program's ``ArchConfig`` of a configuration's ``arch``."""
    from repro.configs.base import ArchConfig
    kw = dict(arch)
    kw["pattern"] = tuple(tuple(b) for b in kw["pattern"])
    return ArchConfig(**kw)


def check_param_tree(model, param_spec) -> None:
    """Refuse a model whose parameter tree differs from the reference's."""
    from bench.harness import gen
    have = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    want = gen.spec_shapes(param_spec)
    if (jax.tree_util.tree_structure(have)
            != jax.tree_util.tree_structure(want)
            or jax.tree_util.tree_leaves(jax.tree_util.tree_map(
                lambda a, b: a.shape != b.shape, have, want)).count(True)):
        raise RuntimeError("the model's parameter tree differs from the "
                           "reference's parameter spec")


@jax.jit
def _diff_norms(xs, ys):
    return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)
                                        - y.astype(jnp.float32))))
            for x, y in zip(xs, ys)]


def diff_norms(a, b) -> dict:
    """``{path: ||a - b||}`` per leaf, in one jitted call."""
    fa = jax.tree_util.tree_flatten_with_path(a)[0]
    vals = _diff_norms([x for _, x in fa], jax.tree_util.tree_leaves(b))
    return {ref_fedpc.path_str(p): float(v) for (p, _), v in zip(fa, vals)}
