"""Distributed (shard_map) fed runtime — runs in a subprocess with 8 host
devices so the main pytest process keeps its single-device view."""
import json
import os
import subprocess
import sys

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_config
from repro.models import build_model
from repro.fed.distributed import build_fed_step, build_fed_sync, fed_state_init
from repro.core.update import master_update_tree
from repro.core.ternary import ternarize_tree, ternarize_tree_round1
from repro.launch.mesh import make_mesh

mesh = make_mesh((4, 2), ("data", "model"))
cfg = get_config("fedpc-paper")
m = build_model(cfg)
params = m.init(jax.random.PRNGKey(0))
F = 4
sizes = jnp.array([100.0, 200.0, 150.0, 50.0])
out = {}

with jax.set_mesh(mesh):
    # --- strategies agree with each other and with the reference math ----
    state = fed_state_init(params, F)
    state["round"] = jnp.asarray(3, jnp.int32)       # exercise Eq.(5) branch
    state["params_prev"] = jax.tree_util.tree_map(
        lambda x: x + 0.01, params)
    state["prev_costs"] = jnp.array([1.0, 1.0, 1.0, 1.0])
    params_F = jax.tree_util.tree_map(
        lambda x: jnp.stack([x + 0.05 * (i + 1) for i in range(F)]), params)
    costs = jnp.array([0.9, 0.5, 0.8, 0.95])

    results = {}
    for strat in ("fedpc", "fedpc_packed", "fedpc_reduce"):
        sync = build_fed_sync(m, mesh, "data", strat)
        new_params, aux = jax.jit(sync)(params_F, costs, sizes, state)
        results[strat] = new_params
    reduce_diff = max(
        float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))))
        for a, b in zip(jax.tree_util.tree_leaves(results["fedpc"]),
                        jax.tree_util.tree_leaves(results["fedpc_reduce"])))
    out["reduce_vs_gather_max_diff"] = reduce_diff
    diff = max(
        float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))))
        for a, b in zip(jax.tree_util.tree_leaves(results["fedpc"]),
                        jax.tree_util.tree_leaves(results["fedpc_packed"])))
    out["packed_vs_plain_max_diff"] = diff

    # --- reference: core master_update_tree on the same inputs ----------
    from repro.core.goodness import select_pilot
    k_star, _ = select_pilot(costs, state["prev_costs"], sizes, 3)
    tern = jax.vmap(lambda q: ternarize_tree(
        q, state["params"], state["params_prev"], 0.2))(params_F)
    p_shares = sizes / jnp.sum(sizes)
    betas = jnp.full((F,), 0.2)
    q_pilot = jax.tree_util.tree_map(lambda x: x[k_star], params_F)
    want = master_update_tree(q_pilot, tern, p_shares, betas, k_star,
                              state["params"], state["params_prev"], 3, 0.01)
    ref_diff = max(
        float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))))
        for a, b in zip(jax.tree_util.tree_leaves(results["fedpc"]),
                        jax.tree_util.tree_leaves(want)))
    out["vs_reference_max_diff"] = ref_diff

    # --- round-1 branch: Eq. (4) codes + p_k-only weights ---------------
    state1 = fed_state_init(params, F)
    sync1 = build_fed_sync(m, mesh, "data", "fedpc")
    got1, _ = jax.jit(sync1)(params_F, costs, sizes, state1)
    k1, _ = select_pilot(costs, state1["prev_costs"], sizes, 1)
    tern1 = jax.vmap(lambda q: ternarize_tree_round1(
        q, state1["params"], 0.01))(params_F)
    q_pilot1 = jax.tree_util.tree_map(lambda x: x[k1], params_F)
    want1 = master_update_tree(q_pilot1, tern1, p_shares, betas, k1,
                               state1["params"], state1["params_prev"],
                               1, 0.01)
    out["round1_vs_reference_max_diff"] = max(
        float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))))
        for a, b in zip(jax.tree_util.tree_leaves(got1),
                        jax.tree_util.tree_leaves(want1)))

    # --- full fed step runs and improves cost over rounds ---------------
    fs = build_fed_step(m, mesh, "data", "fedpc_packed", lr=0.05)
    st = fed_state_init(params, F)
    opt_F = jax.tree_util.tree_map(
        lambda x: jnp.stack([x] * F), m.optimizer.init(params))
    batch_F = {"tokens": jax.random.randint(
        jax.random.PRNGKey(1), (F, 2, 2, 16), 0, cfg.vocab)}
    costs_hist = []
    step = jax.jit(fs)
    for _ in range(4):
        st, opt_F, metrics = step(st, opt_F, batch_F, sizes)
        costs_hist.append(float(metrics["cost_mean"]))
    out["costs"] = costs_hist

    # --- fedavg equals weighted average ----------------------------------
    sync_avg = build_fed_sync(m, mesh, "data", "fedavg")
    new_avg, _ = jax.jit(sync_avg)(params_F, costs, sizes, state)
    w = (sizes / jnp.sum(sizes)).reshape(-1, 1, 1)
    leaf = jax.tree_util.tree_leaves(params_F)[0]
    want0 = jnp.sum(leaf.astype(jnp.float32) *
                    w.reshape((-1,) + (1,) * (leaf.ndim - 1)), axis=0)
    got0 = jax.tree_util.tree_leaves(new_avg)[0]
    out["fedavg_max_diff"] = float(jnp.max(jnp.abs(
        got0.astype(jnp.float32) - want0)))

print("RESULT " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def results():
    env = dict(os.environ, PYTHONPATH=SRC)
    proc = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                          capture_output=True, text=True, timeout=560)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT ")][-1]
    return json.loads(line[len("RESULT "):])


def test_packed_equals_plain(results):
    assert results["packed_vs_plain_max_diff"] < 1e-6


def test_matches_core_reference(results):
    assert results["vs_reference_max_diff"] < 1e-5


def test_round1_matches_core_reference(results):
    """Round 1 must use p_k-only weights (Eq. (3) alpha0 rule), not
    beta-scaled ones — regression test for the round-1 divergence."""
    assert results["round1_vs_reference_max_diff"] < 1e-5


def test_fed_step_cost_improves(results):
    assert results["costs"][-1] < results["costs"][0]


def test_fedavg_weighted_average(results):
    assert results["fedavg_max_diff"] < 1e-5


def test_reduce_strategy_close_to_gather(results):
    # fedpc_reduce sums w_k·T_k in f16 on the wire — small quantization
    # error vs the exact int8 gather is expected and bounded
    assert results["reduce_vs_gather_max_diff"] < 2e-2
