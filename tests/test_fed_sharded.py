"""Model-sharded wire path: sharded-vs-replicated sync parity over several
(fed, model) mesh shapes, both round branches — runs in a subprocess with 8
host devices so the main pytest process keeps its single-device view."""
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
import numpy as np
import jax, jax.numpy as jnp
from repro.launch.mesh import make_mesh
from repro.fed.distributed import build_fed_sync, fed_state_init

k = jax.random.PRNGKey(0)
params = {"w": jax.random.normal(k, (300, 40)),
          "b": jax.random.normal(jax.random.fold_in(k, 5), (40,)),
          "s": jax.random.normal(jax.random.fold_in(k, 6), ())}
out = {}

def tree_max_diff(a, b):
    return max(float(jnp.max(jnp.abs(x.astype(jnp.float32)
                                     - y.astype(jnp.float32))))
               for x, y in zip(jax.tree_util.tree_leaves(a),
                               jax.tree_util.tree_leaves(b)))

for fed, model in ((4, 2), (2, 4), (2, 2), (8, 1)):
    devs = np.array(jax.devices()[: fed * model]).reshape(fed, model)
    mesh = make_mesh(devs.shape, ("data", "model"), devices=list(devs.flat))
    F = fed
    sizes = jnp.linspace(50.0, 200.0, F)
    costs = jnp.linspace(0.9, 0.5, F)
    params_F = jax.tree_util.tree_map(
        lambda x: jnp.stack([x + 0.05 * (i + 1) for i in range(F)]), params)

    # heterogeneous per-worker beta_k + a partial-participation mask (at
    # least one worker dropped, pilot guaranteed in the sampled set)
    betas = jnp.linspace(0.1, 0.35, F)
    mask = (jnp.arange(F) != 1).astype(jnp.float32)

    for t in (1, 3):
        state = fed_state_init(params, F)
        if t > 1:
            state["round"] = jnp.asarray(t, jnp.int32)
            state["params_prev"] = jax.tree_util.tree_map(
                lambda x: x + 0.01, params)
            state["prev_costs"] = jnp.ones((F,))
        with mesh:
            for strat in ("fedpc", "fedpc_packed", "fedpc_reduce"):
                res, res_het = {}, {}
                for shard in (True, False):
                    sync = build_fed_sync(None, mesh, "data", strat,
                                          shard_wire=shard)
                    new_params, aux = jax.jit(sync)(
                        params_F, costs, sizes, state)
                    res[shard] = new_params
                    sync_h = build_fed_sync(None, mesh, "data", strat,
                                            shard_wire=shard, betas=betas)
                    new_h, aux_h = jax.jit(sync_h)(
                        params_F, costs, sizes, state, mask)
                    res_het[shard] = new_h
                key = f"{fed}x{model}_t{t}_{strat}"
                out[key] = tree_max_diff(res[True], res[False])
                out["het_" + key] = tree_max_diff(res_het[True],
                                                  res_het[False])
                out["het_vs_plain_" + key] = tree_max_diff(res_het[True],
                                                           res[True])
                out["het_kstar_" + key] = int(aux_h["k_star"])

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


def test_covers_all_mesh_shapes(results):
    plain = [k for k in results if not k.startswith("het")]
    assert len(plain) == 4 * 2 * 3            # meshes × rounds × strategies


def test_sharded_bitwise_equals_replicated_exact_modes(results):
    """gather / packed move exact int8/uint8 codes — slab math must be
    bitwise identical to the replicated buffer, in the uniform AND the
    heterogeneous-beta_k + partial-participation regimes."""
    for key, diff in results.items():
        if key.startswith("het_vs_plain") or key.startswith("het_kstar"):
            continue
        if key.endswith("fedpc") or key.endswith("fedpc_packed"):
            assert diff == 0.0, f"{key}: {diff}"


def test_sharded_reduce_close_to_replicated(results):
    """fedpc_reduce sums f16 on the wire; psum_scatter+all_gather may order
    the sum differently than a fused psum — bounded, tiny."""
    for key, diff in results.items():
        if key.startswith("het_vs_plain") or key.startswith("het_kstar"):
            continue
        if key.endswith("fedpc_reduce"):
            assert diff < 2e-2, f"{key}: {diff}"


def test_heterogeneous_round_differs_and_avoids_masked_pilot(results):
    """betas+mask actually change the update (not a silent no-op), and the
    masked worker (index 1) is never selected as pilot."""
    assert any(d > 0.0 for k, d in results.items()
               if k.startswith("het_vs_plain"))
    for k, v in results.items():
        if k.startswith("het_kstar"):
            assert v != 1, f"{k}: masked worker won pilot selection"
