"""Secure-aggregation wire on the mesh: masked sharded sync parity.

Subprocess with 8 host devices (like tests/test_fed_sharded.py). The
secure-agg contract under test:
  * with DP off, the masked sharded sync is BITWISE identical to the
    unmasked (mask_seed=None) replicated reference — masks cancel exactly
    in the integer domain, and modular addition is order-free, so the
    psum_scatter+all_gather reduction can never reorder its way out of
    parity — across multiple (fed, model) meshes and both round branches;
  * masked == unmasked holds sharded-vs-sharded and replicated-vs-
    replicated too (mask values can never reach the output);
  * the masked wire is allclose to the plain float wire (fixed-point
    weight rounding only);
  * DP on actually changes the update, and still cancels masks bitwise;
  * the collective-payload audit: nothing float crosses the fed axis
    stacked per worker, no plaintext int8/uint8 code payload crosses on
    the masked wire, and the audit hook records into the ledger.
"""
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
from repro.core.privacy import LeakageLedger
from repro.fed.distributed import build_fed_sync, fed_state_init
from repro.privacy import PrivacySpec

k = jax.random.PRNGKey(0)
params = {"w": jax.random.normal(k, (300, 40)),
          "b": jax.random.normal(jax.random.fold_in(k, 5), (40,)),
          "s": jax.random.normal(jax.random.fold_in(k, 6), ())}
out = {"audits": 0, "audit_payload_dtypes": []}

def tree_max_diff(a, b):
    return max(float(jnp.max(jnp.abs(x.astype(jnp.float32)
                                     - y.astype(jnp.float32))))
               for x, y in zip(jax.tree_util.tree_leaves(a),
                               jax.tree_util.tree_leaves(b)))

SPECS = {
    "m": PrivacySpec(),                        # secure agg, masks on
    "u": PrivacySpec(mask_seed=None),          # same wire, masks off
    "dp": PrivacySpec(dp_epsilon=2.0),         # + randomized response
}

for fed, model in ((4, 2), (2, 4), (8, 1)):
    devs = np.array(jax.devices()[: fed * model]).reshape(fed, model)
    mesh = make_mesh(devs.shape, ("data", "model"), devices=list(devs.flat))
    F = fed
    sizes = jnp.linspace(50.0, 200.0, F)
    costs = jnp.linspace(0.9, 0.5, F)
    params_F = jax.tree_util.tree_map(
        lambda x: jnp.stack([x + 0.05 * (i + 1) for i in range(F)]), params)
    betas = jnp.linspace(0.1, 0.35, F)
    mask = (jnp.arange(F) != 1).astype(jnp.float32)

    for t in (1, 3):
        state = fed_state_init(params, F)
        if t > 1:
            state["round"] = jnp.asarray(t, jnp.int32)
            state["params_prev"] = jax.tree_util.tree_map(
                lambda x: x + 0.01, params)
            state["prev_costs"] = jnp.ones((F,))
        res = {}
        with mesh:
            led = LeakageLedger()
            for shard in (True, False):
                for tag, spec in SPECS.items():
                    sync = build_fed_sync(None, mesh, "data", "fedpc",
                                          shard_wire=shard, privacy=spec,
                                          betas=betas, ledger=led)
                    new_params, aux = jax.jit(sync)(
                        params_F, costs, sizes, state, mask)
                    res[(shard, tag)] = new_params
                sync_p = build_fed_sync(None, mesh, "data", "fedpc",
                                        shard_wire=shard, betas=betas)
                res[(shard, "plain")], _ = jax.jit(sync_p)(
                    params_F, costs, sizes, state, mask)
            out["audits"] += len(led.audits)

        key = f"{fed}x{model}_t{t}"
        # DP off: masked sharded == unmasked replicated (the acceptance
        # comparison) and every other mask/shard combination
        out[key + "_msh_vs_urep"] = tree_max_diff(res[(True, "m")],
                                                  res[(False, "u")])
        out[key + "_msh_vs_mrep"] = tree_max_diff(res[(True, "m")],
                                                  res[(False, "m")])
        out[key + "_ush_vs_urep"] = tree_max_diff(res[(True, "u")],
                                                  res[(False, "u")])
        out[key + "_m_vs_plain"] = tree_max_diff(res[(True, "m")],
                                                 res[(True, "plain")])
        # DP on: masks still cancel (dp-sharded vs dp-sharded is trivial;
        # the real check is dp with masks == dp without masks, same mesh)
        sync_dpu = build_fed_sync(None, mesh, "data", "fedpc",
                                  shard_wire=True,
                                  privacy=PrivacySpec(mask_seed=None,
                                                      dp_epsilon=2.0),
                                  betas=betas)
        with mesh:
            dpu, _ = jax.jit(sync_dpu)(params_F, costs, sizes, state, mask)
        out[key + "_dp_masked_vs_unmasked"] = tree_max_diff(
            res[(True, "dp")], dpu)
        out[key + "_dp_vs_m"] = tree_max_diff(res[(True, "dp")],
                                              res[(True, "m")])

# collective payload audit detail (one mesh is enough)
from repro.privacy import collective_payloads
from repro.core import flat as fl
devs = np.array(jax.devices()).reshape(4, 2)
mesh = make_mesh(devs.shape, ("data", "model"), devices=list(devs.flat))
F = 4
sizes = jnp.linspace(50.0, 200.0, F)
costs = jnp.linspace(0.9, 0.5, F)
params_F = jax.tree_util.tree_map(
    lambda x: jnp.stack([x + 0.05 * (i + 1) for i in range(F)]), params)
state = fed_state_init(params, F)
with mesh:
    sync = build_fed_sync(None, mesh, "data", "fedpc", shard_wire=True,
                          privacy=PrivacySpec())
    payloads = collective_payloads(sync, params_F, costs, sizes, state)
out["audit_payload_dtypes"] = sorted({p["dtype"] for p in payloads})
out["stacked_float_payloads"] = sum(
    1 for p in payloads
    if p["dtype"].startswith("float") and p["shape"][:1] == (F,))
out["code_payloads"] = sum(
    1 for p in payloads if p["dtype"] in ("int8", "uint8"))

# 32-bit conservative modulus on one mesh: bitwise mask cancellation,
# tight parity vs the plain float wire (fb=24), uint32 words on the fed
# axis (the full modulus sweep is per-kernel in tests/test_masked_wire.py)
state = fed_state_init(params, F)
state["round"] = jnp.asarray(3, jnp.int32)
state["params_prev"] = jax.tree_util.tree_map(lambda x: x + 0.01, params)
state["prev_costs"] = jnp.ones((F,))
with mesh:
    s32 = build_fed_sync(None, mesh, "data", "fedpc", shard_wire=True,
                         privacy=PrivacySpec(modulus_bits=32))
    m32, _ = jax.jit(s32)(params_F, costs, sizes, state)
    s32u = build_fed_sync(None, mesh, "data", "fedpc", shard_wire=True,
                          privacy=PrivacySpec(modulus_bits=32,
                                              mask_seed=None))
    u32, _ = jax.jit(s32u)(params_F, costs, sizes, state)
    sp = build_fed_sync(None, mesh, "data", "fedpc", shard_wire=True)
    pl32, _ = jax.jit(sp)(params_F, costs, sizes, state)
    payloads32 = collective_payloads(s32, params_F, costs, sizes, state)
out["m32_vs_u32"] = tree_max_diff(m32, u32)
out["m32_vs_plain"] = tree_max_diff(m32, pl32)
out["audit_payload_dtypes_32"] = sorted({p["dtype"] for p in payloads32})

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


def test_masked_sharded_bitwise_equals_unmasked_replicated(results):
    """Acceptance: DP off -> masked sharded sync bit-identical to the
    unmasked replicated reference, >= 2 meshes x both round branches."""
    keys = [k for k in results if k.endswith("_msh_vs_urep")]
    assert len(keys) == 3 * 2                 # meshes x round branches
    for k in keys:
        assert results[k] == 0.0, f"{k}: {results[k]}"


def test_mask_and_shard_combinations_all_bitwise(results):
    for suffix in ("_msh_vs_mrep", "_ush_vs_urep"):
        for k in (k for k in results if k.endswith(suffix)):
            assert results[k] == 0.0, f"{k}: {results[k]}"


def test_masked_allclose_to_plain_float_wire(results):
    # default wire is the 16-bit modulus: fixpoint_bits=14 weight rounding
    # is the only divergence from the float wire, so the bound is coarser
    # than the 32-bit path's
    for k in (k for k in results if k.endswith("_m_vs_plain")):
        assert 0.0 <= results[k] < 2e-3, f"{k}: {results[k]}"


def test_conservative_32bit_modulus_path(results):
    """modulus_bits=32 on the mesh: masks cancel bitwise, fb=24 rounding
    keeps the tight plain-wire bound, uint32 words cross the fed axis."""
    assert results["m32_vs_u32"] == 0.0
    assert 0.0 <= results["m32_vs_plain"] < 1e-5
    assert "uint32" in results["audit_payload_dtypes_32"]


def test_dp_cancels_masks_and_changes_update(results):
    for k in (k for k in results if k.endswith("_dp_masked_vs_unmasked")):
        assert results[k] == 0.0, f"{k}: {results[k]}"
    assert any(results[k] > 0.0
               for k in results if k.endswith("_dp_vs_m"))


def test_fed_collective_payload_policy(results):
    """What actually crosses the fed axis on the masked wire: uint16
    masked words (the 16-bit default modulus — half the 32-bit path's
    bytes) and the f32 pilot/goodness scalars — never a worker-stacked
    float buffer, never plaintext int8/uint8 codes."""
    assert results["stacked_float_payloads"] == 0
    assert results["code_payloads"] == 0
    assert "uint16" in results["audit_payload_dtypes"]
    # enforcement hook recorded audits (one per first-call masked build)
    assert results["audits"] > 0
