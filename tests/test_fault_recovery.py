"""Dropout-tolerant secure aggregation: seed shares, mask repair, faults.

The contract under test (the Bonawitz-style dropout half of the privacy
wire):

* a dead worker's per-pair mask seeds reconstruct from >= t surviving
  siblings' GF(2^16) Shamir shares — and from NOTHING less: t-1 shares
  are consistent with every candidate secret, a still-live target raises
  ``LeakageError``, and a sibling group below threshold raises
  ``ValueError`` so the round can degrade instead;
* the fused ``mask_repair_2d`` launch subtracts exactly the dead
  workers' committed mask residue, so a faulty masked round is BITWISE
  identical to (a) the same faults on the debug wire (mask_seed=None)
  and (b) a no-fault round whose participation mask is the effective
  survivor set — at both moduli, flat and tree, single-round and under
  ``scan_rounds``, for random fault plans (property test);
* a sibling group that loses too many members degrades to an exact-zero
  subtree (the PR 7 dropped-subtree identity) without aborting;
* the simulator accounts recovery traffic (dealing + reconstruction)
  separately from uplink bytes and logs ``seed_shares`` /
  ``mask_recovery`` ledger events;
* ``kernels.tune`` keys its fallback log per (kind, shape, backend) —
  two kinds at one shape report separately, one key reports once.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.privacy import LeakageError
from repro.core.tree import TreeSpec
from repro.fed import rounds as rd
from repro.fed.faults import FaultPlan
from repro.kernels import ops, tune
from repro.privacy import masking as pvm
from repro.privacy import recovery as pvr
from repro.privacy.spec import PrivacySpec

N, ROWS = 8, 8


# ---------------------------------------------------------------------------
# Shamir dealing over GF(2^16)
# ---------------------------------------------------------------------------

def test_shamir_roundtrip_any_t_subset():
    rng = np.random.default_rng(0)
    secret = rng.integers(0, 1 << 16, (5, 2)).astype(np.uint16)
    shares = pvr.deal_shares(secret, 6, 3)
    xs = np.arange(1, 7, dtype=np.uint16)
    for sel in ([0, 1, 2], [3, 4, 5], [0, 2, 5], [5, 1, 3]):
        got = pvr.reconstruct(shares[sel], xs[sel])
        np.testing.assert_array_equal(got, secret)


def test_shamir_t_minus_one_shares_reveal_nothing():
    """Any t-1 shares are consistent with EVERY candidate secret: for each
    candidate there exists a degree-(t-1) polynomial through the held
    shares with that constant term — so the holder coalition's posterior
    over the secret is uniform. Checked constructively per candidate."""
    secret = np.asarray([[0x1234]], np.uint16)
    t = 3
    shares = pvr.deal_shares(secret, 5, t)
    xs = np.arange(1, 6, dtype=np.uint16)
    held_x, held_y = xs[:t - 1], shares[:t - 1]
    for candidate in (0x0000, 0x1234, 0xBEEF, 0xFFFF):
        pts_x = np.concatenate([np.asarray([0], np.uint16), held_x])
        pts_y = np.concatenate(
            [np.asarray([[[candidate]]], np.uint16), held_y])
        # interpolating the t points (0, candidate) + held shares yields a
        # valid dealing whose share at any fresh x completes the coalition
        # view — reconstructing from it returns the CANDIDATE, not the
        # true secret: the t-1 shares carried no information.
        fresh = pvr.reconstruct(pts_y, pts_x)          # poly at x=0
        assert int(fresh[0, 0]) == candidate


def test_recovered_keys_match_uplink_stream_keys():
    """The reconstructed seeds are bit-identical to the keys the dead
    worker's uplink committed (pair_stream_keys row), flat and grouped."""
    t = jnp.asarray(4, jnp.int32)
    alive = np.ones(N)
    alive[2] = 0.0
    for gsz in (None, 4):
        members, keys = pvr.recover_worker_keys(
            5, 2, N, t, 3, alive=alive, group_size=gsz)
        ref = np.asarray(pvm.pair_stream_keys(5, N, t))[2][members]
        np.testing.assert_array_equal(keys, ref.astype(np.uint32))


def test_recovering_live_worker_raises_leakage_error():
    """Satellite: the recovery control plane refuses a still-live target —
    reconstructing its seeds would strip its masks from a committed
    uplink."""
    alive = np.ones(N)
    with pytest.raises(LeakageError, match="still live"):
        pvr.recover_worker_keys(5, 2, N, jnp.asarray(4, jnp.int32), 3,
                                alive=alive)


def test_below_threshold_group_raises_value_error():
    alive = np.zeros(N)
    alive[1] = 1.0                     # one survivor < threshold 3
    with pytest.raises(ValueError, match="below threshold"):
        pvr.recover_worker_keys(5, 2, N, jnp.asarray(4, jnp.int32), 3,
                                alive=alive)


def test_dealing_is_deterministic_per_round():
    a = pvr.deal_worker_shares(5, 1, N, jnp.asarray(2, jnp.int32), 2,
                               group_size=4)
    b = pvr.deal_worker_shares(5, 1, N, jnp.asarray(2, jnp.int32), 2,
                               group_size=4)
    c = pvr.deal_worker_shares(5, 1, N, jnp.asarray(3, jnp.int32), 2,
                               group_size=4)
    np.testing.assert_array_equal(a[2], b[2])
    assert not np.array_equal(a[2], c[2])    # fresh dealing every round


# ---------------------------------------------------------------------------
# The fused repair kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mb", [16, 32])
@pytest.mark.parametrize("block_rows", [None, 2])
def test_repair_kernel_matches_reference(mb, block_rows):
    rng = np.random.default_rng(7)
    dt = jnp.uint16 if mb == 16 else jnp.uint32
    words = jnp.asarray(rng.integers(0, 1 << mb, (ROWS, 512)), dt)
    keys = jnp.asarray(rng.integers(0, 1 << 32, (6,)), jnp.uint32)
    coeff = jnp.asarray([1, -1, 0, 1, 0, -1], jnp.int32)
    got = ops.flat_mask_repair(words, keys, coeff, interpret=True,
                               block_rows=block_rows)
    ref = pvr.mask_repair_ref(words, keys, coeff, word_bits=mb)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


@pytest.mark.parametrize("mb", [16, 32])
def test_repair_kernel_zero_coeffs_is_identity(mb):
    rng = np.random.default_rng(8)
    dt = jnp.uint16 if mb == 16 else jnp.uint32
    words = jnp.asarray(rng.integers(0, 1 << mb, (ROWS, 512)), dt)
    keys = jnp.asarray(rng.integers(0, 1 << 32, (4,)), jnp.uint32)
    coeff = jnp.zeros((4,), jnp.int32)
    got = ops.flat_mask_repair(words, keys, coeff, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(words))


def test_effective_masks_viability_rule():
    thr, g = 2, 4
    # no deaths: viable regardless of survivor count
    ae, de = pvr.effective_masks(None, jnp.ones(N), thr, g, N)
    np.testing.assert_array_equal(np.asarray(ae), np.ones(N))
    np.testing.assert_array_equal(np.asarray(de), np.zeros(N))
    # one death, >= thr survivors in its group: dead marked for repair
    alive = np.ones(N)
    alive[1] = 0
    ae, de = pvr.effective_masks(None, jnp.asarray(alive), thr, g, N)
    np.testing.assert_array_equal(np.asarray(ae), alive)
    assert np.asarray(de)[1] == 1.0 and np.asarray(de).sum() == 1.0
    # group 0 loses 3 of 4 -> below threshold: whole group zeroes, no
    # repair marks; group 1 untouched
    alive = np.ones(N)
    alive[[0, 1, 2]] = 0
    ae, de = pvr.effective_masks(None, jnp.asarray(alive), thr, g, N)
    np.testing.assert_array_equal(np.asarray(ae),
                                  [0, 0, 0, 0, 1, 1, 1, 1])
    np.testing.assert_array_equal(np.asarray(de), np.zeros(N))
    # participation composes: a non-sampled worker is neither live nor dead
    pm = np.ones(N)
    pm[5] = 0
    alive = np.ones(N)
    alive[6] = 0
    ae, de = pvr.effective_masks(jnp.asarray(pm), jnp.asarray(alive),
                                 thr, g, N)
    np.testing.assert_array_equal(np.asarray(ae),
                                  [1, 1, 1, 1, 1, 0, 0, 1])
    assert np.asarray(de)[6] == 1.0 and np.asarray(de).sum() == 1.0


# ---------------------------------------------------------------------------
# Round-level bitwise parity under faults
# ---------------------------------------------------------------------------

_KEY = jax.random.PRNGKey(0)
_BUFS = jax.random.normal(_KEY, (N, ROWS, 128), jnp.float32)
_P1 = jax.random.normal(jax.random.fold_in(_KEY, 1), (ROWS, 128),
                        jnp.float32)
_SIZES = jnp.arange(1, N + 1, dtype=jnp.float32)
_COSTS = jax.random.uniform(jax.random.fold_in(_KEY, 2), (N,))


def _run_round(spec, tree, faults, mask=None, t0=2, bufs=None, costs=None):
    wire = rd.WirePath(privacy=spec, interpret=True, tree=tree,
                       faults=faults)
    st = rd.init_round_state({"w": jnp.zeros((ROWS * 128,))}, N)
    st = st._replace(buf_p1=_P1, buf_p2=_P1 * 0.5,
                     prev_costs=jnp.linspace(1.0, 2.0, N),
                     round=jnp.asarray(t0, jnp.int32))
    st2, new_buf, info = wire.round_step(
        st, _BUFS if bufs is None else bufs,
        _COSTS if costs is None else costs, _SIZES, mask=mask)
    return np.asarray(new_buf), info


def _effective_survivors(alive, gsz, thr):
    eff = alive.reshape(-1, gsz)
    viable = (eff.sum(1) >= thr) | ((1 - eff).sum(1) == 0)
    return (eff * viable[:, None]).reshape(-1)


@pytest.mark.parametrize("mb", [16, 32])
@pytest.mark.parametrize("fanout", [None, 4])
def test_faulty_round_equals_survivors_only_bitwise(mb, fanout):
    """Acceptance: with post-uplink deaths injected, the recovered masked
    sum == the survivors-only plain sum BITWISE — and the masked run ==
    the debug-wire (mask_seed=None) run under the same faults, so the
    repair term cancels the mask residue exactly."""
    plan = FaultPlan(seed=3, drop_after_uplink=0.3)
    tree = None if fanout is None else TreeSpec(fanout=fanout)
    spec = PrivacySpec(mask_seed=5, modulus_bits=mb, recovery_threshold=2)
    out_f, info = _run_round(spec, tree, plan)
    alive = np.asarray(plan.alive(2, N))
    assert 0 < alive.sum() < N               # the seed actually kills
    np.testing.assert_array_equal(np.asarray(info["alive"]), alive)
    eff = _effective_survivors(alive, N if fanout is None else fanout, 2)
    out_ref, _ = _run_round(spec, tree, None, mask=jnp.asarray(eff))
    np.testing.assert_array_equal(out_f, out_ref)
    dbg = PrivacySpec(mask_seed=None, modulus_bits=mb,
                      recovery_threshold=2)
    out_d, _ = _run_round(dbg, tree, plan)
    np.testing.assert_array_equal(out_f, out_d)


def test_below_threshold_group_degrades_to_zero_subtree():
    """fanout=2 + threshold=2: every group with a death keeps at most one
    survivor, so every hit group zeroes wholesale — the round must not
    abort and must equal the viable-groups-only reference bitwise."""
    plan = FaultPlan(seed=3, drop_after_uplink=0.3)
    spec = PrivacySpec(mask_seed=5, modulus_bits=16, recovery_threshold=2)
    tree = TreeSpec(fanout=2)
    out_f, _ = _run_round(spec, tree, plan)
    alive = np.asarray(plan.alive(2, N))
    eff = _effective_survivors(alive, 2, 2)
    assert eff.sum() < alive.sum()           # some group actually degraded
    out_ref, _ = _run_round(spec, tree, None, mask=jnp.asarray(eff))
    np.testing.assert_array_equal(out_f, out_ref)


def test_plain_wire_faults_fold_into_weights():
    """Without the privacy wire, faults are a pure participation fold:
    the faulty plain round == the no-fault plain round masked to the raw
    survivor set (no viability rule — nothing needs reconstructing)."""
    plan = FaultPlan(seed=3, drop_after_uplink=0.3)
    out_f, _ = _run_round(None, None, plan)
    alive = np.asarray(plan.alive(2, N))
    out_ref, _ = _run_round(None, None, None, mask=jnp.asarray(alive))
    np.testing.assert_array_equal(out_f, out_ref)


def test_masked_faults_require_recovery_threshold():
    plan = FaultPlan(seed=3, drop_after_uplink=0.3)
    spec = PrivacySpec(mask_seed=5, modulus_bits=16)
    with pytest.raises(ValueError, match="recovery_threshold"):
        _run_round(spec, None, plan)


def test_scan_rounds_realizes_faults_per_round():
    plan = FaultPlan(seed=3, drop_after_uplink=0.3,
                     drop_before_uplink=0.1)
    spec = PrivacySpec(mask_seed=5, modulus_bits=16, recovery_threshold=2)
    wire = rd.WirePath(privacy=spec, interpret=True, faults=plan)
    st0 = rd.init_round_state({"w": jnp.zeros((ROWS * 128,))}, N)
    st0 = st0._replace(buf_p1=_P1, buf_p2=_P1 * 0.5)

    def worker_fn(carry, gbuf, t):
        return carry, _BUFS + carry, _COSTS

    st_s, _carry, infos = rd.scan_rounds(wire, st0, worker_fn,
                                         jnp.float32(0.0), 3, _SIZES)
    assert infos["alive"].shape == (3, N)
    for i in range(3):
        np.testing.assert_array_equal(
            np.asarray(infos["alive"][i]),
            np.asarray(plan.alive(1 + i, N)))
    # the scan == the same rounds stepped one by one
    st = st0
    for _ in range(3):
        st, _, _ = wire.round_step(st, _BUFS + 0.0, _COSTS, _SIZES)
    np.testing.assert_array_equal(np.asarray(st_s.buf_p1),
                                  np.asarray(st.buf_p1))


_FAULT_PLANS = st.tuples(
    st.integers(min_value=0, max_value=2**31 - 1),
    st.sampled_from([0.0, 0.15, 0.3]),
    st.sampled_from([0.0, 0.2, 0.45]),
    st.sampled_from([0.0, 0.2]))


@settings(max_examples=8, deadline=None)
@given(_FAULT_PLANS, st.sampled_from([None, 2, 4]),
       st.sampled_from([16, 32]))
def test_property_random_fault_plans_stay_bitwise(plan_args, fanout, mb):
    """Over random (seed, rates) fault plans, fanouts and both moduli:
    recovered cohort sum == bitwise survivors-only sum, and the recovery
    never leaks into the value (debug-wire parity)."""
    seed, p_pre, p_post, p_str = plan_args
    plan = FaultPlan(seed=seed, drop_before_uplink=p_pre,
                     drop_after_uplink=p_post, straggler=p_str)
    tree = None if fanout is None else TreeSpec(fanout=fanout)
    spec = PrivacySpec(mask_seed=5, modulus_bits=mb, recovery_threshold=2)
    if not plan.active:
        out_f, _ = _run_round(spec, tree, None)
        out_ref, _ = _run_round(spec, tree, None,
                                mask=jnp.ones(N))
        np.testing.assert_array_equal(out_f, out_ref)
        return
    out_f, _ = _run_round(spec, tree, plan)
    alive = np.asarray(plan.alive(2, N))
    eff = _effective_survivors(alive, N if fanout is None else fanout, 2)
    if eff.sum() == 0:                       # whole cohort degraded
        return
    out_ref, _ = _run_round(spec, tree, None, mask=jnp.asarray(eff))
    np.testing.assert_array_equal(out_f, out_ref)
    dbg = PrivacySpec(mask_seed=None, modulus_bits=mb,
                      recovery_threshold=2)
    out_d, _ = _run_round(dbg, tree, plan)
    np.testing.assert_array_equal(out_f, out_d)


# ---------------------------------------------------------------------------
# Simulator drivers: parity, ledger forensics, byte accounting
# ---------------------------------------------------------------------------

def _make_sim(cfg):
    from repro.data.pipeline import federated_loaders
    from repro.data.synthetic import SyntheticClassification
    from repro.fed.simulator import FedSimulator
    from repro.fed.worker import Worker, make_worker_configs
    from repro.models.mlp import init_mlp_classifier, mlp_loss_and_grad
    n = cfg.n_workers
    task = SyntheticClassification(n_samples=n * 60, n_features=12,
                                   n_classes=4, seed=0)
    x, y = task.generate()
    splits = [np.arange(k * 60, (k + 1) * 60) for k in range(n)]
    loaders = federated_loaders((x, y), splits, seed=0, batch_menu=(30,))
    cfgs = make_worker_configs(n, [60] * n, seed=0, batch_menu=(30,))
    workers = [Worker(cfg=cfgs[k], loader=loaders[k],
                      loss_and_grad=mlp_loss_and_grad) for k in range(n)]
    params = init_mlp_classifier(jax.random.PRNGKey(0), 12, 4, hidden=(16,))
    return FedSimulator(workers, params, fed_cfg=cfg)


def test_simulator_drivers_and_ledger_under_faults():
    """Both simulator drivers agree under one FaultPlan; the ledger logs
    the recovery control plane (``seed_shares`` dealing, ``mask_recovery``
    reconstruction) and ``SimResult`` books recovery traffic SEPARATELY
    from uplink bytes."""
    from repro.core.fedpc import FedPCConfig
    plan = FaultPlan(seed=3, drop_after_uplink=0.25,
                     drop_before_uplink=0.1)
    spec = PrivacySpec(mask_seed=5, modulus_bits=16, recovery_threshold=2)
    cfg = FedPCConfig(n_workers=6, privacy=spec, faults=plan,
                      tree=TreeSpec(fanout=3))
    sim = _make_sim(cfg)
    res = sim.run_fedpc(rounds=3)
    assert len(res.recovery_bytes_per_round) == 3
    assert all(b > 0 for b in res.recovery_bytes_per_round)
    assert res.total_bytes == pytest.approx(
        np.sum(res.bytes_per_round) + np.sum(res.recovery_bytes_per_round))
    kinds = {k for (_, _, k, _) in sim.ledger.events}
    assert "seed_shares" in kinds and "mask_recovery" in kinds
    # a no-fault run books zero recovery traffic and MORE uplink bytes
    # per round (faulted pre-uplink workers never spent theirs)
    cfg0 = FedPCConfig(n_workers=6, privacy=spec, tree=TreeSpec(fanout=3))
    res0 = _make_sim(cfg0).run_fedpc(rounds=3)
    assert all(b == 0 for b in res0.recovery_bytes_per_round)
    assert all(a <= b for a, b in zip(res.bytes_per_round,
                                      res0.bytes_per_round))
    # scan driver: same plan, same numbers
    sim2 = _make_sim(cfg)
    res2 = sim2.run_fedpc_scan(rounds=3)
    np.testing.assert_allclose(np.asarray(res.costs),
                               np.asarray(res2.costs), rtol=1e-6)
    assert res2.recovery_bytes_per_round == res.recovery_bytes_per_round
    assert res2.pilot_history == res.pilot_history
    k2 = {k for (_, _, k, _) in sim2.ledger.events}
    assert "seed_shares" in k2 and "mask_recovery" in k2


# ---------------------------------------------------------------------------
# Mesh runtime: fault recovery on the sharded wire (subprocess, 8 devices)
# ---------------------------------------------------------------------------

_MESH_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import numpy as np
import jax, jax.numpy as jnp
from repro.launch.mesh import make_mesh
from repro.fed.distributed import build_fed_sync, fed_state_init
from repro.fed.faults import FaultPlan
from repro.privacy import PrivacySpec, effective_masks
from repro.core.tree import TreeSpec

k = jax.random.PRNGKey(0)
params = {"w": jax.random.normal(k, (300, 40)),
          "b": jax.random.normal(jax.random.fold_in(k, 5), (40,))}

def tmax(a, b):
    return max(float(jnp.max(jnp.abs(x.astype(jnp.float32)
                                     - y.astype(jnp.float32))))
               for x, y in zip(jax.tree_util.tree_leaves(a),
                               jax.tree_util.tree_leaves(b)))

plan = FaultPlan(seed=11, drop_after_uplink=0.3)
out = {}
for fed, model, tree in ((4, 2, None), (8, 1, TreeSpec(fanout=2))):
    devs = np.array(jax.devices()[: fed * model]).reshape(fed, model)
    mesh = make_mesh(devs.shape, ("data", "model"), devices=list(devs.flat))
    F = fed
    sizes = jnp.linspace(50.0, 200.0, F)
    costs = jnp.linspace(0.9, 0.5, F)
    params_F = jax.tree_util.tree_map(
        lambda x: jnp.stack([x + 0.05 * (i + 1) for i in range(F)]), params)
    spec = PrivacySpec(mask_seed=5, modulus_bits=16, recovery_threshold=2)
    dbg = PrivacySpec(mask_seed=None, modulus_bits=16, recovery_threshold=2)
    t = 3
    state = fed_state_init(params, F)
    state["round"] = jnp.asarray(t, jnp.int32)
    state["params_prev"] = jax.tree_util.tree_map(lambda x: x + 0.01, params)
    state["prev_costs"] = jnp.ones((F,))
    res = {}
    with mesh:
        for shard in (True, False):
            for tag, sp in (("m", spec), ("u", dbg)):
                sync = build_fed_sync(None, mesh, "data", "fedpc",
                                      shard_wire=shard, privacy=sp,
                                      tree=tree, faults=plan)
                res[(shard, tag)], _ = jax.jit(sync)(
                    params_F, costs, sizes, state, None)
        av = plan.alive(jnp.asarray(t, jnp.int32), F)
        ae, _ = effective_masks(
            None, av, 2, tree.fanout if tree is not None else None, F)
        sync_ref = build_fed_sync(None, mesh, "data", "fedpc",
                                  shard_wire=True, privacy=spec, tree=tree)
        ref, _ = jax.jit(sync_ref)(params_F, costs, sizes, state, ae)
    key = f"{fed}x{model}_tree{tree.fanout if tree else 0}"
    out[key + "_shard_vs_repl"] = tmax(res[(True, "m")], res[(False, "m")])
    out[key + "_masked_vs_debug"] = tmax(res[(True, "m")], res[(True, "u")])
    out[key + "_faulty_vs_survivors"] = tmax(res[(True, "m")], ref)
    out[key + "_alive"] = [float(a) for a in np.asarray(av)]
print("RESULT " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def mesh_results():
    import json
    import os
    import subprocess
    import sys
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env = dict(os.environ, PYTHONPATH=src)
    proc = subprocess.run([sys.executable, "-c", _MESH_SCRIPT], env=env,
                          capture_output=True, text=True, timeout=560)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines()
            if l.startswith("RESULT ")][-1]
    return json.loads(line[len("RESULT "):])


def test_mesh_fault_recovery_bitwise(mesh_results):
    """Acceptance (mesh half): with post-uplink deaths, the masked sharded
    sync == survivors-only reference == debug wire == replicated — all
    bitwise, flat and on a below-threshold-degrading tree."""
    for key, val in mesh_results.items():
        if key.endswith("_alive"):
            assert 0 < sum(val) < len(val)   # the plan actually kills
        else:
            assert val == 0.0, f"{key}: {val}"


# ---------------------------------------------------------------------------
# tune fallback-log keying (regression pin)
# ---------------------------------------------------------------------------

def test_tune_fallback_log_keyed_per_kind_and_shape(capsys):
    """The fallback log keys on (kind, rows, n, backend): two kinds at the
    SAME shape report separately; one key reports exactly once."""
    saved = set(tune._FALLBACK_LOGGED)
    try:
        tune._FALLBACK_LOGGED.clear()
        tune.lookup("mask_repair16", 4096, 1, interpret=True)
        tune.lookup("uplink_masked16", 4096, 1, interpret=True)
        tune.lookup("mask_repair16", 4096, 1, interpret=True)   # repeat
        tune.lookup("mask_repair16", 8192, 1, interpret=True)   # new rows
        lines = [l for l in capsys.readouterr().out.splitlines()
                 if l.startswith("[tune] no plan")]
        assert len(lines) == 3
        assert sum("mask_repair16@(rows=4096" in l for l in lines) == 1
        assert sum("uplink_masked16@(rows=4096" in l for l in lines) == 1
        assert sum("rows=8192" in l for l in lines) == 1
    finally:
        tune._FALLBACK_LOGGED.clear()
        tune._FALLBACK_LOGGED.update(saved)
