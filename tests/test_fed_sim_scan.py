"""Simulator drivers: run_fedpc (Python loop) vs run_fedpc_scan (lax.scan).

The device-resident refactor's simulator-facing contract:
  * the two drivers are bitwise-identical over >= 5 rounds, in the uniform
    AND the partial-participation + heterogeneous-beta_k regimes;
  * neither driver syncs device→host per round (host conversions counted by
    instrumenting the simulator module, as in test_worker_transfers.py —
    the count must not grow with the number of rounds);
  * continuation through the returned RoundState is bitwise equal to an
    uninterrupted run;
  * ledger/byte accounting respect participation (only sampled workers
    upload; the pilot is always sampled).
"""
import jax
import numpy as np
import pytest

import repro.fed.simulator as sim_mod
from repro.data.pipeline import federated_loaders
from repro.data.synthetic import SyntheticClassification
from repro.fed.simulator import FedSimulator
from repro.fed.worker import Worker, make_worker_configs
from repro.models.mlp import init_mlp_classifier, mlp_loss_and_grad

N = 4
SAMPLES = 384            # 96 per worker, divisible by the 32-batch menu

_REAL_FLOAT = float
_REAL_INT = int


def _make_sim(seed=0):
    t = SyntheticClassification(n_samples=SAMPLES, n_features=16,
                                n_classes=5, seed=0)
    x, y = t.generate()
    per = SAMPLES // N
    splits = [np.arange(i * per, (i + 1) * per) for i in range(N)]
    loaders = federated_loaders((x, y), splits, seed=seed, batch_menu=(32,))
    cfgs = make_worker_configs(N, [per] * N, seed=seed, batch_menu=(32,))
    workers = [Worker(cfg=cfgs[k], loader=loaders[k],
                      loss_and_grad=mlp_loss_and_grad) for k in range(N)]
    params = init_mlp_classifier(jax.random.PRNGKey(0), 16, 5, hidden=(32,))
    return FedSimulator(workers, params)


def _assert_same_result(r1, r2):
    assert r1.pilot_history == r2.pilot_history
    assert r1.costs == r2.costs
    assert r1.bytes_per_round == r2.bytes_per_round
    for a, b in zip(jax.tree_util.tree_leaves(r1.params),
                    jax.tree_util.tree_leaves(r2.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# Driver parity (bitwise, >= 5 rounds)
# ---------------------------------------------------------------------------

def test_scan_driver_bitwise_equals_python_driver():
    r1 = _make_sim().run_fedpc(6)
    r2 = _make_sim().run_fedpc_scan(6)
    _assert_same_result(r1, r2)


def test_scan_driver_parity_partial_participation_and_betas():
    kw = dict(participation=0.5, betas=[0.1, 0.2, 0.3, 0.25],
              participation_seed=3)
    r1 = _make_sim().run_fedpc(6, **kw)
    r2 = _make_sim().run_fedpc_scan(6, **kw)
    _assert_same_result(r1, r2)


# ---------------------------------------------------------------------------
# Zero per-round host syncs (both drivers)
# ---------------------------------------------------------------------------

@pytest.fixture
def host_sync_counter(monkeypatch):
    """Counts float(<jax.Array>) / int(<jax.Array>) conversions inside the
    simulator module — each is a blocking device→host read."""
    calls = {"n": 0}

    def counting_float(x=0.0):
        if isinstance(x, jax.Array):
            calls["n"] += 1
        return _REAL_FLOAT(x)

    def counting_int(x=0, *a):
        if isinstance(x, jax.Array):
            calls["n"] += 1
        return _REAL_INT(x, *a) if a else _REAL_INT(x)

    monkeypatch.setattr(sim_mod, "float", counting_float, raising=False)
    monkeypatch.setattr(sim_mod, "int", counting_int, raising=False)
    return calls


@pytest.mark.parametrize("driver", ["run_fedpc", "run_fedpc_scan"])
def test_host_sync_count_independent_of_rounds(driver, host_sync_counter):
    """The per-round loop performs ZERO device→host conversions: the total
    count is the same for 2 rounds and for 5 (setup + the single post-run
    fetch only)."""
    counts = {}
    for rounds in (2, 5):
        sim = _make_sim()
        host_sync_counter["n"] = 0
        getattr(sim, driver)(rounds)
        counts[rounds] = host_sync_counter["n"]
    assert counts[2] == counts[5], (
        f"{driver}: host syncs grew with rounds: {counts}")


# ---------------------------------------------------------------------------
# Continuation through RoundState
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("participation", [None, 0.5])
@pytest.mark.parametrize("driver", ["run_fedpc", "run_fedpc_scan"])
def test_continuation_bitwise(driver, participation):
    """3 rounds + 3 resumed rounds == 6 uninterrupted rounds, bitwise (the
    returned RoundState is the full inter-round protocol state; under
    sampling, masks are keyed by absolute round so the resumed segment
    draws the schedule the uninterrupted run would have)."""
    kw = {} if participation is None else {"participation": participation}
    full = getattr(_make_sim(), driver)(6, **kw)

    sim = _make_sim()
    half = getattr(sim, driver)(3, **kw)
    cont = getattr(sim, driver)(3, state=half.round_state, **kw)
    for a, b in zip(jax.tree_util.tree_leaves(cont.params),
                    jax.tree_util.tree_leaves(full.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert half.pilot_history + cont.pilot_history == full.pilot_history


# ---------------------------------------------------------------------------
# Participation accounting
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("driver", ["run_fedpc", "run_fedpc_scan"])
def test_partial_participation_ledger_and_bytes(driver):
    sim = _make_sim()
    res = getattr(sim, driver)(5, participation=0.5, participation_seed=1)
    # ledger: per round, only sampled workers appear; pilot among them
    by_round = {}
    for (r, w, kind, is_pilot) in sim.ledger.events:
        by_round.setdefault(r, set()).add((w, kind))
    masks = np.asarray(sim_mod.rd.participation_masks(
        jax.random.PRNGKey(1), 5, N, 0.5))
    for i in range(5):
        row = masks[i]
        uploaders = {w for (w, kind) in by_round[i + 1]}
        assert uploaders == set(np.flatnonzero(row > 0).tolist())
        assert row[res.pilot_history[i]] > 0
    # Eq. (8) bytes follow the per-round participant count (2 of 4 here)
    from repro.core import protocol as proto
    mb = proto.model_size_bytes(sim.init_params)
    assert res.bytes_per_round == [proto.fedpc_bytes_per_round(mb, 2)] * 5


def test_worker_beta_menu_reaches_the_wire():
    """Workers drawing private beta_k via make_worker_configs(beta_menu=...)
    change the aggregate (vs the uniform default), and both drivers agree
    on it bitwise."""
    def make_het(seed=0):
        sim = _make_sim(seed)
        for k, w in enumerate(sim.workers):
            w.cfg.beta = (0.1, 0.2, 0.3, 0.25)[k]
        return sim

    r_uni = _make_sim().run_fedpc(4)
    r_het = make_het().run_fedpc(4)
    r_het_scan = make_het().run_fedpc_scan(4)
    _assert_same_result(r_het, r_het_scan)
    diffs = [np.max(np.abs(np.asarray(a) - np.asarray(b)))
             for a, b in zip(jax.tree_util.tree_leaves(r_uni.params),
                             jax.tree_util.tree_leaves(r_het.params))]
    assert max(diffs) > 0.0


def test_federation_scenario_presets():
    """The named regimes of repro.configs.federation drive the simulator."""
    from repro.configs import get_scenario, list_scenarios
    assert {"paper-uniform", "hetero-beta", "cross-device",
            "cross-device-hetero"} <= set(list_scenarios())
    sc = get_scenario("cross-device-hetero")
    betas = sc.betas_for(N, seed=0)
    assert len(betas) == N and all(b in sc.beta_menu for b in betas)
    res = _make_sim().run_fedpc_scan(3, participation=sc.participation,
                                     betas=betas)
    assert len(res.pilot_history) == 3
    assert get_scenario("paper-uniform").betas_for(N) is None


def test_fedavg_mask_renormalizes_over_participants():
    """build_fed_sync('fedavg') with a participation mask averages the
    sampled workers only, shares renormalized (the fedavg branch has no
    collectives, so a 1x1 mesh suffices)."""
    import jax.numpy as jnp
    from repro.launch.mesh import make_mesh
    from repro.fed.distributed import build_fed_sync, fed_state_init

    mesh = make_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1])
    params = {"w": jnp.arange(8.0)}
    F = 4
    params_F = {"w": jnp.stack([params["w"] + i for i in range(F)])}
    sizes = jnp.array([10.0, 20.0, 30.0, 40.0])
    costs = jnp.linspace(0.9, 0.6, F)
    mask = jnp.array([1.0, 0.0, 1.0, 0.0])
    state = fed_state_init(params, F)
    sync = build_fed_sync(None, mesh, "data", "fedavg")
    got, _ = sync(params_F, costs, sizes, state, mask)
    w = np.array([10.0, 0.0, 30.0, 0.0]) / 40.0
    want = (np.asarray(params_F["w"]) * w[:, None]).sum(0)
    np.testing.assert_allclose(np.asarray(got["w"]), want, rtol=1e-6)


def test_scan_driver_rejects_ragged_shards():
    sim = _make_sim()
    sim.workers[0].loader.batch_size = 28     # 96 % 28 != 0
    with pytest.raises(ValueError, match="ragged"):
        sim.run_fedpc_scan(2)


def test_scan_driver_rejects_evasion():
    sim = _make_sim()
    sim.evade_streak = 2
    with pytest.raises(ValueError, match="evade"):
        sim.run_fedpc_scan(2)
