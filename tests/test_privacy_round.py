"""The privacy wire through the round core and the simulator drivers.

Covers:
  * masked round_step keeps the 2-launch / 0-host-sync structure;
  * mask-seed invariance under scan (cancellation survives lax.scan);
  * the PrivacyAccountant composes through scan_rounds and round-trips
    through checkpoint/resume;
  * jaxpr-level §4.2 enforcement: no plaintext code tensor materializes on
    the masked path, the master launch consumes no worker-stacked float
    operand, and the audit REJECTS the plaintext wire when asked to hold
    it to the masked policy;
  * in-scan participation sampling (stateless per-round keys) is
    bit-identical to the precomputed schedule, including on resume;
  * the renormalized-share Eq. (3) variant behind WirePath.renorm_shares;
  * simulator integration: run_fedpc == run_fedpc_scan bitwise with the
    masked wire on, ledger audits recorded, masked byte accounting.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import flat as fl
from repro.core.privacy import LeakageError
from repro.fed import rounds as rd
from repro.privacy import PrivacySpec, check_round_program
from repro.utils import HOST_SYNC_PRIMITIVES, jaxpr_primitive_counts

N = 5


def _tree(seed=0):
    k = jax.random.PRNGKey(seed)
    return {"w": jax.random.normal(k, (41, 23)),
            "b": jax.random.normal(jax.random.fold_in(k, 1), (23,))}


def _fixture(seed=0, n=N, privacy=None):
    tree = _tree(seed)
    layout = fl.layout_of(tree)
    state = rd.init_round_state(tree, n, layout, privacy=privacy)
    key = jax.random.PRNGKey(seed + 77)
    deltas = 0.05 * jax.random.normal(key, (n,) + state.buf_p1.shape)
    sizes = jnp.linspace(20.0, 80.0, n)
    return tree, layout, state, deltas, sizes


def _worker_fn(deltas, n=N):
    def fn(wc, buf, t):
        bufs_q = buf[None] + deltas * (1.0 + 0.1 * t.astype(jnp.float32))
        costs = 1.0 / (t.astype(jnp.float32)
                       + jnp.arange(n, dtype=jnp.float32) + 1.0)
        return wc, bufs_q, costs
    return fn


# ---------------------------------------------------------------------------
# Structure: still two launches, still zero host syncs
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("spec", [PrivacySpec(),
                                  PrivacySpec(dp_epsilon=2.0)])
def test_masked_round_two_launches_no_host_sync(spec):
    wire = rd.WirePath(rd.WireConfig(), interpret=True, privacy=spec)
    _, _, state, deltas, sizes = _fixture(0, privacy=spec)
    bufs = jnp.zeros((N,) + state.buf_p1.shape)
    costs = jnp.ones((N,))
    counts = jaxpr_primitive_counts(
        lambda s, b, c: wire.round_step(s, b, c, sizes), state, bufs, costs)
    assert counts.get("pallas_call") == 2, counts
    assert sum(counts.get(p, 0) for p in HOST_SYNC_PRIMITIVES) == 0, counts


def test_masked_scan_program_two_launches_no_host_sync():
    spec = PrivacySpec(dp_epsilon=2.0)
    wire = rd.WirePath(rd.WireConfig(), interpret=True, privacy=spec)
    _, _, state, deltas, sizes = _fixture(0, privacy=spec)
    counts = jaxpr_primitive_counts(
        lambda s: rd.scan_rounds(wire, s, _worker_fn(deltas), 0, 7, sizes),
        state)
    assert counts.get("pallas_call") == 2, counts
    assert sum(counts.get(p, 0) for p in HOST_SYNC_PRIMITIVES) == 0, counts


# ---------------------------------------------------------------------------
# Mask cancellation through the scan; DP-off closeness to the float wire
# ---------------------------------------------------------------------------

def test_scan_bitwise_invariant_to_masking():
    tree, layout, state, deltas, sizes = _fixture(1)
    worker = _worker_fn(deltas)
    outs = {}
    for tag, seed in (("on", 0), ("other", 123), ("off", None)):
        spec = PrivacySpec(mask_seed=seed, dp_epsilon=2.0)
        wire = rd.WirePath(rd.WireConfig(), interpret=True, privacy=spec)
        st = rd.init_round_state(tree, N, layout, privacy=spec)
        st, _, _ = jax.jit(lambda s, w=wire: rd.scan_rounds(
            w, s, worker, 0, 5, sizes))(st)
        outs[tag] = np.asarray(st.buf_p1)
    np.testing.assert_array_equal(outs["on"], outs["off"])
    np.testing.assert_array_equal(outs["other"], outs["off"])


@pytest.mark.parametrize("mb,rtol,atol", [(32, 1e-5, 1e-6),
                                          (16, 1e-3, 2e-3)])
def test_masked_scan_close_to_plain_wire(mb, rtol, atol):
    """DP off: masked differs from the plain float wire only by the
    fixed-point weight rounding — 2**-25 per weight at the 32-bit modulus
    (fixpoint 24), 2**-15 at 16-bit (fixpoint 14), compounding over the
    5-round scan; the tolerances scale accordingly."""
    tree, layout, state, deltas, sizes = _fixture(2)
    worker = _worker_fn(deltas)
    spec = PrivacySpec(modulus_bits=mb)       # secure agg, DP off
    st_m = rd.init_round_state(tree, N, layout, privacy=spec)
    wire_m = rd.WirePath(rd.WireConfig(), interpret=True, privacy=spec)
    st_m, _, _ = jax.jit(lambda s: rd.scan_rounds(
        wire_m, s, worker, 0, 5, sizes))(st_m)
    wire_p = rd.WirePath(rd.WireConfig(), interpret=True)
    st_p, _, _ = jax.jit(lambda s: rd.scan_rounds(
        wire_p, s, worker, 0, 5, sizes))(state)
    np.testing.assert_allclose(np.asarray(st_m.buf_p1),
                               np.asarray(st_p.buf_p1),
                               rtol=rtol, atol=atol)


# ---------------------------------------------------------------------------
# Accountant: composition through scan + checkpoint/resume
# ---------------------------------------------------------------------------

def test_accountant_composes_and_survives_resume(tmp_path):
    spec = PrivacySpec(dp_epsilon=1.5)
    tree, layout, state0, deltas, sizes = _fixture(8, privacy=spec)
    wire = rd.WirePath(rd.WireConfig(), interpret=True, privacy=spec)
    worker = _worker_fn(deltas)
    run = jax.jit(lambda s, n: rd.scan_rounds(wire, s, worker, 0, n, sizes),
                  static_argnums=1)

    st_full, _, _ = run(state0, 6)
    acc = st_full.accountant
    assert int(acc.spent_rounds) == 6
    np.testing.assert_allclose(float(acc.eps_sum), 6 * spec.eps_round,
                               rtol=1e-6)
    np.testing.assert_allclose(float(acc.epsilon()), 6 * spec.eps_round,
                               rtol=1e-6)
    adv = float(acc.epsilon(spec.delta))
    want_adv = (np.sqrt(2 * np.log(1 / spec.delta) * 6 * spec.eps_round ** 2)
                + 6 * spec.eps_round * (np.exp(spec.eps_round) - 1))
    np.testing.assert_allclose(adv, want_adv, rtol=1e-5)

    st_half, _, _ = run(state0, 3)
    rd.save_round_state(str(tmp_path), st_half)
    like = rd.init_round_state(tree, N, layout, privacy=spec)
    st_loaded, _ = rd.load_round_state(str(tmp_path), like)
    for a, b in zip(st_loaded.accountant, st_half.accountant):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    st_resumed, _, _ = run(st_loaded, 3)
    for a, b in zip(st_resumed, st_full):
        if a is None or b is None:
            assert a is b
            continue
        for x, y in zip(jax.tree_util.tree_leaves(a),
                        jax.tree_util.tree_leaves(b)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    assert int(st_resumed.accountant.spent_rounds) == 6


def test_accountant_untouched_without_dp():
    spec = PrivacySpec()                      # secure agg only
    tree, layout, state, deltas, sizes = _fixture(3, privacy=spec)
    assert state.accountant is None           # no DP, no accountant
    wire = rd.WirePath(rd.WireConfig(), interpret=True, privacy=spec)
    st, _, _ = jax.jit(lambda s: rd.scan_rounds(
        wire, s, _worker_fn(deltas), 0, 4, sizes))(state)
    assert st.accountant is None


# ---------------------------------------------------------------------------
# §4.2 audits at jaxpr level
# ---------------------------------------------------------------------------

def _audit_args(state, sizes):
    bufs = jax.ShapeDtypeStruct((N,) + state.buf_p1.shape, jnp.float32)
    costs = jax.ShapeDtypeStruct((N,), jnp.float32)
    return state, bufs, costs, sizes


def test_audit_masked_round_program_passes():
    spec = PrivacySpec(dp_epsilon=2.0)
    _, _, state, _, sizes = _fixture(0, privacy=spec)
    wire = rd.WirePath(rd.WireConfig(), interpret=True, privacy=spec)
    report = check_round_program(
        lambda s, b, c: wire.round_step(s, b, c, sizes),
        *(_audit_args(state, sizes)[:3]),
        n_workers=N, masked=True)
    assert report["n_launches"] == 2


def test_audit_rejects_plaintext_wire_under_masked_policy():
    """The plaintext path materializes the packed uint8 code buffer — the
    masked policy must catch exactly that."""
    _, _, state, _, sizes = _fixture(0)
    wire = rd.WirePath(rd.WireConfig(), interpret=True)   # no privacy
    with pytest.raises(LeakageError, match="plaintext"):
        check_round_program(
            lambda s, b, c: wire.round_step(s, b, c, sizes),
            *(_audit_args(state, sizes)[:3]),
            n_workers=N, masked=True)
    # without the masked policy the plaintext wire is §4.2-legal (codes
    # only, no stacked float into the master)
    report = check_round_program(
        lambda s, b, c: wire.round_step(s, b, c, sizes),
        *(_audit_args(state, sizes)[:3]),
        n_workers=N, masked=False)
    assert report["n_launches"] == 2


def test_audit_rejects_materialized_mask_tensor_in_uplink():
    """Deliberate regression to the pre-in-kernel-PRNG wire: an 'uplink'
    launch that consumes an HBM-materialized (N, rows, 512) mask tensor
    must be flagged by the masked policy — mask streams belong in
    registers, generated from counter keys."""
    from jax.experimental import pallas as pl

    def leaky_masked_round(bufs_q, masks, p1):
        def uplink(q_ref, m_ref, o_ref):
            o_ref[...] = q_ref[...].astype(jnp.uint32) + m_ref[...]

        y = pl.pallas_call(
            uplink,
            out_shape=jax.ShapeDtypeStruct(masks.shape, jnp.uint32),
            interpret=True)(bufs_q, masks)

        def master(y_ref, p_ref, o_ref):
            s = jnp.sum(y_ref[...], axis=0)
            o_ref[...] = p_ref[...] - s.astype(jnp.float32)

        return pl.pallas_call(
            master,
            out_shape=jax.ShapeDtypeStruct(p1.shape, jnp.float32),
            interpret=True)(y, p1)

    _, _, state, _, sizes = _fixture(0)
    buf = jax.ShapeDtypeStruct(state.buf_p1.shape, jnp.float32)
    bufs = jax.ShapeDtypeStruct((N,) + state.buf_p1.shape, jnp.float32)
    masks = jax.ShapeDtypeStruct((N,) + state.buf_p1.shape, jnp.uint32)
    with pytest.raises(LeakageError, match="materialized mask"):
        check_round_program(leaky_masked_round, bufs, masks, buf,
                            n_workers=N, masked=True)
    # the unmasked policy has no opinion about integer operands
    report = check_round_program(leaky_masked_round, bufs, masks, buf,
                                 n_workers=N, masked=False)
    assert report["n_launches"] == 2


def test_audit_rejects_stacked_float_into_master():
    """A deliberately leaky 'master' launch whose operand list carries the
    worker-stacked full-precision buffers must be flagged."""
    from jax.experimental import pallas as pl

    def leaky(bufs_q, p1, p2):
        def k(q_ref, o_ref):
            o_ref[...] = jnp.sum(q_ref[...], axis=0)

        return pl.pallas_call(
            k, out_shape=jax.ShapeDtypeStruct(p1.shape, jnp.float32),
            interpret=True)(bufs_q)

    _, _, state, _, sizes = _fixture(0)
    buf = jax.ShapeDtypeStruct(state.buf_p1.shape, jnp.float32)
    bufs = jax.ShapeDtypeStruct((N,) + state.buf_p1.shape, jnp.float32)
    with pytest.raises(LeakageError, match="worker axis"):
        check_round_program(leaky, bufs, buf, buf,
                            n_workers=N, masked=False)


# ---------------------------------------------------------------------------
# In-scan participation sampling (stateless per-round keys)
# ---------------------------------------------------------------------------

def test_in_scan_participation_matches_precomputed_schedule():
    tree, layout, state, deltas, sizes = _fixture(4)
    worker = _worker_fn(deltas)
    wire = rd.WirePath(rd.WireConfig(), interpret=True)
    key = jax.random.PRNGKey(5)
    masks = rd.participation_masks(key, 6, N, 0.6)
    st_a, _, inf_a = jax.jit(lambda s: rd.scan_rounds(
        wire, s, worker, 0, 6, sizes, masks=masks))(state)
    st_b, _, inf_b = jax.jit(lambda s: rd.scan_rounds(
        wire, s, worker, 0, 6, sizes, participation=0.6,
        participation_key=key))(state)
    for a, b in zip(jax.tree_util.tree_leaves(st_a),
                    jax.tree_util.tree_leaves(st_b)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(inf_a["mask"]),
                                  np.asarray(inf_b["mask"]))


def test_in_scan_participation_resume_reproduces_schedule():
    """Keyed by ABSOLUTE round: 3+3 resumed rounds == 6 uninterrupted."""
    tree, layout, state, deltas, sizes = _fixture(5)
    worker = _worker_fn(deltas)
    wire = rd.WirePath(rd.WireConfig(), interpret=True)
    key = jax.random.PRNGKey(6)
    run = jax.jit(lambda s, n: rd.scan_rounds(
        wire, s, worker, 0, n, sizes, participation=0.6,
        participation_key=key), static_argnums=1)
    st_full, _, _ = run(state, 6)
    st_half, _, _ = run(state, 3)
    st_resumed, _, _ = run(st_half, 3)
    np.testing.assert_array_equal(np.asarray(st_resumed.buf_p1),
                                  np.asarray(st_full.buf_p1))


def test_in_scan_participation_validation():
    tree, layout, state, deltas, sizes = _fixture(0)
    wire = rd.WirePath(rd.WireConfig(), interpret=True)
    worker = _worker_fn(deltas)
    key = jax.random.PRNGKey(0)
    with pytest.raises(ValueError, match="not both"):
        rd.scan_rounds(wire, state, worker, 0, 2, sizes,
                       masks=jnp.ones((2, N)), participation=0.5,
                       participation_key=key)
    with pytest.raises(ValueError, match="participation_key"):
        rd.scan_rounds(wire, state, worker, 0, 2, sizes, participation=0.5)
    with pytest.raises(ValueError, match="in \\(0, 1\\]"):
        rd.scan_rounds(wire, state, worker, 0, 2, sizes, participation=1.5,
                       participation_key=key)


# ---------------------------------------------------------------------------
# Renormalized-share Eq. (3) variant
# ---------------------------------------------------------------------------

def test_renorm_shares_default_off_is_bitwise_unchanged():
    _, _, state, deltas, sizes = _fixture(6)
    worker = _worker_fn(deltas)
    mask = jnp.asarray([1.0, 0.0, 1.0, 1.0, 0.0])
    _, bufs_q, costs = worker(0, state.buf_p1, state.round)
    plain = rd.WirePath(rd.WireConfig())
    flagged = rd.WirePath(rd.WireConfig(), renorm_shares=False)
    _, a, _ = plain.round_step(state, bufs_q, costs, sizes, mask=mask)
    _, b, _ = flagged.round_step(state, bufs_q, costs, sizes, mask=mask)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_renorm_shares_weights_oracle():
    wire = rd.WirePath(rd.WireConfig(), renorm_shares=True)
    sizes = jnp.asarray([10.0, 20.0, 30.0, 40.0, 50.0])
    p = sizes / sizes.sum()
    mask = jnp.asarray([1.0, 0.0, 1.0, 1.0, 0.0])
    k_star = 2
    w = wire.weights(p, k_star, 3, mask=mask)
    pm = np.asarray(p) * np.asarray(mask)
    want = pm / pm.sum() * wire.cfg.beta
    want[k_star] = 0.0
    np.testing.assert_allclose(np.asarray(w), want, rtol=1e-6)
    # full participation: renorm is a no-op up to the fp division by ~1.0
    w_full = wire.weights(p, k_star, 3, mask=jnp.ones((5,)))
    w_plain = rd.WirePath(rd.WireConfig()).weights(
        p, k_star, 3, mask=jnp.ones((5,)))
    np.testing.assert_allclose(np.asarray(w_full), np.asarray(w_plain),
                               rtol=1e-6)


def test_renorm_shares_round_magnitude_invariant():
    """With renorm, the sum of Eq. (3) weights over the sampled set equals
    beta * (1 - p_pilot_renormalized) regardless of how few reported —
    the FedAvg-style constant-magnitude convention."""
    wire = rd.WirePath(rd.WireConfig(), renorm_shares=True)
    sizes = jnp.ones((N,))
    p = sizes / sizes.sum()
    for mask in (jnp.asarray([1, 1, 1, 0, 0.0]),
                 jnp.asarray([1, 1, 1, 1, 1.0])):
        k_star = 0
        w = wire.weights(p, k_star, 3, mask=mask)
        m = int(mask.sum())
        np.testing.assert_allclose(float(jnp.sum(w)),
                                   wire.cfg.beta * (m - 1) / m, rtol=1e-5)


# ---------------------------------------------------------------------------
# Simulator integration
# ---------------------------------------------------------------------------

def _make_sim(privacy=None, n=3, renorm=False):
    from repro.core.fedpc import FedPCConfig
    from repro.data.pipeline import BatchIterator
    from repro.fed.worker import Worker, make_worker_configs
    from repro.models.mlp import init_mlp_classifier, mlp_loss_and_grad

    rng = np.random.default_rng(0)
    x = rng.normal(size=(60, 8)).astype(np.float32)
    y = rng.integers(0, 3, 60).astype(np.int32)
    splits = [np.arange(0, 20), np.arange(20, 40), np.arange(40, 60)]
    cfgs = make_worker_configs(n, [20, 20, 20], seed=1, batch_menu=(10,))
    workers = [
        Worker(cfg=cfgs[k],
               loader=BatchIterator((x[s], y[s]), 10, seed=k),
               loss_and_grad=mlp_loss_and_grad)
        for k, s in enumerate(splits)
    ]
    params = init_mlp_classifier(jax.random.PRNGKey(0), 8, 3, hidden=(16,))
    cfg = FedPCConfig(n_workers=n, privacy=privacy, renorm_shares=renorm)
    from repro.fed.simulator import FedSimulator as FS
    return FS(workers, params, cfg), params


def test_simulator_masked_drivers_bitwise_equal_and_audited():
    spec = PrivacySpec(dp_epsilon=2.0)
    sim_a, _ = _make_sim(privacy=spec)
    res_a = sim_a.run_fedpc(rounds=4)
    sim_b, _ = _make_sim(privacy=spec)
    res_b = sim_b.run_fedpc_scan(rounds=4)
    for a, b in zip(jax.tree_util.tree_leaves(res_a.params),
                    jax.tree_util.tree_leaves(res_b.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert res_a.pilot_history == res_b.pilot_history
    # enforcement hook ran in BOTH runtimes and recorded the audit
    assert [a["runtime"] for a in sim_a.ledger.audits] == ["run_fedpc"]
    assert [a["runtime"] for a in sim_b.ledger.audits] == ["run_fedpc_scan"]
    assert all(a["masked"] for a in sim_a.ledger.audits)
    # the DP accountant rode along
    acc = res_a.round_state.accountant
    assert int(acc.spent_rounds) == 4
    # masked uplinks record only the allowed §4.2 fields — and the code
    # kind is the masked-wire one (the master never saw plaintext codes)
    kinds = {k for (_, _, k, _) in sim_a.ledger.events}
    assert kinds == {"cost", "pilot_params", "masked_words"}


def test_simulator_privacy_with_partial_participation():
    """The shipped secure-agg-ldp regime: privacy enforcement + C-fraction
    sampling must coexist (the audit's mask spec must trace correctly) and
    both drivers must still agree bitwise."""
    spec = PrivacySpec(dp_epsilon=4.0)
    outs = []
    for driver in ("run_fedpc", "run_fedpc_scan"):
        sim, _ = _make_sim(privacy=spec)
        res = getattr(sim, driver)(4, participation=0.67)
        assert len(sim.ledger.audits) == 1
        outs.append(res)
    for a, b in zip(jax.tree_util.tree_leaves(outs[0].params),
                    jax.tree_util.tree_leaves(outs[1].params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert outs[0].pilot_history == outs[1].pilot_history


def test_fed_sync_rejects_privacy_with_fedavg():
    """fedavg psums full-precision params — combining it with an active
    PrivacySpec must fail loudly, not silently run a plaintext wire."""
    from repro.launch.mesh import make_mesh
    from repro.fed.distributed import build_fed_sync
    devs = np.array(jax.devices()[:1]).reshape(1, 1)
    mesh = make_mesh(devs.shape, ("data", "model"), devices=list(devs.flat))
    with pytest.raises(ValueError, match="fedavg"):
        build_fed_sync(None, mesh, "data", "fedavg",
                       privacy=PrivacySpec())


def test_simulator_masked_byte_accounting():
    from repro.core import protocol as proto
    from repro.utils import tree_size
    spec = PrivacySpec()                       # 16-bit modulus default
    sim, params = _make_sim(privacy=spec)
    res = sim.run_fedpc(rounds=2)
    v = tree_size(params) * 4
    want = proto.fedpc_masked_bytes_per_round(v, 3,
                                              word_bits=spec.modulus_bits)
    assert res.bytes_per_round[0] == want
    assert want > proto.fedpc_bytes_per_round(v, 3)   # secure agg costs ...

    spec32 = PrivacySpec(modulus_bits=32)
    sim32, _ = _make_sim(privacy=spec32)
    res32 = sim32.run_fedpc(rounds=2)
    want32 = proto.fedpc_masked_bytes_per_round(v, 3, word_bits=32)
    assert res32.bytes_per_round[0] == want32
    assert want < want32                       # ... half as much at 16-bit

    sim_p, _ = _make_sim()
    res_p = sim_p.run_fedpc(rounds=2)
    assert res_p.bytes_per_round[0] == proto.fedpc_bytes_per_round(v, 3)
