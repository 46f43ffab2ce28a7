"""Observability layer: device-resident round records, JSONL traces,
byte cross-checks against the ``core.protocol`` models.

Contracts pinned here:
  * both simulator drivers export bitwise-identical telemetry (the scan
    stacks the same device records the Python loop fetches);
  * telemetry riding the carry adds NO kernel launches and NO host syncs
    to the round program (jaxpr-counted, scan included);
  * checkpoint/resume continues the telemetry carry and record stream
    exactly where the interrupted run stopped;
  * the JSONL schema round-trips and rejects malformed events;
  * every exported round's bytes equal an independent in-test
    re-derivation through ``core.protocol`` — flat, tree, masked-16/32
    and faulty-round runs (the SimResult byte views are the same data);
  * tuner sweeps emit one plan event per timed candidate;
  * ``run_fedpc_scan``'s host spans reach a ``jax.profiler`` capture and
    nest as documented; the round body's device scopes reach the compiled
    program's ``op_name`` metadata and add no jaxpr equations;
  * the fault-code constants mirrored into ``telemetry.record`` (to
    avoid an import cycle) stay identical to ``repro.fed.faults``.
"""
import contextlib
import glob
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import flat as fl
from repro.core import protocol as proto
from repro.core.fedpc import FedPCConfig
from repro.core.tree import TreeSpec
from repro.data.pipeline import federated_loaders
from repro.data.synthetic import SyntheticClassification
from repro.fed import faults as ft
from repro.fed import rounds as rd
from repro.fed.faults import FaultPlan
from repro.fed.simulator import FedSimulator
from repro.fed.worker import Worker, make_worker_configs
from repro.kernels import tune
from repro.models.mlp import init_mlp_classifier, mlp_loss_and_grad
from repro.privacy.spec import PrivacySpec
from repro.telemetry import record as tmr
from repro.telemetry import trace as tmt
from repro.utils import (
    HOST_SYNC_PRIMITIVES, iter_jaxpr_eqns, jaxpr_primitive_counts,
)

N = 6
PER = 60


def _make_sim(cfg, seed=0):
    task = SyntheticClassification(n_samples=N * PER, n_features=12,
                                   n_classes=4, seed=0)
    x, y = task.generate()
    splits = [np.arange(k * PER, (k + 1) * PER) for k in range(N)]
    loaders = federated_loaders((x, y), splits, seed=seed, batch_menu=(30,))
    cfgs = make_worker_configs(N, [PER] * N, seed=seed, batch_menu=(30,))
    workers = [Worker(cfg=cfgs[k], loader=loaders[k],
                      loss_and_grad=mlp_loss_and_grad) for k in range(N)]
    params = init_mlp_classifier(jax.random.PRNGKey(0), 12, 4, hidden=(16,))
    return FedSimulator(workers, params, fed_cfg=cfg)


def _faulty_cfg(fanout=3, mb=16):
    return FedPCConfig(
        n_workers=N,
        privacy=PrivacySpec(mask_seed=5, modulus_bits=mb,
                            recovery_threshold=2),
        tree=TreeSpec(fanout=fanout),
        faults=FaultPlan(seed=3, drop_before_uplink=0.1,
                         drop_after_uplink=0.25))


# ---------------------------------------------------------------------------
# Mirrored constants (import-cycle avoidance must not drift)
# ---------------------------------------------------------------------------

def test_fault_constants_pinned_to_faults_module():
    assert tmr.FAULT_NONE == ft.FAULT_NONE
    assert tmr.DROP_BEFORE == ft.DROP_BEFORE


# ---------------------------------------------------------------------------
# Driver parity: scan and Python loop export identical telemetry
# ---------------------------------------------------------------------------

def test_driver_trace_parity_bitwise():
    r1 = _make_sim(_faulty_cfg()).run_fedpc(rounds=3)
    r2 = _make_sim(_faulty_cfg()).run_fedpc_scan(rounds=3)
    assert r1.telemetry is not None and r2.telemetry is not None
    assert r1.telemetry.meta["driver"] == "run_fedpc"
    assert r2.telemetry.meta["driver"] == "run_fedpc_scan"
    # event streams are identical (ints exact; device costs computed by
    # the same float32 program are bitwise equal across drivers)
    assert r1.telemetry.rounds == r2.telemetry.rounds
    assert r1.telemetry.workers == r2.telemetry.workers
    assert r1.telemetry.edges == r2.telemetry.edges
    # cumulative carry totals agree too
    t1, t2 = r1.round_state.telemetry, r2.round_state.telemetry
    for a, b in zip(t1, t2):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert int(t1.rounds) == 3
    assert int(t1.sampled) == sum(r["n_sampled"]
                                  for r in r1.telemetry.rounds)


# ---------------------------------------------------------------------------
# Structure: telemetry adds no launches, no host syncs
# ---------------------------------------------------------------------------

def _tree(seed=0):
    k = jax.random.PRNGKey(seed)
    return {"w": jax.random.normal(k, (41, 23)),
            "b": jax.random.normal(jax.random.fold_in(k, 1), (23,))}


def _fixture(seed=0, privacy=None, telemetry=True):
    tree = _tree(seed)
    layout = fl.layout_of(tree)
    state = rd.init_round_state(tree, N, layout, privacy=privacy,
                                telemetry=telemetry)
    key = jax.random.PRNGKey(seed + 77)
    deltas = 0.05 * jax.random.normal(key, (N,) + state.buf_p1.shape)
    sizes = jnp.linspace(20.0, 80.0, N)
    return tree, layout, state, deltas, sizes


def _worker_fn(deltas):
    def fn(wc, buf, t):
        bufs_q = buf[None] + deltas * (1.0 + 0.1 * t.astype(jnp.float32))
        costs = 1.0 / (t.astype(jnp.float32)
                       + jnp.arange(N, dtype=jnp.float32) + 1.0)
        return wc, bufs_q, costs
    return fn


@pytest.mark.parametrize("spec", [None, PrivacySpec(),
                                  PrivacySpec(dp_epsilon=2.0)])
def test_round_step_with_telemetry_two_launches_no_host_sync(spec):
    wire = rd.WirePath(rd.WireConfig(), interpret=True, privacy=spec)
    _, _, state, _, sizes = _fixture(0, privacy=spec)
    assert state.telemetry is not None
    bufs = jnp.zeros((N,) + state.buf_p1.shape)
    costs = jnp.ones((N,))
    counts = jaxpr_primitive_counts(
        lambda s, b, c: wire.round_step(s, b, c, sizes), state, bufs, costs)
    assert counts.get("pallas_call") == 2, counts
    assert sum(counts.get(p, 0) for p in HOST_SYNC_PRIMITIVES) == 0, counts


def test_scan_with_telemetry_two_launches_no_host_sync():
    spec = PrivacySpec(dp_epsilon=2.0)
    wire = rd.WirePath(rd.WireConfig(), interpret=True, privacy=spec)
    _, _, state, deltas, sizes = _fixture(0, privacy=spec)
    counts = jaxpr_primitive_counts(
        lambda s: rd.scan_rounds(wire, s, _worker_fn(deltas), 0, 7, sizes),
        state)
    assert counts.get("pallas_call") == 2, counts
    assert sum(counts.get(p, 0) for p in HOST_SYNC_PRIMITIVES) == 0, counts


def test_telemetry_off_still_runs():
    wire = rd.WirePath(rd.WireConfig(), interpret=True)
    _, _, state, deltas, sizes = _fixture(0, telemetry=False)
    assert state.telemetry is None
    st, _, infos = jax.jit(lambda s: rd.scan_rounds(
        wire, s, _worker_fn(deltas), 0, 3, sizes))(state)
    assert st.telemetry is None
    assert infos["telemetry"].n_sampled.shape == (3,)


# ---------------------------------------------------------------------------
# Checkpoint/resume: carry totals and record stream continue exactly
# ---------------------------------------------------------------------------

def test_checkpoint_resume_trace_continuity(tmp_path):
    spec = PrivacySpec(dp_epsilon=2.0)
    tree, layout, state0, deltas, sizes = _fixture(3, privacy=spec)
    wire = rd.WirePath(rd.WireConfig(), interpret=True, privacy=spec)
    worker = _worker_fn(deltas)

    def run(st, n):
        return jax.jit(lambda s: rd.scan_rounds(
            wire, s, worker, 0, n, sizes))(st)

    st_full, _, infos_full = run(state0, 4)
    st_half, _, infos_a = run(state0, 2)
    rd.save_round_state(str(tmp_path), st_half)
    like = rd.init_round_state(tree, N, layout, privacy=spec)
    st_loaded, _ = rd.load_round_state(str(tmp_path), like)
    for a, b in zip(jax.tree_util.tree_leaves(st_loaded.telemetry),
                    jax.tree_util.tree_leaves(st_half.telemetry)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    st_resumed, _, infos_b = run(st_loaded, 2)
    # carry totals: resumed == uninterrupted, bitwise
    for a, b in zip(jax.tree_util.tree_leaves(st_resumed.telemetry),
                    jax.tree_util.tree_leaves(st_full.telemetry)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert int(st_resumed.telemetry.rounds) == 4
    # record stream: segment A ++ segment B == the 4-round run's records
    cat = jax.tree_util.tree_map(
        lambda a, b: jnp.concatenate([jnp.atleast_1d(a),
                                      jnp.atleast_1d(b)]),
        infos_a["telemetry"], infos_b["telemetry"])
    for a, b in zip(jax.tree_util.tree_leaves(cat),
                    jax.tree_util.tree_leaves(infos_full["telemetry"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# JSONL schema: round-trip + rejection of malformed events
# ---------------------------------------------------------------------------

def test_trace_jsonl_roundtrip(tmp_path):
    res = _make_sim(_faulty_cfg()).run_fedpc_scan(rounds=2)
    path = str(tmp_path / "trace.jsonl")
    n = res.telemetry.write(path)
    events = tmt.read_trace(path)
    assert len(events) == n
    summary = tmt.summarize(events)
    assert summary.bytes_per_round == res.telemetry.bytes_per_round
    assert (summary.recovery_bytes_per_round
            == res.telemetry.recovery_bytes_per_round)
    assert summary.pilots == res.telemetry.pilots
    assert summary.meta == res.telemetry.meta


def test_schema_rejects_malformed_events():
    meta = {"ev": "meta", "schema": tmt.SCHEMA_VERSION, "source": "t"}
    ok_round = {"ev": "round", "t": 1, "pilot": 0, "n_sampled": 4,
                "n_used": 4, "n_dead": 0, "n_pre_uplink": 0,
                "n_recovered": 0, "n_degraded": 0, "cost": 1.0,
                "wire_bytes": 10.0, "recovery_bytes": 0.0}
    tmt.validate_trace([meta, ok_round])
    with pytest.raises(ValueError, match="unknown trace event kind"):
        tmt.validate_event({"ev": "nope"})
    with pytest.raises(ValueError, match="missing field"):
        tmt.validate_event({k: v for k, v in ok_round.items()
                            if k != "pilot"})
    with pytest.raises(ValueError, match="unknown fields"):
        tmt.validate_event({**ok_round, "extra": 1})
    with pytest.raises(ValueError, match="bool"):
        tmt.validate_event({**ok_round, "n_dead": True})
    with pytest.raises(ValueError, match="must start with a meta"):
        tmt.validate_trace([ok_round])
    with pytest.raises(ValueError, match="schema"):
        tmt.validate_trace([{**meta, "schema": 99}])
    with pytest.raises(ValueError, match="empty trace"):
        tmt.validate_trace([])
    with pytest.raises(ValueError, match="sent"):
        tmt.validate_event({"ev": "worker", "t": 1, "worker": 0,
                            "sampled": True, "fault": 0, "pilot": False,
                            "sent": "gradients"})


def test_summarize_rejects_tampered_bytes(tmp_path):
    res = _make_sim(_faulty_cfg()).run_fedpc_scan(rounds=2)
    events = res.telemetry.events()
    bad = [dict(e) for e in events]
    for e in bad:
        if e["ev"] == "round":
            e["wire_bytes"] += 1.0
            break
    with pytest.raises(tmt.TelemetryMismatch, match="stored wire bytes"):
        tmt.summarize(bad)


# ---------------------------------------------------------------------------
# Byte model matrix: trace bytes == core/protocol, re-derived in-test
# ---------------------------------------------------------------------------

def _expected_bytes(meta, r):
    """An independent re-derivation of one round's bytes straight from the
    protocol functions (not via telemetry.round_bytes)."""
    mb, n = meta["model_bytes"], r["n_sampled"]
    masked = meta["wire"] == "masked"
    if meta["fanout"]:
        wire = proto.fedpc_tree_bytes_per_round(
            mb, n, meta["fanout"],
            word_bits=meta["modulus_bits"] if masked else None)
    elif masked:
        wire = proto.fedpc_masked_bytes_per_round(
            mb, n, word_bits=meta["modulus_bits"])
    else:
        wire = proto.fedpc_bytes_per_round(mb, n)
    rec_b = 0.0
    if meta["faults_active"]:
        leaf_bits = meta["modulus_bits"] if masked else 2.0
        wire -= mb * r["n_pre_uplink"] * leaf_bits / 32.0
        if meta["masking"] and meta["recovery_threshold"]:
            g = meta["fanout"] or None
            rec_b = (proto.recovery_dealing_bytes_per_round(
                         meta["n_workers"], g)
                     + proto.recovery_reconstruction_bytes(
                         r["n_recovered"], meta["recovery_threshold"], g,
                         n_workers=meta["n_workers"]))
    return float(wire), float(rec_b)


_MATRIX = {
    "flat": FedPCConfig(n_workers=N),
    "tree": FedPCConfig(n_workers=N, tree=TreeSpec(fanout=3)),
    "masked16": FedPCConfig(n_workers=N,
                            privacy=PrivacySpec(mask_seed=5,
                                                modulus_bits=16)),
    "masked32": FedPCConfig(n_workers=N,
                            privacy=PrivacySpec(mask_seed=5,
                                                modulus_bits=32)),
    "faulty": _faulty_cfg(),
}


@pytest.mark.parametrize("name", sorted(_MATRIX))
def test_trace_bytes_match_protocol_models(name):
    res = _make_sim(_MATRIX[name]).run_fedpc_scan(rounds=2)
    summary = res.telemetry
    assert summary is not None and len(summary.rounds) == 2
    for r in summary.rounds:
        wire, rec_b = _expected_bytes(summary.meta, r)
        assert r["wire_bytes"] == wire
        assert r["recovery_bytes"] == rec_b


@pytest.mark.parametrize("name", ["flat", "faulty"])
def test_simresult_views_are_telemetry_rollup(name):
    """Satellite 1 regression pin: the old hand-built SimResult byte lists
    and the telemetry rollup are the same numbers (build_trace would have
    raised on any divergence; this pins the VIEW wiring too)."""
    res = _make_sim(_MATRIX[name]).run_fedpc(rounds=2)
    assert res.bytes_per_round == res.telemetry.bytes_per_round
    assert (res.recovery_bytes_per_round
            == res.telemetry.recovery_bytes_per_round)
    assert res.total_bytes == pytest.approx(
        np.sum(res.bytes_per_round) + np.sum(res.recovery_bytes_per_round))
    assert res.total_bytes == pytest.approx(res.telemetry.total_bytes)


def test_fedavg_baseline_keeps_backing_lists():
    res = _make_sim(FedPCConfig(n_workers=N)).run_fedavg(rounds=2)
    assert res.telemetry is None
    assert len(res.bytes_per_round) == 2
    mb = None
    for b in res.bytes_per_round:
        mb = b if mb is None else mb
        assert b == mb                      # constant 2VN per round
    assert res.total_bytes == pytest.approx(np.sum(res.bytes_per_round))


# ---------------------------------------------------------------------------
# Tuner sweeps emit plan events through the same trace schema
# ---------------------------------------------------------------------------

def test_tune_sweeps_emit_plan_events():
    events = []

    def sink(event):
        tmt.validate_event(event)
        events.append(event)

    tune.set_trace_writer(tmt.plan_emitter(sink))
    try:
        out1 = tune.autotune_stacked(32, 4, interpret=True, reps=1)
        out2 = tune.autotune_mask_repair(32, 4, interpret=True, reps=1)
        out3 = tune.autotune_partial_sum(32, 2, 4, interpret=True, reps=1)
    finally:
        tune.set_trace_writer(None)
    assert len(events) == (len(out1["timings"]) + len(out2["timings"])
                           + len(out3["timings"]))
    for out in (out1, out2, out3):
        kind_evs = [e for e in events if e["kind"] == out["kind"]]
        bests = [e for e in kind_evs if e["best"]]
        assert len(bests) == 1
        assert bests[0]["block_rows"] == out["best"]["block_rows"]
        assert {(e["block_rows"], e["block_workers"]) for e in kind_evs} \
            == {(t["block_rows"], t["block_workers"])
                for t in out["timings"]}
    # hook cleared: further sweeps emit nothing
    n = len(events)
    tune.autotune_stacked(32, 4, interpret=True, reps=1)
    assert len(events) == n


def test_plan_trace_writer_roundtrip(tmp_path):
    path = str(tmp_path / "plans.jsonl")
    with tmt.TraceWriter(path, source="test_bench") as w:
        tune.set_trace_writer(tmt.plan_emitter(w.emit))
        try:
            tune.autotune_mask_repair(32, 4, interpret=True, reps=1)
        finally:
            tune.set_trace_writer(None)
    events = tmt.read_trace(path)
    assert events[0]["source"] == "test_bench"
    summary = tmt.summarize(events)
    assert summary.plans and not summary.rounds
    assert sum(e["best"] for e in summary.plans) == 1


# ---------------------------------------------------------------------------
# Profiler labels: host spans of the scan driver, device scopes of its body
# ---------------------------------------------------------------------------

PREPARE_LEAVES = ("fed/scan/state", "fed/scan/audit", "fed/scan/schedules",
                  "fed/scan/compile")
FINISH_LEAVES = ("fed/scan/wait", "fed/scan/ledger", "fed/scan/trace")


@pytest.fixture(scope="module")
def traced_scan(tmp_path_factory):
    """Two masked, faulted scan calls under one profiler capture (the first
    compiles): ``(calls, sim)``, ``calls`` holding each call's ``fed/``
    spans as ``{name: (start_ns, end_ns)}``."""
    sim = _make_sim(_faulty_cfg())
    logdir = str(tmp_path_factory.mktemp("profile"))
    jax.profiler.start_trace(logdir)
    try:
        res = sim.run_fedpc_scan(rounds=2)
        res = sim.run_fedpc_scan(rounds=2, state=res.round_state)
        jax.block_until_ready(res.params)
    finally:
        jax.profiler.stop_trace()
    path = sorted(glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True))[-1]
    spans = sorted((ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                   for plane in jax.profiler.ProfileData.from_file(path).planes
                   if plane.name.startswith("/host:")
                   for line in plane.lines for ev in line.events
                   if ev.name.startswith("fed/"))
    calls = [{n: (s, e) for s, e, n in spans if s >= c0 and e <= c1}
             for c0, c1, name in spans if name == "fed/scan"]
    return calls, sim


def _inside(inner, outer):
    return outer[0] <= inner[0] and inner[1] <= outer[1]


def test_scan_host_spans_nest(traced_scan):
    calls, _ = traced_scan
    assert len(calls) == 2
    for i, call in enumerate(calls):
        want = {"fed/scan", "fed/scan/prepare", "fed/scan/dispatch",
                "fed/scan/finish", *PREPARE_LEAVES, *FINISH_LEAVES}
        if i:                   # served by the compiled program
            want.discard("fed/scan/compile")
        assert set(call) == want
        for name, span in call.items():
            assert _inside(span, call["fed/scan"]), name
        for leaf in PREPARE_LEAVES[:3 if i else 4]:
            assert _inside(call[leaf], call["fed/scan/prepare"]), leaf
        for leaf in FINISH_LEAVES:
            assert _inside(call[leaf], call["fed/scan/finish"]), leaf
        # the call's steps follow each other in program order
        order = ["fed/scan/prepare", "fed/scan/dispatch", "fed/scan/finish"]
        assert all(call[a][1] <= call[b][0]
                   for a, b in zip(order, order[1:]))
        leaves = [n for n in PREPARE_LEAVES + FINISH_LEAVES if n in call]
        assert all(call[a][1] <= call[b][0]
                   for a, b in zip(leaves, leaves[1:]))


def test_scan_program_carries_device_scopes(traced_scan):
    _, sim = traced_scan
    (prog,) = sim.scan_programs.values()
    op_names = [line.split('op_name="', 1)[1].split('"', 1)[0]
                for line in prog.compiled.as_text().splitlines()
                if 'op_name="' in line]
    for scope in ("fed/train/optimizer/", "fed/flatten/", "fed/unflatten/"):
        assert any(scope in n for n in op_names), scope


def test_device_scopes_add_no_equations(monkeypatch):
    """The scan program's jaxpr, equation for equation, with the round
    body's named scopes and with every named scope turned off."""
    sim = _make_sim(_faulty_cfg())
    rounds, t0 = 2, 1
    layout = fl.layout_of(sim.init_params)
    masks, betas_arr = sim._resolve_scenario(None, None, rounds, 0, t0)
    state = rd.init_round_state(sim.init_params, N, layout,
                                privacy=sim.fed_cfg.privacy)
    worker_carry = tuple((w.opt.init(sim.init_params),
                          jnp.asarray(0, jnp.int32)) for w in sim.workers)
    schedules = tuple(jnp.zeros(
        (rounds, w.cfg.local_epochs * w.loader.steps_per_epoch(),
         w.loader.batch_size), jnp.int32) for w in sim.workers)
    shards = tuple(tuple(jnp.asarray(a) for a in w.loader.arrays)
                   for w in sim.workers)
    wire = sim._wire_path(None, None)
    args = (state, worker_carry, schedules, shards, masks,
            jnp.asarray(sim.sizes), betas_arr, jnp.asarray(t0, jnp.int32))

    def eqn_counts():
        # a new partial each time: make_jaxpr caches traces by function
        body = partial(sim._scan_body, wire, layout, rounds)
        jaxpr = jax.make_jaxpr(body)(*args)
        counts: dict = {}
        for eqn in iter_jaxpr_eqns(jaxpr.jaxpr):
            name = eqn.primitive.name
            counts[name] = counts.get(name, 0) + 1
            if "fed/train/optimizer" in str(eqn.source_info.name_stack):
                counts["in optimizer scope"] = counts.get(
                    "in optimizer scope", 0) + 1
        return counts

    scoped = eqn_counts()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain = eqn_counts()
    assert scoped.pop("in optimizer scope") > 0
    assert "in optimizer scope" not in plain
    assert plain == scoped
    assert scoped.get("pallas_call", 0) > 0
    assert sum(scoped.get(p, 0) for p in HOST_SYNC_PRIMITIVES) == 0
