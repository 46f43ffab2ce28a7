"""The plain references against the repository's models and round, at a
tiny size on the CPU."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.harness import gen
from bench.harness import spec as bench_spec
from bench.harness.cells import arch_config
from bench.reference import fedpc as ref_fedpc
from bench.tests import tiny


@pytest.mark.parametrize("pair", [tiny.tiny_xlstm, tiny.tiny_phi4],
                         ids=["xlstm", "phi4"])
def test_reference_loss_and_grads_match_the_model(pair):
    from repro.models import build_model
    _wl, cfg = pair()
    ref = bench_spec.reference(cfg["reference"])
    spec = ref.param_spec(cfg["arch"])
    params = gen.make_weights(spec, 2 ** 40 + 3)
    model = build_model(arch_config(cfg["arch"]))
    toks = jnp.asarray(gen.token_shards(5, 1, 2, 16, cfg["arch"]["vocab"],
                                        4)[0])
    with jax.default_matmul_precision("highest"):
        lm, gm = jax.value_and_grad(
            lambda p: model.loss(p, {"tokens": toks})[0])(params)
        lr, gr = jax.value_and_grad(
            lambda p: ref.loss(p, toks, cfg["arch"]))(params)
    assert float(lr) == pytest.approx(float(lm), rel=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(gm),
                    jax.tree_util.tree_leaves(gr)):
        scale = float(jnp.max(jnp.abs(b))) + 1e-12
        assert float(jnp.max(jnp.abs(a - b))) <= 1e-4 * scale


def test_weights_and_tokens_follow_the_seed():
    _wl, cfg = tiny.tiny_xlstm()
    spec = bench_spec.reference("xlstm").param_spec(cfg["arch"])
    a = gen.make_weights(spec, 2 ** 33 + 1)
    b = gen.make_weights(spec, 2 ** 33 + 1)
    c = gen.make_weights(spec, 2 ** 33 + 2)
    la, lb, lc = (jax.tree_util.tree_leaves(x) for x in (a, b, c))
    assert all(bool(jnp.array_equal(x, y)) for x, y in zip(la, lb))
    assert not bool(jnp.array_equal(la[0], lc[0]))
    t1 = gen.token_shards(2 ** 35, 4, 2, 32, 100, 4)
    t2 = gen.token_shards(2 ** 35, 4, 2, 32, 100, 4)
    assert all(np.array_equal(x, y) for x, y in zip(t1, t2))
    rows = np.concatenate(t1)
    assert len({r.tobytes() for r in rows}) == len(rows)   # rows all differ


def test_fault_schedule_copy_matches_the_program():
    from repro.fed.faults import FaultPlan
    plan = FaultPlan(seed=15, drop_after_uplink=0.25)
    for t in range(1, 30):
        want = np.asarray(plan.alive(t, 4)) > 0
        assert np.array_equal(ref_fedpc.fault_alive(15, t, 4, 0.25), want)


@pytest.mark.parametrize("pair,rounds", [(tiny.tiny_xlstm, 1),
                                         (tiny.tiny_phi4, 1),
                                         (tiny.tiny_mesh, 2)],
                         ids=["plain", "secagg-tree-drop", "mesh-4-devices"])
def test_round_replay_follows_the_program(pair, rounds):
    """The program's first rounds and the reference's replay of them agree
    far inside the committed limits on the CPU (the mesh cell's driver on
    four host devices, one worker each). (Later rounds drift apart by the
    chaos of Adam steps on gradients near zero; one round is the test of
    the arithmetic, two of the mesh's sync, whose round-1 codes are empty
    at its wire's alpha1.)"""
    wl, cfg = tiny.with_changes(pair(), rounds_per_call=1,
                                check_rounds=rounds,
                                setup_calls=max(2, rounds))
    cell = bench_spec.driver(wl["driver"])(wl, cfg, seed=2 ** 32 + 17)
    cell.setup()
    cell.release()
    from bench.harness.check import compare
    nums = compare(cell.prog, cell.reference(wl["limits"]))
    assert nums["pilot_mismatch"] == 0
    for name in set(wl["limits"]) - {"pilot_mismatch"}:
        assert nums[name] < wl["limits"][name] / 3, (name, nums)
