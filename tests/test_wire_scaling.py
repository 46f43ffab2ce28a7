"""Worker-scalable wire kernels: the grid-accumulated master, the
rows-major stacked uplink, and the block-size autotuner.

The contract under test:
  * the accumulating master is BITWISE equal to the order-exact oracle
    (``ref.packed_master_accum_ref`` under jit) for every
    (block_rows, block_workers) plan — including odd block sizes,
    non-divisible worker counts (N = 33), and masked / beta_k-weighted
    ``w`` — so autotuning can never change results;
  * master VMEM per grid step is independent of N (the old kernel's was
    linear in N);
  * the stacked uplink's grid is rows-major (worker axis minor) so the
    shared history block index is constant across consecutive steps, and
    every plan packs bitwise like the per-worker loop;
  * either kernel is exactly ONE pallas launch under every plan;
  * the tuner: backend heuristics, explicit-plan snapping, table
    save/load, and that the ``ops`` wrappers consult a pinned plan.
"""
from functools import partial

import jax
import jax.extend.core as jax_core
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref, tune
from repro.utils import iter_jaxpr_eqns, jaxpr_primitive_counts


def _wire_fixture(n, rows_flat, seed=0):
    k = jax.random.PRNGKey(seed)
    bufs_q = jax.random.normal(k, (n, rows_flat, 128))
    p1 = jax.random.normal(jax.random.fold_in(k, 1), (rows_flat, 128))
    p2 = jax.random.normal(jax.random.fold_in(k, 2), (rows_flat, 128))
    return bufs_q, p1, p2


def _plans(r4, n):
    """Every structurally distinct plan family: single step, worker grid,
    multi-row grid, odd row blocks, worker sub-blocks (incl. the divisors
    of a non-divisible N like 33 → 3, 11)."""
    cands = [(r4, n), (r4, 1), (None, None)]
    for br in {max(1, r4 // 2), 3 if r4 % 3 == 0 else 1}:
        if r4 % br == 0:
            cands.append((br, 1))
    for bw in (3, 11, 2, 4):
        if n % bw == 0 and bw < n:
            cands.append((r4, bw))
    return cands


# ---------------------------------------------------------------------------
# Accumulating master: bitwise vs the order-exact oracle, every plan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [1, 8, 33])
@pytest.mark.parametrize("t", [1, 3])
def test_master_accum_bitwise_every_plan(n, t):
    rows_flat = 96                       # r4 = 24: odd block 3 divides it
    r4 = rows_flat // 4
    bufs_q, p1, p2 = _wire_fixture(n, rows_flat, seed=10 * n + t)
    betas = jnp.linspace(0.1, 0.3, n)
    packed = ops.flat_ternary_pack_stacked(
        bufs_q, p1, p2, t=t, beta=betas, alpha1=0.01, interpret=True)
    q = jax.random.normal(jax.random.PRNGKey(99), (rows_flat, 128))
    # masked + beta_k-weighted w: pilot zeroed, two workers masked out
    w = jnp.linspace(0.01, 0.05, n) * betas
    w = jnp.where(jnp.arange(n) == n // 2, 0.0, w)
    if n > 2:
        w = w.at[1].set(0.0)

    oracle = jax.jit(partial(ref.packed_master_accum_ref, t=t, alpha0=0.01))
    want = np.asarray(oracle(q.reshape(-1), packed.reshape(n, -1), w,
                             p1.reshape(-1), p2.reshape(-1)))
    for br, bw in _plans(r4, n):
        got = ops.flat_master_update(
            q, packed, w, p1, p2, t=t, alpha0=0.01, interpret=True,
            block_rows=br, block_workers=bw)
        np.testing.assert_array_equal(np.asarray(got).reshape(-1), want,
                                      err_msg=f"plan ({br}, {bw})")


def test_master_accum_agrees_with_einsum_oracle():
    """The sequential accumulation is the same math as the einsum oracle
    (allclose — reduction order differs)."""
    n, rows_flat = 8, 256
    bufs_q, p1, p2 = _wire_fixture(n, rows_flat, seed=3)
    packed = ops.flat_ternary_pack_stacked(
        bufs_q, p1, p2, t=3, beta=0.2, alpha1=0.01, interpret=True)
    q = jax.random.normal(jax.random.PRNGKey(4), (rows_flat, 128))
    w = jnp.full((n,), 0.02).at[2].set(0.0)
    got = ops.flat_master_update(q, packed, w, p1, p2, t=3, alpha0=0.01,
                                 interpret=True)
    want = ref.packed_master_update_ref(
        q.reshape(-1), packed.reshape(n, -1), w, p1.reshape(-1),
        p2.reshape(-1), 3, 0.01)
    np.testing.assert_allclose(np.asarray(got).reshape(-1), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("plan", [(None, None), (8, 1), (24, 4)])
def test_master_single_launch_every_plan(plan):
    n, rows_flat = 8, 96
    br, bw = plan
    q = jnp.zeros((rows_flat, 128))
    packed = jnp.zeros((n, rows_flat // 4, 128), jnp.uint8)
    w = jnp.full((n,), 0.02)
    counts = jaxpr_primitive_counts(
        lambda a, b, c: ops.flat_master_update(
            a, b, c, q, q, t=3, alpha0=0.01, interpret=True,
            block_rows=br, block_workers=bw),
        q, packed, w)
    assert counts.get("pallas_call") == 1, counts


# ---------------------------------------------------------------------------
# Stacked uplink: bitwise vs per-worker loop, rows-major grid structure
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [1, 8, 33])
def test_stacked_uplink_bitwise_every_plan(n):
    rows_flat = 96
    r4 = rows_flat // 4
    bufs_q, p1, p2 = _wire_fixture(n, rows_flat, seed=n)
    betas = jnp.linspace(0.1, 0.3, n)
    for t in (1, 3):
        want = jnp.stack([ops.flat_ternary_pack(
            bufs_q[i], p1, p2, t=t, beta=betas[i], alpha1=0.01,
            interpret=True) for i in range(n)]).reshape(n, r4, 128)
        for br, bw in _plans(r4, n):
            got = ops.flat_ternary_pack_stacked(
                bufs_q, p1, p2, t=t, beta=betas, alpha1=0.01,
                interpret=True, block_rows=br, block_workers=bw)
            np.testing.assert_array_equal(
                np.asarray(got), np.asarray(want),
                err_msg=f"t={t} plan ({br}, {bw})")


def _pallas_eqn(fn, *args):
    jaxpr = jax.make_jaxpr(fn)(*args)
    for eqn in iter_jaxpr_eqns(jaxpr.jaxpr, into_pallas=False):
        if eqn.primitive.name == "pallas_call":
            return eqn
    raise AssertionError("no pallas_call in jaxpr")


def test_stacked_uplink_grid_is_rows_major_worker_minor():
    """The multi-step plan's grid must iterate (row blocks, worker blocks)
    with workers MINOR, and the history operands' block index must not
    depend on the worker axis — that is what lets consecutive steps reuse
    the fetched history block instead of re-reading it N times."""
    n, rows_flat = 4, 256
    r4 = rows_flat // 4
    bufs_q, p1, p2 = _wire_fixture(n, rows_flat)
    eqn = _pallas_eqn(
        lambda a, b, c: ops.flat_ternary_pack_stacked(
            a, b, c, t=3, beta=0.2, alpha1=0.01, interpret=True,
            block_rows=r4 // 2, block_workers=1),
        bufs_q, p1, p2)
    gm = eqn.params["grid_mapping"]
    assert gm.grid == (2, n)             # (row blocks, worker blocks)
    # history block mappings (operands 1 and 2) ignore the worker index
    hist_maps = [bm for bm in gm.block_mappings
                 if tuple(getattr(d, "block_size", d)
                          for d in bm.block_shape) == (r4 // 2, 512)][:2]
    assert len(hist_maps) == 2
    for bm in hist_maps:
        idx = jax_core.jaxpr_as_fun(bm.index_map_jaxpr)
        i0 = idx(jnp.int32(0), jnp.int32(0))
        for k in range(1, n):            # worker step changes nothing
            np.testing.assert_array_equal(
                np.asarray(idx(jnp.int32(0), jnp.int32(k))),
                np.asarray(i0))
        assert int(idx(jnp.int32(1), jnp.int32(0))[0]) != int(i0[0])


def test_stacked_uplink_single_launch_and_no_int8():
    n, rows_flat = 8, 256
    bufs_q, p1, p2 = _wire_fixture(n, rows_flat)
    counts = jaxpr_primitive_counts(
        lambda a, b, c: ops.flat_ternary_pack_stacked(
            a, b, c, t=3, beta=0.2, alpha1=0.01, interpret=True),
        bufs_q, p1, p2)
    assert counts.get("pallas_call") == 1, counts


# ---------------------------------------------------------------------------
# Master VMEM model: O(block), independent of N
# ---------------------------------------------------------------------------

def test_master_vmem_independent_of_workers():
    br = 64
    base = tune.master_vmem_tile_bytes(br, 1)
    for n in (8, 32, 64, 256):
        assert tune.master_vmem_tile_bytes(br, 1) == base
        old = tune.master_vmem_tile_bytes_preaccum(br, n)
        assert old - base == (n - 1) * br * 128   # old model: linear in N


# ---------------------------------------------------------------------------
# Autotuner
# ---------------------------------------------------------------------------

def test_tune_heuristics():
    # cpu-interpret: fewest steps (one grid step)
    assert tune.default_plan("master", 256, 8, "cpu-interpret") == {
        "block_rows": 256, "block_workers": 8}
    # compiled backends: VMEM tile, one worker per step
    plan = tune.default_plan("master", 256, 8, "tpu")
    assert plan == {"block_rows": 64, "block_workers": 1}
    assert tune.fit_block_workers(33, 8) == 3
    assert tune.fit_block_workers(33, 11) == 11
    assert tune.fit_block_workers(1, 4) == 1
    assert tune.fit_block_rows(24, 64) == 24
    assert tune.fit_block_rows(8400 // 4, 64) in range(1, 65)


def test_ops_wrappers_consult_pinned_plan():
    """set_plan() must steer the wrappers' grid (observable in the jaxpr)."""
    n, rows_flat = 4, 256
    r4 = rows_flat // 4
    bufs_q, p1, p2 = _wire_fixture(n, rows_flat)
    key = ("uplink_stacked", r4, n, "cpu-interpret")
    try:
        tune.set_plan("uplink_stacked", r4, n,
                      {"block_rows": r4 // 2, "block_workers": 2},
                      backend="cpu-interpret")
        eqn = _pallas_eqn(
            lambda a, b, c: ops.flat_ternary_pack_stacked(
                a, b, c, t=3, beta=0.2, alpha1=0.01, interpret=True),
            bufs_q, p1, p2)
        assert eqn.params["grid_mapping"].grid == (2, 2)
    finally:
        tune._TABLE.pop(key, None)


def test_autotune_sweep_stores_winner_and_roundtrips(tmp_path):
    r4, n = 16, 4
    rec = tune.autotune_stacked(r4, n, interpret=True, reps=1)
    assert rec["timings"] and all(t["us"] > 0 for t in rec["timings"])
    key = ("uplink_stacked", r4, n, "cpu-interpret")
    try:
        assert key in tune._TABLE
        assert tune._TABLE[key] == rec["best"]
        rec_m = tune.autotune_master(r4, n, interpret=True, reps=1)
        assert ("master", r4, n, "cpu-interpret") in tune._TABLE
        assert rec_m["best"]["block_rows"] <= r4

        path = str(tmp_path / "tuned.json")
        tune.save_table(path)
        saved = dict(tune._TABLE)
        tune.clear_table()
        assert tune.load_table(path) == len(saved)
        assert tune._TABLE == saved
    finally:
        tune._TABLE.pop(key, None)
        tune._TABLE.pop(("master", r4, n, "cpu-interpret"), None)
