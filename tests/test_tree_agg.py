"""Hierarchical tree aggregation: tree == flat BITWISE at every fanout.

The contract under test (ISSUE 8 tentpole):

* the plain tree rides the integer wire (fixed-point weights, uint32
  words) and is bitwise equal to the flat integer comparator for every
  fanout, ragged last sibling groups included — modular accumulation is
  order-free, so tree shape can never change bits;
* the masked tree (sibling-scoped leaf masks + per-level node masks from
  the level-salted stream) produces bitwise the same round output as the
  flat masked path at BOTH moduli, with and without participation, under
  ``lax.scan``, and composed with ``renorm_shares``;
* a fully-dropped subtree contributes an exactly-zero partial;
* launches grow with tree depth (``levels + 2``), not with N, and the
  round program stays free of host syncs;
* the §4.2 audits still hold: the tree round program passes, and a
  de-masked (signed-int) partial crossing a fed collective below the
  root raises :class:`LeakageError`;
* the Eq. (8) tree byte model: the link into the root carries w_L ≤
  fanout buffers, per-level bytes shrink ~fanout× as the tree ascends.

Mesh parity (tree butterfly reduce vs flat psum on (4,2)/(2,4) meshes)
runs in a subprocess with 8 host devices, like tests/test_fed_sharded*.
"""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import protocol as proto
from repro.core.privacy import LeakageError
from repro.core.tree import TreeSpec
from repro.fed import rounds as rd
from repro.kernels import ops, tune
from repro.privacy import audit as pv_audit
from repro.privacy import masking as pvm
from repro.privacy.spec import PrivacySpec
from repro.utils import HOST_SYNC_PRIMITIVES, jaxpr_primitive_counts

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

ROWS = 32


def _mk(n, seed=0):
    k = jax.random.PRNGKey(seed)
    bufs_q = jax.random.normal(k, (n, ROWS, 128))
    p1 = jax.random.normal(jax.random.fold_in(k, 1), (ROWS, 128))
    p2 = jax.random.normal(jax.random.fold_in(k, 2), (ROWS, 128))
    costs = jax.random.uniform(jax.random.fold_in(k, 3), (n,))
    sizes = jnp.arange(1.0, n + 1.0)
    return bufs_q, p1, p2, costs, sizes


def _state(n, p1, t=3):
    return rd.RoundState(buf_p1=p1, buf_p2=jnp.zeros_like(p1),
                         prev_costs=jnp.full((n,), jnp.inf),
                         round=jnp.asarray(t, jnp.int32))


# ---------------------------------------------------------------------------
# TreeSpec shape algebra
# ---------------------------------------------------------------------------

def test_treespec_levels_and_widths():
    ts = TreeSpec(fanout=2)
    assert ts.level_widths(8) == [8, 4, 2]
    assert ts.n_levels(8) == 2
    assert ts.level_widths(5) == [5, 3, 2]          # ragged groups
    assert TreeSpec(fanout=4).level_widths(16) == [16, 4]
    assert TreeSpec(fanout=4).level_widths(7) == [7, 2]
    assert TreeSpec(fanout=8).n_levels(64) == 1     # 8 partials → root
    assert TreeSpec(fanout=8).n_levels(65) == 2
    # pinned depth overrides auto-derivation
    assert TreeSpec(fanout=2, levels=3).level_widths(8) == [8, 4, 2, 1]
    assert TreeSpec(fanout=2).launches(16) == 3 + 2   # L=3
    assert TreeSpec(fanout=4).launches(16) == 1 + 2   # L=1
    # last level's sibling group spans all remaining nodes
    assert TreeSpec(fanout=4).sibling_size(1, 7) == 2
    assert TreeSpec(fanout=2).sibling_size(1, 8) == 2
    assert TreeSpec(fanout=2).sibling_size(2, 8) == 2


def test_treespec_validation():
    with pytest.raises(ValueError):
        TreeSpec(fanout=1)
    with pytest.raises(ValueError):
        TreeSpec(fanout=2, levels=0)


# ---------------------------------------------------------------------------
# Plain tree: bitwise == the flat integer comparator, every fanout
# ---------------------------------------------------------------------------

def _flat_integer_round(bufs_q, k_star, w, p1, p2, t):
    """The flat comparator on the SAME integer wire the plain tree rides:
    unmasked uint32 words, fb=24 fixed-point weights, one modular master."""
    n = bufs_q.shape[0]
    wq = pvm.quantize_weights(w, rd.TREE_PLAIN_FIXPOINT_BITS)
    y = ops.flat_ternary_pack_masked(
        bufs_q, p1, p2, t=t, beta=0.2, alpha1=0.01, wq=wq,
        pair_keys=jnp.zeros((n, n), jnp.uint32),
        pair_signs=jnp.zeros((n, n), jnp.int32),
        rr_keys=jnp.zeros((n,), jnp.uint32),
        word_bits=rd.TREE_PLAIN_WORD_BITS, use_masks=False)
    return ops.flat_masked_master_update(
        jnp.take(bufs_q, k_star, axis=0), y, jnp.sum(wq), p1, p2, t=t,
        alpha0=0.01, scale_mult=2.0 ** -rd.TREE_PLAIN_FIXPOINT_BITS)


@pytest.mark.parametrize("fanout", [2, 4, 8])
@pytest.mark.parametrize("n", [5, 8, 9])
def test_plain_tree_bitwise_equals_flat(fanout, n):
    bufs_q, p1, p2, costs, sizes = _mk(n)
    wire = rd.WirePath(tree=TreeSpec(fanout=fanout))
    t = jnp.asarray(3, jnp.int32)
    k_star = jnp.asarray(1, jnp.int32)
    w = wire.weights(sizes / sizes.sum(), k_star, t)
    out_tree, _ = wire.round_from_stacked(bufs_q, k_star, w, p1, p2, t=t)
    out_flat = _flat_integer_round(bufs_q, k_star, w, p1, p2, t)
    assert np.array_equal(np.asarray(out_tree), np.asarray(out_flat))


def test_plain_tree_round1_branch():
    bufs_q, p1, p2, costs, sizes = _mk(6)
    wire = rd.WirePath(tree=TreeSpec(fanout=2))
    t = jnp.asarray(1, jnp.int32)
    k_star = jnp.asarray(0, jnp.int32)
    w = wire.weights(sizes / sizes.sum(), k_star, t)
    out_tree, _ = wire.round_from_stacked(bufs_q, k_star, w, p1, p2, t=t)
    out_flat = _flat_integer_round(bufs_q, k_star, w, p1, p2, t)
    assert np.array_equal(np.asarray(out_tree), np.asarray(out_flat))


# ---------------------------------------------------------------------------
# Masked tree: bitwise == the flat masked round, both moduli
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("modulus_bits", [16, 32])
@pytest.mark.parametrize("fanout,n", [(2, 8), (4, 8), (2, 7)])
def test_masked_tree_bitwise_equals_flat(modulus_bits, fanout, n):
    bufs_q, p1, p2, costs, sizes = _mk(n)
    spec = PrivacySpec(secure_agg=True, modulus_bits=modulus_bits)
    flat = rd.WirePath(privacy=spec)
    tree = rd.WirePath(privacy=spec, tree=TreeSpec(fanout=fanout))
    _, out_f, _ = flat.round_step(_state(n, p1), bufs_q, costs, sizes)
    _, out_t, _ = tree.round_step(_state(n, p1), bufs_q, costs, sizes)
    assert np.array_equal(np.asarray(out_f), np.asarray(out_t))


@pytest.mark.parametrize("modulus_bits", [16, 32])
def test_masked_tree_parity_under_participation(modulus_bits):
    n = 8
    bufs_q, p1, p2, costs, sizes = _mk(n)
    spec = PrivacySpec(secure_agg=True, modulus_bits=modulus_bits)
    mask = jnp.array([1, 0, 1, 1, 1, 0, 1, 1], jnp.float32)
    for renorm in (False, True):
        flat = rd.WirePath(privacy=spec, renorm_shares=renorm)
        tree = rd.WirePath(privacy=spec, renorm_shares=renorm,
                           tree=TreeSpec(fanout=2))
        _, out_f, _ = flat.round_step(_state(n, p1), bufs_q, costs, sizes,
                                      mask=mask)
        _, out_t, _ = tree.round_step(_state(n, p1), bufs_q, costs, sizes,
                                      mask=mask)
        assert np.array_equal(np.asarray(out_f), np.asarray(out_t)), renorm


def test_masked_tree_parity_under_scan():
    n = 8
    bufs_q, p1, p2, costs, sizes = _mk(n)
    spec = PrivacySpec(secure_agg=True, modulus_bits=16)
    # Per-round inputs vary by integer gather only: float math on the
    # carry inside the body would let XLA's FMA contraction fuse the two
    # programs differently and shift the INPUTS by 1 ulp — the wire
    # itself is bitwise invariant.
    per_round = jnp.stack([bufs_q, bufs_q * 1.5, bufs_q - 0.25])

    def worker_fn(wc, gbuf, t):
        return wc, jnp.take(per_round, (t - 1) % 3, axis=0), costs

    outs = {}
    for name, wire in (("flat", rd.WirePath(privacy=spec)),
                       ("tree", rd.WirePath(privacy=spec,
                                            tree=TreeSpec(fanout=2)))):
        st, _, _ = rd.scan_rounds(
            wire, _state(n, p1, t=1), worker_fn, None, 3, sizes,
            participation=0.75, participation_key=jax.random.PRNGKey(9))
        outs[name] = np.asarray(st.buf_p1)
    assert np.array_equal(outs["flat"], outs["tree"])


def test_dropped_subtree_partial_is_exactly_zero():
    """Satellite 1 regression: when every leaf under a subtree is dropped,
    that subtree's partial is exactly 0 — no mask residue (its nodes pair
    with no active sibling), no field residue (zero weights)."""
    n = 8
    bufs_q, p1, p2, costs, sizes = _mk(n)
    mask = jnp.array([1, 1, 1, 1, 0, 0, 0, 0], jnp.float32)
    for modulus_bits in (16, 32):
        spec = PrivacySpec(secure_agg=True, modulus_bits=modulus_bits)
        tree = rd.WirePath(privacy=spec, tree=TreeSpec(fanout=2))
        t = jnp.asarray(3, jnp.int32)
        w = tree.weights(sizes / sizes.sum(), 0, t, mask=mask)
        y, _ = tree.uplink_masked(bufs_q, p1, p2, t=t, w=w, pmask=mask)
        top = tree._tree_fold_masked(y, t=t, pmask=mask)
        # last level has 2 nodes; node 1 spans dropped leaves 4..7
        assert top.shape[0] == 2
        assert not np.asarray(top[1]).any()
        assert np.asarray(top[0]).any()


def test_tree_activity_folds_up():
    mask = jnp.array([1, 0, 0, 0, 0, 0, 1, 1], jnp.float32)
    a1 = pvm.tree_activity(mask, 2)
    assert np.array_equal(np.asarray(a1), [1, 0, 0, 1])
    a2 = pvm.tree_activity(a1, 2)
    assert np.array_equal(np.asarray(a2), [1, 1])
    # ragged fold pads with inactive leaves
    assert np.array_equal(
        np.asarray(pvm.tree_activity(jnp.array([1.0, 0.0, 1.0]), 2)),
        [1, 1])


# ---------------------------------------------------------------------------
# Structure: launches grow with depth, not N; zero host syncs
# ---------------------------------------------------------------------------

def _round_counts(n, tree, privacy=None):
    bufs_q, p1, p2, costs, sizes = _mk(n)
    wire = rd.WirePath(privacy=privacy, tree=tree)
    return jaxpr_primitive_counts(
        lambda s, b, c, z: wire.round_step(s, b, c, z),
        _state(n, p1), bufs_q, costs, sizes)


@pytest.mark.parametrize("privacy", [None,
                                     PrivacySpec(secure_agg=True)])
def test_launches_scale_with_depth_not_n(privacy):
    ts = TreeSpec(fanout=8)
    # N=8 and N=64 share depth L=1 → identical launch count (levels + 2)
    c8 = _round_counts(8, ts, privacy)
    c64 = _round_counts(64, ts, privacy)
    assert c8.get("pallas_call") == ts.launches(8) == 3
    assert c64.get("pallas_call") == ts.launches(64) == 3
    # deeper tree at the same N adds exactly one launch per level
    c_deep = _round_counts(64, TreeSpec(fanout=2), privacy)
    assert c_deep.get("pallas_call") == TreeSpec(fanout=2).launches(64) == 7
    for c in (c8, c64, c_deep):
        assert not HOST_SYNC_PRIMITIVES & set(c), c


def test_flat_round_is_two_launches_still():
    c = _round_counts(8, None)
    assert c.get("pallas_call") == 2


# ---------------------------------------------------------------------------
# §4.2 audits on the tree path
# ---------------------------------------------------------------------------

def test_audit_passes_on_masked_tree_round():
    # n != rows//4 — the float-stacked rule keys on shape[0] == n_workers,
    # so an (8, 512) history slab at n=8 would collide coincidentally
    n = 6
    bufs_q, p1, p2, costs, sizes = _mk(n)
    spec = PrivacySpec(secure_agg=True)
    wire = rd.WirePath(privacy=spec, tree=TreeSpec(fanout=2))
    report = pv_audit.check_round_program(
        wire.round_step, _state(n, p1), bufs_q, costs, sizes,
        n_workers=n, masked=True)
    assert report["n_launches"] == TreeSpec(fanout=2).launches(n)


def test_demasked_partial_below_root_raises():
    """A signed-int (= de-masked, de-biased) buffer crossing a fed
    collective is the LeakageError the extended audit exists to catch."""
    from jax.sharding import PartitionSpec as P
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1,), ("f",), devices=jax.devices("cpu")[:1])

    def leaky(x):
        body = lambda v: jax.lax.psum(
            jax.lax.bitcast_convert_type(v, jnp.int32), "f")
        return jax.shard_map(body, mesh=mesh, in_specs=P(), out_specs=P(),
                             axis_names=frozenset({"f"}),
                             check_vma=False)(x)

    words = jnp.zeros((8, 512), jnp.uint32)
    with pytest.raises(LeakageError, match="below the root"):
        pv_audit.check_fed_collectives(leaky, words, n_fed=4, masked=True)
    # unmasked runtimes still move signed payloads legitimately
    pv_audit.check_fed_collectives(leaky, words, n_fed=4, masked=False)


# ---------------------------------------------------------------------------
# Byte model: per-level fanout× reduction, root link O(fanout)
# ---------------------------------------------------------------------------

def test_tree_bytes_model():
    V, n = 1000.0, 64
    flat = proto.fedpc_masked_bytes_per_round(V, n, word_bits=16)
    tree = proto.fedpc_tree_bytes_per_round(V, n, 8, word_bits=16)
    # the tree adds interior-edge bytes on top of the same leaf uplinks…
    widths = TreeSpec(fanout=8).level_widths(n)
    expect = V * (n + 1) + V * (n - 1) * 16 / 32
    for w_l in widths[1:]:
        expect += V * w_l * 16 / 32
    assert tree == pytest.approx(expect)
    # …but the link INTO the root carries w_L ≤ fanout partials, not N-1
    assert widths[-1] <= 8
    # per-level payload shrinks fanout× exactly while groups stay full
    assert widths[1] == n // 8
    # plaintext tree: 2-bit leaves, word-wide (uint32) interior partials
    plain = proto.fedpc_tree_bytes_per_round(V, n, 8)
    expect_p = V * (n + 1) + V * (n - 1) * 2 / 32
    for w_l in widths[1:]:
        expect_p += V * w_l * 32 / 32
    assert plain == pytest.approx(expect_p)
    assert proto.fedpc_bytes_per_round(V, n) < plain < flat


# ---------------------------------------------------------------------------
# Tuner: partial_sum kinds resolve, fallback chain is reported once
# ---------------------------------------------------------------------------

def test_partial_sum_fallback_logged_once(capsys):
    tune._FALLBACK_LOGGED.discard(
        ("partial_sum_masked16", 4096, 2, "cpu-interpret"))
    tune.lookup("partial_sum_masked16", 4096, 2, interpret=True)
    out1 = capsys.readouterr().out
    assert "fell back" in out1
    assert "partial_sum_masked16 -> partial_sum_masked -> partial_sum" in out1
    tune.lookup("partial_sum_masked16", 4096, 2, interpret=True)
    assert "fell back" not in capsys.readouterr().out


def test_partial_sum_plans_never_change_bits():
    n, fanout = 8, 2
    bufs_q, p1, p2, _, sizes = _mk(n)
    packed = ops.flat_ternary_pack_stacked(bufs_q, p1, p2, t=3, beta=0.2,
                                           alpha1=0.01)
    wq = pvm.quantize_weights(sizes / sizes.sum(), 24)
    ref = ops.flat_partial_sum(packed, wq, fanout=fanout)
    for br, bg in ((ROWS // 4, 4), (2, 1), (4, 2)):
        out = ops.flat_partial_sum(packed, wq, fanout=fanout,
                                   block_rows=br, block_groups=bg)
        assert np.array_equal(np.asarray(ref), np.asarray(out)), (br, bg)


# ---------------------------------------------------------------------------
# Mesh: tree butterfly reduce == flat psum, (4,2) and (2,4)
# ---------------------------------------------------------------------------

MESH_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import numpy as np
import jax, jax.numpy as jnp
from repro.launch.mesh import make_mesh
from repro.core.tree import TreeSpec
from repro.fed.distributed import build_fed_sync, fed_state_init
from repro.privacy import PrivacySpec

k = jax.random.PRNGKey(0)
params = {"w": jax.random.normal(k, (300, 40)),
          "b": jax.random.normal(jax.random.fold_in(k, 5), (40,))}
out = {}

def tree_max_diff(a, b):
    return max(float(jnp.max(jnp.abs(x.astype(jnp.float32)
                                     - y.astype(jnp.float32))))
               for x, y in zip(jax.tree_util.tree_leaves(a),
                               jax.tree_util.tree_leaves(b)))

for fed, model in ((4, 2), (2, 4)):
    devs = np.array(jax.devices()[: fed * model]).reshape(fed, model)
    mesh = make_mesh(devs.shape, ("data", "model"), devices=list(devs.flat))
    F = fed
    sizes = jnp.linspace(50.0, 200.0, F)
    costs = jnp.linspace(0.9, 0.5, F)
    params_F = jax.tree_util.tree_map(
        lambda x: jnp.stack([x + 0.05 * (i + 1) for i in range(F)]),
        params)
    mask = (jnp.arange(F) != 1).astype(jnp.float32)
    state = fed_state_init(params, F)
    state["round"] = jnp.asarray(3, jnp.int32)
    state["params_prev"] = jax.tree_util.tree_map(lambda x: x + 0.01,
                                                  params)
    state["prev_costs"] = jnp.ones((F,))
    wb = 16 if fed == 4 else 32
    spec = PrivacySpec(modulus_bits=wb)
    with mesh:
        s_tree = build_fed_sync(None, mesh, "data", "fedpc",
                                shard_wire=True, privacy=spec,
                                tree=TreeSpec(fanout=2))
        s_flat = build_fed_sync(None, mesh, "data", "fedpc",
                                shard_wire=True, privacy=spec)
        for tag, m in (("full", None), ("part", mask)):
            a, _ = jax.jit(s_tree)(params_F, costs, sizes, state, m)
            b, _ = jax.jit(s_flat)(params_F, costs, sizes, state, m)
            out[f"{fed}x{model}_wb{wb}_{tag}"] = tree_max_diff(a, b)

# validation: the mesh tree needs the masked wire and power-of-two shapes
devs = np.array(jax.devices()[:4]).reshape(4, 1)
mesh = make_mesh(devs.shape, ("data", "model"), devices=list(devs.flat))
for kwargs, tag in ((dict(), "plain"),
                    (dict(privacy=PrivacySpec(),
                          tree_fanout=3), "fanout3")):
    try:
        fo = kwargs.pop("tree_fanout", 2)
        build_fed_sync(None, mesh, "data", "fedpc",
                       tree=TreeSpec(fanout=fo), **kwargs)
        out["reject_" + tag] = False
    except ValueError:
        out["reject_" + tag] = True

print("RESULT " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def mesh_results():
    env = dict(os.environ, PYTHONPATH=SRC)
    proc = subprocess.run([sys.executable, "-c", MESH_SCRIPT], env=env,
                          capture_output=True, text=True, timeout=560)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines()
            if l.startswith("RESULT ")][-1]
    return json.loads(line[len("RESULT "):])


def test_mesh_tree_reduce_bitwise_equals_flat(mesh_results):
    keys = [k for k in mesh_results if "_wb" in k]
    assert len(keys) == 4
    for k in keys:
        assert mesh_results[k] == 0.0, f"{k}: {mesh_results[k]}"


def test_mesh_tree_requires_masked_power_of_two(mesh_results):
    assert mesh_results["reject_plain"] is True
    assert mesh_results["reject_fanout3"] is True
