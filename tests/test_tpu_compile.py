"""Ahead-of-time compiles of the main-path wire kernels for a TPU v5e.

The Pallas interpreter accepts kernels the chip's compiler refuses (a block
that breaks the (8, 128) tiling, a scalar loaded from a memory space the
vector unit cannot read, 16-bit vector arithmetic the v5e lacks). So every
kernel of the main path is compiled here for a *described* ``v5e:2x2``
topology — no chip attached — at the real Phase-A widths of
``chip_smoke.py``: xLSTM-350M cut to one layer period, N = 4 workers, on the
tiling the tuner picks for the chip. Each compile must produce a Mosaic
``tpu_custom_call``; the two round programs must lower EVERY launch to one.

The topology is described inside a module fixture (never at import), so
only the test worker that runs this file loads the TPU compiler.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core import flat as fl
from repro.core.tree import TreeSpec
from repro.fed import rounds as rd
from repro.fed.faults import FaultPlan
from repro.kernels import fused_wire as fw
from repro.kernels import masked_wire as mw
from repro.kernels import partial_sum as ps
from repro.kernels import tune
from repro.models import build_model
from repro.privacy.spec import PrivacySpec
from repro.utils import iter_jaxpr_eqns

N = 4                   # Phase-A cohort
FANOUT = 2              # Phase-B tree
WIDE = 512


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A described-topology compile is written to the persistent cache but
    can never be read back without a chip — keep these compiles off it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def phase_a():
    """(params shapes, layout) of xLSTM-350M at one mLSTM+sLSTM period —
    the published widths, cut in depth only."""
    cfg = get_config("xlstm-350m").replace(n_layers=2)
    shapes = jax.eval_shape(build_model(cfg).init, jax.random.PRNGKey(0))
    return shapes, fl.layout_of(shapes)


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _mosaic_calls(fn, *specs) -> tuple[int, str]:
    text = jax.jit(fn).lower(*specs).compile().as_text()
    return text.count("tpu_custom_call"), text


def _plan(kind, r4, n):
    br, bw = tune.default_plan(kind, r4, n, backend="tpu").values()
    return dict(block_rows=br, block_workers=tune.fit_block_workers(n, bw))


def test_phase_a_rows_tile_the_chip(phase_a):
    _, layout = phase_a
    assert layout.n > 130_000_000                 # published widths
    assert (layout.rows // fl.PACK) % tune.BLOCK_ROWS == 0


def test_stacked_uplink_compiles(one_chip, phase_a):
    r4 = phase_a[1].rows // fl.PACK
    s = functools.partial(_spec, one_chip)
    fn = functools.partial(fw.ternary_pack_stacked_2d, interpret=False,
                           **_plan("uplink_stacked", r4, N))
    calls, _ = _mosaic_calls(
        fn, s((N, r4, WIDE), jnp.float32), s((r4, WIDE), jnp.float32),
        s((r4, WIDE), jnp.float32), s((), jnp.int32), s((N,), jnp.float32),
        s((), jnp.float32))
    assert calls >= 1


def test_packed_master_compiles(one_chip, phase_a):
    r4 = phase_a[1].rows // fl.PACK
    s = functools.partial(_spec, one_chip)
    fn = functools.partial(fw.packed_master_update_2d, interpret=False,
                           **_plan("master", r4, N))
    calls, _ = _mosaic_calls(
        fn, s((r4, WIDE), jnp.float32), s((N, r4, 128), jnp.uint8),
        s((N,), jnp.float32), s((r4, WIDE), jnp.float32),
        s((r4, WIDE), jnp.float32), s((), jnp.int32), s((), jnp.float32))
    assert calls >= 1


@pytest.mark.parametrize("word_bits,rr_threshold",
                         [(16, 0), (32, 0), (16, 4096)])
def test_masked_uplink_compiles(one_chip, phase_a, word_bits, rr_threshold):
    r4 = phase_a[1].rows // fl.PACK
    s = functools.partial(_spec, one_chip)
    fn = functools.partial(mw.ternary_pack_masked_2d, interpret=False,
                           word_bits=word_bits, rr_threshold=rr_threshold,
                           **_plan("uplink_masked16", r4, N))
    calls, _ = _mosaic_calls(
        fn, s((N, r4, WIDE), jnp.float32), s((r4, WIDE), jnp.float32),
        s((r4, WIDE), jnp.float32), s((), jnp.int32), s((), jnp.float32),
        s((), jnp.float32), s((N,), jnp.uint32), s((N, N), jnp.uint32),
        s((N, N), jnp.int32), s((N,), jnp.uint32))
    assert calls >= 1


@pytest.mark.parametrize("word", [jnp.uint16, jnp.uint32])
def test_masked_master_compiles(one_chip, phase_a, word):
    r4 = phase_a[1].rows // fl.PACK
    s = functools.partial(_spec, one_chip)
    fn = functools.partial(mw.masked_master_update_2d, interpret=False,
                           **_plan("master_masked16", r4, N))
    calls, _ = _mosaic_calls(
        fn, s((r4, WIDE), jnp.float32), s((N, r4, WIDE), word),
        s((), jnp.uint32), s((r4, WIDE), jnp.float32),
        s((r4, WIDE), jnp.float32), s((), jnp.int32), s((), jnp.float32),
        s((), jnp.float32))
    assert calls >= 1


def test_partial_sum_compiles(one_chip, phase_a):
    r4 = phase_a[1].rows // fl.PACK
    s = functools.partial(_spec, one_chip)
    br, bg = tune.default_plan("partial_sum", r4, FANOUT,
                               backend="tpu").values()
    fn = functools.partial(ps.partial_sum_2d, fanout=FANOUT,
                           word_bits=rd.TREE_PLAIN_WORD_BITS,
                           interpret=False, block_rows=br, block_groups=bg)
    calls, _ = _mosaic_calls(fn, s((N, r4, 128), jnp.uint8),
                             s((N,), jnp.uint32))
    assert calls >= 1


@pytest.mark.parametrize("word", [jnp.uint16, jnp.uint32])
def test_masked_partial_sum_compiles(one_chip, phase_a, word):
    r4 = phase_a[1].rows // fl.PACK
    s = functools.partial(_spec, one_chip)
    g = N // FANOUT
    br, bg = tune.default_plan("partial_sum_masked16", r4, FANOUT,
                               backend="tpu").values()
    fn = functools.partial(ps.masked_partial_sum_2d, fanout=FANOUT,
                           sibling=g, interpret=False, block_rows=br,
                           block_groups=bg)
    calls, _ = _mosaic_calls(fn, s((N, r4, WIDE), word),
                             s((g, g), jnp.uint32), s((g, g), jnp.int32))
    assert calls >= 1


@pytest.mark.parametrize("word", [jnp.uint16, jnp.uint32])
def test_mask_repair_compiles(one_chip, phase_a, word):
    r4 = phase_a[1].rows // fl.PACK
    s = functools.partial(_spec, one_chip)
    n_pairs = N * (N - 1) // 2
    fn = functools.partial(mw.mask_repair_2d, interpret=False,
                           block_rows=_plan("mask_repair16", r4, 1)
                           ["block_rows"])
    calls, _ = _mosaic_calls(fn, s((r4, WIDE), word),
                             s((n_pairs,), jnp.uint32),
                             s((n_pairs,), jnp.int32))
    assert calls >= 1


def _round_specs(sharding, params_shapes, privacy=None):
    layout = fl.layout_of(params_shapes)
    state = jax.eval_shape(
        lambda p: rd.init_round_state(p, N, layout, privacy=privacy),
        params_shapes)
    with_sh = lambda x: _spec(sharding, x.shape, x.dtype)
    return (jax.tree_util.tree_map(with_sh, state),
            _spec(sharding, (N, layout.rows, fl.LANES), jnp.float32),
            _spec(sharding, (N,), jnp.float32),
            _spec(sharding, (N,), jnp.float32))


def _pallas_launches(fn, *specs) -> int:
    jaxpr = jax.make_jaxpr(fn)(*specs)
    return sum(e.primitive.name == "pallas_call"
               for e in iter_jaxpr_eqns(jaxpr.jaxpr, into_pallas=False))


@pytest.mark.parametrize("scenario", ["plain", "masked_tree_faulty"])
def test_round_program_is_all_mosaic(one_chip, phase_a, scenario):
    """A whole compiled round at Phase-A/B widths: every Pallas launch of
    the round program is a Mosaic kernel (2 per plain round; levels + 2,
    plus the dropout repair, per faulty tree round)."""
    if scenario == "plain":
        wire, privacy, want = rd.WirePath(interpret=False), None, 2
    else:
        privacy = PrivacySpec(recovery_threshold=2)
        tree = TreeSpec(fanout=FANOUT)
        wire = rd.WirePath(interpret=False, privacy=privacy, tree=tree,
                           faults=FaultPlan(seed=0, drop_after_uplink=0.25))
        want = tree.launches(N) + 1
    specs = _round_specs(one_chip, phase_a[0], privacy)
    step = lambda st, b, c, sz: wire.round_step(st, b, c, sz)[1]
    launches = _pallas_launches(step, *specs)
    calls, _ = _mosaic_calls(step, *specs)
    assert launches == want
    assert calls == launches
