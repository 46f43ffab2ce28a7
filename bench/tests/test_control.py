"""The control — the plain reference computed in bfloat16, put in the
program's place — must come out not correct under the committed limits,
where the program itself comes out correct."""
import jax.numpy as jnp
import pytest

from bench.harness import spec
from bench.harness.check import compare, verdict
from bench.tests import tiny


@pytest.mark.parametrize("pair", [tiny.tiny_xlstm, tiny.tiny_phi4,
                                  tiny.tiny_mesh],
                         ids=["xlstm", "phi4", "mesh"])
def test_control_fails_where_the_program_passes(pair):
    wl, cfg = tiny.with_changes(pair(), rounds_per_call=1, check_rounds=1,
                                setup_calls=2)
    cell = spec.driver(wl["driver"])(wl, cfg, seed=2 ** 33 + 29)
    cell.setup()
    cell.release()
    ref = cell.reference(wl["limits"])
    ok, _ = verdict(compare(cell.prog, ref), wl["limits"])
    assert ok
    own = cell.reference(wl["limits"], pilots=None)
    ctl = cell.reference(wl["limits"], pilots=None, dtype=jnp.bfloat16,
                         precision="default")
    ref_like = dict(own, pilot_ok=[a == b for a, b in zip(ctl["pilots"],
                                                          own["pilots"])])
    ok, rows = verdict(compare(ctl, ref_like), wl["limits"])
    assert not ok, rows
