"""A whole run with the timed path broken underneath must come out with
``correct`` false; the same run unbroken, true.

The harness's look for a chip is skipped (the CPU stands in) and the cells
are the tiny ones of ``tiny.py`` with the committed limits; everything
else is the run as the driver makes it.
"""
import json

import jax
import pytest

from bench import run
from bench.harness import spec
from bench.tests import tiny


def _drive(monkeypatch, capsys, pair, seed=2 ** 31 + 11, check_rounds=1):
    wl, cfg = tiny.with_changes(pair, rounds_per_call=1,
                                check_rounds=check_rounds,
                                setup_calls=max(2, check_rounds))
    monkeypatch.setattr(run, "require_devices",
                        lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(spec, "workload", lambda name, *a: wl)
    monkeypatch.setattr(spec, "config", lambda name, *a: cfg)
    cell_metrics = spec.cell_metrics      # the metrics of the benchmark's
    monkeypatch.setattr(spec, "cell_metrics",  # cell, for any tiny cell
                        lambda bench, _c: cell_metrics(
                            bench, bench["workloads"][0]["name"]))
    assert run.main(["--workload", wl["name"], "--seed", str(seed),
                     "--seconds", "0.5", "--trace", "0"]) == 0
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1])


def _state_unchanged(monkeypatch):
    from repro.fed import rounds as rd
    orig = rd.scan_rounds

    def frozen(wire, state, *a, **k):
        _new, wc, infos = orig(wire, state, *a, **k)
        return state, wc, infos
    monkeypatch.setattr(rd, "scan_rounds", frozen)


def _half_batch(monkeypatch):
    from repro.fed.worker import Worker
    orig = Worker.scan_train

    def half(self, params, opt_state, step, batches):
        s = batches[0].shape[-1]
        return orig(self, params, opt_state, step,
                    tuple(b[..., :s // 2] for b in batches))
    monkeypatch.setattr(Worker, "scan_train", half)


def _no_wire(monkeypatch):
    import jax.numpy as jnp
    from repro.fed import rounds as rd
    orig = rd.WirePath.round_from_stacked

    def pilot_only(self, bufs_q, k_star, *a, **k):
        _new, wire = orig(self, bufs_q, k_star, *a, **k)
        return jnp.take(bufs_q, k_star, axis=0), wire
    monkeypatch.setattr(rd.WirePath, "round_from_stacked", pilot_only)


def _token_altered(monkeypatch):
    from repro.data import pipeline
    orig = pipeline.BatchIterator.__post_init__

    def altered(self):
        toks = self.arrays[0].copy()
        toks[:, toks.shape[1] // 2] = (toks[:, toks.shape[1] // 2] + 1) % 7
        self.arrays = (toks,) + tuple(self.arrays[1:])
        orig(self)
    monkeypatch.setattr(pipeline.BatchIterator, "__post_init__", altered)


# The xLSTM cell is driven through its own three checked rounds: a wire
# left out shows in its compared numbers only from the second round on.
XLSTM_ROUNDS = 3


@pytest.mark.parametrize("pair,rounds", [(tiny.tiny_xlstm, XLSTM_ROUNDS),
                                         (tiny.tiny_phi4, 1)],
                         ids=["plain", "secagg"])
def test_sound_run_is_correct(monkeypatch, capsys, pair, rounds):
    res = _drive(monkeypatch, capsys, pair(), check_rounds=rounds)
    assert res["correct"] is True
    assert list(res)[-1] == "checks"
    assert set(res["checks"]) == set(pair()[0]["limits"])
    assert {"tokens_per_s", "setup_s"} <= set(res["metrics"])


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch, _no_wire,
                                   _token_altered],
                         ids=["state-unchanged", "half-batch", "no-wire",
                              "token-altered"])
def test_broken_path_is_caught(monkeypatch, capsys, fault):
    fault(monkeypatch)
    res = _drive(monkeypatch, capsys, tiny.tiny_xlstm(),
                 check_rounds=XLSTM_ROUNDS)
    assert res["correct"] is False, res["checks"]


def _wrap_fed_step(monkeypatch, wrap):
    """Break the mesh cell's step: ``wrap(step)`` in place of each
    ``build_fed_step`` result."""
    from repro.fed import distributed
    orig = distributed.build_fed_step

    def broken(*a, **k):
        return wrap(orig(*a, **k))
    monkeypatch.setattr(distributed, "build_fed_step", broken)


def _mesh_state_unchanged(monkeypatch):
    def wrap(step):
        def frozen(state, opt, batch, sizes, mask=None):
            _new, opt, metrics = step(state, opt, batch, sizes, mask)
            return state, opt, metrics
        return frozen
    _wrap_fed_step(monkeypatch, wrap)


def _mesh_half_batch(monkeypatch):
    def wrap(step):
        def half(state, opt, batch, sizes, mask=None):
            toks = batch["tokens"]
            return step(state, opt, {"tokens": toks[..., :toks.shape[-1]
                                                   // 2]}, sizes, mask)
        return half
    _wrap_fed_step(monkeypatch, wrap)


def _mesh_token_altered(monkeypatch):
    def wrap(step):
        def altered(state, opt, batch, sizes, mask=None):
            toks = batch["tokens"]
            mid = toks.shape[-1] // 2
            toks = toks.at[..., mid].set((toks[..., mid] + 1) % 7)
            return step(state, opt, {"tokens": toks}, sizes, mask)
        return altered
    _wrap_fed_step(monkeypatch, wrap)


def _mesh_no_exchange(monkeypatch):
    """Every chip keeps its own codes and weights: the all-gather of the
    codes and the all-reduce of the pilot's weights left out."""
    import jax.numpy as jnp
    chips = tiny.tiny_mesh()[0]["chips"]
    monkeypatch.setattr(jax.lax, "all_gather",
                        lambda x, axis_name, **k: jnp.broadcast_to(
                            x, (chips,) + x.shape))
    monkeypatch.setattr(jax.lax, "psum", lambda x, axis_name, **k: x)


@pytest.mark.parametrize("fault", [None, _mesh_state_unchanged,
                                   _mesh_half_batch, _mesh_no_exchange,
                                   _mesh_token_altered],
                         ids=["sound", "state-unchanged", "half-batch",
                              "no-exchange", "token-altered"])
def test_mesh_cell_on_four_devices(monkeypatch, capsys, fault):
    """The four-chip cell's driver on four host devices: sound, correct;
    with its step broken underneath, not correct."""
    if fault is not None:
        fault(monkeypatch)
    res = _drive(monkeypatch, capsys, tiny.tiny_mesh(),
                 check_rounds=XLSTM_ROUNDS)
    assert res["correct"] is (fault is None), res["checks"]


def test_broken_secure_aggregation_is_caught(monkeypatch, capsys):
    _no_wire(monkeypatch)
    res = _drive(monkeypatch, capsys, tiny.tiny_phi4())
    assert res["correct"] is False, res["checks"]
