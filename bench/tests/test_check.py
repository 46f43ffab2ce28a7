"""The compared numbers on hand-made readings: the worst leaf, the median
leaf's floor, the leaves left out, and where each gap comes from."""
import pytest

from bench.harness.check import compare, explain, verdict


def _pair():
    grads = {"a": 1.0, "b": 2.0, "c": 3.0, "tiny": 1e-6}
    ref = {"costs": [4.0, 2.0], "pilot_ok": [True, True],
           "first_grad_norms": grads,
           "opt_norms": [{"velocity/a": 1.0, "velocity/b": 2.0,
                          "velocity/c": 3.0, "velocity/tiny": 1e-6}],
           "change_norms": {"a": 1.0, "b": 2.0, "c": 4.0, "tiny": 1e-6}}
    prog = {"costs": [4.0, 2.1],
            "opt_norms": [{"velocity/a": 1.5, "velocity/b": 2.0,
                           "velocity/c": 3.0, "velocity/tiny": 5.0}],
            "change_norms": {"a": 1.0, "b": 2.0, "c": 3.0, "tiny": 7.0}}
    return prog, ref


def test_compare():
    prog, ref = _pair()
    got = compare(prog, ref)
    assert got["loss_gap"] == pytest.approx(0.05)
    # leaf a: |1.5 - 1| over the median leaf's norm 2; "tiny" is left out
    assert got["opt_state_gap"] == pytest.approx(0.25)
    assert got["opt_state_median_gap"] == pytest.approx(0.0)   # a, b, c
    assert got["param_change_gap"] == pytest.approx(0.25)   # c: 1 / 4
    assert got["param_change_median_gap"] == pytest.approx(0.0)   # a, b, c
    assert got["pilot_mismatch"] == 0
    assert verdict(got, {"loss_gap": 0.051, "pilot_mismatch": 0})[0]
    assert not verdict(got, {"loss_gap": 0.04})[0]
    assert not verdict({}, {"loss_gap": 1.0})[0]


def test_explain_names_the_worst_leaf():
    prog, ref = _pair()
    got = explain(prog, ref)
    assert got["loss_gap_by_round"] == pytest.approx([0.0, 0.05])
    assert got["opt_state_gap.velocity"] == (pytest.approx(0.25), 0,
                                             "velocity/a", 1.5, 1.0)
    assert got["param_change_gap"] == (pytest.approx(0.25), "c", 3.0, 4.0)
    assert list(got["param_change_gaps"]) == ["c", "a", "b"]
    assert "opt_state_gap.mu" not in got
