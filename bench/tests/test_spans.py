"""The program's labels read from a hand-built trace: ``fed/`` host spans
inside the benchmark's calls, scoped operations in the HLO text, and the
benchmark's own readings left as they were."""
import dataclasses

import pytest

from bench.harness import spans, trace
from bench.metrics import device_idle_share
from bench.spans import scope_of
from bench.tests import test_trace as base

US = 1e-6
ROUNDS = 4          # two calls of two rounds
HLO = "\n".join([
    "HloModule jit__unknown, is_scheduled=true",
    base.HLO,
    '  %fusion.13 = f32[8]{0} fusion(f32[8]{0} %a), metadata={op_name="'
    'jit(<unknown>)/while/body/closed_call/fed/train/optimizer/mul"}',
    '  %all-gather.1 = u8[4,64]{1,0} all-gather(u8[1,64]{1,0} %p), '
    'metadata={op_name="jit(<unknown>)/while/body/fed/flatten/concatenate"}',
])
# (name, start, end) in microseconds, inside the calls at 0..100, 150..200
FED_SPANS = [
    ("fed/scan", 1, 99), ("fed/scan/prepare", 1, 9),
    ("fed/scan/state", 1, 3), ("fed/scan/audit", 3, 8),
    ("fed/scan/schedules", 8, 9), ("fed/scan/dispatch", 9, 10),
    ("fed/scan/finish", 10, 99), ("fed/scan/wait", 10, 42),
    ("fed/scan/ledger", 42, 60), ("fed/scan/trace", 60, 99),
    ("fed/scan", 151, 199), ("fed/scan/prepare", 151, 158),
    ("fed/scan/state", 151, 152), ("fed/scan/audit", 152, 157),
    ("fed/scan/schedules", 157, 158), ("fed/scan/dispatch", 158, 159),
    ("fed/scan/finish", 159, 199), ("fed/scan/wait", 159, 196),
    ("fed/scan/ledger", 196, 198), ("fed/scan/trace", 198, 199),
]


def _profile():
    pd = base._profile()
    host, dev = pd.planes
    host.lines[0].events += [base.Ev(n, s * 1e3, (e - s) * 1e3)
                             for n, s, e in FED_SPANS]
    # the round program until 189 us, then an eager call
    dev.lines[1] = base.Line("XLA Modules", [
        base.Ev("jit__unknown(7)", 0, 189e3),
        base.Ev("jit_stack(9)", 189e3, 11e3)])
    return pd


def _read():
    pd = _profile()
    modules, names = spans.op_names([HLO])
    red = trace.reduce_profile(pd, trace.kernel_scopes([HLO]))
    host = spans.host_spans(pd)
    ops = spans.scoped_ops(red, pd, modules, names)
    return red, spans.idle_gaps(red, host), ops


def test_op_names_cover_every_instruction_with_metadata():
    modules, names = spans.op_names([HLO])
    assert modules == {"jit__unknown"}
    assert set(names) == {"tpu.3", "tpu.4", "tpu.5", "fusion.12",
                          "fusion.13", "all-gather.1"}
    assert names["fusion.13"].endswith("/fed/train/optimizer/mul")


def test_host_spans_are_the_programs_alone():
    pd = _profile()
    red = trace.reduce_profile(pd, trace.kernel_scopes([HLO]))
    got, want = sorted(spans.host_spans(pd)), sorted(FED_SPANS)
    assert [n for n, _, _ in got] == [n for n, _, _ in want]
    assert [(s, e) for _, s, e in got] == [
        (pytest.approx(s * US), pytest.approx(e * US)) for _, s, e in want]
    assert all(not n.startswith("fed/") for n, _, _ in red.host_spans)


def test_the_four_readings():
    red, gaps, ops = _read()
    got = spans.readings(red, gaps, ops, ROUNDS)
    # the idle time the chip spent in the driver's steps, not the steps'
    # lengths: prepare's gap 0..10 us (midpoint in audit); finish outside
    # wait holds 40..50 and 195..200 (ledger), while its spans last
    # (89 - 32) + (40 - 37) us, much of it with the device busy
    assert got["host_prep_ms_per_round"] == pytest.approx(10e-3 / ROUNDS)
    assert got["host_finish_ms_per_round"] == pytest.approx(15e-3 / ROUNDS)
    assert got["optimizer_ms_per_round"] == pytest.approx(20e-3 / ROUNDS)
    assert got["flatten_ms_per_round"] == pytest.approx(10e-3 / ROUNDS)
    # 95 us of operations: 30 under fed/ scopes, 60 under wire/ scopes,
    # 5 in the eager call after the program
    assert got["unscoped_device_share"] == pytest.approx(100 * 5 / 95)
    assert got["other_programs_device_share"] == pytest.approx(100 * 5 / 95)


def test_idle_time_goes_to_the_innermost_span():
    red, gaps, _ = _read()
    got = spans.idle_by_span(red, gaps)
    want = {"fed/scan/audit": 10, "fed/scan/ledger": 15,
            "outside bench spans": 95}
    assert got == {k: pytest.approx(v * US) for k, v in want.items()}
    assert sum(got.values()) == pytest.approx(red.window_s
                                              - red.mean_busy_s)


def test_longest_gaps_are_labelled_by_fed_spans():
    red, gaps, _ = _read()
    got = dict(trace.breakdown(dataclasses.replace(red, gaps=gaps))[
        "idle_gaps"])
    assert got == {
        "outside bench spans (longest of 1)": pytest.approx(95 * US),
        "fed/scan/audit (longest of 1)": pytest.approx(10 * US),
        "fed/scan/ledger (longest of 2)": pytest.approx(10 * US)}


def test_the_benchmarks_readings_do_not_move():
    """The program's spans and module names change nothing the benchmark
    already reads from a trace."""
    scopes = trace.kernel_scopes([base.HLO])
    old = trace.reduce_profile(base._profile(), scopes)
    new = trace.reduce_profile(_profile(), scopes)
    assert (new.window_s, new.busy_s) == (old.window_s, old.busy_s)
    assert device_idle_share.read({"reduction": new}) == (
        device_idle_share.read({"reduction": old}))
    assert trace.wire_ops(new) == trace.wire_ops(old)
    assert trace.breakdown(new) == trace.breakdown(old)


def test_scope_of_names_the_layer():
    assert scope_of(None) == "(other programs)"
    assert scope_of("jit(f)/fed/unflatten/slice") == "fed/unflatten"
    assert scope_of("jit(f)/wire/master/r64n4/tpu/jit(k)/x") == "wire/master"
    assert scope_of("jit(f)/while/body/dot_general") == "(no scope)"
