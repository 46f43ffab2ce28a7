"""The program's labels read from a hand-built trace: ``fed/`` host spans
inside the benchmark's calls and scoped operations in the HLO text, as
the per-layer readers get them in ``ctx``; the benchmark's other readings
left as they were."""
import pytest

from bench.harness import spec, trace
from bench.metrics import device_idle_share
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
    return trace.reduce_profile(_profile(), [HLO])


def _ctx(red, rounds=ROUNDS):
    """The part of ``bench/run.py``'s ``ctx`` that these readers use."""
    return {"reduction": red, "rounds": rounds,
            "scoped_ops": red.scoped,
            "device_s_by_scope": trace.device_s_by_scope(red),
            "idle_by_span": trace.idle_by_span(red)}


def test_op_names_cover_every_instruction_with_metadata():
    modules, names = trace.op_names([HLO])
    assert modules == {"jit__unknown"}
    assert set(names) == {"tpu.3", "tpu.4", "tpu.5", "fusion.12",
                          "fusion.13", "all-gather.1"}
    assert names["fusion.13"].endswith("/fed/train/optimizer/mul")


def test_host_spans_are_the_programs_and_the_benchmarks():
    red = _read()
    want = sorted(FED_SPANS + [("bench/call", 0, 100),
                               ("bench/call", 150, 200)])
    got = sorted(red.host_spans)
    assert [n for n, _, _ in got] == [n for n, _, _ in want]
    assert [(s, e) for _, s, e in got] == [
        (pytest.approx(s * US), pytest.approx(e * US)) for _, s, e in want]
    # a span of neither prefix is no label
    assert "unrelated" not in {n for n, _, _ in red.host_spans}


def test_the_four_readings():
    ctx = _ctx(_read())
    got = {name: spec.metric_reader(name)(ctx) for name in (
        "host_prep_ms_per_round", "host_finish_ms_per_round",
        "optimizer_ms_per_round", "flatten_ms_per_round")}
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
    by = ctx["device_s_by_scope"]
    total = sum(op.dur for _, op in ctx["scoped_ops"])
    unscoped = by.get("(no scope)", 0.0) + by.get("(other programs)", 0.0)
    assert 100 * unscoped / total == pytest.approx(100 * 5 / 95)
    assert 100 * by["(other programs)"] / total == pytest.approx(100 * 5 / 95)


def test_readers_find_nothing_where_the_program_wrote_nothing():
    """No ``fed/`` spans and no scopes (as in a cell whose driver is not
    the simulator): the four readers return nothing, never 0."""
    red = trace.reduce_profile(base._profile(), [base.HLO])
    ctx = _ctx(red)
    for name in ("host_prep_ms_per_round", "host_finish_ms_per_round",
                 "optimizer_ms_per_round", "flatten_ms_per_round"):
        assert spec.metric_reader(name)(ctx) is None, name


def test_idle_time_goes_to_the_innermost_span():
    red = _read()
    got = trace.idle_by_span(red)
    want = {"fed/scan/audit": 10, "fed/scan/ledger": 15,
            "outside bench spans": 95}
    assert got == {k: pytest.approx(v * US) for k, v in want.items()}
    assert sum(got.values()) == pytest.approx(red.window_s
                                              - red.mean_busy_s)


def test_longest_gaps_are_labelled_by_fed_spans():
    got = dict(trace.breakdown(_read())["idle_gaps"])
    assert got == {
        "outside bench spans (longest of 1)": pytest.approx(95 * US),
        "fed/scan/audit (longest of 1)": pytest.approx(10 * US),
        "fed/scan/ledger (longest of 2)": pytest.approx(10 * US)}


def test_the_benchmarks_readings_do_not_move():
    """The program's spans and module names change nothing the benchmark
    already reads from a trace; the idle gaps keep their lengths and take
    the program's span names as labels."""
    old = trace.reduce_profile(base._profile(), [base.HLO])
    new = trace.reduce_profile(_profile(), [base.HLO])
    assert (new.window_s, new.busy_s) == (old.window_s, old.busy_s)
    assert device_idle_share.read({"reduction": new}) == (
        device_idle_share.read({"reduction": old}))
    assert trace.wire_ops(new) == trace.wire_ops(old)
    assert trace.breakdown(new)["device_ops"] == (
        trace.breakdown(old)["device_ops"])
    assert sorted(s for _, s in new.gaps) == sorted(s for _, s in old.gaps)


def test_scope_of_names_the_layer():
    assert trace.scope_of(None) == "(other programs)"
    assert trace.scope_of("jit(f)/fed/unflatten/slice") == "fed/unflatten"
    assert trace.scope_of("jit(f)/wire/master/r64n4/tpu/jit(k)/x") == (
        "wire/master")
    assert trace.scope_of("jit(f)/while/body/dot_general") == "(no scope)"
