"""The trace reduction on a hand-built trace: busy union, scopes, gaps."""
from dataclasses import dataclass, field

import pytest

from bench.harness import trace


@dataclass
class Ev:
    name: str
    start_ns: float
    duration_ns: float
    stats: list = field(default_factory=list)


@dataclass
class Line:
    name: str
    events: list


@dataclass
class Plane:
    name: str
    lines: list


UPLINK = ('%tpu.3 = u8[4,64,128]{2,1,0:T(8,128)} custom-call(f32[4,64,512]'
          '{2,1,0:T(8,128)} %b, f32[64,512]{1,0} %p1, f32[64,512]{1,0} %p2, '
          'f32[4]{0:T(128)S(1)} %beta), custom_call_target="tpu_custom_call"')
MASTER = ('%tpu.4 = f32[64,512]{1,0} custom-call(f32[64,512]{1,0} %q, '
          'u8[4,64,128]{2,1,0} %c), custom_call_target="tpu_custom_call", '
          'operand_layout_constraints={f32[64,512]{1,0}}')
# a Mosaic kernel of local training, under no wire scope
OTHER = ('%tpu.5 = f32[8,128]{1,0} custom-call(f32[8,128]{1,0} %h), '
         'custom_call_target="tpu_custom_call"')
# the compiled program's HLO text, as ``Compiled.as_text`` prints it
HLO = "\n".join([
    "ENTRY %main {",
    "  " + UPLINK + ', metadata={op_name="jit(f)/while/body/closed_call/'
    'wire/uplink_stacked/r64n4/tpu/jit(ternary_pack_stacked_2d)/'
    'pallas_call" stack_frame_id=3}',
    "  ROOT " + MASTER + ', metadata={op_name="jit(f)/while/body/wire/'
    'master/r64n4/tpu/jit(packed_master_update_2d)/pallas_call"}',
    "  " + OTHER + ', metadata={op_name="jit(f)/while/body/slstm/'
    'jit(recurrence)/pallas_call"}',
    "  %fusion.12 = f32[8]{0} fusion(f32[8]{0} %a), metadata={op_name="
    '"jit(f)/wire/uplink_stacked/r64n4/tpu/add"}',
    "}"])


def _profile():
    us = 1000.0
    host = Plane("/host:CPU", [Line("python", [
        Ev("bench/call", 0, 100 * us),
        Ev("bench/call", 150 * us, 50 * us),
        Ev("unrelated", 0, 500 * us)])])
    ops = [
        Ev("%while.7 = (s32[], f32[8]{0}) while((s32[], f32[8]{0}) %t), "
           "condition=%c, body=%b", 10 * us, 30 * us),
        Ev("%fusion.12 = f32[8]{0} fusion(f32[8]{0} %a)", 10 * us, 20 * us),
        Ev("%fusion.13 = f32[8]{0} fusion(f32[8]{0} %a)", 20 * us, 20 * us),
        Ev(UPLINK, 50 * us, 10 * us),
        Ev("%all-gather.1 = u8[4,64]{1,0} all-gather(u8[1,64]{1,0} %p)",
           55 * us, 10 * us),                       # overlaps the kernel
        Ev(MASTER, 160 * us, 30 * us),
        Ev(OTHER, 190 * us, 5 * us),
        Ev("%fusion.99 = f32[8]{0} fusion(f32[8]{0} %a)", 300 * us, 10 * us),
    ]
    dev = Plane("/device:TPU:0", [Line("XLA Ops", ops),
                                  Line("XLA Modules", [Ev("m", 0, 1e9)])])
    return type("PD", (), {"planes": [host, dev]})()


def _reduce():
    return trace.reduce_profile(_profile(), [HLO])


def test_kernel_scopes_from_hlo_text():
    got = trace.kernel_scopes([HLO])
    assert set(got) == {"tpu.3", "tpu.4", "tpu.5"}
    assert "/wire/master/r64n4/" in got["tpu.4"]


def test_busy_union_and_window():
    red = _reduce()
    assert red.window_s == pytest.approx(200e-6)
    # 10..40 (fusions), 50..65 (kernel + gather), 160..195 (master, other)
    assert red.busy_s == [pytest.approx(80e-6)]
    assert red.mean_busy_s == pytest.approx(80e-6)
    assert red.chips == 1


def test_wire_kernels_and_their_bytes():
    red = _reduce()
    got = trace.wire_ops(red)
    up = 4 * 64 * 128 + 4 * 64 * 512 * 4 + 2 * 64 * 512 * 4 + 4 * 4
    master = 64 * 512 * 4 * 2 + 4 * 64 * 128   # the layout constraint
    assert got == [(up, pytest.approx(10e-6)),    # after the target is
                   (master, pytest.approx(30e-6))]  # not counted; the
    # kernel outside a wire scope is not a wire kernel


def test_kernels_without_their_scopes_are_not_wire_kernels():
    assert trace.wire_ops(trace.reduce_profile(_profile())) == []


def test_breakdown_labels_leaf_ops():
    labels = dict(trace.breakdown(_reduce())["device_ops"])
    assert labels["wire/master packed_master_update_2d"] == pytest.approx(
        30e-6)
    assert labels["wire/uplink_stacked ternary_pack_stacked_2d"] == (
        pytest.approx(10e-6))
    assert labels["mosaic recurrence"] == pytest.approx(5e-6)
    assert labels["fusion"] == pytest.approx(40e-6)
    assert "while" not in labels         # a container, not an operation


def test_gap_attribution():
    red = _reduce()
    gaps = sorted(red.gaps, key=lambda g: -g[1])
    # 0..10, 40..50 and 195..200 lie in calls; 65..160 has its midpoint
    # between them, where only a span that is not the benchmark's runs
    assert gaps[0] == ("outside bench spans", pytest.approx(95e-6))
    assert [g for g in gaps if g[0] == "bench/call"] == [
        ("bench/call", pytest.approx(10e-6))] * 2 + [
        ("bench/call", pytest.approx(5e-6))]
    idle = trace.breakdown(red)["idle_gaps"]
    assert idle[0][0].startswith("outside bench spans")


def test_union_length():
    assert trace.union_length([(0, 1), (0.5, 2), (3, 4)]) == 3
    assert trace.union_length([]) == 0


def test_missing_spans_is_an_error():
    pd = _profile()
    pd.planes[0].lines[0].events = []
    with pytest.raises(ValueError):
        trace.reduce_profile(pd)


def test_collectives_by_their_opcode():
    """``sync_collective_ms_per_round`` sums the collectives, synchronous
    and asynchronous halves, whatever the instruction is called, and
    nothing else; mean over the chips, per round."""
    from bench.harness import spec
    us = 1000.0
    coll = [
        "%psum.7 = f32[256576,512]{1,0:T(8,128)} all-reduce(f32[256576,512]"
        "{1,0} %r), channel_id=1, replica_groups={{0,1,2,3}}",
        "%all-reduce.4 = (f32[4]{0:T(128)}, f32[]{:T(128)}) all-reduce("
        "f32[4]{0} %a, f32[] %b), channel_id=6",
        "%all-gather-start.1 = (u8[1,64]{1,0}, u8[4,64]{1,0}) "
        "all-gather-start(u8[1,64]{1,0} %p), dimensions={0}",
        "%all-gather-done.1 = u8[4,64]{1,0} all-gather-done((u8[1,64]{1,0}, "
        "u8[4,64]{1,0}) %all-gather-start.1)",
        "%collective-permute.2 = f32[8]{0} collective-permute(f32[8]{0} %x)",
    ]
    other = ["%fusion.3 = f32[8]{0} fusion(f32[8]{0} %all-reduce.4), "
             "kind=kLoop", UPLINK]
    host = Plane("/host:CPU", [Line("python", [Ev("bench/call", 0,
                                                   100 * us)])])
    devs = [Plane(f"/device:TPU:{d}", [Line("XLA Ops", [
        Ev(text, 10 * i * us, (d + 1) * us)
        for i, text in enumerate(coll + other)])]) for d in range(2)]
    red = trace.reduce_profile(type("PD", (), {"planes": [host] + devs})())
    read = spec.metric_reader("sync_collective_ms_per_round")
    # five collectives of 1 us on device 0 and of 2 us on device 1, over
    # 2 chips and 4 rounds
    assert read({"reduction": red, "rounds": 4}) == pytest.approx(
        1e3 * (5 * 1 + 5 * 2) * 1e-6 / 2 / 4)
    no_coll = trace.reduce_profile(type("PD", (), {"planes": [host, Plane(
        "/device:TPU:0", [Line("XLA Ops", [Ev(t, 0, us) for t in other])])
    ]})())
    assert read({"reduction": no_coll, "rounds": 4}) is None
