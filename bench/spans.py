"""Where a call's host and device time go, by the program's own labels.

    python3 bench/spans.py --workload <cell> --seed <n> [--calls <k>]

Sets the cell up as ``bench/run.py`` does, times ``--calls`` calls with
the profiler off, then as many under the profiler (each in a
``bench/call`` span, as the benchmark traces them), and reads the capture
with ``bench/harness/spans.py``: the four per-round readings, the window's
idle time by the innermost host span, the longest idle gaps by the
benchmark's label rule, the device time by scope and by operation,
each ``fed/`` span's time per call, and the spans of the slowest and the
median traced call (where a stalled call grew).
Prints one JSON object as its last stdout line. Runs only on a TPU, like
the benchmark; it decides no ``correct``. A traced xLSTM call holds
about half a million device operations: ``--calls 1`` reads in a few
minutes. Until ``bench/run.py``'s traced window reads these labels
itself, this is a second entry point; then it goes (PERF.md §7).
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def scope_of(op_name) -> str:
    """The program scope an operation's ``op_name`` lies in."""
    if op_name is None:
        return "(other programs)"
    for mark in ("fed/train/optimizer/", "fed/flatten/", "fed/unflatten/"):
        if mark in op_name:
            return mark.rstrip("/")
    m = re.search(r"(?:^|/)(wire/[\w.-]+)/", op_name)
    return m.group(1) if m else "(no scope)"


def timed_calls(cell, calls: int, span=None) -> list:
    import jax
    out = []
    for _ in range(calls):
        t0 = time.perf_counter()
        if span:
            with jax.profiler.TraceAnnotation(span):
                res = cell.call()
        else:
            res = cell.call()
        del res
        out.append(time.perf_counter() - t0)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--calls", type=int, default=None,
                    help="calls with the profiler off, then on (default: "
                         "the cell's trace_calls)")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax

    from bench import run as bench_run
    from bench.harness import spans, spec, trace

    wl = spec.workload(args.workload)
    cfg = spec.config(wl["config"])
    try:
        devices = bench_run.require_devices(wl["chips"])
    except bench_run.NoChip as e:
        return e.code
    calls = args.calls or wl["trace_calls"]
    cell = bench_run.make_cell(wl, cfg, args.seed)
    cell.setup()
    setup_s = time.perf_counter() - T_START
    call_off = timed_calls(cell, calls)

    tmp = tempfile.mkdtemp(prefix="bench-spans-")
    try:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tmp, profiler_options=opts)
        try:
            call_on = timed_calls(cell, calls, trace.CALL_SPAN)
        finally:
            jax.profiler.stop_trace()
        pd = trace.load(tmp)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    texts = [p.compiled.as_text() for p in cell.sim.scan_programs.values()]
    red = trace.reduce_profile(pd, trace.kernel_scopes(texts))
    modules, names = spans.op_names(texts)
    host = spans.host_spans(pd)
    gaps = spans.idle_gaps(red, host)
    ops = spans.scoped_ops(red, pd, modules, names)
    rounds = calls * cell.rounds

    span_ms, scope_ms, label_ms = {}, {}, {}
    for name, s, e in host:
        span_ms[name] = span_ms.get(name, 0.0) + 1e3 * (e - s) / calls
    per_round = 1e3 / red.chips / rounds
    for name, op in ops:
        scope = scope_of(name)
        scope_ms[scope] = scope_ms.get(scope, 0.0) + op.dur * per_round
        by = label_ms.setdefault(trace.op_label(op), {})
        by[scope] = by.get(scope, 0.0) + op.dur * per_round
    top = sorted(label_ms, key=lambda k: -sum(label_ms[k].values()))[:12]
    by_len = sorted((e - s, s, e) for n, s, e in host if n == "fed/scan")

    def steps_ms(call):     # each step's length in one call
        _, c0, c1 = call
        return {n: 1e3 * (e - s) for n, s, e in host if c0 <= s and e <= c1}
    result = {
        "cell": args.workload, "seed": args.seed, "calls": calls,
        "rounds_per_call": cell.rounds, "setup_s": setup_s,
        "device": {"kind": devices[0].device_kind, "count": len(devices)},
        "call_s_off": call_off, "call_s_on": call_on,
        "window_s": red.window_s,
        "device_idle_share": 100.0 * (1.0 - red.mean_busy_s / red.window_s),
        "readings": spans.readings(red, gaps, ops, rounds),
        "span_ms_per_call": span_ms,
        "slowest_call_ms": steps_ms(by_len[-1]),
        "median_call_ms": steps_ms(by_len[len(by_len) // 2]),
        "idle_ms_per_call_by_span": {
            k: 1e3 * v / calls
            for k, v in spans.idle_by_span(red, gaps).items()},
        "longest_gaps": trace.breakdown(
            dataclasses.replace(red, gaps=gaps))["idle_gaps"],
        "device_ms_per_round_by_scope": scope_ms,
        "device_ms_per_round_by_label_and_scope": {k: label_ms[k]
                                                   for k in top},
        "device_ops": trace.breakdown(red)["device_ops"],
    }
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
