"""The benchmark: one cell, one seed, one run.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Finds the cell in ``bench/workloads/<cell>.json``, its configuration in
``bench/configs/``, the driver that runs it in ``bench/drivers/``, and the
metrics it reports in ``BENCHMARK.json``.
Set-up (loading, weights and tokens from the seed, compilation, the first
calls) is timed as ``setup_s``; then, with ``--trace 0``, the window calls
the timed path until ``--seconds`` have passed and reports the end-to-end
metrics; with ``--trace 1`` it traces a few calls with the profiler and
reports the per-layer metrics through the readers in ``bench/metrics/``.
Last, with the program's state freed, the plain reference replays the
first calls and decides ``correct``.

Runs only on a TPU: with no TPU, or fewer chips than the cell asks for,
it exits with code 2 and prints no result. The last stdout line is one
JSON object; the compared numbers and their limits are also the last
lines on stderr.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
CACHE_DIR = ROOT / ".jax_cache"


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def say(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


class NoChip(SystemExit):
    pass


def require_devices(chips: int):
    """The first ``chips`` TPU devices; exits 2 without them."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: no TPU (JAX platform {devices[0].platform!r}); the "
              f"benchmark runs only on the chip", file=sys.stderr)
        raise NoChip(2)
    if len(devices) < chips:
        print(f"bench: the cell needs {chips} chips, JAX sees "
              f"{len(devices)}", file=sys.stderr)
        raise NoChip(2)
    return devices[:chips]


def peak_bytes(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def traced_window(cell, calls: int):
    """``calls`` calls under the profiler; returns (reduction, tokens,
    rounds)."""
    import shutil

    import jax
    from bench.harness import trace

    texts = cell.hlo_texts()
    tmp = tempfile.mkdtemp(prefix="bench-trace-")
    try:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tmp, profiler_options=opts)
        try:
            for _ in range(calls):
                with jax.profiler.TraceAnnotation(trace.CALL_SPAN):
                    res = cell.call()
                del res
        finally:
            jax.profiler.stop_trace()
        red = trace.reduce_profile(trace.load(tmp), texts)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return red, calls * cell.tokens_per_call, calls * cell.rounds


def main(argv=None) -> int:
    args = parse(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench.harness import spec

    bench = spec.benchmark()
    e2e, per_layer = spec.cell_metrics(bench, args.workload)
    wl = spec.workload(args.workload)
    cfg = spec.config(wl["config"])
    make_cell = spec.driver(wl["driver"])
    flops = spec.flops_counter(cfg["reference"])

    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # No size-bounded eviction: its bookkeeping files went missing on the
    # chip host and every write then failed, so every run compiled anew.
    jax.config.update("jax_compilation_cache_max_size", -1)
    try:
        devices = require_devices(wl["chips"])
    except NoChip as e:
        return e.code
    import repro  # noqa: F401 — the system under test must be present
    dev = devices[0]
    print(f"bench: platform={dev.platform} device_kind={dev.device_kind} "
          f"count={len(devices)} cell={args.workload} seed={args.seed}",
          file=sys.stderr, flush=True)
    peaks = spec.peaks(dev.device_kind) if dev.platform == "tpu" else None

    cell = make_cell(wl, cfg, args.seed)
    cell.setup()
    setup_s = time.perf_counter() - T_START
    say(f"set-up {setup_s:.3f} s")
    metrics, breakdown, extra_dev = {}, None, {}
    attempted = failed = 0
    if args.trace == 0:
        win = cell.window(args.seconds)
        attempted = win["rounds"]
        failed = win.get("failed", 0)
        values = {"setup_s": setup_s,
                  "tokens_per_s": win["tokens"] / win["seconds"],
                  "peak_hbm_gib": peak_bytes(devices) / 2 ** 30}
        for m in e2e:
            if values.get(m["name"]) is None:
                raise RuntimeError(f"cell cannot report {m['name']}")
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
        say(f"window {win['seconds']:.3f} s, {win['calls']} calls, "
            f"{win['rounds']} rounds, {win['tokens']} tokens; a call "
            f"{min(win['call_s']):.3f}-{max(win['call_s']):.3f} s")
    else:
        from bench.harness import trace
        red, tokens, rounds = traced_window(cell, wl["trace_calls"])
        attempted = rounds
        ctx = {"reduction": red, "tokens": tokens, "rounds": rounds,
               "peaks": peaks, "n_workers": len(wl["workers"]),
               "flops_per_token": flops(cfg["arch"], wl["seq_len"]),
               "wire_ops": trace.wire_ops(red),
               "scoped_ops": red.scoped,
               "device_s_by_scope": trace.device_s_by_scope(red),
               "idle_by_span": trace.idle_by_span(red),
               **cell.program_counters()}
        for m in per_layer:
            v = spec.metric_reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        breakdown = trace.breakdown(red)
        extra_dev = {"busy_s": red.mean_busy_s, "window_s": red.window_s}
    peak = peak_bytes(devices)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak, **extra_dev}

    cell.release()
    from bench.harness.check import verdict
    limits = wl["limits"]
    t_ref = time.perf_counter()
    numbers = cell.check(limits)
    say(f"reference {time.perf_counter() - t_ref:.3f} s")
    ok, rows = verdict(numbers, limits)
    result = {"correct": ok, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {n: {"value": v, "limit": lim} for n, v, lim in rows}
    for n, v, lim in rows:
        print(f"check {n} = {v!r} (limit {lim!r})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
