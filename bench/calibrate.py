"""Readings that the check's limits are set from: program, control, faults.

    python3 bench/calibrate.py --workload <cell> --seeds 11 12 13 ... \
        [--control-seeds 3] [--out FILE]

Runs on the chip, in one process, with the cell's own driver and chips.
For every seed it makes the cell's own
set-up (the program's first calls, exactly as a run makes them), frees
the program and replays those rounds with the plain float32 reference:
that gives the program's readings of every compared number (the lower
readings). For the first ``--control-seeds`` seeds it also puts into the
program's place, each compared against the reference that follows its
own pilot choices:

* ``control``: the reference computed in bfloat16 (the precision below
  the configuration's float32);
* ``half_batch``: every local step's loss over half of its tokens;
* ``token``: one token of every batch altered where it is fed;
* ``no_wire``: Eq. (3) without the other workers' codes;

and, as a witness and not a fault, ``default_precision``: the reference
in float32 at the default matmul precision, the program's own (single
bfloat16 passes on the TPU), against the reference at ``highest``. Every
record carries ``explain``: the loss gap of each round and the worst leaf
of each norm gap, with both norms.

A state left unchanged reads 1 on ``param_change_gap`` by construction
and needs no run. The benchmark's own runs never run this.
"""
import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import os
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    import jax
    import jax.numpy as jnp
    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_compilation_cache_max_size", -1)
    if jax.devices()[0].platform != "tpu":
        print("calibrate: runs only on the chip", file=sys.stderr)
        return 2
    from bench.harness import spec
    from bench.harness.check import _included, compare, explain

    wl = spec.workload(args.workload)
    cfg = spec.config(wl["config"])
    make_cell = spec.driver(wl["driver"])
    limits = wl["limits"]
    arch, s_len = cfg["arch"], wl["seq_len"]
    out = open(args.out, "w") if args.out else None

    def emit(rec):
        line = json.dumps(rec)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()

    for i, seed in enumerate(args.seeds):
        t0 = time.perf_counter()
        cell = make_cell(wl, cfg, seed)
        cell.setup()
        cell.release()
        ref = cell.reference(limits)
        grads = ref["first_grad_norms"]
        emit({"seed": seed, "who": "program",
              "readings": compare(cell.prog, ref),
              "explain": explain(cell.prog, ref),
              "left_out": sorted(set(grads) - _included(grads)),
              "costs": cell.prog["costs"], "ref_costs": ref["costs"],
              "pilots": cell.prog["pilots"],
              "seconds": time.perf_counter() - t0})
        if i >= args.control_seeds:
            continue
        own = (ref if ref["pilots"] == cell.prog["pilots"]
               else cell.reference(limits, pilots=None))
        loss = cell.ref.loss
        variants = {
            "control": dict(dtype=jnp.bfloat16, precision="default"),
            "half_batch": dict(loss_override=lambda p, t: loss(
                p, t[:, :s_len // 2], arch)),
            "token": dict(loss_override=lambda p, t: loss(
                p, t.at[:, s_len // 2].set((t[:, s_len // 2] + 1)
                                           % arch["vocab"]), arch)),
            "no_wire": dict(drop_wire=True),
            "default_precision": dict(precision="default"),
        }
        for name, kw in variants.items():
            t1 = time.perf_counter()
            got = cell.reference(limits, pilots=None, **kw)
            ref_like = dict(own, pilot_ok=[a == b for a, b in zip(
                got["pilots"], own["pilots"])])
            emit({"seed": seed, "who": name,
                  "readings": compare(got, ref_like),
                  "explain": explain(got, ref_like),
                  "seconds": time.perf_counter() - t1})
    return 0


if __name__ == "__main__":
    sys.exit(main())
