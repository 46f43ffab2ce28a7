"""Block-size autotuner for the wire kernels.

Which (block_rows, block_workers) plan wins is a property of the *backend*,
not the math: on TPU the grid must tile VMEM (small row blocks, one worker
per step so the master's memory stays O(block)); under cpu-interpret every
grid step pays the interpreter's full block machinery, so the fastest plan
is the one with the fewest steps (whole-row blocks, a (1, 1) grid). Every
plan computes bitwise-identical results (the uplink is elementwise; the
master accumulates workers in a fixed sequential order), so tuning is free
to pick purely on time.

The table maps ``(kind, rows, n_workers, backend)`` → plan. ``lookup``
never times anything: it returns the tuned entry if one exists, else the
backend heuristic — so production paths (the ``ops`` wrappers call
``lookup`` whenever the caller leaves ``block_rows``/``block_workers`` as
None) pay a dict probe, nothing more. ``autotune_stacked`` /
``autotune_master`` run the actual timed sweep and fill the table; the
kernel benchmark (`benchmarks/kernels_bench.py`) runs them per shape so
per-size regressions (e.g. the old hand-tuned 16M fused-uplink loss) are
tuned away instead of patched.

``save_table``/``load_table`` persist the table as JSON; pointing the
``REPRO_TUNE_TABLE`` environment variable at such a file pre-loads it at
import (e.g. a table tuned once on real TPU hardware).
"""
from __future__ import annotations

import json
import math
import os
import time
from typing import Callable

import jax
import jax.numpy as jnp

# TPU-shaped fallbacks (mirrors fused_wire; duplicated to avoid an import
# cycle with the kernels that consult this module through ops).
BLOCK_ROWS = 64
BLOCK_WORKERS = 1

KINDS = ("uplink", "uplink_stacked", "master", "uplink_masked",
         "master_masked", "uplink_masked16", "master_masked16",
         "partial_sum", "partial_sum_masked", "partial_sum_masked16",
         "mask_repair", "mask_repair16")

# Masked kernels share the grid geometry of their plaintext counterparts
# (same block shapes over the same (rows, N) iteration space), so an
# untuned masked kind borrows down a chain of geometry twins: the 16-bit
# modulus kinds fall back to the 32-bit masked plans, which fall back to
# the unmasked kinds, which fall back to the backend heuristic. The tree
# sub-aggregate kinds (keyed by fanout in the n_workers slot, block_workers
# meaning output groups per step) chain the same way.
MASKED_FALLBACK = {"uplink_masked16": "uplink_masked",
                   "master_masked16": "master_masked",
                   "uplink_masked": "uplink_stacked",
                   "master_masked": "master",
                   "partial_sum_masked16": "partial_sum_masked",
                   "partial_sum_masked": "partial_sum",
                   "mask_repair16": "mask_repair",
                   "mask_repair": "uplink"}

# (kind, rows, n_workers, backend) -> {"block_rows": int, "block_workers": int}
_TABLE: dict[tuple[str, int, int, str], dict] = {}

# Fallback-chain resolutions already reported, one line per (kind, rows, n,
# backend) — tuner gaps surface in bench output instead of silently
# borrowing another kind's plan forever.
_FALLBACK_LOGGED: set[tuple[str, int, int, str]] = set()

# Interpret-mode sweeps execute one Python-level step per grid tile; cap the
# plans a cpu sweep will even try so autotuning stays seconds, not minutes.
_MAX_SWEEP_STEPS_INTERPRET = 16

# Optional sweep-trace sink: hook(kind, rows, n, backend, timings, best)
# called once per completed sweep so BENCH_kernels.json provenance is
# reconstructable from a telemetry trace (telemetry.trace.plan_emitter
# adapts a TraceWriter into this signature). None = off.
_TRACE_HOOK = None


def set_trace_writer(hook) -> None:
    """Install (or clear, with None) the sweep-trace hook every autotune
    sweep reports through — one call per sweep with its full timing list."""
    global _TRACE_HOOK
    _TRACE_HOOK = hook


def _emit_sweep(kind, rows, n, backend, timings, best) -> None:
    if _TRACE_HOOK is not None:
        _TRACE_HOOK(kind, rows, n, backend, timings, best)


def use_interpreter(interpret: bool | None = None) -> bool:
    """THE interpret-mode decision for every Pallas launch: an explicit
    request wins; otherwise kernels run in the Pallas interpreter only on
    the ``cpu`` platform and compile (Mosaic) everywhere else — a chip
    never falls back to the interpreter."""
    if interpret is None:
        return jax.default_backend() == "cpu"
    return bool(interpret)


def backend_tag(interpret: bool | None = None) -> str:
    """The table's backend key: 'cpu-interpret' for interpret mode, else
    the real jax backend ('tpu', ...)."""
    return ("cpu-interpret" if use_interpreter(interpret)
            else jax.default_backend())


def fit_block_rows(rows: int, want: int) -> int:
    """Largest multiple of gcd(rows, want) ≤ ``want`` that divides ``rows``.

    The gcd floors the probe (≤ want/g steps vs a unit-step scan) and —
    since padded rows and ``want`` are both multiples of 8 — guarantees the
    result stays 8-sublane aligned (e.g. rows=8400, want=64 → 48, not the
    unaligned 60 a plain divisor scan would pick). The single
    implementation behind ``ops._block_rows_for``."""
    if rows <= want:
        return rows
    g = math.gcd(rows, want)
    b = (want // g) * g
    while rows % b:
        b -= g
    return b


def fit_block_workers(n: int, want: int) -> int:
    """Largest divisor of ``n`` that is ≤ ``want`` (worker blocks must tile
    the worker axis exactly — N=33 with want=8 gives 3, not 8)."""
    want = max(1, min(n, want))
    for b in range(want, 0, -1):
        if n % b == 0:
            return b
    return 1


# Group-axis "all of them" sentinel of the partial-sum default: the ops
# wrappers fit block_workers to the level width, so a huge want collapses
# the group grid to one step (the cpu-interpret optimum at any width).
_ALL_GROUPS = 1 << 30


def default_plan(kind: str, rows: int, n_workers: int = 1,
                 backend: str | None = None) -> dict:
    """The untimed heuristic: fewest steps on cpu-interpret (per-step
    machinery dominates), VMEM-sized O(block) tiles elsewhere. For the
    partial-sum kinds ``n_workers`` holds the fanout and ``block_workers``
    means output groups per grid step — the cpu single-step plan wants ALL groups
    (the ops wrapper clamps to the level width)."""
    backend = backend or backend_tag()
    if backend == "cpu-interpret":
        if kind.startswith("partial_sum"):
            return {"block_rows": rows, "block_workers": _ALL_GROUPS}
        return {"block_rows": rows, "block_workers": max(1, n_workers)}
    if kind.startswith("partial_sum"):
        return {"block_rows": fit_block_rows(rows, BLOCK_ROWS),
                "block_workers": 1}
    return {"block_rows": fit_block_rows(rows, BLOCK_ROWS),
            "block_workers": fit_block_workers(max(1, n_workers),
                                               BLOCK_WORKERS)}


def lookup(kind: str, rows: int, n_workers: int = 1, *,
           interpret: bool | None = None) -> tuple[int, int]:
    """(block_rows, block_workers) for a shape — tuned entry or heuristic.

    Never times anything; this is the hot-path call the ``ops`` wrappers
    make when the caller leaves the block sizes unspecified. When the
    requested kind has no entry and resolution walks the
    ``MASKED_FALLBACK`` chain, the traversal is reported once per (kind,
    rows, n, backend) so tuner gaps are visible in bench output instead of
    silently borrowing another kind's plan.
    """
    backend = backend_tag(interpret)
    probe = kind
    chain = [kind]
    plan = _TABLE.get((probe, rows, max(1, n_workers), backend))
    while plan is None and probe in MASKED_FALLBACK:
        probe = MASKED_FALLBACK[probe]
        chain.append(probe)
        plan = _TABLE.get((probe, rows, max(1, n_workers), backend))
    if len(chain) > 1:
        key = (kind, rows, max(1, n_workers), backend)
        if key not in _FALLBACK_LOGGED:
            _FALLBACK_LOGGED.add(key)
            landed = (f"tuned '{probe}' plan" if plan is not None
                      else f"'{backend}' heuristic")
            print(f"[tune] no plan for {kind}@(rows={rows}, "
                  f"n={max(1, n_workers)}, {backend}); fell back "
                  f"{' -> '.join(chain)} to the {landed}")
    if plan is None:
        plan = default_plan(kind, rows, n_workers, backend)
    return plan["block_rows"], plan["block_workers"]


def set_plan(kind: str, rows: int, n_workers: int, plan: dict, *,
             backend: str | None = None) -> None:
    """Pin a plan (tests / externally-tuned tables)."""
    _TABLE[(kind, rows, max(1, n_workers), backend or backend_tag())] = dict(plan)


def clear_table() -> None:
    _TABLE.clear()


def master_vmem_tile_bytes(block_rows: int, block_workers: int) -> int:
    """VMEM footprint model of one accumulating-master grid step: the four
    resident (block_rows, 512) float32 blocks (q, p1, p2, and the
    output/accumulator) plus the (block_workers, block_rows, 128) packed
    uint8 sub-block. Independent of N at fixed ``block_workers`` — the
    property that lets federation size scale without growing the tile
    (the pre-accumulation kernel held all N packed blocks: N·block_rows·128
    bytes, linear in N)."""
    float_block = block_rows * 512 * 4
    return 4 * float_block + block_workers * block_rows * 128


def master_vmem_tile_bytes_preaccum(block_rows: int, n_workers: int) -> int:
    """Footprint of the OLD (pre-grid-accumulation) master tile, which
    blocked the full worker axis: scales linearly with N."""
    float_block = block_rows * 512 * 4
    return 4 * float_block + n_workers * block_rows * 128


def _time_us(fn: Callable, reps: int) -> float:
    jax.block_until_ready(fn())                       # compile/warm
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, (time.perf_counter() - t0) * 1e6)
    return best


def _candidate_plans(rows: int, n: int, backend: str) -> list[dict]:
    """Small, deduplicated sweep: the single-step plan, whole-row blocks with
    worker sub-blocks, and VMEM-tile plans."""
    cands = [
        {"block_rows": rows, "block_workers": n},            # one step
        {"block_rows": rows, "block_workers": 1},            # worker grid
        {"block_rows": fit_block_rows(rows, BLOCK_ROWS),
         "block_workers": 1},                                # TPU tile
        {"block_rows": fit_block_rows(rows, 256),
         "block_workers": fit_block_workers(n, 8)},
    ]
    seen, out = set(), []
    for c in cands:
        key = (c["block_rows"], c["block_workers"])
        steps = (rows // c["block_rows"]) * (n // c["block_workers"])
        if key in seen:
            continue
        if (backend == "cpu-interpret"
                and steps > _MAX_SWEEP_STEPS_INTERPRET):
            continue                       # interpret: each step is Python
        seen.add(key)
        out.append(c)
    return out


def _sweep(kind: str, rows: int, n: int, run_plan: Callable, *,
           interpret: bool | None, reps: int) -> dict:
    backend = backend_tag(interpret)
    timings = []
    for plan in _candidate_plans(rows, n, backend):
        us = _time_us(lambda p=plan: run_plan(p), reps)
        timings.append({**plan, "us": us})
    best = min(timings, key=lambda r: r["us"])
    _TABLE[(kind, rows, n, backend)] = {
        "block_rows": best["block_rows"],
        "block_workers": best["block_workers"]}
    _emit_sweep(kind, rows, n, backend, timings, best)
    return {"kind": kind, "rows": rows, "n_workers": n, "backend": backend,
            "best": {k: best[k] for k in ("block_rows", "block_workers")},
            "timings": timings}


def autotune_stacked(rows: int, n_workers: int, *,
                     interpret: bool | None = None, reps: int = 2,
                     seed: int = 0) -> dict:
    """Timed sweep of the stacked-uplink plans for (rows, N); stores the
    winner in the table and returns the full sweep record. ``rows`` is the
    kernel-view row count (flat rows / 4)."""
    from repro.kernels import fused_wire as fw
    itp = use_interpreter(interpret)
    k = jax.random.PRNGKey(seed)
    q = jax.random.normal(k, (n_workers, rows, fw.LANES * fw.PACK))
    p1 = jax.random.normal(jax.random.fold_in(k, 1),
                           (rows, fw.LANES * fw.PACK))
    p2 = jax.random.normal(jax.random.fold_in(k, 2),
                           (rows, fw.LANES * fw.PACK))

    def run_plan(plan):
        return fw.ternary_pack_stacked_2d(
            q, p1, p2, 3, 0.2, 0.01, interpret=itp,
            block_rows=plan["block_rows"],
            block_workers=plan["block_workers"])

    return _sweep("uplink_stacked", rows, n_workers, run_plan,
                  interpret=itp, reps=reps)


def autotune_master(rows: int, n_workers: int, *,
                    interpret: bool | None = None, reps: int = 2,
                    seed: int = 0) -> dict:
    """Timed sweep of the accumulating-master plans for (rows, N)."""
    from repro.kernels import fused_wire as fw
    itp = use_interpreter(interpret)
    k = jax.random.PRNGKey(seed)
    wide = fw.LANES * fw.PACK
    q = jax.random.normal(k, (rows, wide))
    p1 = jax.random.normal(jax.random.fold_in(k, 1), (rows, wide))
    p2 = jax.random.normal(jax.random.fold_in(k, 2), (rows, wide))
    packed = jax.random.randint(jax.random.fold_in(k, 3),
                                (n_workers, rows, fw.LANES), 0,
                                256).astype(jnp.uint8)
    w = jnp.full((n_workers,), 0.02)

    def run_plan(plan):
        return fw.packed_master_update_2d(
            q, packed, w, p1, p2, 3, 0.01, interpret=itp,
            block_rows=plan["block_rows"],
            block_workers=plan["block_workers"])

    return _sweep("master", rows, n_workers, run_plan,
                  interpret=itp, reps=reps)


def _masked_inputs(rows: int, n_workers: int, seed: int, word_bits: int):
    """Shared operands of the masked-kernel sweeps: random history views
    plus the tiny per-pair key/sign matrices the in-kernel PRNG consumes
    (sweep timings therefore include the real mask-generation cost)."""
    from repro.kernels import fused_wire as fw
    from repro.privacy import dp as pdp
    from repro.privacy import masking as pvm
    k = jax.random.PRNGKey(seed)
    wide = fw.LANES * fw.PACK
    q = jax.random.normal(k, (n_workers, rows, wide))
    p1 = jax.random.normal(jax.random.fold_in(k, 1), (rows, wide))
    p2 = jax.random.normal(jax.random.fold_in(k, 2), (rows, wide))
    keys = pvm.pair_stream_keys(seed, n_workers, 3)
    signs = pvm.pair_signs(n_workers)
    rrk = pdp.rr_stream_keys(seed + 1, 3, n_workers)
    fb = 14 if word_bits == 16 else 24
    wq = jnp.full((n_workers,), (1 << fb) // max(n_workers, 1), jnp.uint32)
    return q, p1, p2, keys, signs, rrk, wq


def autotune_masked_uplink(rows: int, n_workers: int, *,
                           interpret: bool | None = None, reps: int = 2,
                           seed: int = 0, word_bits: int = 32) -> dict:
    """Timed sweep of the masked-uplink (secure-agg) plans for (rows, N) at
    one wire modulus; fills the ``uplink_masked16``/``uplink_masked`` kind
    by ``word_bits``."""
    from repro.kernels import masked_wire as mw
    itp = use_interpreter(interpret)
    q, p1, p2, keys, signs, rrk, wq = _masked_inputs(rows, n_workers, seed,
                                                     word_bits)

    def run_plan(plan):
        return mw.ternary_pack_masked_2d(
            q, p1, p2, 3, 0.2, 0.01, wq, keys, signs, rrk,
            rr_threshold=0, word_bits=word_bits, interpret=itp,
            block_rows=plan["block_rows"],
            block_workers=plan["block_workers"])

    kind = "uplink_masked16" if word_bits == 16 else "uplink_masked"
    return _sweep(kind, rows, n_workers, run_plan, interpret=itp, reps=reps)


def autotune_masked_master(rows: int, n_workers: int, *,
                           interpret: bool | None = None, reps: int = 2,
                           seed: int = 0, word_bits: int = 32) -> dict:
    """Timed sweep of the sum-then-unmask master plans for (rows, N) at one
    wire modulus."""
    from repro.kernels import masked_wire as mw
    itp = use_interpreter(interpret)
    q, p1, p2, keys, signs, rrk, wq = _masked_inputs(rows, n_workers, seed,
                                                     word_bits)
    word = jnp.uint16 if word_bits == 16 else jnp.uint32
    masked = jax.random.bits(jax.random.PRNGKey(seed + 3),
                             (n_workers, rows, q.shape[-1]),
                             jnp.uint32).astype(word)
    fb = 14 if word_bits == 16 else 24

    def run_plan(plan):
        return mw.masked_master_update_2d(
            q[0], masked, jnp.sum(wq), p1, p2, 3, 0.01, 2.0 ** -fb,
            interpret=itp, block_rows=plan["block_rows"],
            block_workers=plan["block_workers"])

    kind = "master_masked16" if word_bits == 16 else "master_masked"
    return _sweep(kind, rows, n_workers, run_plan, interpret=itp, reps=reps)


def autotune_partial_sum(rows: int, fanout: int, n_children: int, *,
                         interpret: bool | None = None, reps: int = 2,
                         seed: int = 0, word_bits: int = 32,
                         masked: bool = False) -> dict:
    """Timed sweep of the tree sub-aggregate plans for (rows, fanout) at
    one level width ``n_children``; fills the ``partial_sum*`` kind picked
    by ``masked``/``word_bits``. The table key holds the fanout in the
    n_workers slot and the winning ``block_workers`` means output groups
    per grid step (clamped to the level width by the ops wrappers)."""
    from repro.kernels import partial_sum as psk
    from repro.privacy import masking as pvm
    itp = use_interpreter(interpret)
    backend = backend_tag(itp)
    g = -(-n_children // fanout)
    pad_c = g * fanout
    wide = 512
    key = jax.random.PRNGKey(seed)
    if masked:
        kind = ("partial_sum_masked16" if word_bits == 16
                else "partial_sum_masked")
        word = jnp.uint16 if word_bits == 16 else jnp.uint32
        y = jax.random.bits(key, (pad_c, rows, wide),
                            jnp.uint32).astype(word)
        keys = pvm.pair_stream_keys(seed, g, 3)
        sib = max(1, min(g, fanout))
        signs = pvm.tree_pair_signs(g, sib)

        def run_plan(plan):
            return psk.masked_partial_sum_2d(
                y, keys, signs, fanout=fanout, sibling=sib, interpret=itp,
                block_rows=plan["block_rows"],
                block_groups=plan["block_workers"])
    else:
        kind = "partial_sum"
        packed = jax.random.bits(key, (pad_c, rows, 128),
                                 jnp.uint32).astype(jnp.uint8)
        fb = 14 if word_bits == 16 else 24
        wq = jnp.full((pad_c,), (1 << fb) // max(pad_c, 1), jnp.uint32)

        def run_plan(plan):
            return psk.partial_sum_2d(
                packed, wq, fanout=fanout, word_bits=word_bits,
                interpret=itp, block_rows=plan["block_rows"],
                block_groups=plan["block_workers"])

    cands, seen = [], set()
    for c in ({"block_rows": rows, "block_workers": g},
              {"block_rows": rows, "block_workers": 1},
              {"block_rows": fit_block_rows(rows, BLOCK_ROWS),
               "block_workers": 1}):
        ck = (c["block_rows"], c["block_workers"])
        steps = (rows // c["block_rows"]) * (g // c["block_workers"])
        if ck in seen or (backend == "cpu-interpret"
                          and steps > _MAX_SWEEP_STEPS_INTERPRET):
            continue
        seen.add(ck)
        cands.append(c)
    timings = [{**plan, "us": _time_us(lambda p=plan: run_plan(p), reps)}
               for plan in cands]
    best = min(timings, key=lambda r: r["us"])
    _TABLE[(kind, rows, fanout, backend)] = {
        "block_rows": best["block_rows"],
        "block_workers": best["block_workers"]}
    _emit_sweep(kind, rows, fanout, backend, timings, best)
    return {"kind": kind, "rows": rows, "n_workers": fanout,
            "n_children": n_children, "backend": backend,
            "best": {k: best[k] for k in ("block_rows", "block_workers")},
            "timings": timings}


def autotune_mask_repair(rows: int, n_pairs: int, *,
                         interpret: bool | None = None, reps: int = 2,
                         seed: int = 0, word_bits: int = 32) -> dict:
    """Timed sweep of the dropout-repair kernel plans for (rows, P repair
    pairs) at one wire modulus; fills ``mask_repair16``/``mask_repair``
    keyed with n_workers=1 (the kernel has no worker axis — only a row
    grid). Half the coefficients are zero so the sweep times the in-kernel
    zero-skip path a real faulted round exercises."""
    from repro.kernels import masked_wire as mw
    from repro.privacy import masking as pvm
    itp = use_interpreter(interpret)
    backend = backend_tag(itp)
    word = jnp.uint16 if word_bits == 16 else jnp.uint32
    y = jax.random.bits(jax.random.PRNGKey(seed), (rows, 512),
                        jnp.uint32).astype(word)
    keys = pvm.stream_key(seed, jnp.arange(max(1, n_pairs)), 3)
    coeff = jnp.where(jnp.arange(max(1, n_pairs)) % 2 == 0, 1, 0
                      ).astype(jnp.int32)

    def run_plan(plan):
        return mw.mask_repair_2d(y, keys, coeff, interpret=itp,
                                 block_rows=plan["block_rows"])

    kind = "mask_repair16" if word_bits == 16 else "mask_repair"
    cands, seen = [], set()
    for c in ({"block_rows": rows, "block_workers": 1},
              {"block_rows": fit_block_rows(rows, 256), "block_workers": 1},
              {"block_rows": fit_block_rows(rows, BLOCK_ROWS),
               "block_workers": 1}):
        ck = c["block_rows"]
        steps = rows // c["block_rows"]
        if ck in seen or (backend == "cpu-interpret"
                          and steps > _MAX_SWEEP_STEPS_INTERPRET):
            continue
        seen.add(ck)
        cands.append(c)
    timings = [{**plan, "us": _time_us(lambda p=plan: run_plan(p), reps)}
               for plan in cands]
    best = min(timings, key=lambda r: r["us"])
    _TABLE[(kind, rows, 1, backend)] = {
        "block_rows": best["block_rows"],
        "block_workers": best["block_workers"]}
    _emit_sweep(kind, rows, 1, backend, timings, best)
    return {"kind": kind, "rows": rows, "n_workers": 1,
            "n_pairs": n_pairs, "backend": backend,
            "best": {k: best[k] for k in ("block_rows", "block_workers")},
            "timings": timings}


def save_table(path: str) -> None:
    """Persist the tuned table as JSON ({'kind|rows|n|backend': plan})."""
    data = {"|".join(map(str, k)): v for k, v in sorted(_TABLE.items())}
    with open(path, "w") as f:
        json.dump(data, f, indent=2, sort_keys=True)


def load_table(path: str, *, replace: bool = False) -> int:
    """Merge (or replace) the table from a ``save_table`` JSON; returns the
    number of entries loaded."""
    with open(path) as f:
        data = json.load(f)
    if replace:
        _TABLE.clear()
    for key, plan in data.items():
        kind, rows, n, backend = key.split("|")
        _TABLE[(kind, int(rows), int(n), backend)] = {
            "block_rows": int(plan["block_rows"]),
            "block_workers": int(plan["block_workers"])}
    return len(data)


_env_table = os.environ.get("REPRO_TUNE_TABLE")
if _env_table and os.path.exists(_env_table):
    load_table(_env_table)
