"""Chip smoke check: the FedPC federated round at xLSTM-350M widths on a TPU.

Drives the system's main path through the entry points a user calls —
``repro.launch.train.build_simulation`` → ``FedSimulator.run_fedpc_scan`` on
one chip, ``repro.fed.distributed.build_fed_step`` across four — at the
published widths of ``xlstm-350m`` (d_model 1024, 4 heads × 256, vocab
50,304, mLSTM projection factor 2), cut in depth only, with random weights
from a seed. Every phase checks its output against the repo's own
references and fails loudly; nothing is caught.

  python chip_smoke.py             # one chip: Phase A (plain wire) and
                                   # Phase B (masked-16 tree wire, dropout)
  python chip_smoke.py --chips 4   # four chips: cross-silo build_fed_step
                                   # sync vs master_update_tree, nothing else

Times printed here are smoke readings of one run, not benchmark numbers.
The script refuses to run (exit 2, no result line) when JAX finds no TPU.
Its last stdout line is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.core.fedpc import FedPCConfig  # noqa: E402
from repro.core.tree import TreeSpec  # noqa: E402
from repro.fed import faults as ft  # noqa: E402
from repro.fed import rounds as rd  # noqa: E402
from repro.kernels import ref  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.train import build_simulation  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.privacy import masking as pvm  # noqa: E402
from repro.privacy import recovery as pvr  # noqa: E402
from repro.privacy.ref import masked_codes_ref  # noqa: E402
from repro.privacy.spec import PrivacySpec  # noqa: E402
from repro.utils import iter_jaxpr_eqns  # noqa: E402

ARCH = "xlstm-350m"
# Depth: one mLSTM+sLSTM period, chosen from memory_analysis() of the
# Phase-A scan program compiled for a described v5e (4 workers, batch 1 x
# 256 tokens): one period asks for 11.8 GiB before donation (arguments 3.4
# + outputs 3.4 + temporaries 4.9), two periods 19.1 GiB (temporaries
# 10.8) — about 15 GiB even after donating the 4.2 GiB of carried state,
# which leaves no room on a 16 GB chip for the full-size wire checks.
PERIODS = 1
N_WORKERS = 4
SEQ_LEN = 256
FANOUT = 2
# FaultPlan seed whose stream drops exactly one worker after its uplink in
# each of rounds 1..4 (asserted in phase_b).
FAULT_SEED = 15
FAULT_P = 0.25


def smoke_config(periods: int = PERIODS):
    cfg = get_config(ARCH)
    return cfg.replace(n_layers=periods * len(cfg.pattern))


def say(phase: str, **kw) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in kw.items()),
          flush=True)


def _peak_bytes() -> str:
    stats = jax.devices()[0].memory_stats() or {}
    return str(stats.get("peak_bytes_in_use", "not reported"))


def _pallas_launches(fn, *args) -> int:
    jaxpr = jax.make_jaxpr(fn)(*args)
    return sum(e.primitive.name == "pallas_call"
               for e in iter_jaxpr_eqns(jaxpr.jaxpr, into_pallas=False))


def check_round_program(wire: rd.WirePath, state, n: int, want: int,
                        on_chip: bool) -> int:
    """Compile one round of ``wire`` at these widths: it must hold exactly
    ``want`` Pallas launches and, on the chip, every one of them must be a
    Mosaic ``tpu_custom_call`` (an interpreted launch lowers to plain HLO
    loops and would leave the count short)."""
    bufs = jax.ShapeDtypeStruct((n,) + state.buf_p1.shape, jnp.float32)
    vec = jax.ShapeDtypeStruct((n,), jnp.float32)
    step = lambda st, b, c, s: wire.round_step(st, b, c, s)[1]
    launches = _pallas_launches(step, state, bufs, vec, vec)
    if launches != want:
        raise AssertionError(f"round program has {launches} Pallas "
                             f"launches, the README promises {want}")
    if on_chip:
        text = jax.jit(step).lower(state, bufs, vec, vec).compile().as_text()
        calls = text.count("tpu_custom_call")
        if calls != launches:
            raise AssertionError(f"{launches} Pallas launches but {calls} "
                                 f"Mosaic kernels in the compiled round")
    return launches


def _run_twice(sim, rounds: int, phase: str, min_calls: int,
               on_chip: bool):
    """``rounds`` rounds from scratch (compile + run), then ``rounds`` more
    resumed from the returned state, which must reuse the compiled
    program. Returns the two results."""
    t0 = time.perf_counter()
    res = sim.run_fedpc_scan(rounds)
    first_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    res2 = sim.run_fedpc_scan(rounds, state=res.round_state)
    steady_s = time.perf_counter() - t0
    if len(sim.scan_programs) != 1:
        raise AssertionError("the resumed run compiled a second program")
    (prog,) = sim.scan_programs.values()
    costs = list(res.costs) + list(res2.costs)
    if not np.all(np.isfinite(costs)):
        raise AssertionError(f"non-finite round costs {costs}")
    calls = prog.compiled.as_text().count("tpu_custom_call")
    if on_chip and calls < min_calls:
        raise AssertionError(f"scan program holds {calls} Mosaic kernels, "
                             f"fewer than the {min_calls} launches a round "
                             f"makes")
    ma = prog.compiled.memory_analysis()
    gib = lambda b: f"{b / 2**30:.3f}"
    say(phase, rounds=2 * rounds, costs=[round(c, 4) for c in costs],
        program_GiB=f"args {gib(ma.argument_size_in_bytes)} out "
                    f"{gib(ma.output_size_in_bytes)} temp "
                    f"{gib(ma.temp_size_in_bytes)} alias "
                    f"{gib(ma.alias_size_in_bytes)}",
        compile_s=f"{prog.compile_s:.3f}",
        first_call_s=f"{first_s:.3f}", steady_s=f"{steady_s:.3f}",
        mosaic_kernels_in_scan_program=calls,
        note="smoke reading, not a benchmark")
    return res, res2


def _worker_bufs(state, n: int, seed: int):
    """N workers' local buffers: the global model plus a per-worker step."""
    key = jax.random.PRNGKey(seed)
    noise = jax.random.normal(key, (n,) + state.buf_p1.shape, jnp.float32)
    return state.buf_p1[None] + 1e-3 * noise


def _row_chunks(x, c: int):
    """``x`` (..., R, W) → (c, ..., R/c, W): equal row chunks for lax.map."""
    r, w = x.shape[-2:]
    return jnp.moveaxis(x.reshape(x.shape[:-2] + (c, r // c, w)), -3, 0)


def _n_chunks(rows: int, most: int = 2048) -> int:
    """The fewest equal chunks of at most ``most`` rows of 512. The flat jnp
    oracles reshape to a 4-wide minor axis, which the chip pads to 128
    lanes (32x): run whole they would not fit next to the model."""
    return next(c for c in range(1, rows + 1)
                if rows % c == 0 and rows // c <= most)


def plain_wire_check(wire: rd.WirePath, n: int, t: int):
    """Jitted ``(bufs, p1, p2, w) -> (uplink_equal, max_abs_diff, worst)``:
    one round's plain wire (uplink + master kernels) against the jnp
    oracles of ``kernels/ref.py``, chunk by chunk. ``worst`` is the
    largest master deviation as a share of the f32 rounding bound."""
    beta, alpha0 = wire.cfg.beta, wire.cfg.alpha0

    def check(bufs, p1, p2, w):
        packed = wire.uplink_stacked(bufs, p1, p2, t=t)
        got = wire.master(bufs[0], packed, w, p1, p2, t=t)
        rows = p1.shape[0] // 4
        wide = lambda x: x.reshape(x.shape[:-2] + (rows, 512))

        def chunk(xs):
            q, pk, a, b, g = (x.reshape(x.shape[:-2] + (-1,)) for x in xs)
            up = jax.vmap(lambda qk: ref.ternary_pack_ref(qk, a, b, beta))(q)
            want = ref.packed_master_accum_ref(q[0], pk, w, a, b, t, alpha0)
            # The kernel and the oracle fold the same N weighted fields in
            # the same order, but XLA and Mosaic may each contract a
            # multiply-add into an FMA: at most N + 2 f32 roundings, each
            # within one ulp of the largest partial
            # |q| + sum_k |w_k| |P^{t-1} - P^{t-2}|.
            eps = jnp.finfo(jnp.float32).eps
            scale = jnp.abs(q[0]) + jnp.sum(jnp.abs(w)) * jnp.abs(a - b)
            diff = jnp.abs(g - want)
            return (jnp.array_equal(up, pk), jnp.max(diff),
                    jnp.max(diff / ((n + 2) * eps * scale + 1e-30)))

        c = _n_chunks(rows)
        ok, diff, worst = jax.lax.map(chunk, tuple(
            _row_chunks(x, c) for x in (wide(bufs), packed, wide(p1),
                                         wide(p2), wide(got))))
        return jnp.all(ok), jnp.max(diff), jnp.max(worst)

    return jax.jit(check)


def check_plain_wire(sim, state, n: int) -> dict:
    """One round's plain wire at full size against the jnp oracles: the
    packed uplink bytes exactly, the master output within a bound on f32
    rounding."""
    wire = rd.WirePath(rd.WireConfig.from_fedpc(sim.fed_cfg))
    t = int(state.round)
    sizes = jnp.asarray(sim.sizes)
    w = wire.weights(sizes / jnp.sum(sizes), 0, t)
    ok, diff, worst = plain_wire_check(wire, n, t)(
        _worker_bufs(state, n, seed=1), state.buf_p1, state.buf_p2, w)
    if not bool(ok):
        raise AssertionError("packed uplink bytes differ from "
                             "kernels.ref.ternary_pack_ref")
    if float(worst) > 1.0:
        raise AssertionError(f"master output off the oracle by "
                             f"{float(worst):.3f} of the f32 rounding bound")
    return {"uplink_bytes_exact": True,
            "master_max_abs_diff": float(diff),
            "master_worst_share_of_bound": float(worst)}


def phase_a(cfg, *, rounds: int = 3, seq_len: int = SEQ_LEN,
            on_chip: bool = True) -> dict:
    """Plain wire: N = 4 workers, paper-uniform private hyper-parameters,
    ``run_fedpc_scan``; then one round's wire against the oracles."""
    _, sim = build_simulation(cfg, workers=N_WORKERS,
                              sequences=2 * N_WORKERS, seq_len=seq_len,
                              batch_menu=(1,))
    res, res2 = _run_twice(sim, rounds, "phase A", 2, on_chip)
    del res
    launches = check_round_program(rd.WirePath(), res2.round_state,
                                   N_WORKERS, 2, on_chip)
    wire = check_plain_wire(sim, res2.round_state, N_WORKERS)
    say("phase A", round_launches=launches, **wire, peak_bytes_in_use=
        _peak_bytes())
    return {"launches": launches, **wire}


def masked_aggregate_check(wire: rd.WirePath, t: int):
    """Jitted ``(bufs, p1, p2, w, wq_ref, alive) -> (equal, sum_wq)``: one
    faulty masked round (uplink, dead-row drop, tree folds, mask repair)
    whose recovered aggregate — the modular sum of the root's word
    partials — must equal, bit for bit, the sum of ``privacy/ref.py``'s
    unmasked fixed-point fields weighted by the survivors-only ``wq_ref``.
    Modular integer sums: exact on any device."""
    spec, cfg = wire.privacy, wire.cfg
    mod = jnp.uint32((1 << spec.modulus_bits) - 1)

    def check(bufs, p1, p2, w, wq_ref, alive):
        y_top, wq, _ = wire.aggregate_masked(bufs, p1, p2, t=t, w=w,
                                             alive=alive)
        rows = p1.shape[0] // 4
        wide = lambda x: x.reshape(x.shape[:-2] + (rows, 512))

        def chunk(xs):
            q, a, b, y = xs
            zeros = lambda dt: jnp.zeros(q.shape, dt)
            codes = masked_codes_ref(q, a, b, t, cfg.beta, cfg.alpha1,
                                     wq_ref, zeros(spec.word_dtype),
                                     zeros(jnp.uint32), 0)
            want = jnp.sum(codes.astype(jnp.uint32), axis=0) & mod
            got = jnp.sum(y.astype(jnp.uint32), axis=0) & mod
            return jnp.array_equal(got, want)

        c = _n_chunks(rows)
        ok = jax.lax.map(chunk, tuple(_row_chunks(x, c) for x in (
            wide(bufs), wide(p1), wide(p2), y_top)))
        return jnp.all(ok), jnp.sum(wq)

    return jax.jit(check)


def check_recovered_aggregate(spec: PrivacySpec, tree, plan, state,
                              sizes, n: int, t: int) -> dict:
    """One faulty masked round at full size against the survivors-only
    reference (:func:`masked_aggregate_check`)."""
    wire = rd.WirePath(privacy=spec, tree=tree, faults=plan)
    alive = plan.alive(t, n)
    gsz = tree.fanout if tree is not None else None
    alive_eff, dead_eff = pvr.effective_masks(None, alive,
                                              spec.recovery_threshold, gsz, n)
    w = wire.weights(sizes / jnp.sum(sizes), 0, t)
    wq_ref = pvm.quantize_weights(w, spec.fixpoint_bits) * (alive_eff > 0)
    ok, sum_wq = masked_aggregate_check(wire, t)(
        _worker_bufs(state, n, seed=2), state.buf_p1, state.buf_p2, w,
        wq_ref, alive)
    if not bool(ok):
        raise AssertionError(f"tree={gsz}: recovered aggregate differs from "
                             f"the survivors-only reference")
    if int(sum_wq) != int(jnp.sum(wq_ref)):
        raise AssertionError("surviving fixed-point weights differ")
    return {"dead": np.flatnonzero(np.asarray(alive) == 0).tolist(),
            "used": np.flatnonzero(np.asarray(alive_eff) > 0).tolist(),
            "repaired": np.flatnonzero(np.asarray(dead_eff) > 0).tolist()}


def phase_b(cfg, *, rounds: int = 2, seq_len: int = SEQ_LEN,
            on_chip: bool = True) -> dict:
    """Privacy wire at the same widths: masked-16 secure aggregation over a
    fanout-2 tree, one worker dropping after its uplink every round."""
    spec = PrivacySpec(recovery_threshold=2)
    tree = TreeSpec(fanout=FANOUT)
    plan = ft.FaultPlan(seed=FAULT_SEED, drop_after_uplink=FAULT_P)
    for t in range(1, 2 * rounds + 1):
        codes = np.asarray(plan.codes(t, N_WORKERS))
        if (codes != ft.FAULT_NONE).sum() != 1 or (
                codes == ft.DROP_AFTER).sum() != 1:
            raise AssertionError(f"fault plan round {t}: {codes}")
    fed_cfg = FedPCConfig(n_workers=N_WORKERS, privacy=spec, tree=tree,
                          faults=plan)
    _, sim = build_simulation(cfg, workers=N_WORKERS,
                              sequences=2 * N_WORKERS, seq_len=seq_len,
                              batch_menu=(1,), fed_cfg=fed_cfg)
    want = tree.launches(N_WORKERS) + 1           # + the dropout repair
    res, res2 = _run_twice(sim, rounds, "phase B", want, on_chip)
    del res
    state = res2.round_state
    wire = rd.WirePath(privacy=spec, tree=tree, faults=plan)
    launches = check_round_program(wire, state, N_WORKERS, want, on_chip)
    sizes = jnp.asarray(sim.sizes)
    out = {"launches": launches}
    for name, tr in (("tree", tree), ("flat", None)):
        out[name] = check_recovered_aggregate(spec, tr, plan, state, sizes,
                                              N_WORKERS, t=2 * rounds)
    say("phase B", round_launches=launches, tree_round=out["tree"],
        flat_round=out["flat"], aggregate="bitwise equal to survivors-only",
        peak_bytes_in_use=_peak_bytes())
    return out


def _compile_all_mosaic(jitted, *args, want: int, on_chip: bool):
    """AOT-compile ``jitted`` for ``args``; on the chip the program must
    hold at least ``want`` Mosaic kernels (one per Pallas launch)."""
    compiled = jitted.lower(*args).compile()
    calls = compiled.as_text().count("tpu_custom_call")
    if on_chip and calls < want:
        raise AssertionError(f"{calls} Mosaic kernels in the compiled "
                             f"program, fewer than its {want} launches")
    return compiled, calls


def phase_four_chip(cfg, *, rounds: int = 3, seq_len: int = SEQ_LEN,
                    on_chip: bool = True) -> dict:
    """Cross-silo: one FedPC worker per chip through ``build_fed_step`` on a
    (fed=4, model=1) mesh, ``fedpc_packed``. One sync against the
    single-device ``core.update.master_update_tree`` reference, worker
    placement, then ``rounds`` full fed steps."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core.goodness import select_pilot
    from repro.core.ternary import ternarize_tree
    from repro.core.update import master_update_tree
    from repro.fed.distributed import (build_fed_step, build_fed_sync,
                                       fed_state_init)
    from repro.launch.mesh import make_mesh

    devices = jax.devices()
    f = len(devices)
    mesh = make_mesh((f, 1), ("data", "model"), devices=devices)
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    per_fed = NamedSharding(mesh, P("data"))
    stack = lambda tree: jax.device_put(jax.tree_util.tree_map(
        lambda x: jnp.stack([x] * f), tree), per_fed)
    sizes = jnp.linspace(100.0, 175.0, f)
    with jax.set_mesh(mesh):
        state = fed_state_init(params, f)
        state["round"] = jnp.asarray(3, jnp.int32)      # Eq. (5) branch
        state["params_prev"] = jax.tree_util.tree_map(lambda x: x + 0.01,
                                                      params)
        state["prev_costs"] = jnp.ones((f,))
        params_f = jax.device_put(jax.tree_util.tree_map(
            lambda x: jnp.stack([x + 0.05 * (i + 1) for i in range(f)]),
            params), per_fed)
        leaf = jax.tree_util.tree_leaves(params_f)[0]
        slabs = {s.index[0].start: s.device.id
                 for s in leaf.addressable_shards}
        if sorted(slabs) != list(range(f)) or len(set(slabs.values())) != f:
            raise AssertionError(f"worker slabs not one per chip: {slabs}")
        costs = jnp.linspace(0.9, 0.5, f)
        sync, sync_calls = _compile_all_mosaic(
            jax.jit(build_fed_sync(m, mesh, "data", "fedpc_packed")),
            params_f, costs, sizes, state, want=2, on_chip=on_chip)
        got, _ = sync(params_f, costs, sizes, state)
        k_star, _ = select_pilot(costs, state["prev_costs"], sizes, 3)
        tern = jax.vmap(lambda q: ternarize_tree(
            q, state["params"], state["params_prev"], 0.2))(params_f)
        want = master_update_tree(
            jax.tree_util.tree_map(lambda x: x[k_star], params_f), tern,
            sizes / jnp.sum(sizes), jnp.full((f,), 0.2), k_star,
            state["params"], state["params_prev"], 3, 0.01)
        # tests/test_fed_distributed.py's bound: the packed wire is exact
        # and only the f32 order of the N-term Eq. (3) sum differs.
        diff = max(float(jnp.max(jnp.abs(a - b))) for a, b in zip(
            jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)))
        if diff >= 1e-5:
            raise AssertionError(f"sync off master_update_tree by {diff}")
        say("4 chips", sync_vs_master_update_tree_max_abs_diff=diff,
            worker_slab_devices=[slabs[i] for i in range(f)],
            mosaic_kernels_in_sync=sync_calls)

        # Pin the carried state's placement so every round runs the one
        # compiled program: public state replicated, worker state one slab
        # per chip (the compiled step refuses any other placement).
        rep = NamedSharding(mesh, P())
        st = jax.device_put(fed_state_init(params, f), rep)
        opt_f = stack(m.optimizer.init(params))
        batches = [{"tokens": jax.device_put(jax.random.randint(
            jax.random.PRNGKey(10 + r), (f, 1, 1, seq_len), 0, cfg.vocab),
            per_fed)} for r in range(rounds)]
        t0 = time.perf_counter()
        step, step_calls = _compile_all_mosaic(
            jax.jit(build_fed_step(m, mesh, "data", "fedpc_packed", lr=0.01),
                    in_shardings=(rep, per_fed, per_fed, rep),
                    out_shardings=(rep, per_fed, rep)),
            st, opt_f, batches[0], sizes, want=2, on_chip=on_chip)
        compile_s = time.perf_counter() - t0
        cost_hist, times = [], []
        for batch in batches:
            t0 = time.perf_counter()
            st, opt_f, metrics = step(st, opt_f, batch, sizes)
            cost_hist.append(float(metrics["cost_mean"]))
            times.append(time.perf_counter() - t0)
        if not np.all(np.isfinite(cost_hist)):
            raise AssertionError(f"non-finite fed-step costs {cost_hist}")
        opt_leaf = jax.tree_util.tree_leaves(opt_f)[0]
        opt_devs = {s.index[0].start: s.device.id
                    for s in opt_leaf.addressable_shards}
        if len(set(opt_devs.values())) != f:
            raise AssertionError(f"worker optimizer state not one per chip: "
                                 f"{opt_devs}")
        say("4 chips", rounds=rounds, costs=[round(c, 4) for c in cost_hist],
            mosaic_kernels_in_step=step_calls, compile_s=f"{compile_s:.3f}",
            first_call_s=f"{times[0]:.3f}",
            later_calls_s=" ".join(f"{x:.3f}" for x in times[1:]),
            note="smoke reading, not a benchmark",
            peak_bytes_in_use=_peak_bytes())
    return {"sync_max_abs_diff": diff, "costs": cost_hist}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip cross-silo sync")
    args = ap.parse_args(argv)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (platform {devices[0].platform!r}); "
              f"this check runs only on the chip", file=sys.stderr)
        return 2
    enable_compile_cache()
    if len(devices) != args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} devices", file=sys.stderr)
        return 2
    cfg = smoke_config()
    n_params = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(
        jax.eval_shape(build_model(cfg).init, jax.random.PRNGKey(0))))
    say("config", arch=ARCH, n_layers=cfg.n_layers, d_model=cfg.d_model,
        heads=f"{cfg.n_heads}x{cfg.resolved_head_dim}", vocab=cfg.vocab,
        params=n_params, workers=N_WORKERS, seq_len=SEQ_LEN,
        depth_reason="one mLSTM+sLSTM period: memory_analysis of the scan "
                     "program for a described v5e gives 11.8 GiB before "
                     "donation at one period, 19.1 GiB at two")
    if args.chips == 4:
        phase_four_chip(cfg)
    else:
        phase_a(cfg)
        phase_b(cfg)
    d = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
