"""Compile a one-chip cell's round program for a described TPU v5e.

    JAX_PLATFORMS=cpu python3 bench/aot.py <cell> [<cell> ...]

Builds the cell's simulator on the CPU, then lowers the same multi-round
scan program that ``FedSimulator.run_fedpc_scan`` compiles (Mosaic wire
kernels, the workload's rounds per call) against shapes placed on a
described ``v5e`` chip, and prints the compiler's ``memory_analysis``.
Nothing runs: this says whether the program fits, never how fast it is.
"""
import os
import sys
from functools import partial
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def compile_cell(name: str) -> dict:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from bench.harness import gen, spec
    from bench.drivers.simulator import SimCell
    from repro.core import flat as fl
    from repro.fed import rounds as rd
    from repro.telemetry import record as tmr

    wl = spec.workload(name)
    cfg = spec.config(wl["config"])
    cell = SimCell(wl, cfg, seed=0)
    cell.build()
    sim = cell.sim
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)
    on_chip = lambda tree: jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype), tree)
    params = gen.spec_shapes(cell.spec)
    layout = fl.layout_of(params)
    fc = sim.fed_cfg
    wire = rd.WirePath(rd.WireConfig.from_fedpc(fc), privacy=fc.privacy,
                       tree=fc.tree, faults=fc.faults, interpret=False)
    n, rounds = sim.n, cell.rounds
    tel = on_chip(jax.eval_shape(tmr.TelemetryCarry.zero))
    state = rd.RoundState(sds((layout.rows, fl.LANES), jnp.float32),
                          sds((layout.rows, fl.LANES), jnp.float32),
                          sds((n,), jnp.float32), sds((), jnp.int32),
                          None, tel)
    carry = tuple((on_chip(jax.eval_shape(w.opt.init, params)),
                   sds((), jnp.int32)) for w in sim.workers)
    sched = tuple(sds((rounds, w.cfg.local_epochs
                       * w.loader.steps_per_epoch(), w.loader.batch_size),
                      jnp.int32) for w in sim.workers)
    shards = tuple((sds(w.loader.arrays[0].shape, jnp.int32),)
                   for w in sim.workers)
    args = (state, carry, sched, shards, None, sds((n,), jnp.float32),
            None, sds((), jnp.int32))
    compiled = jax.jit(partial(sim._scan_body, wire, layout, rounds),
                       donate_argnums=(0, 1)).lower(*args).compile()
    ma = compiled.memory_analysis()
    gib = 2 ** 30
    return {"cell": name, "params": layout.n,
            "argument_gib": ma.argument_size_in_bytes / gib,
            "output_gib": ma.output_size_in_bytes / gib,
            "temp_gib": ma.temp_size_in_bytes / gib,
            "alias_gib": ma.alias_size_in_bytes / gib,
            "mosaic_kernels": compiled.as_text().count("tpu_custom_call")}


def main(argv) -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    jax.config.update("jax_enable_compilation_cache", False)
    for name in argv:
        print(compile_cell(name), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
