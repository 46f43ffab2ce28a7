"""Training launcher.

Two modes:
  * ``simulate``   — the paper's testbed: N in-process workers, any arch
                     (reduced by default), FedPC/FedAvg/Phong, synthetic LM
                     data. Runs anywhere.
  * ``distributed``— the TPU-mesh runtime: fed workers = slices of the mesh
                     'data' axis, sync through shard_map collectives
                     (fed/distributed.py), one mesh over the process's
                     own devices. On the CPU pass ``--devices 8`` to
                     emulate with host devices.

Examples:
  PYTHONPATH=src python -m repro.launch.train simulate --arch qwen3-14b \
      --workers 4 --rounds 20
  PYTHONPATH=src python -m repro.launch.train distributed --devices 8 \
      --fed-axis data --strategy fedpc_packed --rounds 5
"""
import argparse
import os
import sys


def build_simulation(cfg, *, workers: int, sequences: int, seq_len: int,
                     seed: int = 0, batch_menu=(16, 8), fed_cfg=None,
                     evade_streak: int = 0):
    """The simulator's whole set-up: config → model → SyntheticLM shards →
    workers with private hyper-parameters → ``FedSimulator``. Returns
    ``(model, sim)``; the launcher and ``chip_smoke.py`` both start here."""
    import jax
    import jax.numpy as jnp
    from repro.data.pipeline import BatchIterator
    from repro.data.synthetic import SyntheticLM, sequence_split
    from repro.fed.simulator import FedSimulator
    from repro.fed.worker import Worker, make_worker_configs
    from repro.models import build_model

    m = build_model(cfg)
    toks = SyntheticLM(n_sequences=sequences, seq_len=seq_len,
                       vocab=cfg.vocab, seed=seed).generate()
    splits = sequence_split(len(toks), workers, seed=seed)
    loss_fn = jax.jit(jax.value_and_grad(
        lambda p, b: m.loss(p, {"tokens": jnp.asarray(b[0])}), has_aux=True))
    wcfgs = make_worker_configs(workers, [len(s) for s in splits],
                                seed=seed, batch_menu=batch_menu)
    fleet = [Worker(cfg=wcfgs[k],
                    loader=BatchIterator((toks[splits[k]],),
                                         wcfgs[k].batch_size, seed=k),
                    loss_and_grad=loss_fn)
             for k in range(workers)]
    params = m.init(jax.random.PRNGKey(seed))
    return m, FedSimulator(fleet, params, fed_cfg=fed_cfg,
                           evade_streak=evade_streak)


def _simulate(args):
    from repro.configs import get_config
    from repro.checkpoint import save_checkpoint

    cfg = get_config(args.arch)
    if not args.full_size:
        cfg = cfg.reduced()
    _, sim = build_simulation(cfg, workers=args.workers,
                              sequences=args.sequences,
                              seq_len=args.seq_len, seed=args.seed,
                              evade_streak=args.evade_streak)
    res = getattr(sim, f"run_{args.algo}")(args.rounds)
    print(f"[train] {args.algo} on {cfg.name}: cost {res.costs[0]:.4f} -> "
          f"{res.costs[-1]:.4f}, bytes {res.total_bytes/1e6:.2f} MB")
    if args.ckpt:
        print("[train] saved:", save_checkpoint(
            args.ckpt, res.params, step=args.rounds,
            metadata={"arch": cfg.name, "algo": args.algo}))
    return 0


def _distributed(args):
    if args.devices:        # CPU emulation: that many virtual host devices
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.devices}")
    import jax
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.fed.distributed import build_fed_step, fed_state_init
    from repro.launch.mesh import make_mesh
    from repro.models import build_model

    devices = jax.devices()
    if len(devices) % args.fed_workers:
        raise SystemExit(f"{len(devices)} devices do not split into "
                         f"{args.fed_workers} fed workers")
    mesh = make_mesh((args.fed_workers, len(devices) // args.fed_workers),
                     ("data", "model"), devices=devices)
    cfg = get_config(args.arch)
    if not args.full_size:
        cfg = cfg.reduced()
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(args.seed))
    F = args.fed_workers
    state = fed_state_init(params, F)
    opt_F = jax.tree_util.tree_map(
        lambda x: jnp.stack([x] * F), m.optimizer.init(params))
    sizes = jnp.asarray([100.0 + 25 * k for k in range(F)])
    fed_step = jax.jit(build_fed_step(m, mesh, args.fed_axis, args.strategy,
                                      lr=args.lr))
    key = jax.random.PRNGKey(args.seed)
    with jax.set_mesh(mesh):
        for r in range(args.rounds):
            key, k2 = jax.random.split(key)
            batch_F = {"tokens": jax.random.randint(
                k2, (F, args.local_steps, args.local_batch, args.seq_len),
                0, cfg.vocab)}
            state, opt_F, metrics = fed_step(state, opt_F, batch_F, sizes)
            print(f"[train] round {r + 1}: cost={float(metrics['cost_mean']):.4f} "
                  f"pilot={int(metrics['k_star'])}")
    return 0


def main():
    ap = argparse.ArgumentParser()
    sub = ap.add_subparsers(dest="mode", required=True)

    sim = sub.add_parser("simulate")
    sim.add_argument("--arch", default="fedpc-paper")
    sim.add_argument("--algo", default="fedpc",
                     choices=["fedpc", "fedavg", "phong"])
    sim.add_argument("--workers", type=int, default=4)
    sim.add_argument("--rounds", type=int, default=10)
    sim.add_argument("--seq-len", type=int, default=64)
    sim.add_argument("--sequences", type=int, default=192)
    sim.add_argument("--seed", type=int, default=0)
    sim.add_argument("--evade-streak", type=int, default=0)
    sim.add_argument("--full-size", action="store_true")
    sim.add_argument("--ckpt", default=None)

    dist = sub.add_parser("distributed")
    dist.add_argument("--arch", default="fedpc-paper")
    dist.add_argument("--strategy", default="fedpc_packed",
                      choices=["fedpc", "fedpc_packed", "fedpc_reduce", "fedavg"])
    dist.add_argument("--devices", type=int, default=0,
                      help="CPU only: emulate this many host devices "
                           "(0 = the process's own jax.devices())")
    dist.add_argument("--fed-workers", type=int, default=4)
    dist.add_argument("--fed-axis", default="data")
    dist.add_argument("--rounds", type=int, default=3)
    dist.add_argument("--local-steps", type=int, default=2)
    dist.add_argument("--local-batch", type=int, default=2)
    dist.add_argument("--seq-len", type=int, default=32)
    dist.add_argument("--lr", type=float, default=0.02)
    dist.add_argument("--seed", type=int, default=0)
    dist.add_argument("--full-size", action="store_true")

    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    sys.exit(_simulate(args) if args.mode == "simulate"
             else _distributed(args))


if __name__ == "__main__":
    main()
