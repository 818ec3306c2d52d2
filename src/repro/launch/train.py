"""Training launcher.

One device:
    PYTHONPATH=src python -m repro.launch.train --arch llama3.2-3b \
        --reduced --steps 100 --batch 8 --seq 128 --connection fal

Sharded: add ``--mesh MODEL`` to run the sharded train step over every
device present, as a (data, model) mesh with MODEL devices on the model
axis (``--mesh 4 --tp explicit`` on a four-chip host).  The production
16x16 / 2x16x16 shapes are compiled, not run, by ``launch/dryrun.py``.
"""
import argparse


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gpt2-117m")
    ap.add_argument("--connection", default=None,
                    help="preln|parallel|fal|falplus (default: config's)")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--schedule", default="cosine",
                    choices=["cosine", "onecycle", "wsd"])
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--mesh", type=int, default=None, metavar="MODEL",
                    help="shard over every device present: a (data, model) "
                         "mesh with MODEL devices on the model axis")
    ap.add_argument("--tp", default="gspmd", choices=["gspmd", "explicit"],
                    help="with --mesh: explicit = shard_map partial-sum TP "
                         "stack (the paper's per-block collective structure)")
    ap.add_argument("--sp", action="store_true",
                    help="with --tp explicit: Megatron-SP sequence-parallel "
                         "LN regions — inter-block activations sharded over "
                         "the model axis, reduce-scatter/all-gather pairs "
                         "instead of all-reduces (1/tp the reduce bytes)")
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    from repro.configs.base import get_config
    from repro.core.plan import ExecutionPlan
    from repro.launch import mesh as MX
    from repro.launch.compile_cache import enable_compile_cache
    from repro.train import trainer

    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if args.connection:
        cfg = cfg.replace(connection=args.connection)

    if args.tp == "explicit" and not args.mesh:
        raise ValueError("--tp explicit requires --mesh (the explicit-TP "
                         "stack shards over the device mesh)")
    if args.sp and (args.tp != "explicit" or not args.mesh):
        raise ValueError("--sp requires --mesh and --tp explicit "
                         "(sequence-parallel LN regions live inside the "
                         "explicit partial-sum shard_map stack)")
    if args.mesh:
        mesh = MX.make_host_mesh(args.mesh)
        plan = ExecutionPlan.from_mesh(mesh, tp=args.tp, sp=args.sp,
                                       model_axis=MX.MODEL)
    else:
        plan = ExecutionPlan.single_device()
    plan.validate(cfg)   # loud errors before any tracing

    print(f"training {cfg.arch_id} connection={cfg.connection} "
          f"layers={cfg.n_layers} d={cfg.d_model} tp={plan.tp.value} "
          f"sp={plan.sequence_parallel}", flush=True)
    state, hist = trainer.train(
        cfg, steps=args.steps, batch=args.batch, seq_len=args.seq,
        lr=args.lr, seed=args.seed, plan=plan,
        num_microbatches=args.microbatches, schedule=args.schedule,
        ckpt_dir=args.ckpt)
    print(f"final loss {hist[-1]['loss']:.4f}")


if __name__ == "__main__":
    main()
