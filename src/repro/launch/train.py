"""Training launcher: `python -m repro.launch.train --arch paper_lm ...`

Thin CLI over repro.train.loop — builds the RawArray dataset if absent,
constructs the model + loader, runs the fault-tolerant loop (auto-resume).
For the multi-chip production meshes, combine with the sharded step
factories in repro.distributed.steps (see launch/dryrun.py for the AOT
path; this driver targets the hardware actually present).
"""

from __future__ import annotations

import argparse
import os


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--arch", default="paper_lm")
    p.add_argument("--steps", type=int, default=200)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--workdir", default="runs/train")
    p.add_argument("--dataset", default=None, help="existing RaDataset dir")
    p.add_argument("--lr", type=float, default=1e-3)
    p.add_argument("--ckpt-every", type=int, default=50)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--fresh", action="store_true")
    p.add_argument(
        "--device-feed", action="store_true",
        help="wrap the loader in DeviceLoader (DESIGN.md §12): keep "
             "RA_DEVICE_BUFS batches resident on device, overlapping host "
             "read + H2D with the train step; quantized fields decode "
             "on-device via the fused Pallas kernel",
    )
    p.add_argument(
        "--device-bufs", type=int, default=None,
        help="device-resident batch depth (default: RA_DEVICE_BUFS or 2)",
    )
    p.add_argument(
        "--mesh-hosts", default=None,
        help="data-mesh membership (DESIGN.md §15): comma-separated host "
             "names, one jax process per host, listed in process-index "
             "order (default: RA_MESH_HOSTS)",
    )
    p.add_argument(
        "--mesh-host", default=None,
        help="this process's mesh host name (default: RA_MESH_HOST)",
    )
    args = p.parse_args(argv)

    from repro.configs import get_config
    from repro.data import DataLoader, RaDataset, make_token_dataset
    from repro.distributed.optimizer import AdamWConfig
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models import build_model
    from repro.train import TrainLoopConfig, train

    enable_compile_cache()
    cfg = get_config(args.arch)
    os.makedirs(args.workdir, exist_ok=True)
    ds_root = args.dataset or os.path.join(args.workdir, "dataset")
    if not os.path.exists(os.path.join(ds_root, "manifest.json")):
        # shard_rows small enough that a mesh has shards to deal out
        make_token_dataset(ds_root, n_docs=2048, seq_len=min(256, cfg.max_seq),
                           vocab=cfg.vocab, shard_rows=256)
    # data mesh (DESIGN.md §15): shard-ownership ingest across jax processes
    mesh = None
    if args.mesh_hosts or args.mesh_host:
        from repro.distributed.data_mesh import DataMesh

        names = [h.strip() for h in (args.mesh_hosts or "").split(",") if h.strip()]
        if not names or not args.mesh_host:
            p.error("--mesh-hosts and --mesh-host must be given together")
        mesh = DataMesh(args.mesh_host, names)
    else:
        from repro.distributed.data_mesh import DataMesh

        mesh = DataMesh.from_env()  # RA_MESH_HOSTS / RA_MESH_HOST, else None
    loader = DataLoader(RaDataset(ds_root), args.batch, seed=args.seed, mesh=mesh)
    if args.device_feed:
        from repro.data import DeviceLoader

        loader = DeviceLoader(loader, bufs=args.device_bufs)
    out = train(
        build_model(cfg),
        loader,
        TrainLoopConfig(
            steps=args.steps,
            ckpt_every=args.ckpt_every,
            ckpt_dir=os.path.join(args.workdir, "ckpt"),
            adamw=AdamWConfig(lr=args.lr, warmup_steps=20, total_steps=max(args.steps, 200),
                              moment_dtype=cfg.opt_moment_dtype),
        ),
        resume=not args.fresh,
    )
    print(f"done: steps={out['steps']} wall={out['wall_s']:.1f}s preempted={out['preempted']}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
