"""Training entrypoint.

Two modes:
* direct  — run the trainer locally (one cluster's worth of work)
* lidc    — express the job as a named Interest into a multi-cluster
            overlay and let the network place it (the paper's workflow)

    PYTHONPATH=src python -m repro.launch.train --arch lidc-demo --steps 20
    PYTHONPATH=src python -m repro.launch.train --arch qwen2-0.5b \
        --steps 10 --via-lidc --clusters 3
"""

from __future__ import annotations

import argparse
import json


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="lidc-demo")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced same-family config")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--remat", default="none",
                    choices=["none", "dots", "full"])
    ap.add_argument("--microbatch", type=int, default=1)
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--lake-dir", default=None,
                    help="directory-backed data lake (persists checkpoints)")
    ap.add_argument("--run-name", default=None)
    ap.add_argument("--via-lidc", action="store_true",
                    help="submit through the LIDC overlay instead of local")
    ap.add_argument("--clusters", type=int, default=3)
    ap.add_argument("--chips", type=int, default=8)
    args = ap.parse_args()

    from .compile_cache import configure_compile_cache
    configure_compile_cache()
    from ..configs.base import get_config, smoke_of
    cfg = smoke_of(args.arch) if args.smoke else get_config(args.arch)

    if args.via_lidc:
        from ..runtime.fleet import build_fleet
        sys_ = build_fleet(n_clusters=args.clusters, chips=max(args.chips, 8),
                           archs=[cfg.arch_id] if not args.smoke else [],
                           ckpt_every=args.ckpt_every)
        fields = {"app": "train", "arch": cfg.arch_id, "shape": "custom",
                  "chips": args.chips, "steps": args.steps}
        print(f"submitting {fields} into a {args.clusters}-cluster overlay")
        handle = sys_.client.run_job(fields)
        if handle is None:
            raise SystemExit("no cluster answered")
        print("state:", handle.state)
        print(json.dumps(handle.result or {}, indent=1, default=str))
        if handle.state != "Completed":
            raise SystemExit(f"job {handle.state}: {handle.error}")
        return

    from ..datalake import DataLake, DirStore
    from ..train.trainer import run_training
    lake = DataLake(store=DirStore(args.lake_dir)) if args.lake_dir \
        else DataLake()
    run_name = args.run_name or f"cli-{cfg.arch_id}"
    res = run_training(cfg, steps=args.steps, batch=args.batch, seq=args.seq,
                       lake=lake, run_name=run_name,
                       ckpt_every=args.ckpt_every, lr=args.lr,
                       remat=args.remat, microbatch=args.microbatch,
                       on_step=lambda s, l: print(f"step {s:5d} loss {l:.4f}"))
    print(f"done: {res.steps_done} steps, final loss {res.final_loss:.4f}, "
          f"{res.wall_time:.1f}s" + (f", resumed from {res.resumed_from}"
                                     if res.resumed_from else ""))


if __name__ == "__main__":
    main()
