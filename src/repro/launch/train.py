"""Production training launcher.

Assembles the full stack for one host of a (multi-pod) training job:

  simulated data grid (or a real one behind the same interfaces)
  → replicated dataset shards (broker-selected on every fetch)
  → fault-tolerant TrainLoop (checkpoint/restart, straggler monitor,
    chaos schedule if requested)
  → per-arch config from the registry, reduced or full.

On this CPU container the full production meshes only *lower* (see
dryrun.py); ``--reduced`` runs a real training loop end to end. The same
launcher drives both, which is the point: config, data plane and loop are
identical, only the mesh axis sizes change.

  PYTHONPATH=src python -m repro.launch.train --arch granite-moe-3b-a800m \
      --reduced --steps 100 --batch 8 --seq 128 --chaos
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import Optional

from repro.checkpoint.manager import CheckpointManager
from repro.configs.base import get_arch, list_archs
from repro.data.datasets import ShardManifest, SyntheticCorpus, materialize_on_grid
from repro.data.pipeline import BatchSpec, DataPipeline
from repro.storage.endpoint import build_demo_grid
from repro.storage.faults import FaultInjector
from repro.train.loop import LoopConfig, TrainLoop
from repro.train.optim import AdamWConfig
from repro.train.train_step import TrainConfig


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True, choices=list_archs())
    ap.add_argument("--reduced", action="store_true",
                    help="train the reduced config (CPU-feasible)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--grad-compression", action="store_true")
    ap.add_argument("--int8-moments", action="store_true")
    ap.add_argument("--endpoints", type=int, default=8)
    ap.add_argument("--replication", type=int, default=2)
    ap.add_argument("--shards", type=int, default=16)
    ap.add_argument("--checkpoint-every", type=int, default=50)
    ap.add_argument("--async-checkpoint", action="store_true")
    ap.add_argument("--chaos", action="store_true",
                    help="schedule random endpoint kills/degradations")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--metrics-out", default=None)
    args = ap.parse_args(argv)
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    elif cfg.param_counts()["total"] > 5e8:
        print(
            f"WARNING: {args.arch} full config on CPU — use --reduced "
            "(full configs are exercised via launch.dryrun)",
            file=sys.stderr,
        )

    # --- the data grid ---
    grid = build_demo_grid(args.endpoints, max(args.endpoints // 2, 2), seed=args.seed)
    host = "client://train-host0"
    grid.add_client(host, zone="zone0")
    manifest = ShardManifest(
        f"{args.arch}-corpus", args.shards, tokens_per_shard=50_000,
        vocab_size=cfg.vocab_size, seed=args.seed,
    )
    materialize_on_grid(SyntheticCorpus(manifest), grid, replication=args.replication)

    pipeline = DataPipeline(
        host, 0, 1, grid, manifest, BatchSpec(args.batch, args.seq)
    )
    broker = grid.broker_for(host)
    ckpt = CheckpointManager(f"run-{args.arch}", grid, broker,
                             replication=args.replication, chunk_bytes=1 << 20)

    faults: Optional[FaultInjector] = None
    if args.chaos:
        faults = FaultInjector(grid)
        n = faults.chaos(horizon=3600.0, mtbf=600.0, mttr=120.0, seed=args.seed)
        print(f"chaos: scheduled {n} fault events")

    tc = TrainConfig(
        optimizer=AdamWConfig(
            lr=args.lr,
            moments_dtype="int8" if args.int8_moments else "float32",
        ),
        n_microbatches=args.microbatches,
        warmup_steps=max(args.steps // 20, 1),
        total_steps=args.steps,
        grad_compression=args.grad_compression,
    )
    lc = LoopConfig(
        total_steps=args.steps,
        checkpoint_every=args.checkpoint_every,
        log_every=max(args.steps // 20, 1),
        async_checkpoint=args.async_checkpoint,
        repair_every=args.checkpoint_every * 2 if args.chaos else 0,
    )
    loop = TrainLoop(cfg, tc, lc, pipeline, ckpt, faults=faults, rng_seed=args.seed)
    loop.run()

    losses = loop.losses()
    summary = {
        "arch": args.arch,
        "steps": len(losses),
        "loss_first": losses[0] if losses else None,
        "loss_last": losses[-1] if losses else None,
        "events": loop.events[-20:],
        "pipeline": pipeline.stats,
        "broker": broker.stats,
        "checkpoint": ckpt.stats,
        "fleet": loop.monitor.fleet_summary(),
    }
    print(json.dumps(summary, indent=2, default=str))
    if args.metrics_out:
        with open(args.metrics_out, "w") as f:
            json.dump({"summary": summary, "losses": losses}, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
