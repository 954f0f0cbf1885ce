"""Serving launcher: broker-selected weight loading + batched generation.

Demonstrates the paper's mechanism on the *model distribution* path: the
checkpointed weights are replicated across the grid; a serving replica
brokers each weight-chunk read (rank = predicted bandwidth to *this*
host), then serves batched greedy generation with the reduced config.

  PYTHONPATH=src python -m repro.launch.serve --arch mistral-nemo-12b \
      --reduced --batch 4 --max-new 32
"""

from __future__ import annotations

import argparse
import json
import sys

import jax
import numpy as np

from repro.checkpoint.manager import CheckpointManager
from repro.configs.base import get_arch, list_archs
from repro.data.tokenizer import ByteTokenizer
from repro.models import transformer
from repro.serve.engine import ServeEngine
from repro.storage.endpoint import build_demo_grid


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True, choices=list_archs())
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--endpoints", type=int, default=6)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="dump the shared metrics registry (JSON + Prometheus "
                         "exposition) after serving")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="dump Chrome trace-event JSON (load in Perfetto)")
    args = ap.parse_args(argv)
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()

    cfg = get_arch(args.arch).reduced()
    rng = jax.random.PRNGKey(args.seed)
    params = transformer.init_params(cfg, rng)

    # publish weights onto the grid, then load them back through the broker
    grid = build_demo_grid(args.endpoints, 3, seed=args.seed)
    host = "client://serve-replica0"
    grid.add_client(host, zone="zone1")
    broker = grid.broker_for(host)
    # attach the broker's registry to every GRIS it polls: query counts and
    # TTL hit rates land in the same exposition as the broker's own series
    for ep in grid.endpoints.values():
        ep.gris.metrics = broker.metrics
    mgr = CheckpointManager(f"weights-{args.arch}", grid, broker,
                            replication=2, chunk_bytes=1 << 20)
    mgr.save(0, params)
    engine = ServeEngine.from_grid(
        cfg, mgr, 0, jax.eval_shape(lambda: params),
        max_seq=args.prompt_len + args.max_new + 8,
    )
    print(f"weights loaded via broker: {broker.stats['fetches']} fetches, "
          f"{broker.stats['failovers']} failovers, "
          f"{engine.selection_stats['batches']} batched selection launches "
          f"({engine.selection_stats['coalescing_ratio']:.1f}x coalescing)")
    tok = ByteTokenizer(cfg.vocab_size)
    rng_np = np.random.default_rng(args.seed)
    prompts = rng_np.integers(4, cfg.vocab_size, (args.batch, args.prompt_len)).astype(np.int32)

    extras = {}
    if cfg.family == "vlm":
        extras["patch_embeds"] = 0.01 * np.ones(
            (args.batch, cfg.n_patches, cfg.d_model), np.float32
        )
    if cfg.enc_dec:
        extras["frames"] = 0.01 * np.ones(
            (args.batch, cfg.enc_seq, cfg.d_model), np.float32
        )
    result = engine.generate(prompts, max_new=args.max_new, extras=extras or None)
    print(json.dumps({
        "arch": args.arch,
        "generated_tokens": int(result.n_generated.sum()),
        "prefill_s": round(result.prefill_s, 3),
        "decode_s": round(result.decode_s, 3),
        "decode_tok_per_s": round(result.decode_tokens_per_s, 1),
    }, indent=2))
    if args.metrics_out:
        broker.metrics.dump_json(args.metrics_out, extra={"arch": args.arch})
        print(f"metrics registry -> {args.metrics_out}")
    if args.trace_out:
        broker.tracer.dump_json(args.trace_out)
        print(f"chrome trace -> {args.trace_out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
