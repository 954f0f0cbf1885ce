#!/usr/bin/env python3
"""Chip smoke: the broker's batched selection path on one TPU chip.

Runs :func:`repro.launch.smoke.run_smoke` — a grid of 10,000 storage
endpoints (``--endpoints``), 4,096 files of 3 replicas, one warm-up flush
and four size flushes of 64 kernel-tier requests through
``BatchScheduler`` → ``DataBroker.select_many`` → the compiled matchrank
kernel — and checks that every request took ``batched_kernel``, that the
tier-1 program holds a Mosaic ``tpu_custom_call``, and that every ranking
equals the paper-faithful interpreter's. A second phase publishes
per-source transfer history, site averages and circuit breakers, then
flushes four batches of 64 requests sent with no ad (the broker's default
read ad, with its guarded clauses and fallback rank chain) through the
same compiled kernel, with the same check, and requires each branch of
the rank chain to rank at least 10 % of the rows.

Usage (from the checkout's root, on a machine with a TPU)::

    python3 chip_smoke.py [--endpoints N] [--seed S]

It exits non-zero, printing no result, when JAX finds no TPU or when a
check fails. On success the last line of standard output is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``.
Everything runs in this one process.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--endpoints", type=int, default=10_000,
                    help="storage endpoints in the grid (device snapshot rows)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    src = Path(__file__).resolve().parent / "src"
    sys.path.insert(0, str(src))
    try:
        from repro.launch.cache import enable_compile_cache
        from repro.launch.smoke import run_smoke
    except ImportError as e:
        print(f"chip_smoke: the repro package is not beside this script: {e}",
              file=sys.stderr)
        return 2

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform}); not running",
              file=sys.stderr)
        return 1
    print(f"compile cache: {enable_compile_cache()}")

    report = run_smoke(endpoints=args.endpoints, seed=args.seed)
    for line in report.lines():
        print(line)
    for m in report.mismatches[:10]:
        print(f"  mismatch: {m}")
    if not (report.ok and report.mosaic):
        print("chip_smoke: FAILED", file=sys.stderr)
        return 1
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
