#!/usr/bin/env python3
"""One point of the sweep for an open-loop cell's rate: a whole run of the
cell, as ``run.py`` makes it, at another offered rate.

Usage, from the root of a checkout, on the chip, one process per point::

    for r in 72 80 88; do
        python3 benchmarks/chip/sweep.py --workload fleet15k.restore_open \\
            --seed 3 --seconds 30 --rate $r
    done

A rate is sustained when the requests answered inside the window are at
least 99 % of those offered, so the queue grew by no more than 1 % of the
window's work. The rate a traffic file holds is 0.8 of the highest rate
that every seed tried sustained. One JSON line.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--rate", type=float, required=True, help="offered req/s")
    args = ap.parse_args(argv)
    sys.path[0] = str(ROOT)
    sys.path.insert(1, str(ROOT / "src"))
    from benchmarks.chip import harness

    cell = harness.load_cell(args.workload)
    if cell.traffic["loop"] != "open":
        print(f"sweep.py: {args.workload} is not an open-loop cell", file=sys.stderr)
        return 2
    try:
        harness.device_info(cell.chips)
    except harness.NoChip as e:
        print(f"sweep.py: {e}; not running", file=sys.stderr)
        return 1
    cell.traffic = dict(cell.traffic, rate_rps=args.rate)
    cell.end_to_end = [{"name": n, "unit": "ms"} for n in ("select_p50_ms", "select_p99_ms")]
    cell.end_to_end.append({"name": "select_rps", "unit": "req/s"})
    out = harness.run_cell(cell, args.seed, args.seconds, False, t_process=T_PROCESS)
    answered = out["metrics"]["select_rps"]["value"]
    print(json.dumps({
        "rate_rps": args.rate,
        "seed": args.seed,
        "sustained": answered >= 0.99 * args.rate,
        "answered_rps": answered,
        "p50_ms": out["metrics"]["select_p50_ms"]["value"],
        "p99_ms": out["metrics"]["select_p99_ms"]["value"],
        "backlog_at_end": out["notes"]["backlog_at_end"],
        "flushes": out["notes"]["flushes"],
        "correct": out["correct"],
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
