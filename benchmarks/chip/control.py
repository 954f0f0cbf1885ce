#!/usr/bin/env python3
"""The readings the check's limits are set from: sound runs of a cell and
runs of its control, at the cell's own size and window, in one process.

Usage, from the root of a checkout, on the chip::

    python3 benchmarks/chip/control.py --workload fleet15k.restore_open \\
        --seconds 30 --sound 11,12,13 --bf16 21,22,23

``--sound`` seeds run the program as the configuration states it.
``--bf16`` seeds run the control: the same program with every score the
kernel returns rounded to bfloat16 before the ranked list is assembled,
the precision step below the f32 ranks the configurations state; the
check has to read not correct there. One JSON line per run with the
numbers compared. The compiled programs of the first run serve the rest,
so only the first set-up is long.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]


def bf16_scores(rig) -> None:
    """The control: the kernel's scores rounded to bfloat16 where the
    broker turns them into ranked lists."""
    import ml_dtypes
    import numpy as np

    orig = rig.broker._ranked_from_scores

    def lower(lfn, replicas, st, mask, score):
        score = np.asarray(score, np.float32).astype(ml_dtypes.bfloat16).astype(np.float32)
        return orig(lfn, replicas, st, mask, score)

    rig.broker._ranked_from_scores = lower


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--sound", default="", help="comma-separated seeds")
    ap.add_argument("--bf16", default="", help="comma-separated seeds")
    args = ap.parse_args(argv)
    sys.path[0] = str(ROOT)
    sys.path.insert(1, str(ROOT / "src"))
    from benchmarks.chip import harness

    cell = harness.load_cell(args.workload)
    try:
        harness.device_info(cell.chips)
    except harness.NoChip as e:
        print(f"control.py: {e}; not running", file=sys.stderr)
        return 1
    harness.enable_compile_cache()
    counter = harness.CompileCounter()
    runs = [("sound", int(s)) for s in args.sound.split(",") if s]
    runs += [("bf16", int(s)) for s in args.bf16.split(",") if s]
    for kind, seed in runs:
        t0 = time.perf_counter()
        out = harness.run_cell(
            cell, seed, args.seconds, False, t_process=t0, counter=counter,
            fault=bf16_scores if kind == "bf16" else None,
        )
        print(json.dumps({
            "run": kind, "seed": seed, "correct": out["correct"],
            "attempted": out["attempted"], "checks": out["checks"],
            "setup_s": out["metrics"]["setup_s"]["value"],
            "compiles_in_window": out["notes"]["compiles_in_window"],
            "policy_refused_pct": out["notes"]["policy_refused_pct"],
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
