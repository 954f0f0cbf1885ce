#!/usr/bin/env python3
"""The chip benchmark of the broker's served selection path: one cell.

Usage, from the root of a checkout, on a machine with the chips the cell
asks for::

    python3 benchmarks/chip/run.py --workload fleet15k.restore_open \\
        --seed 7 --seconds 30 --trace 0

``--trace 0`` prints the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics, read from the broker's spans and counters and from a
profiler trace of the window. The last line of standard output is one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics``, ``device``
(and with ``--trace 1`` ``breakdown``), and last ``checks``, each number
compared with the plain reference beside its limit; the same numbers are
the last lines of standard error.

It exits 1, printing no result, when JAX finds no TPU or fewer chips than
the cell asks for, and 2 when the program (``src/repro``) is not beside it.
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
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", metavar="PATH",
                    help="with --trace 1, also write the trace's events as JSON here")
    args = ap.parse_args(argv)

    # the benchmark's package, not its directory: its module names must not
    # shadow the standard library's
    sys.path[0] = str(ROOT)
    sys.path.insert(1, str(ROOT / "src"))
    try:
        import repro  # noqa: F401
    except ImportError as e:
        print(f"run.py: the program (src/repro) is not in this checkout: {e}", file=sys.stderr)
        return 2
    from benchmarks.chip import harness

    cell = harness.load_cell(args.workload)
    try:
        device = harness.device_info(cell.chips)
    except harness.NoChip as e:
        print(f"run.py: {e}; not running", file=sys.stderr)
        return 1
    print(f"device: platform {device['platform']}, kind {device['kind']}, count {device['count']}")
    out = harness.run_cell(
        cell, args.seed, args.seconds, bool(args.trace), t_process=T_PROCESS,
        keep_trace=args.keep_trace,
    )
    notes = out.pop("notes")
    print("notes: " + json.dumps(notes))
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})", file=sys.stderr)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
