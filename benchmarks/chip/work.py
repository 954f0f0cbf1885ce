"""The work of one matchrank launch, counted from the problem and not from
the implementation, and the chip's peaks to hold it against.

For a launch of B requests over S live snapshot rows:

* operations: ``S * sum_b (live terms_b + 2 * live rank weights_b)`` (a
  compare per threshold term; a multiply and an add per rank weight);
* bytes: the live attribute columns (those any request of the batch
  references), values and validity as stored (f32 each), read once, plus
  the answer (an f32 score and an i32 row per request).

No lane padding of the attribute axis, no ``[B, S]`` intermediate and no
per-request re-read is counted, so no implementation can read above the
roofline, and today's padding and re-reads show as a low share.
"""

from __future__ import annotations

import json
from pathlib import Path
from typing import Any, Dict, Iterable, Tuple

HERE = Path(__file__).resolve().parent

CELL_BYTES = 4  # f32 value and f32 validity, as the snapshot stores them
ANSWER_BYTES = 8  # f32 score + i32 row per request


class UnknownDevice(LookupError):
    pass


def peaks(device_kind: str) -> Dict[str, Any]:
    """The peaks of a device kind; an unknown kind is an error."""
    with open(HERE / "peaks.json") as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise UnknownDevice(f"no peaks for device kind {device_kind!r} in peaks.json")
    return table[device_kind]


def template_attrs(template: Dict[str, Any]) -> Tuple[set, int, int]:
    """→ (referenced attributes, live terms, live rank weights)."""
    terms = template["requirements"]
    rank = template["rank"]
    weights = [a for a, w in rank.items() if float(w) != 0] if "quotient" not in rank else list(rank["quotient"])
    attrs = {a.lower() for a, _, _ in terms} | {a.lower() for a in weights}
    return attrs, len(terms), len(weights)


def launch_work(templates: Iterable[Dict[str, Any]], rows: int) -> Tuple[float, float]:
    """→ (operations, bytes) of one launch of these requests over ``rows``."""
    cols: set = set()
    ops = 0
    b = 0
    for tpl in templates:
        attrs, terms, weights = template_attrs(tpl)
        cols |= attrs
        ops += rows * (terms + 2 * weights)
        b += 1
    nbytes = rows * len(cols) * 2 * CELL_BYTES + b * ANSWER_BYTES
    return float(ops), float(nbytes)


def roofline_share(ops: float, nbytes: float, seconds: float, peak: Dict[str, Any]) -> Tuple[float, str]:
    """→ (share of the roofline in %, the bound: "compute" or "memory")."""
    t_ops = ops / float(peak["flops_per_s"])
    t_mem = nbytes / float(peak["hbm_bytes_per_s"])
    bound = "compute" if t_ops >= t_mem else "memory"
    return 100.0 * max(t_ops, t_mem) / seconds, bound
