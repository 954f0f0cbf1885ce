"""The plain reference: two-sided matchmaking of one request, written out
from the paper's semantics over the benchmark's own record of the grid.

It imports nothing of the program and reads nothing the program made. The
GRIS state it matches against is the one the benchmark drew for the TTL
epoch in which the serving snapshot had to be built (see
:func:`snapshot_epochs`); a stale snapshot therefore gives wrong answers.

Semantics (``repro.core.matchmaker`` and the paper's section 5.1.2):

* the request's requirements are a conjunction of ``other.attr OP value``
  thresholds over the endpoint's published attributes;
* a site with a usage policy admits the request only when the policy
  (``other.reqdSpace <= 10G``, read on the request) holds: two-sided match;
* the rank is the request's linear rank over the endpoint's attributes, or
  the default read ad's ``diskTransferRate / (1 + loadFactor)`` (no
  transfer history exists, so its history clauses are Undefined and fall
  through);
* matched replicas are ordered by rank, descending, ties by host name.
"""

from __future__ import annotations

import operator
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

_OPS = {
    "<": operator.lt,
    "<=": operator.le,
    ">": operator.gt,
    ">=": operator.ge,
    "==": operator.eq,
    "!=": operator.ne,
}

#: relative rank difference under which two ranks count as tied (f32 rounding)
RANK_RTOL = 1e-6


def snapshot_epochs(flush_clocks: Sequence[float], ttl: float,
                    epoch_len: Optional[float] = None) -> List[int]:
    """The GRIS epoch each flush must serve, by the freshness guarantee: the
    snapshot set-up builds at clock 0 serves until a flush comes ``ttl`` or
    more after it, and that flush rebuilds it from the state then published."""
    epoch_len = ttl if epoch_len is None else epoch_len
    out = []
    b = 0.0
    for t in flush_clocks:
        if t - b >= ttl:
            b = t
        out.append(int(np.floor(b / epoch_len)))
    return out


def endpoint_attrs(world, epoch: int, eps: np.ndarray) -> Dict[str, np.ndarray]:
    """The attributes a set of endpoints publish in an epoch (lower-cased)."""
    free, load = world.state(epoch)
    return {
        "availablespace": free[eps].astype(np.float64),
        "totalspace": free[eps].astype(np.float64),
        "loadfactor": load[eps].astype(np.float64),
        "disktransferrate": world.disk_rate[eps].astype(np.float64),
    }


def answer(world, template: Dict[str, Any], reqd_space: int, file: int,
           epoch: int) -> Tuple[List[Tuple[str, float]], int]:
    """→ (the ranked replicas [(endpoint url, rank)] of one request, the
    replicas that meet its requirements and that a site policy refuses)."""
    eps = np.asarray(world.replicas[file])
    attrs = endpoint_attrs(world, epoch, eps)
    ok = np.ones(len(eps), bool)
    for attr, op, value in template["requirements"]:
        ok &= _OPS[op](attrs[attr.lower()], float(value))
    pol = world.config["usage_policy"]
    admits = _OPS[pol["op"]](float(reqd_space), float(pol["value"]))
    refused = int(np.count_nonzero(ok & world.has_policy[eps])) if not admits else 0
    ok &= ~world.has_policy[eps] | admits
    rank_spec = template["rank"]
    if "quotient" in rank_spec:
        num, den = (a.lower() for a in rank_spec["quotient"])
        rank = attrs[num] / (1.0 + attrs[den])
    else:
        rank = np.zeros(len(eps))
        for attr, w in rank_spec.items():
            rank = rank + float(w) * attrs[attr.lower()]
    out = [(world.urls[e], float(r)) for e, r, m in zip(eps.tolist(), rank, ok) if m]
    out.sort(key=lambda ur: (-ur[1], ur[0]))
    return out, refused


def compare(served: Tuple[str, List[Tuple[str, float]]],
            want: List[Tuple[str, float]]) -> Tuple[bool, float]:
    """→ (same ranked list, largest relative rank gap) of one request.

    ``served`` is ("ok", [(url, rank)]) or (error name, []). An empty
    reference answer is a NoMatchError. Replicas whose reference ranks lie
    within ``RANK_RTOL`` of each other form one tie group: the groups'
    order and membership must match. Inside a group f32 rounding may order
    the served ranks either way, so the group's served entries must be in
    the order of their served ranks, and equal served ranks in host-name
    order (the stated tie-break)."""
    kind, got = served
    if not want:
        return kind == "NoMatchError", 0.0
    if kind != "ok" or len(got) != len(want):
        return False, 0.0
    ref_rank = dict(want)
    gap = 0.0
    for url, r in got:
        if url in ref_rank:
            ref = ref_rank[url]
            gap = max(gap, abs(r - ref) / max(abs(ref), 1.0))
    same = True
    start = 0
    while start < len(want):
        end = start + 1
        while end < len(want) and abs(want[end][1] - want[end - 1][1]) <= RANK_RTOL * max(
            abs(want[end - 1][1]), 1.0
        ):
            end += 1
        group = got[start:end]
        if {u for u, _ in want[start:end]} != {u for u, _ in group}:
            same = False
        elif group != sorted(group, key=lambda ur: (-ur[1], ur[0])):
            same = False
        start = end
    return same, gap
