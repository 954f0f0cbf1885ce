"""Rank-order sparse top-k: the CPU steady-state fast path.

The dense batched dispatch touches every (request, candidate) pair, so a
B=64 × S=10k launch is bound by elementwise throughput no matter how the
arithmetic is arranged. In the steady state the broker answers *top-k*
selections against a snapshot that changes once per GRIS epoch — so the
candidate rows can be pre-sorted by rank score once per (snapshot,
rank-weights) pair and each request answered by scanning candidates in
rank-descending order until k rows pass its requirements. Expected probes
per request ≈ k / selectivity, independent of S.

Two host-side pieces:

* :func:`canonicalize_plans` folds a conjunctive-threshold
  :class:`~repro.kernels.matchrank.ops.KernelPlan` batch into per-column
  ``[lo, hi]`` intervals (strict ops via f32 ``nextafter``, ``==`` as a
  point interval). ``!=`` terms are not interval-shaped → returns None
  and the caller falls back to the dense path.
* :func:`topk_in_rank_order` walks candidates in cached rank order in
  chunks, testing the whole request batch against each chunk at once.

Ties (equal f32 scores) resolve to the lowest candidate index — the same
order ``lax.top_k`` and the kernel's carry merge produce — because the
order is a *stable* argsort of the negated scores.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, List, Optional, Sequence, Tuple

import numpy as np

from repro.core.compile import OPCODES

__all__ = ["IntervalBatch", "canonicalize_plans", "rank_scores", "topk_in_rank_order"]

_OP_LT = OPCODES["<"]
_OP_LE = OPCODES["<="]
_OP_GT = OPCODES[">"]
_OP_GE = OPCODES[">="]
_OP_EQ = OPCODES["=="]
_OP_NE = OPCODES["!="]

_F32_INF = np.float32(np.inf)


@dataclass(frozen=True)
class IntervalBatch:
    """B conjunctive plans canonicalized to per-column intervals: request
    b admits row s iff for every used column c,
    ``valid[s,c] and lo[b,c] <= attrs[s,c] <= hi[b,c]``.

    ``undef_rank[b]`` marks plans whose rank references an attribute
    outside the vocabulary (lowered as weight on the padding column):
    Condor's convention makes that rank 0.0 for *every* candidate."""

    lo: np.ndarray  # [B, A] f32
    hi: np.ndarray  # [B, A] f32
    used: np.ndarray  # [B, A] bool
    weights: np.ndarray  # [B, A] f32 (logical width, padding trimmed)
    bias: np.ndarray  # [B] f32
    undef_rank: np.ndarray  # [B] bool

    @property
    def b(self) -> int:
        return self.lo.shape[0]

    @property
    def n_attrs(self) -> int:
        return self.lo.shape[1]


def _above(v: np.float32) -> np.float32:
    """Smallest f32 strictly greater than v (x > v  ⟺  x >= _above(v))."""
    return np.nextafter(np.float32(v), _F32_INF)


def _below(v: np.float32) -> np.float32:
    return np.nextafter(np.float32(v), -_F32_INF)


def _plan_interval(plan, n_attrs: int):
    """Per-plan interval fold, memoized on the plan object (plans are
    shared across calls via the PlanCache, so the Python term walk is
    paid once per distinct request shape). Returns None for ``!=``, and
    for a plan that is not :attr:`~.ops.KernelPlan.plain` — terms that
    Undefined passes, gated rank alternatives or a quotient rank — which
    neither intervals nor one rank order per epoch can answer."""
    cached = getattr(plan, "_interval_cache", None)
    if cached is not None and cached[0] == n_attrs:
        return cached[1]
    lo = np.full((n_attrs,), -np.inf, dtype=np.float32)
    hi = np.full((n_attrs,), np.inf, dtype=np.float32)
    used = np.zeros((n_attrs,), dtype=bool)
    result = None
    active = np.asarray(plan.term_role) > 0.5
    sel = np.asarray(plan.sel)
    ops = np.asarray(plan.op_codes)
    thr = np.asarray(plan.thresholds, dtype=np.float32)
    ok = plan.plain
    for t in range(sel.shape[0] if ok else 0):
        if not active[t]:
            continue
        c = int(sel[t].argmax())
        if sel[t, c] <= 0.0:
            continue
        op, v = int(ops[t]), np.float32(thr[t])
        if c >= n_attrs or (op == _OP_LT and v == -_F32_INF):
            # always-false term (absent requirement attribute):
            # empty interval on column 0 ⇒ the request never matches
            lo[0], hi[0] = np.inf, -np.inf
            used[0] = True
            continue
        if op == _OP_GT:
            lo[c] = max(lo[c], _above(v))
        elif op == _OP_GE:
            lo[c] = max(lo[c], v)
        elif op == _OP_LT:
            hi[c] = min(hi[c], _below(v))
        elif op == _OP_LE:
            hi[c] = min(hi[c], v)
        elif op == _OP_EQ:
            lo[c] = max(lo[c], v)
            hi[c] = min(hi[c], v)
        else:  # != is not an interval
            ok = False
            break
        used[c] = True
    if ok:
        w_full = np.asarray(plan.weights[0], dtype=np.float32)
        # weight on a padding column = rank references an out-of-vocabulary
        # attribute ⇒ rank Undefined ⇒ 0.0 for every candidate
        undef = bool((w_full[n_attrs:] != 0).any())
        bias = np.float32(plan.bias[0])
        result = (lo, hi, used, w_full[:n_attrs], bias, undef)
    try:
        plan._interval_cache = (n_attrs, result)
    except AttributeError:  # pragma: no cover - exotic plan types
        pass
    return result


def canonicalize_plans(plans: Sequence, n_attrs: int) -> Optional[IntervalBatch]:
    """Fold each plan's active threshold terms into [lo, hi] intervals.

    Returns None when any plan falls outside the interval subset (a ``!=``
    term) — semantics the caller must then get from the dense path.
    """
    parts = [_plan_interval(p, n_attrs) for p in plans]
    if any(p is None for p in parts):
        return None
    return IntervalBatch(
        lo=np.stack([p[0] for p in parts]),
        hi=np.stack([p[1] for p in parts]),
        used=np.stack([p[2] for p in parts]),
        weights=np.stack([p[3] for p in parts]),
        bias=np.array([p[4] for p in parts], dtype=np.float32),
        undef_rank=np.array([p[5] for p in parts], dtype=bool),
    )


def rank_scores(
    attrs: np.ndarray, valid: np.ndarray, weights: np.ndarray, bias: float
) -> np.ndarray:
    """Condor rank semantics, matching the dense ref exactly: rows where
    any non-zero-weight attribute is invalid rank 0.0 (the whole rank is
    Undefined, bias included); everywhere else Σ w_a·attr_a + bias."""
    w = np.asarray(weights, dtype=np.float32)
    svals = (attrs @ w + np.float32(bias)).astype(np.float32)
    wactive = w != 0
    if wactive.any():
        bad = ~valid[:, wactive].all(axis=1)
        svals[bad] = 0.0
    return svals


def _default_rank_order(
    attrs: np.ndarray, valid: np.ndarray
) -> Callable[[np.ndarray, float], Tuple[np.ndarray, np.ndarray]]:
    def rank_order(weights: np.ndarray, bias: float) -> Tuple[np.ndarray, np.ndarray]:
        svals = rank_scores(attrs, valid, weights, bias)
        return np.argsort(-svals, kind="stable"), svals

    return rank_order


def topk_in_rank_order(
    attrs: np.ndarray,  # [S, A] f32 — live rows only, logical width
    valid: np.ndarray,  # [S, A] bool
    batch: IntervalBatch,
    *,
    k: int = 1,
    admit: Optional[np.ndarray] = None,  # [B, S] bool/float pre-mask
    rank_order: Optional[
        Callable[[np.ndarray, float], Tuple[np.ndarray, np.ndarray]]
    ] = None,
    chunk: int = 256,
) -> Tuple[np.ndarray, np.ndarray]:
    """→ (topk_idx [B,k] i64, topk_scores [B,k] f32); slots past a
    request's match count hold (-1, -inf).

    ``rank_order(weights, bias) -> (order, svals)`` supplies the
    rank-descending candidate order and final per-row scores — pass a
    snapshot's cached one so the sort is paid once per (epoch,
    rank-expression), not per call. Requests are grouped by (weights,
    bias); each group walks its own order.
    """
    s = attrs.shape[0]
    b = batch.b
    valid = np.asarray(valid, dtype=bool)
    if admit is not None:
        admit = np.asarray(admit) > 0
    if rank_order is None:
        rank_order = _default_rank_order(attrs, valid)

    ti = np.full((b, k), -1, dtype=np.int64)
    ts = np.full((b, k), -np.inf, dtype=np.float32)
    if s == 0:
        return ti, ts

    groups: dict = {}
    for bi in range(b):
        key = (
            batch.weights[bi].tobytes(),
            float(batch.bias[bi]),
            bool(batch.undef_rank[bi]),
        )
        groups.setdefault(key, []).append(bi)

    for (_, gbias, gundef), members in groups.items():
        if gundef:
            # rank Undefined for every candidate ⇒ all scores 0.0; the
            # candidate order is plain row order (stable-tie semantics)
            order = np.arange(s, dtype=np.int64)
            svals = np.zeros((s,), dtype=np.float32)
        else:
            order, svals = rank_order(batch.weights[members[0]], gbias)
        # requests whose folded interval is empty can never match
        live = np.array(
            [bi for bi in members if not (batch.lo[bi] > batch.hi[bi]).any()],
            dtype=np.int64,
        )
        found = np.zeros(b, dtype=np.int64)
        pos = 0
        while live.size and pos < s:
            rows = order[pos : pos + chunk]
            a_ch, v_ch = attrs[rows], valid[rows]
            ok = np.ones((rows.size, live.size), dtype=bool)
            for c in range(batch.n_attrs):
                u = batch.used[live, c]
                if not u.any():
                    continue
                x = a_ch[:, c : c + 1]
                p = (
                    (x >= batch.lo[live, c][None, :])
                    & (x <= batch.hi[live, c][None, :])
                    & v_ch[:, c : c + 1]
                )
                ok &= np.where(u[None, :], p, True)
            if admit is not None:
                ok &= admit[live][:, rows].T
            if k == 1:
                hit = ok.any(axis=0)
                if hit.any():
                    win = live[hit]
                    r = rows[ok.argmax(axis=0)[hit]]
                    ti[win, 0] = r
                    ts[win, 0] = svals[r]
                    found[win] = 1
                    live = live[~hit]
                pos += chunk
                continue
            done: List[int] = []
            for j, bi in enumerate(live):
                hits = np.nonzero(ok[:, j])[0]
                if hits.size:
                    take = hits[: k - found[bi]]
                    r = rows[take]
                    ti[bi, found[bi] : found[bi] + take.size] = r
                    ts[bi, found[bi] : found[bi] + take.size] = svals[r]
                    found[bi] += take.size
                if found[bi] >= k:
                    done.append(j)
            if done:
                live = np.delete(live, done)
            pos += chunk
    return ti, ts
