"""Jit'd wrapper + request lowering for the matchrank kernel.

``matchrank`` pads/validates inputs and dispatches to the Pallas kernel
(or the pure-jnp ref as a fallback). ``lower_request`` turns a ClassAd
request into kernel operands via the conjunctive-threshold / linear-rank
extractors of :mod:`repro.core.compile` — the bridge from the paper's
language to the TPU hot loop. ``matchrank_topk`` composes the fused scores
with ``lax.top_k`` for k > 1. Every wrapper's ``interpret=None`` resolves
from the platform (:func:`repro.kernels.resolve_interpret`): the kernels
compile on a TPU and run in the Pallas interpreter on the CPU backend.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.classads import ClassAd
from repro.core.compile import (
    OPCODES,
    CompileError,
    extract_conjunctive_terms,
    extract_linear_rank,
)

from .kernel import matchrank_batched_pallas, matchrank_pallas
from .ref import NEG_INF, matchrank_batched_ref, matchrank_ref

__all__ = [
    "KernelPlan",
    "BatchedPlan",
    "lower_request",
    "stack_plans",
    "matchrank",
    "matchrank_topk",
    "matchrank_batched",
    "matchrank_batched_topk",
    "lower_matchrank_batched",
    "pad_columns",
]


def _pad_to(x: np.ndarray, n: int, axis: int = 0, fill=0.0) -> np.ndarray:
    pad = n - x.shape[axis]
    if pad <= 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return np.pad(x, widths, constant_values=fill)


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


@dataclass
class KernelPlan:
    """Kernel operands lowered from a ClassAd request over a fixed
    attribute vocabulary (column order)."""

    attr_names: List[str]  # column order, len = A (pre-pad)
    sel: np.ndarray  # [T_PAD, A_PAD]
    op_codes: np.ndarray  # [T_PAD] i32
    thresholds: np.ndarray  # [T_PAD] f32
    term_active: np.ndarray  # [T_PAD] f32
    weights: np.ndarray  # [A_PAD] f32
    bias: np.ndarray  # [1] f32
    a_pad: int
    t_pad: int


def lower_request(
    request: ClassAd,
    attr_names: Sequence[str],
    *,
    env: Optional[Dict] = None,
    t_pad: int = 16,
) -> KernelPlan:
    """Lower (requirements, rank) to kernel operands, or raise CompileError.

    This is the 'predicate pushdown' contract: the request must be a
    conjunction of threshold comparisons and a linear rank — the common
    case for storage selection (space/bandwidth gates, bandwidth rank).
    Anything richer takes the columnar-JAX or interpreter path instead.
    """
    names = [n.lower() for n in attr_names]
    index = {n: i for i, n in enumerate(names)}
    a = len(names)
    a_pad = max(_round_up(a, 128), 128)

    req = request.lookup_expr("requirements")
    terms = []
    if req is not None:
        extracted = extract_conjunctive_terms(req, request, env=env)
        if extracted is None:
            raise CompileError("requirements not conjunctive-threshold")
        terms = extracted
    if len(terms) > t_pad:
        t_pad = _round_up(len(terms), 8)

    sel = np.zeros((t_pad, a_pad), dtype=np.float32)
    op_codes = np.zeros((t_pad,), dtype=np.int32)
    thresholds = np.zeros((t_pad,), dtype=np.float32)
    term_active = np.zeros((t_pad,), dtype=np.float32)
    for t, term in enumerate(terms):
        if term.attr not in index:
            # attribute absent from the vocabulary: every candidate is
            # Undefined on it ⇒ nothing can match. Encode as an
            # always-false active term on column 0.
            sel[t, 0] = 1.0
            op_codes[t] = OPCODES["<"]
            thresholds[t] = float("-inf")
            term_active[t] = 1.0
            continue
        sel[t, index[term.attr]] = 1.0
        op_codes[t] = OPCODES[term.op]
        thresholds[t] = np.float32(term.threshold)
        term_active[t] = 1.0

    rank_expr = request.lookup_expr("rank")
    weights = np.zeros((a_pad,), dtype=np.float32)
    bias = np.zeros((1,), dtype=np.float32)
    if rank_expr is not None:
        lin = extract_linear_rank(rank_expr, request, env=env)
        if lin is None:
            raise CompileError("rank not linear")
        for attr, w in lin.items():
            if attr == "":
                bias[0] += np.float32(w)
            elif attr in index:
                weights[index[attr]] += np.float32(w)
            # weight on an unknown attribute ⇒ rank Undefined ⇒ 0 for all;
            # encode by an impossible validity demand: weight on padding col
            else:
                weights[a_pad - 1] += np.float32(w) if w != 0 else 0.0

    return KernelPlan(
        list(names), sel, op_codes, thresholds, term_active, weights, bias, a_pad, t_pad
    )


@dataclass
class BatchedPlan:
    """B stacked :class:`KernelPlan`\\ s over one shared attribute
    vocabulary, padded to a common T_PAD — the operand set of the
    multi-request kernel."""

    attr_names: List[str]
    sel: np.ndarray  # [B, T_PAD, A_PAD]
    op_codes: np.ndarray  # [B, T_PAD] i32
    thresholds: np.ndarray  # [B, T_PAD] f32
    term_active: np.ndarray  # [B, T_PAD] f32
    weights: np.ndarray  # [B, A_PAD] f32
    bias: np.ndarray  # [B] f32
    a_pad: int
    t_pad: int

    @property
    def b(self) -> int:
        return self.sel.shape[0]


def stack_plans(plans: Sequence[KernelPlan]) -> BatchedPlan:
    """Stack per-request plans into one batched operand set.

    All plans must share the attribute vocabulary (they were lowered
    against the same snapshot); T_PAD is re-padded to the batch maximum
    (padded terms are inactive, so semantics are unchanged).
    """
    if not plans:
        raise ValueError("stack_plans needs at least one plan")
    first = plans[0]
    for p in plans[1:]:
        if p.attr_names != first.attr_names or p.a_pad != first.a_pad:
            raise ValueError("stacked plans must share an attribute vocabulary")
    t_pad = max(p.t_pad for p in plans)

    def pt(x, fill=0.0):
        return _pad_to(x, t_pad, axis=0, fill=fill)

    return BatchedPlan(
        attr_names=list(first.attr_names),
        sel=np.stack([pt(p.sel) for p in plans]),
        op_codes=np.stack([pt(p.op_codes) for p in plans]),
        thresholds=np.stack([pt(p.thresholds) for p in plans]),
        term_active=np.stack([pt(p.term_active) for p in plans]),
        weights=np.stack([p.weights for p in plans]),
        bias=np.concatenate([p.bias for p in plans]),
        a_pad=first.a_pad,
        t_pad=t_pad,
    )


def pad_columns(
    attrs: np.ndarray, valid: np.ndarray, a_pad: int, block_s: int = 512
) -> Tuple[np.ndarray, np.ndarray, int]:
    """Pad [S, A] column blocks to [S_PAD, A_PAD]; padded rows invalid.

    Non-finite attribute cells (NaN/±inf from a misbehaving publisher)
    are zeroed and marked invalid — Condor's Undefined semantics —
    instead of poisoning the f32 cast and every comparison downstream.
    """
    s, a = attrs.shape
    s_pad = max(_round_up(s, block_s), block_s)
    attrs_f = np.asarray(attrs, dtype=np.float32)
    finite = np.isfinite(attrs_f)
    if not finite.all():
        attrs_f = np.where(finite, attrs_f, np.float32(0.0))
        valid = np.asarray(valid, dtype=bool) & finite
    attrs_p = _pad_to(_pad_to(attrs_f, a_pad, axis=1), s_pad, axis=0)
    valid_p = _pad_to(_pad_to(valid.astype(np.float32), a_pad, axis=1), s_pad, axis=0)
    return attrs_p, valid_p, s_pad


@functools.partial(
    jax.jit, static_argnames=("block_s", "use_kernel", "interpret")
)
def _dispatch(
    attrs, valid, admit, sel, op_codes, thresholds, term_active, weights, bias,
    *, block_s: int, use_kernel: bool, interpret: Optional[bool],
):
    if use_kernel:
        return matchrank_pallas(
            attrs, valid, admit, sel, op_codes, thresholds, term_active,
            weights, bias, block_s=block_s, interpret=interpret,
        )
    return matchrank_ref(
        attrs, valid, sel, op_codes, thresholds, term_active, weights, bias, admit
    )


@functools.partial(
    jax.jit, static_argnames=("k", "block_s", "use_kernel", "interpret")
)
def _dispatch_topk(
    attrs, valid, admit, sel, op_codes, thresholds, term_active, weights, bias,
    *, k: int, block_s: int, use_kernel: bool, interpret: Optional[bool],
):
    """Fused scores + top-k in one jitted program — no host round-trip."""
    mask, score, _, _ = _dispatch(
        attrs, valid, admit, sel, op_codes, thresholds, term_active, weights,
        bias, block_s=block_s, use_kernel=use_kernel, interpret=interpret,
    )
    vals, idx = jax.lax.top_k(score, k)
    return vals, idx


@functools.partial(
    jax.jit, static_argnames=("k", "block_s", "use_kernel", "interpret")
)
def _dispatch_batched(
    attrs, valid, admit, sel, op_codes, thresholds, term_active, weights, bias,
    *, k: int, block_s: int, use_kernel: bool, interpret: Optional[bool],
):
    if use_kernel:
        return matchrank_batched_pallas(
            attrs, valid, admit, sel, op_codes, thresholds, term_active,
            weights, bias, block_s=block_s, k=k, interpret=interpret,
        )
    return matchrank_batched_ref(
        attrs, valid, admit, sel, op_codes, thresholds, term_active, weights,
        bias, k=k,
    )


#: numpy comparator per opcode (shared encoding with core.compile.OPCODES)
_CMP_OPS = {
    0: np.less,
    1: np.less_equal,
    2: np.greater,
    3: np.greater_equal,
    4: np.equal,
    5: np.not_equal,
}


def _topk_desc_stable(score: np.ndarray, k: int) -> np.ndarray:
    """One row's top-k indices with the ``lax.top_k`` contract — score
    descending, ties → lowest index — via O(S + k·log k) argpartition
    instead of a full sort."""
    s = score.shape[0]
    if k >= s:
        return np.argsort(-score, kind="stable")[:k]
    part = np.argpartition(-score, k - 1)[:k]
    v = score[part].min()  # k-th value; ties at v need index-stable picking
    gt = np.nonzero(score > v)[0]
    eq = np.nonzero(score == v)[0][: k - gt.size]
    idx = np.concatenate([gt, eq])
    return idx[np.argsort(-score[idx], kind="stable")]


def _matchrank_batched_dense_host(
    attrs, valid, batched: BatchedPlan, admit, s: int, k: int
) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Host evaluation of the dense batched fallback, tiled by *shared
    work* instead of materializing the [B, S, T] einsum of the jnp ref
    (which made the fallback ~370× slower than the sparse walk).

    Terms are grouped by (column, opcode) — one vectorized compare per
    group serves every request that asked it (broker batches are
    near-duplicate plans differing only in thresholds) — and rank forms
    by (weights, bias) — one [S, A] matvec per distinct rank expression.
    Semantics are element-identical to :func:`.ref.matchrank_batched_ref`
    (fail-closed Undefined terms, Condor rank-Undefined → 0.0, top-k
    ties → lowest row index).
    """
    a_host = np.asarray(attrs, dtype=np.float32)[:s]
    v_raw = np.asarray(valid)[:s]
    b = batched.b
    aw = a_host.shape[1]  # logical or pre-padded width, both fine
    na = len(batched.attr_names)

    def vcol(c: int) -> np.ndarray:  # one validity column, bool, on demand
        col = np.ascontiguousarray(v_raw[:, c])
        return col if col.dtype == bool else col > 0.5

    mask = np.empty((b, s), dtype=bool)
    if admit is None:
        mask[:] = True
    else:
        mask[:] = np.asarray(admit)[:, :s] > 0.5

    act = batched.term_active > 0.5  # [B, T]
    cols = batched.sel.argmax(axis=2)  # [B, T] — one-hot column per term
    groups: Dict[Tuple[int, int], List[Tuple[int, np.float32]]] = {}
    for bi in range(b):
        for t in np.nonzero(act[bi])[0]:
            key = (int(cols[bi, t]), int(batched.op_codes[bi, t]))
            groups.setdefault(key, []).append(
                (bi, np.float32(batched.thresholds[bi, t]))
            )
    for (c, op), members in groups.items():
        thr = np.array([m[1] for m in members], dtype=np.float32)
        colv = np.ascontiguousarray(a_host[:, c])  # strided col read once
        # [M, S] — member rows contiguous for the fold below
        passed = _CMP_OPS[op](colv[None, :], thr[:, None]) & vcol(c)[None, :]
        for j, (bi, _) in enumerate(members):
            mask[bi] &= passed[j]

    rgroups: Dict[Tuple[bytes, float], List[int]] = {}
    for bi in range(b):
        rkey = (batched.weights[bi].tobytes(), float(batched.bias[bi]))
        rgroups.setdefault(rkey, []).append(bi)
    score = np.empty((b, s), dtype=np.float32)
    for (wb, bias), members in rgroups.items():
        wv = np.frombuffer(wb, dtype=np.float32)
        if (np.abs(wv[na:]) > 0).any():
            # weight on a padding column = rank references an attribute
            # outside the vocabulary ⇒ Undefined ⇒ 0.0 for every row
            sv = np.zeros((s,), dtype=np.float32)
        else:
            w = wv[:aw]
            sv = (a_host @ w + np.float32(bias)).astype(np.float32)
            wcols = np.nonzero(w)[0]
            if wcols.size:
                okw = vcol(wcols[0]).copy()
                for c in wcols[1:]:
                    okw &= vcol(c)
                sv[~okw] = 0.0
        for bi in members:
            score[bi] = sv

    out_score = np.where(mask, score, np.float32(NEG_INF))
    keff = min(k, s)
    if keff == 1:
        # the broker's common case: one vectorized argmax (ties → lowest)
        m = out_score.argmax(axis=1)
        ti = m[:, None].astype(np.int32)
        ts = out_score[np.arange(b), m][:, None].astype(np.float32)
    else:
        ti = np.empty((b, keff), dtype=np.int32)
        ts = np.empty((b, keff), dtype=np.float32)
        for bi in range(b):
            idx = _topk_desc_stable(out_score[bi], keff)
            ti[bi] = idx
            ts[bi] = out_score[bi, idx]
    return mask, out_score, ti, ts


def _is_prepadded(attrs, a_pad: int, block_s: int) -> bool:
    """True when the candidate block is already device-padded (snapshot
    path): lane-aligned columns, block-aligned rows."""
    s, a = attrs.shape
    return a == a_pad and s > 0 and s % block_s == 0


def _prepare_columns(
    attrs, valid, a_pad: int, block_s: int, n_rows: Optional[int]
) -> Tuple[Any, Any, int, int]:
    """→ (attrs_p, valid_p, s, s_pad). Skips the host pad entirely when the
    inputs are already padded (e.g. held resident by a ReplicaSnapshot)."""
    if _is_prepadded(attrs, a_pad, block_s):
        s_pad = attrs.shape[0]
        s = int(n_rows) if n_rows is not None else s_pad
        return attrs, valid, s, s_pad
    s = attrs.shape[0] if n_rows is None else int(n_rows)
    attrs_p, valid_p, s_pad = pad_columns(
        np.asarray(attrs), np.asarray(valid), a_pad, block_s
    )
    return jnp.asarray(attrs_p), jnp.asarray(valid_p), s, s_pad


def matchrank(
    attrs: np.ndarray,  # [S, A] f32 (unpadded, or pre-padded [S_PAD, A_PAD])
    valid: np.ndarray,  # [S, A] bool/f32
    plan: KernelPlan,
    *,
    admit: Optional[np.ndarray] = None,  # [S] pre-mask (folded policies)
    n_rows: Optional[int] = None,  # real row count when pre-padded
    block_s: int = 512,
    use_kernel: bool = True,
    interpret: Optional[bool] = None,
) -> Tuple[np.ndarray, np.ndarray, float, int]:
    """Run the fused match+rank+top-1. Returns (mask[S], score[S],
    best_score, best_idx) trimmed back to the unpadded S.

    Pre-padded device-resident inputs (``attrs.shape == [S_PAD, A_PAD]``
    with ``S_PAD % block_s == 0``) skip the host-side ``pad_columns`` +
    transfer — pass ``n_rows`` for the live row count.
    """
    attrs_p, valid_p, s, s_pad = _prepare_columns(
        attrs, valid, plan.a_pad, block_s, n_rows
    )
    admit_p = np.zeros((s_pad,), dtype=np.float32)
    if admit is None:
        admit_p[:s] = 1.0
    else:
        admit_p[:s] = np.asarray(admit, dtype=np.float32)[:s]

    mask, score, best_s, best_i = _dispatch(
        attrs_p, valid_p, jnp.asarray(admit_p),
        jnp.asarray(plan.sel), jnp.asarray(plan.op_codes),
        jnp.asarray(plan.thresholds), jnp.asarray(plan.term_active),
        jnp.asarray(plan.weights), jnp.asarray(plan.bias),
        block_s=block_s, use_kernel=use_kernel, interpret=interpret,
    )
    return (
        np.asarray(mask)[:s],
        np.asarray(score)[:s],
        float(best_s[0]),
        int(best_i[0]),
    )


def matchrank_topk(
    attrs: np.ndarray,
    valid: np.ndarray,
    plan: KernelPlan,
    k: int,
    *,
    admit: Optional[np.ndarray] = None,
    n_rows: Optional[int] = None,
    block_s: int = 512,
    use_kernel: bool = True,
    interpret: Optional[bool] = None,
) -> Tuple[np.ndarray, np.ndarray]:
    """Top-k selection: fused scores + ``lax.top_k`` inside ONE jitted
    program (scores never leave the device before the top-k). Returns
    (indices[k], scores[k]); unmatched slots have score -inf."""
    attrs_p, valid_p, s, s_pad = _prepare_columns(
        attrs, valid, plan.a_pad, block_s, n_rows
    )
    admit_p = np.zeros((s_pad,), dtype=np.float32)
    if admit is None:
        admit_p[:s] = 1.0
    else:
        admit_p[:s] = np.asarray(admit, dtype=np.float32)[:s]

    vals, idx = _dispatch_topk(
        attrs_p, valid_p, jnp.asarray(admit_p),
        jnp.asarray(plan.sel), jnp.asarray(plan.op_codes),
        jnp.asarray(plan.thresholds), jnp.asarray(plan.term_active),
        jnp.asarray(plan.weights), jnp.asarray(plan.bias),
        k=min(k, s), block_s=block_s, use_kernel=use_kernel,
        interpret=interpret,
    )
    return np.asarray(idx), np.asarray(vals)


def matchrank_batched(
    attrs: np.ndarray,  # [S, A] (unpadded) or pre-padded [S_PAD, A_PAD]
    valid: np.ndarray,
    plans: "BatchedPlan | Sequence[KernelPlan]",
    *,
    admit: Optional[np.ndarray] = None,  # [B, S] per-request pre-mask
    n_rows: Optional[int] = None,
    k: int = 1,
    block_s: int = 512,
    use_kernel: bool = True,
    interpret: Optional[bool] = None,
) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Batched fused match+rank+top-k: B requests against ONE candidate
    block in a single kernel launch.

    Returns (mask [B,S] bool, score [B,S] f32, topk_idx [B,k] i32,
    topk_scores [B,k] f32), trimmed to the live row count. Top-k slots
    beyond a request's match count hold score -inf (index is meaningless
    there, as in :func:`matchrank_topk`).
    """
    batched = plans if isinstance(plans, BatchedPlan) else stack_plans(list(plans))
    if not use_kernel:
        # grouped host evaluation — the jnp ref's [B,S,T] einsums are kept
        # as a parity oracle only (see _matchrank_batched_dense_host)
        s = attrs.shape[0] if n_rows is None else int(n_rows)
        return _matchrank_batched_dense_host(attrs, valid, batched, admit, s, k)
    operands, static, s = _batched_launch(
        attrs, valid, batched, admit, n_rows, k, block_s
    )
    mask, score, topk_s, topk_i = _dispatch_batched(
        *operands, **static, interpret=interpret
    )
    return (
        np.asarray(mask)[:, :s],
        np.asarray(score)[:, :s],
        np.asarray(topk_i),
        np.asarray(topk_s),
    )


def _batched_launch(
    attrs, valid, batched: BatchedPlan, admit, n_rows, k: int, block_s: int
) -> Tuple[Tuple[Any, ...], Dict[str, Any], int]:
    """→ (operands, static arguments, live rows) of the kernel launch that
    :func:`matchrank_batched` makes."""
    attrs_p, valid_p, s, s_pad = _prepare_columns(
        attrs, valid, batched.a_pad, block_s, n_rows
    )
    admit_p = np.zeros((batched.b, s_pad), dtype=np.float32)
    if admit is None:
        admit_p[:, :s] = 1.0
    else:
        admit_p[:, :s] = np.asarray(admit, dtype=np.float32)[:, :s]
    operands = (
        attrs_p, valid_p, jnp.asarray(admit_p),
        jnp.asarray(batched.sel), jnp.asarray(batched.op_codes),
        jnp.asarray(batched.thresholds), jnp.asarray(batched.term_active),
        jnp.asarray(batched.weights), jnp.asarray(batched.bias),
    )
    return operands, dict(k=min(k, s), block_s=block_s, use_kernel=True), s


def lower_matchrank_batched(
    attrs: np.ndarray,
    valid: np.ndarray,
    plans: "BatchedPlan | Sequence[KernelPlan]",
    *,
    admit: Optional[np.ndarray] = None,
    n_rows: Optional[int] = None,
    k: int = 1,
    block_s: int = 512,
    interpret: Optional[bool] = None,
) -> jax.stages.Lowered:
    """The program :func:`matchrank_batched` launches with ``use_kernel``
    for these operands, lowered and not run — what the backend compiles
    (a Mosaic ``tpu_custom_call`` on a TPU, the interpreter elsewhere)."""
    batched = plans if isinstance(plans, BatchedPlan) else stack_plans(list(plans))
    operands, static, _ = _batched_launch(
        attrs, valid, batched, admit, n_rows, k, block_s
    )
    return _dispatch_batched.lower(*operands, **static, interpret=interpret)


def matchrank_batched_topk(
    attrs: np.ndarray,  # [S, A] (unpadded) or pre-padded [S_PAD, A_PAD]
    valid: np.ndarray,
    plans: Sequence[KernelPlan],
    *,
    k: int = 1,
    admit: Optional[np.ndarray] = None,  # [B, S] per-request pre-mask
    n_rows: Optional[int] = None,
    rank_order=None,  # Callable[[weights], (order, svals)] — snapshot cache
    use_sparse: Optional[bool] = None,
    block_s: int = 512,
    use_kernel: bool = False,
    interpret: Optional[bool] = None,
) -> Tuple[np.ndarray, np.ndarray]:
    """Batched top-k *selection*: B requests → (topk_idx [B,k],
    topk_scores [B,k]); slots past a request's match count hold -inf
    (and index -1 on the sparse path).

    The steady-state CPU fast path answers each request by scanning
    candidates in precomputed rank-descending order until k pass its
    interval-canonicalized requirements (expected probes ≈ k/selectivity
    — see :mod:`.sparse`); plans outside the interval subset, or
    ``use_sparse=False``, fall back to the dense batched launch. Pass a
    :meth:`ReplicaSnapshot.rank_order <repro.core.snapshot.ReplicaSnapshot.rank_order>`
    so the per-(epoch, rank-weights) sort is amortized across calls.
    """
    from .sparse import canonicalize_plans, topk_in_rank_order

    plans = list(plans)
    na = len(plans[0].attr_names)
    if use_sparse is not False:
        batch = canonicalize_plans(plans, na)
        if batch is not None:
            a_host = np.asarray(attrs, dtype=np.float32)
            v_host = np.asarray(valid)
            s = a_host.shape[0] if n_rows is None else int(n_rows)
            return topk_in_rank_order(
                a_host[:s, :na],
                v_host[:s, :na] > 0.5 if v_host.dtype != bool else v_host[:s, :na],
                batch,
                k=k,
                admit=admit,
                rank_order=rank_order,
            )
        if use_sparse:
            raise CompileError("plan batch not interval-canonicalizable")
    _, _, ti, ts = matchrank_batched(
        attrs, valid, plans, admit=admit, n_rows=n_rows, k=k,
        block_s=block_s, use_kernel=use_kernel, interpret=interpret,
    )
    return ti.astype(np.int64), ts
