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

import contextlib
import functools
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core.classads import ClassAd
from repro.core.compile import (
    OPCODES,
    CompileError,
    ConjTerm,
    RankAlternative,
    extract_conjunctive_terms,
    extract_rank_alternatives,
)

from .kernel import matchrank_batched_pallas, matchrank_pallas
from .ref import (
    NEG_INF,
    RANK_SLOTS,
    UNDEF_PASSES,
    matchrank_batched_ref,
    matchrank_ref,
)

__all__ = [
    "KernelPlan",
    "BatchedPlan",
    "lower_request",
    "stack_plans",
    "matchrank",
    "matchrank_topk",
    "matchrank_batched",
    "matchrank_candidates",
    "candidate_bucket",
    "admit_matrix",
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
    attribute vocabulary (column order).

    Terms are rows of ``sel``/``op_codes``/``thresholds``/``term_role``.
    ``term_role`` says what a row is: 0 padding, 1 a requirement, ``2 + j``
    a gate term of rank alternative j. ``op_codes`` holds the comparison
    (:data:`repro.core.compile.OPCODES`), plus :data:`UNDEF_PASSES` where
    an Undefined attribute passes the term. The rank is the first of
    :data:`RANK_SLOTS` alternatives whose gate terms all pass (a slot with
    no gate terms always does): ``weights[j]·x + bias[j]`` over
    ``weights[R + j]·x + bias[R + j]``. Every plan has these shapes, so a
    batch of any mix of plans stacks to the same operands."""

    attr_names: List[str]  # column order, len = A (pre-pad)
    sel: np.ndarray  # [T_PAD, A_PAD]
    op_codes: np.ndarray  # [T_PAD] i32
    thresholds: np.ndarray  # [T_PAD] f32
    term_role: np.ndarray  # [T_PAD] f32
    weights: np.ndarray  # [2R, A_PAD] f32: numerators, then denominators
    bias: np.ndarray  # [2R] f32
    a_pad: int
    t_pad: int

    @functools.cached_property
    def plain(self) -> bool:
        """Fail-closed requirement terms and one linear rank: no guarded
        term and no fallback rank alternative. The broker counts the
        launch's other plans as guarded. Plans are shared read-only
        through the plan cache, so this is worked out once per plan."""
        return (
            not (self.op_codes & UNDEF_PASSES).any()
            and not (self.term_role > 1.5).any()
            and not self.weights[RANK_SLOTS].any()
            and self.bias[RANK_SLOTS] == 1.0
        )


def _encode_linear(lin: Dict[str, float], index: Dict[str, int], w: np.ndarray) -> float:
    """Write a linear form's weights into one row ``w``; → its bias. A
    weight on an attribute outside the vocabulary goes to the last padding
    column, which no row holds: the form is Undefined everywhere."""
    bias = 0.0
    for attr, wt in lin.items():
        if attr == "":
            bias += wt
        elif attr in index:
            w[index[attr]] += np.float32(wt)
        elif wt != 0:
            w[-1] += np.float32(wt)
    return bias


def lower_request(
    request: ClassAd,
    attr_names: Sequence[str],
    *,
    env: Optional[Dict] = None,
    t_pad: int = 16,
) -> KernelPlan:
    """Lower (requirements, rank) to kernel operands, or raise CompileError.

    This is the 'predicate pushdown' contract
    (:func:`repro.core.compile.extract_conjunctive_terms`,
    :func:`~repro.core.compile.extract_rank_alternatives`): requirements
    that are a conjunction of threshold comparisons, each possibly guarded
    as ``isUndefined(other.a) || <request constants> || other.a OP c``;
    a rank that is a linear form, a quotient of two, or an ``ifThenElse``
    chain of at most :data:`RANK_SLOTS` of them gated by guarded
    conjunctions — the broker's default read ad among them. Anything
    richer (a general ``||``, an unguarded gate, a product of attributes)
    takes the columnar-JAX or interpreter path instead.
    """
    names = [n.lower() for n in attr_names]
    index = {n: i for i, n in enumerate(names)}
    a = len(names)
    a_pad = max(_round_up(a, 128), 128)

    rows: List[Tuple[ConjTerm, int]] = []  # (term, role)
    req = request.lookup_expr("requirements")
    if req is not None:
        extracted = extract_conjunctive_terms(req, request, env=env)
        if extracted is None:
            raise CompileError("requirements not conjunctive-threshold")
        rows += [(t, 1) for t in extracted]
    alts = [RankAlternative((), {})]  # no rank: 0.0
    rank_expr = request.lookup_expr("rank")
    if rank_expr is not None:
        alts = extract_rank_alternatives(rank_expr, request, env=env)
        if alts is None:
            raise CompileError("rank not a chain of guarded linear alternatives")
        if len(alts) > RANK_SLOTS:
            raise CompileError(f"rank chain of {len(alts)} > {RANK_SLOTS} alternatives")
    for j, alt in enumerate(alts):
        rows += [(t, 2 + j) for t in alt.gate]
    # an attribute outside the vocabulary is Undefined for every candidate:
    # a term that Undefined passes always passes, any other never does
    rows = [(t, role) for t, role in rows if t.attr in index or not t.undefined_passes]
    if len(rows) > t_pad:
        t_pad = _round_up(len(rows), 8)

    sel = np.zeros((t_pad, a_pad), dtype=np.float32)
    op_codes = np.zeros((t_pad,), dtype=np.int32)
    thresholds = np.zeros((t_pad,), dtype=np.float32)
    term_role = np.zeros((t_pad,), dtype=np.float32)
    for t, (term, role) in enumerate(rows):
        term_role[t] = role
        if term.attr not in index:
            # never passes: an always-false term on column 0
            sel[t, 0] = 1.0
            op_codes[t] = OPCODES["<"]
            thresholds[t] = float("-inf")
            continue
        sel[t, index[term.attr]] = 1.0
        op_codes[t] = OPCODES[term.op] | (UNDEF_PASSES if term.undefined_passes else 0)
        thresholds[t] = np.float32(term.threshold)

    weights = np.zeros((2 * RANK_SLOTS, a_pad), dtype=np.float32)
    bias = np.zeros((2 * RANK_SLOTS,), dtype=np.float32)
    bias[RANK_SLOTS:] = 1.0
    for j, alt in enumerate(alts):
        bias[j] = _encode_linear(alt.num, index, weights[j])
        if alt.den is not None:
            bias[RANK_SLOTS + j] = _encode_linear(alt.den, index, weights[RANK_SLOTS + j])

    return KernelPlan(
        list(names), sel, op_codes, thresholds, term_role, weights, bias, a_pad, t_pad
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
    term_role: np.ndarray  # [B, T_PAD] f32
    weights: np.ndarray  # [B, 2R, A_PAD] f32
    bias: np.ndarray  # [B, 2R] f32
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
        term_role=np.stack([pt(p.term_role) for p in plans]),
        weights=np.stack([p.weights for p in plans]),
        bias=np.stack([p.bias for p in plans]),
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
    attrs, valid, admit, sel, op_codes, thresholds, term_role, weights, bias,
    *, block_s: int, use_kernel: bool, interpret: Optional[bool],
):
    if use_kernel:
        return matchrank_pallas(
            attrs, valid, admit, sel, op_codes, thresholds, term_role,
            weights, bias, block_s=block_s, interpret=interpret,
        )
    return matchrank_ref(
        attrs, valid, sel, op_codes, thresholds, term_role, weights, bias, admit
    )


@functools.partial(
    jax.jit, static_argnames=("k", "block_s", "use_kernel", "interpret")
)
def _dispatch_topk(
    attrs, valid, admit, sel, op_codes, thresholds, term_role, weights, bias,
    *, k: int, block_s: int, use_kernel: bool, interpret: Optional[bool],
):
    """Fused scores + top-k in one jitted program — no host round-trip."""
    mask, score, _, _ = _dispatch(
        attrs, valid, admit, sel, op_codes, thresholds, term_role, weights,
        bias, block_s=block_s, use_kernel=use_kernel, interpret=interpret,
    )
    vals, idx = jax.lax.top_k(score, k)
    return vals, idx


#: the smallest candidate-row bucket of a launch (see :func:`candidate_bucket`)
MIN_CANDIDATES = 8
#: a candidate slot that holds no row: past every row, so the launch's
#: scatter drops it (a negative pad would wrap to the last row)
NO_ROW = np.iinfo(np.int32).max


def candidate_bucket(count: int, s_pad: int) -> int:
    """C, the candidate columns of a launch whose longest candidate list
    has ``count`` rows: the next power of two, at least
    :data:`MIN_CANDIDATES`, at most ``s_pad`` (then the columns can hold
    every row). A few buckets keep the count of compiled programs small."""
    c = MIN_CANDIDATES
    while c < count:
        c *= 2
    return min(c, s_pad)


def _plan_fields(t_pad: int, a_pad: int) -> List[Tuple[str, int, int]]:
    """(field, start, stop) of each plan operand in a packed launch row;
    the request's candidate rows follow the last one."""
    q = 2 * RANK_SLOTS
    sizes = (
        ("sel", t_pad * a_pad), ("op_codes", t_pad), ("thresholds", t_pad),
        ("term_role", t_pad), ("weights", q * a_pad), ("bias", q),
    )
    out, o = [], 0
    for name, n in sizes:
        out.append((name, o, o + n))
        o += n
    return out


def _pack_launch(batched: BatchedPlan, cand: np.ndarray) -> np.ndarray:
    """One i32 row per request: its plan operands (the f32 ones bit for
    bit) and then its ``[C]`` candidate rows — the launch's single
    host-to-device transfer."""
    b = batched.b
    fields = _plan_fields(batched.t_pad, batched.a_pad)
    end = fields[-1][2]
    buf = np.empty((b, end + cand.shape[1]), dtype=np.int32)
    as_f32 = buf.view(np.float32)
    for name, lo, hi in fields:
        dst = buf if name == "op_codes" else as_f32
        dst[:, lo:hi] = getattr(batched, name).reshape(b, hi - lo)
    buf[:, end:] = cand
    return buf


def _candidate_matrix(rows: Sequence[Sequence[int]], s_pad: int) -> np.ndarray:
    """Ragged per-request candidate rows → ``[B, C]`` i32, padded with
    :data:`NO_ROW`."""
    c = candidate_bucket(max((len(r) for r in rows), default=0), s_pad)
    cand = np.full((len(rows), c), NO_ROW, dtype=np.int32)
    for bi, r in enumerate(rows):
        cand[bi, : len(r)] = r
    return cand


@functools.partial(
    jax.jit, static_argnames=("t_pad", "k", "block_s", "use_kernel", "interpret")
)
def _dispatch_batched(
    attrs, valid, packed,
    *, t_pad: int, k: int, block_s: int, use_kernel: bool, interpret: Optional[bool],
):
    """The candidate-row launch: unpack the plans and the ``[B, C]``
    candidate rows from ``packed`` (:func:`_pack_launch`), scatter the rows
    into the kernel's ``[B, S_PAD]`` admit pre-mask, run the kernel, and
    gather mask and score back at the candidate rows. → one i32
    ``[B, 2C + 2k]`` array: mask (0/1), score bits, top-k score bits,
    top-k rows."""
    b, width = packed.shape
    s_pad, a_pad = attrs.shape
    fields = _plan_fields(t_pad, a_pad)
    end = fields[-1][2]
    # lax primitives, not jnp indexing: this program is traced once per
    # batch size at start-up, and the tracing is most of a cached compile
    as_f32 = lax.bitcast_convert_type(lax.slice_in_dim(packed, 0, end, axis=1), jnp.float32)
    part = {
        name: lax.slice_in_dim(packed if name == "op_codes" else as_f32, lo, hi, axis=1)
        for name, lo, hi in fields
    }
    cand = lax.slice_in_dim(packed, end, width, axis=1)
    # each candidate as a row of the flattened [B·S_PAD] admit; an empty
    # slot points one past its end, where the scatter drops it
    live = lax.lt(cand, s_pad)
    flat = lax.select(
        live,
        lax.add(cand, lax.mul(lax.broadcasted_iota(jnp.int32, cand.shape, 0), s_pad)),
        lax.full(cand.shape, b * s_pad, jnp.int32),
    ).reshape(b, -1, 1)
    admit = lax.scatter(
        lax.full((b * s_pad,), 0.0, jnp.float32), flat, lax.full(cand.shape, 1.0, jnp.float32),
        lax.ScatterDimensionNumbers((), (0,), (0,)),
        mode=lax.GatherScatterMode.FILL_OR_DROP,
    ).reshape(b, s_pad)
    operands = (
        attrs, valid, admit, part["sel"].reshape(b, t_pad, a_pad), part["op_codes"],
        part["thresholds"], part["term_role"],
        part["weights"].reshape(b, 2 * RANK_SLOTS, a_pad), part["bias"],
    )
    if use_kernel:
        mask, score, topk_s, topk_i = matchrank_batched_pallas(
            *operands, block_s=block_s, k=k, interpret=interpret
        )
    else:
        mask, score, topk_s, topk_i = matchrank_batched_ref(*operands, k=k)
    at = lax.min(flat, lax.full(flat.shape, b * s_pad - 1, jnp.int32))

    def at_rows(x):  # [B, S_PAD] → [B, C] at the candidate rows
        return lax.gather(
            x.reshape(b * s_pad), at, lax.GatherDimensionNumbers((), (0,), (0,)), (1,),
            mode=lax.GatherScatterMode.PROMISE_IN_BOUNDS,
        )

    cmask = lax.bitwise_and(at_rows(mask), live)
    cscore = lax.select(live, at_rows(score), lax.full(live.shape, NEG_INF, jnp.float32))
    return lax.concatenate(
        [
            cmask.astype(jnp.int32), lax.bitcast_convert_type(cscore, jnp.int32),
            lax.bitcast_convert_type(topk_s, jnp.int32), topk_i.astype(jnp.int32),
        ],
        1,
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
    work* instead of materializing the [B, S, T] einsum of the jnp ref.

    Terms are grouped by (column, opcode) — one vectorized compare per
    group serves every request that asked it (broker batches are
    near-duplicate plans differing only in thresholds) — and rank forms
    by (weights, bias) — one [S, A] matvec per alternative of each
    distinct rank expression. Semantics are element-identical to
    :func:`.ref.matchrank_batched_ref` (fail-closed Undefined terms unless
    flagged, the first alternative whose gate holds, Condor
    rank-Undefined → 0.0, top-k ties → lowest row index).
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

    role = np.rint(batched.term_role).astype(np.int64)  # [B, T]
    cols = batched.sel.argmax(axis=2)  # [B, T] — one-hot column per term
    groups: Dict[Tuple[int, int], List[Tuple[int, int, np.float32]]] = {}
    for bi in range(b):
        for t in np.nonzero(role[bi])[0]:
            key = (int(cols[bi, t]), int(batched.op_codes[bi, t]))
            groups.setdefault(key, []).append(
                (bi, int(role[bi, t]), np.float32(batched.thresholds[bi, t]))
            )
    gates: Dict[Tuple[int, int], np.ndarray] = {}  # (request, slot) → [S] bool
    for (c, op), members in groups.items():
        thr = np.array([m[2] for m in members], dtype=np.float32)
        colv = np.ascontiguousarray(a_host[:, c])  # strided col read once
        ok = vcol(c)
        # [M, S] — member rows contiguous for the fold below
        passed = _CMP_OPS[op & ~UNDEF_PASSES](colv[None, :], thr[:, None]) & ok[None, :]
        if op & UNDEF_PASSES:
            passed |= ~ok[None, :]
        for j, (bi, rl, _) in enumerate(members):
            if rl == 1:
                mask[bi] &= passed[j]
            else:
                g = gates.get((bi, rl - 2))
                gates[(bi, rl - 2)] = passed[j].copy() if g is None else g & passed[j]

    def value(w: np.ndarray, bias: np.ndarray, j: int) -> np.ndarray:
        """Alternative j of a rank form: num / den, 0.0 where Undefined."""
        wn, wd = w[j], w[RANK_SLOTS + j]
        if (np.abs(wn[na:]) > 0).any() or (np.abs(wd[na:]) > 0).any():
            # weight on a padding column = the value references an
            # attribute outside the vocabulary ⇒ Undefined ⇒ 0.0
            return np.zeros((s,), dtype=np.float32)
        num = (a_host @ wn[:aw] + bias[j]).astype(np.float32)
        wcols = np.nonzero(wn[:aw])[0]
        if wd[:aw].any():
            den = (a_host @ wd[:aw] + bias[RANK_SLOTS + j]).astype(np.float32)
            wcols = np.union1d(wcols, np.nonzero(wd[:aw])[0])
        else:
            den = np.full((s,), bias[RANK_SLOTS + j], dtype=np.float32)
        okw = den != 0
        for c in wcols:
            okw &= vcol(c)
        one = den == 1
        if not one.all():
            num = np.where(one, num, num / np.where(okw, den, np.float32(1)))
        return np.where(okw, num, np.float32(0)).astype(np.float32)

    rgroups: Dict[Tuple[bytes, bytes], List[int]] = {}
    for bi in range(b):
        rkey = (batched.weights[bi].tobytes(), batched.bias[bi].tobytes())
        rgroups.setdefault(rkey, []).append(bi)
    score = np.empty((b, s), dtype=np.float32)
    for members in rgroups.values():
        w, bias = batched.weights[members[0]], batched.bias[members[0]]
        values: Dict[int, np.ndarray] = {}
        for bi in members:
            out = np.zeros((s,), dtype=np.float32)
            open_ = np.ones((s,), dtype=bool)  # rows no earlier gate took
            for j in range(RANK_SLOTS):
                if j not in values:
                    values[j] = value(w, bias, j)
                g = gates.get((bi, j))
                if g is None:  # no gate terms: every open row takes it
                    out[open_] = values[j][open_]
                    break
                take = open_ & g
                out[take] = values[j][take]
                open_ &= ~g
            score[bi] = out

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
    return _to_device(attrs_p), _to_device(valid_p), s, s_pad


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
        jnp.asarray(plan.thresholds), jnp.asarray(plan.term_role),
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
        jnp.asarray(plan.thresholds), jnp.asarray(plan.term_role),
        jnp.asarray(plan.weights), jnp.asarray(plan.bias),
        k=min(k, s), block_s=block_s, use_kernel=use_kernel,
        interpret=interpret,
    )
    return np.asarray(idx), np.asarray(vals)


def matchrank_candidates(
    attrs: np.ndarray,  # [S, A] (unpadded) or pre-padded [S_PAD, A_PAD]
    valid: np.ndarray,
    plans: "BatchedPlan | Sequence[KernelPlan]",
    rows: Sequence[Sequence[int]],  # per request: its candidate rows
    *,
    n_rows: Optional[int] = None,
    k: int = 1,
    block_s: int = 512,
    use_kernel: bool = True,
    interpret: Optional[bool] = None,
    tracer: Optional[Any] = None,
) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Batched fused match+rank+top-k over each request's candidate rows
    — the served form of the launch.

    Request i is admitted only at ``rows[i]``: distinct rows below the
    live row count, or :data:`NO_ROW` for a slot that holds none. The
    lists go in a ``[B, C]`` bucket (:func:`candidate_bucket` of the
    longest) and the results come back the same way: returns (mask [B,C]
    bool, score [B,C] f32, topk_idx [B,k] i32, topk_scores [B,k] f32),
    where column j of request i is ``rows[i][j]``, and empty slots and
    columns past a request's list hold mask False and score -inf. The
    top-k is over all rows, as :func:`matchrank_batched` gives it.

    With ``use_kernel`` the plans and candidate rows cross to the device
    in one packed array and the results come back in one; a ``tracer``
    (:class:`repro.obs.Tracer`) times the two sides in
    ``broker.kernel_launch.copy_in`` (plan stacking, packing and the put)
    and ``broker.kernel_launch.fetch`` (the one copy back, which waits for
    the device). Without it the grouped host evaluation answers.
    """
    span = tracer.span if tracer is not None else _no_span
    with span("broker.kernel_launch.copy_in"):
        batched = plans if isinstance(plans, BatchedPlan) else stack_plans(list(plans))
        if use_kernel:
            operands, static, s, c = _candidate_launch(
                attrs, valid, batched, rows, n_rows, k, block_s
            )
    if not use_kernel:
        # grouped host evaluation — the jnp ref's [B,S,T] einsums are kept
        # as a parity oracle only (see _matchrank_batched_dense_host)
        s = attrs.shape[0] if n_rows is None else int(n_rows)
        cand = _candidate_matrix(rows, s)
        mask, score, ti, ts = _matchrank_batched_dense_host(
            attrs, valid, batched, admit_matrix(rows, s), s, k
        )
        live = cand < s
        at = np.minimum(cand, s - 1)
        cmask = np.take_along_axis(mask, at, axis=1) & live
        cscore = np.where(live, np.take_along_axis(score, at, axis=1), np.float32(NEG_INF))
        return cmask, cscore, ti, ts
    out = _dispatch_batched(*operands, **static, interpret=interpret)
    with span("broker.kernel_launch.fetch"):
        out = _to_host(out)
    kk = static["k"]
    return (
        out[:, :c] != 0,
        out[:, c : 2 * c].view(np.float32),
        out[:, 2 * c + kk :],
        out[:, 2 * c : 2 * c + kk].view(np.float32),
    )


def admit_matrix(rows: Sequence[Sequence[int]], n: int) -> np.ndarray:
    """Per-request candidate rows → the ``[B, n]`` admit pre-mask they
    stand for (an empty slot, any row ≥ n, admits nothing)."""
    admit = np.zeros((len(rows), n), dtype=np.float32)
    for bi, r in enumerate(rows):
        r = np.asarray(r, dtype=np.int64)
        admit[bi, r[r < n]] = 1.0
    return admit


def _admitted_rows(admit: Optional[np.ndarray], b: int, s: int) -> List[np.ndarray]:
    """A dense ``[B, S]`` pre-mask (None: every row) → each request's
    admitted rows."""
    if admit is None:
        return [np.arange(s)] * b
    a = np.asarray(admit)[:, :s] > 0.5
    return [np.flatnonzero(a[bi]) for bi in range(b)]


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
    tracer: Optional[Any] = None,
) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Batched fused match+rank+top-k: B requests against ONE candidate
    block in a single kernel launch.

    Returns (mask [B,S] bool, score [B,S] f32, topk_idx [B,k] i32,
    topk_scores [B,k] f32), trimmed to the live row count. Top-k slots
    beyond a request's match count hold score -inf (index is meaningless
    there, as in :func:`matchrank_topk`).

    The dense form of :func:`matchrank_candidates`: the admitted rows of
    each request are its candidates, and the results are laid back out
    over every row (mask False and score -inf off the candidates, as the
    kernel gives them there). ``tracer`` spans as there.
    """
    b = plans.b if isinstance(plans, BatchedPlan) else len(plans)
    s = attrs.shape[0] if n_rows is None else int(n_rows)
    if not use_kernel:
        batched = plans if isinstance(plans, BatchedPlan) else stack_plans(list(plans))
        return _matchrank_batched_dense_host(attrs, valid, batched, admit, s, k)
    rows = _admitted_rows(admit, b, s)
    cmask, cscore, ti, ts = matchrank_candidates(
        attrs, valid, plans, rows, n_rows=n_rows, k=k, block_s=block_s,
        interpret=interpret, tracer=tracer,
    )
    mask = np.zeros((b, s), dtype=bool)
    score = np.full((b, s), NEG_INF, dtype=np.float32)
    for bi, r in enumerate(rows):
        mask[bi, r] = cmask[bi, : len(r)]
        score[bi, r] = cscore[bi, : len(r)]
    return mask, score, ti, ts


def _no_span(name: str):
    return contextlib.nullcontext()


def _to_device(x: np.ndarray) -> jax.Array:
    """A launch's host-to-device transfer."""
    return jax.device_put(x)


def _to_host(x: jax.Array) -> np.ndarray:
    """A launch's device-to-host transfer (waits for the device)."""
    return np.asarray(x)


def _candidate_launch(
    attrs, valid, batched: BatchedPlan, rows, n_rows, k: int, block_s: int
) -> Tuple[Tuple[Any, ...], Dict[str, Any], int, int]:
    """→ (operands, static arguments, live rows, C) of the kernel launch
    that :func:`matchrank_candidates` makes."""
    attrs_p, valid_p, s, s_pad = _prepare_columns(
        attrs, valid, batched.a_pad, block_s, n_rows
    )
    cand = _candidate_matrix(rows, s_pad)
    packed = _to_device(_pack_launch(batched, cand))
    static = dict(t_pad=batched.t_pad, k=min(k, s), block_s=block_s, use_kernel=True)
    return (attrs_p, valid_p, packed), static, s, cand.shape[1]


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
    s = attrs.shape[0] if n_rows is None else int(n_rows)
    operands, static, _, _ = _candidate_launch(
        attrs, valid, batched, _admitted_rows(admit, batched.b, s), n_rows, k, block_s
    )
    return _dispatch_batched.lower(*operands, **static, interpret=interpret)
