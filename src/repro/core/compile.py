"""ClassAd → columnar tensor compiler (beyond-paper, TPU adaptation).

The paper matches one request against tens of ads with a tree-walking
interpreter. At fleet scale (10⁴ clients × 10⁴ replicas, selection on
every shard fetch), the Match Phase becomes a hot loop. The TPU-native
observation is that matchmaking over *numeric* attributes is a columnar
predicate + scoring problem:

    attrs[S, A] (server attribute matrix)  ×  one compiled (requirements,
    rank) program  →  mask[S], score[S]  →  top-k.

This module compiles the request's ``requirements``/``rank`` ASTs — and
each *distinct* server-policy expression (servers publish policies drawn
from a small set of admin templates, so we group by expression source) —
into closures over an array namespace ``xp``. The same compiled program
executes under numpy (float64 — bit-identical selection semantics for the
broker) or ``jax.numpy`` under ``jit`` (float32 — throughput path, and the
front half of the Pallas ``matchrank`` kernel).

Undefined/Error semantics survive vectorization: every column carries a
validity mask and boolean results are Kleene (value, defined) pairs with
Condor's absorption rules (``False && Undefined == False``). Error is
conservatively folded into "not defined" — for match gating and ranking
the two are indistinguishable (neither is ``True``; a non-numeric rank is
0.0), so selections are identical to the interpreter's (property-tested).

Expressions that fall outside the columnar subset (string ops, list ops,
nested-ad selects) raise :class:`CompileError`; callers fall back to the
interpreter — the paper-faithful path is always available.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from .classads import (
    AttrRef,
    BinOp,
    ClassAd,
    Error,
    Expr,
    FuncCall,
    Literal,
    Ternary,
    UnaryOp,
    Undefined,
    evaluate,
)
from .matchmaker import rank_value

__all__ = [
    "CompileError",
    "Tri",
    "Num",
    "CompiledProgram",
    "compile_program",
    "ColumnTable",
    "build_columns",
    "vectorized_match",
    "extract_conjunctive_terms",
    "extract_linear_rank",
    "extract_rank_alternatives",
    "ConjTerm",
    "RankAlternative",
]


class CompileError(ValueError):
    """Expression falls outside the columnar subset."""


# ---------------------------------------------------------------------------
# Runtime representations
# ---------------------------------------------------------------------------


@dataclass
class Num:
    """A numeric array (or scalar) with a validity mask."""

    val: Any  # xp array [S] or python float
    ok: Any  # xp bool array [S] or python bool


@dataclass
class Tri:
    """Kleene boolean: (value, defined). Undefined/Error ⇒ defined=False."""

    val: Any
    ok: Any


class ColumnTable:
    """Named numeric columns with validity masks over S candidates."""

    def __init__(self, n: int):
        self.n = n
        self.cols: Dict[str, np.ndarray] = {}
        self.valid: Dict[str, np.ndarray] = {}

    def add(self, name: str, values: np.ndarray, valid: np.ndarray) -> None:
        self.cols[name.lower()] = values
        self.valid[name.lower()] = valid

    def has(self, name: str) -> bool:
        return name.lower() in self.cols

    def names(self) -> List[str]:
        return sorted(self.cols)


# ---------------------------------------------------------------------------
# Compilation
# ---------------------------------------------------------------------------

_SUPPORTED_FUNCS = {"ifthenelse", "isundefined", "abs", "min", "max", "floor", "ceiling"}


@dataclass
class _Ctx:
    """Compile-time context: which side is columns, which is constants."""

    column_ad: Optional[ClassAd]  # the ad whose attrs become columns (may be None)
    const_ad: Optional[ClassAd]  # the ad whose attrs are evaluated to scalars
    column_names: Callable[[str], bool]  # does a name exist as a column?
    env: Dict[str, Any]
    refs: List[str] = field(default_factory=list)  # columns referenced


def _const_value(ctx: _Ctx, name: str) -> Any:
    """Evaluate a constant-side attribute to a scalar at compile time."""
    if ctx.const_ad is None:
        return Undefined
    return ctx.const_ad.eval_attr(name, None, ctx.env)


def _lit_num(x: float) -> Callable:
    def run(tbl, xp):
        return Num(x, True)

    return run


def _lit_tri(b: Optional[bool]) -> Callable:
    def run(tbl, xp):
        if b is None:
            return Tri(False, False)
        return Tri(bool(b), True)

    return run


def _col_ref(name: str) -> Callable:
    low = name.lower()

    def run(tbl, xp):
        return Num(tbl.cols[low], tbl.valid[low])

    return run


def _broadcast_ok(a, b, xp):
    return xp.logical_and(a, b) if not (a is True and b is True) else True


def _and_ok(a, b, xp):
    if a is True:
        return b
    if b is True:
        return a
    return xp.logical_and(a, b)


def compile_expr(expr: Expr, ctx: _Ctx) -> Tuple[str, Callable]:
    """Compile to a closure ``f(table, xp) -> Num | Tri``.

    Returns ('num'|'tri', fn). Raises CompileError outside the subset.
    """
    if isinstance(expr, Literal):
        v = expr.value
        if isinstance(v, bool):
            return "tri", _lit_tri(v)
        if isinstance(v, (int, float)):
            return "num", _lit_num(float(v))
        if v is Undefined or v is Error:
            return "tri", _lit_tri(None)
        raise CompileError(f"literal {v!r} not columnar")

    if isinstance(expr, AttrRef):
        return _compile_attr(expr, ctx)

    if isinstance(expr, UnaryOp):
        kind, f = compile_expr(expr.operand, ctx)
        if expr.op == "!":
            if kind != "tri":
                raise CompileError("! on non-boolean")

            def run_not(tbl, xp, f=f):
                t = f(tbl, xp)
                return Tri(xp.logical_not(t.val), t.ok)

            return "tri", run_not
        if kind != "num":
            raise CompileError("unary +/- on non-numeric")
        sign = -1.0 if expr.op == "-" else 1.0

        def run_neg(tbl, xp, f=f, sign=sign):
            v = f(tbl, xp)
            return Num(v.val * sign, v.ok)

        return "num", run_neg

    if isinstance(expr, BinOp):
        return _compile_binop(expr, ctx)

    if isinstance(expr, Ternary):
        ck, cf = compile_expr(expr.cond, ctx)
        if ck != "tri":
            raise CompileError("ternary condition must be boolean")
        tk, tf = compile_expr(expr.then, ctx)
        ek, ef = compile_expr(expr.other, ctx)
        if tk != ek:
            raise CompileError("ternary arms must have the same kind")
        if tk == "num":

            def run_tern_n(tbl, xp, cf=cf, tf=tf, ef=ef):
                c, t, e = cf(tbl, xp), tf(tbl, xp), ef(tbl, xp)
                val = xp.where(c.val, t.val, e.val)
                ok = _and_ok(c.ok, xp.where(c.val, _ok_arr(t.ok, xp), _ok_arr(e.ok, xp)), xp)
                return Num(val, ok)

            return "num", run_tern_n

        def run_tern_b(tbl, xp, cf=cf, tf=tf, ef=ef):
            c, t, e = cf(tbl, xp), tf(tbl, xp), ef(tbl, xp)
            val = xp.where(c.val, t.val, e.val)
            ok = _and_ok(c.ok, xp.where(c.val, _ok_arr(t.ok, xp), _ok_arr(e.ok, xp)), xp)
            return Tri(val, ok)

        return "tri", run_tern_b

    if isinstance(expr, FuncCall):
        return _compile_func(expr, ctx)

    raise CompileError(f"{type(expr).__name__} not columnar")


def _ok_arr(ok, xp):
    return ok if ok is not True else xp.asarray(True)


def _compile_attr(expr: AttrRef, ctx: _Ctx) -> Tuple[str, Callable]:
    name = expr.name
    scope = expr.scope
    # Decide column vs constant, mirroring the interpreter's lookup order:
    # unqualified → self (const side here is 'my'), then other.
    if scope == "other":
        side = "column"
    elif scope == "my":
        side = "const"
    else:
        if ctx.const_ad is not None and name.lower() in ctx.const_ad:
            side = "const"
        elif ctx.column_names(name):
            side = "column"
        elif name.lower() in ctx.env:
            v = ctx.env[name.lower()]
            if isinstance(v, bool):
                return "tri", _lit_tri(v)
            if isinstance(v, (int, float)):
                return "num", _lit_num(float(v))
            raise CompileError(f"env value {name} not numeric")
        else:
            # unknown everywhere: Undefined
            return "tri", _lit_tri(None)

    if side == "const":
        v = _const_value(ctx, name)
        if isinstance(v, bool):
            return "tri", _lit_tri(v)
        if isinstance(v, (int, float)):
            return "num", _lit_num(float(v))
        if v is Undefined or v is Error:
            return "tri", _lit_tri(None)
        raise CompileError(f"constant attr {name} is non-numeric: {v!r}")

    # column side — even when compiling the *request* ("other" = server),
    # or a server policy (unqualified = server's own columns).
    ctx.refs.append(name.lower())
    low = name.lower()

    def run(tbl, xp, low=low):
        if low not in tbl.cols:
            # column absent for every candidate ⇒ Undefined
            return Num(xp.zeros((tbl.n,)), xp.zeros((tbl.n,), dtype=bool))
        return Num(tbl.cols[low], tbl.valid[low])

    return "num", run


_NUM_BIN = {"+", "-", "*", "/", "%"}
_CMP_BIN = {"==", "!=", "<", "<=", ">", ">="}


def _compile_binop(expr: BinOp, ctx: _Ctx) -> Tuple[str, Callable]:
    op = expr.op
    if op in ("&&", "||"):
        lk, lf = compile_expr(expr.left, ctx)
        rk, rf = compile_expr(expr.right, ctx)
        if lk != "tri" or rk != "tri":
            raise CompileError(f"{op} on non-boolean")
        if op == "&&":

            def run_and(tbl, xp, lf=lf, rf=rf):
                l, r = lf(tbl, xp), rf(tbl, xp)
                val = xp.logical_and(l.val, r.val)
                l_ok, r_ok = _ok_arr(l.ok, xp), _ok_arr(r.ok, xp)
                # defined if both defined, or either side is a defined False
                ok = xp.logical_or(
                    xp.logical_and(l_ok, r_ok),
                    xp.logical_or(
                        xp.logical_and(l_ok, xp.logical_not(l.val)),
                        xp.logical_and(r_ok, xp.logical_not(r.val)),
                    ),
                )
                return Tri(val, ok)

            return "tri", run_and

        def run_or(tbl, xp, lf=lf, rf=rf):
            l, r = lf(tbl, xp), rf(tbl, xp)
            val = xp.logical_or(l.val, r.val)
            l_ok, r_ok = _ok_arr(l.ok, xp), _ok_arr(r.ok, xp)
            ok = xp.logical_or(
                xp.logical_and(l_ok, r_ok),
                xp.logical_or(
                    xp.logical_and(l_ok, l.val), xp.logical_and(r_ok, r.val)
                ),
            )
            return Tri(val, ok)

        return "tri", run_or

    if op in ("=?=", "=!="):
        raise CompileError("identity comparison not columnar")  # rarely numeric

    lk, lf = compile_expr(expr.left, ctx)
    rk, rf = compile_expr(expr.right, ctx)
    if lk != "num" or rk != "num":
        raise CompileError(f"{op} requires numeric operands")

    if op in _CMP_BIN:
        import operator

        fns = {
            "==": operator.eq,
            "!=": operator.ne,
            "<": operator.lt,
            "<=": operator.le,
            ">": operator.gt,
            ">=": operator.ge,
        }
        cmp = fns[op]

        def run_cmp(tbl, xp, lf=lf, rf=rf, cmp=cmp):
            l, r = lf(tbl, xp), rf(tbl, xp)
            return Tri(cmp(l.val, r.val), _and_ok(l.ok, r.ok, xp))

        return "tri", run_cmp

    if op in _NUM_BIN:

        def run_arith(tbl, xp, lf=lf, rf=rf, op=op):
            l, r = lf(tbl, xp), rf(tbl, xp)
            ok = _and_ok(l.ok, r.ok, xp)
            if op == "+":
                v = l.val + r.val
            elif op == "-":
                v = l.val - r.val
            elif op == "*":
                v = l.val * r.val
            elif op == "/":
                denom_ok = r.val != 0
                v = l.val / xp.where(denom_ok, r.val, 1.0)
                ok = _and_ok(ok, denom_ok, xp)
            else:  # %
                denom_ok = r.val != 0
                v = xp.where(denom_ok, l.val - xp.trunc(l.val / xp.where(denom_ok, r.val, 1.0)) * r.val, 0.0)
                ok = _and_ok(ok, denom_ok, xp)
            return Num(v, ok)

        return "num", run_arith

    raise CompileError(f"operator {op} not columnar")  # pragma: no cover


def _compile_func(expr: FuncCall, ctx: _Ctx) -> Tuple[str, Callable]:
    name = expr.name
    if name not in _SUPPORTED_FUNCS:
        raise CompileError(f"builtin {name}() not columnar")
    if name == "isundefined":
        (arg,) = expr.args
        kind, f = compile_expr(arg, ctx)

        def run_isundef(tbl, xp, f=f):
            v = f(tbl, xp)
            ok = _ok_arr(v.ok, xp)
            return Tri(xp.logical_not(ok), True)

        return "tri", run_isundef
    if name == "ifthenelse":
        c, t, e = expr.args
        return compile_expr(Ternary(c, t, e), ctx)
    if name == "abs":
        (arg,) = expr.args
        kind, f = compile_expr(arg, ctx)
        if kind != "num":
            raise CompileError("abs on non-numeric")

        def run_abs(tbl, xp, f=f):
            v = f(tbl, xp)
            return Num(xp.abs(v.val), v.ok)

        return "num", run_abs
    if name in ("floor", "ceiling"):
        (arg,) = expr.args
        kind, f = compile_expr(arg, ctx)
        if kind != "num":
            raise CompileError(f"{name} on non-numeric")
        g = np.floor if name == "floor" else np.ceil

        def run_fc(tbl, xp, f=f, name=name):
            v = f(tbl, xp)
            fn = xp.floor if name == "floor" else xp.ceil
            return Num(fn(v.val), v.ok)

        return "num", run_fc
    # min/max over 2+ numeric args
    fs = []
    for a in expr.args:
        kind, f = compile_expr(a, ctx)
        if kind != "num":
            raise CompileError(f"{name} on non-numeric")
        fs.append(f)
    take_min = name == "min"

    def run_mm(tbl, xp, fs=tuple(fs), take_min=take_min):
        vals = [f(tbl, xp) for f in fs]
        acc = vals[0].val
        ok = vals[0].ok
        for v in vals[1:]:
            acc = xp.minimum(acc, v.val) if take_min else xp.maximum(acc, v.val)
            ok = _and_ok(ok, v.ok, xp)
        return Num(acc, ok)

    return "num", run_mm


# ---------------------------------------------------------------------------
# Whole-program compilation
# ---------------------------------------------------------------------------


@dataclass
class CompiledProgram:
    """A compiled (requirements, rank) pair for one request (plus the
    distinct server-policy programs it must be symmetric against)."""

    req_fn: Optional[Callable]  # f(tbl, xp) -> Tri, None means no requirements
    rank_fn: Optional[Callable]  # f(tbl, xp) -> Num, None means rank 0
    referenced: List[str]

    def run(self, tbl: ColumnTable, xp=np) -> Tuple[np.ndarray, np.ndarray]:
        """→ (mask[S] bool, rank[S] float). Undefined rank → 0."""
        if self.req_fn is None:
            mask = xp.ones((tbl.n,), dtype=bool)
        else:
            t = self.req_fn(tbl, xp)
            ok = _ok_arr(t.ok, xp)
            mask = xp.logical_and(xp.asarray(t.val), ok)
            mask = xp.broadcast_to(mask, (tbl.n,))
        if self.rank_fn is None:
            rank = xp.zeros((tbl.n,))
        else:
            r = self.rank_fn(tbl, xp)
            ok = _ok_arr(r.ok, xp)
            rank = xp.where(ok, r.val, 0.0)
            rank = xp.broadcast_to(xp.asarray(rank, dtype=xp.asarray(0.0).dtype), (tbl.n,))
        return mask, rank


def compile_program(
    request: ClassAd,
    *,
    column_names: Callable[[str], bool],
    env: Optional[Dict[str, Any]] = None,
) -> CompiledProgram:
    """Compile a request ad's requirements+rank against server columns."""
    env = {k.lower(): v for k, v in (env or {}).items()}
    ctx = _Ctx(column_ad=None, const_ad=request, column_names=column_names, env=env)
    req_fn = None
    if "requirements" in request:
        kind, fn = compile_expr(request["requirements"], ctx)
        if kind != "tri":
            raise CompileError("requirements must be boolean")
        req_fn = fn
    rank_fn = None
    if "rank" in request:
        kind, fn = compile_expr(request["rank"], ctx)
        if kind == "tri":
            # boolean rank: true→1.0 (Condor)
            bfn = fn

            def rank_from_bool(tbl, xp, bfn=bfn):
                t = bfn(tbl, xp)
                return Num(xp.where(t.val, 1.0, 0.0), t.ok)

            rank_fn = rank_from_bool
        else:
            rank_fn = fn
    return CompiledProgram(req_fn, rank_fn, sorted(set(ctx.refs)))


def compile_policy(
    policy_expr: Expr,
    request: ClassAd,
    *,
    column_names: Callable[[str], bool],
    env: Optional[Dict[str, Any]] = None,
) -> Callable:
    """Compile a *server-side* policy: unqualified/my = server columns,
    other = the (constant) request. Returns f(tbl, xp) -> Tri."""
    env = {k.lower(): v for k, v in (env or {}).items()}

    # Swap roles: other.→const(request); unqualified/my.→columns.
    def swap(expr: Expr) -> Expr:
        if isinstance(expr, AttrRef):
            if expr.scope == "other":
                return AttrRef("my", expr.name)  # resolves in const_ad
            if expr.scope == "my" or expr.scope is None:
                return AttrRef("other", expr.name)  # resolves to columns
            return expr
        if isinstance(expr, UnaryOp):
            return UnaryOp(expr.op, swap(expr.operand))
        if isinstance(expr, BinOp):
            return BinOp(expr.op, swap(expr.left), swap(expr.right))
        if isinstance(expr, Ternary):
            return Ternary(swap(expr.cond), swap(expr.then), swap(expr.other))
        if isinstance(expr, FuncCall):
            return FuncCall(expr.name, tuple(swap(a) for a in expr.args))
        return expr

    ctx = _Ctx(column_ad=None, const_ad=request, column_names=column_names, env=env)
    kind, fn = compile_expr(swap(policy_expr), ctx)
    if kind != "tri":
        raise CompileError("policy must be boolean")
    return fn


# ---------------------------------------------------------------------------
# Column building + end-to-end vectorized match
# ---------------------------------------------------------------------------


def build_columns(entries: Sequence[Dict[str, Any]], names: Sequence[str]) -> ColumnTable:
    """Assemble named numeric columns (with validity) from entry dicts."""
    n = len(entries)
    tbl = ColumnTable(n)
    for name in names:
        low = name.lower()
        vals = np.zeros((n,), dtype=np.float64)
        ok = np.zeros((n,), dtype=bool)
        for i, e in enumerate(entries):
            v = None
            for k, x in e.items():
                if k.lower() == low:
                    v = x
                    break
            if isinstance(v, bool):
                vals[i] = 1.0 if v else 0.0
                ok[i] = True
            elif isinstance(v, (int, float)):
                vals[i] = float(v)
                ok[i] = True
        tbl.add(low, vals, ok)
    return tbl


def vectorized_match(request: ClassAd, views: Sequence, *, env=None, xp=np):
    """Drop-in replacement for the interpreted Match Phase.

    Returns rank-sorted ``RankedReplica`` list identical to the
    interpreter's, or None if the request (or any server policy) falls
    outside the columnar subset.
    """
    from .broker import RankedReplica  # local import to avoid cycle
    from .classads import parse as parse_expr

    if not views:
        return []
    entries = [v.entry for v in views]
    present: set = set()
    for e in entries:
        present.update(k.lower() for k in e.keys())

    try:
        prog = compile_program(request, column_names=lambda n: n.lower() in present, env=env)
        # group server policies by source text; compile each once
        policy_groups: Dict[str, List[int]] = {}
        for i, v in enumerate(views):
            pexpr = v.ad.lookup_expr("requirements")
            key = repr(pexpr) if pexpr is not None else ""
            policy_groups.setdefault(key, []).append(i)
        policy_fns: Dict[str, Optional[Callable]] = {}
        for key in policy_groups:
            if key == "":
                policy_fns[key] = None
                continue
            policy_fns[key] = compile_policy(
                parse_expr(key), request, column_names=lambda n: n.lower() in present, env=env
            )
    except CompileError:
        return None

    names = set(prog.referenced)
    # policies may reference more columns; recompile-collect via a dry ref scan
    tbl = build_columns(entries, sorted(present))  # build all numeric columns
    mask, rank = prog.run(tbl, xp)
    mask = np.asarray(mask, dtype=bool).copy()
    rank = np.asarray(rank, dtype=np.float64)

    for key, idxs in policy_groups.items():
        fn = policy_fns[key]
        if fn is None:
            continue
        t = fn(tbl, xp)
        ok = t.ok if t.ok is not True else np.ones((tbl.n,), dtype=bool)
        pol = np.logical_and(np.broadcast_to(np.asarray(t.val), (tbl.n,)),
                             np.broadcast_to(np.asarray(ok), (tbl.n,)))
        sel = np.zeros((tbl.n,), dtype=bool)
        sel[idxs] = True
        mask &= np.where(sel, pol, True)

    order = _ranked_indices(mask, rank, views)
    return [RankedReplica(views[i], float(rank[i])) for i in order]


def _ranked_indices(mask: np.ndarray, rank: np.ndarray, views) -> List[int]:
    """Descending rank with the interpreter's deterministic tiebreak."""

    def name_of(i):
        e = views[i].entry
        for attr in ("name", "hostname", "endpoint", "url"):
            for k, v in e.items():
                if k.lower() == attr and isinstance(v, str):
                    return v
        return f"resource-{i}"

    idx = [i for i in range(len(views)) if mask[i]]
    idx.sort(key=lambda i: (-rank[i], name_of(i), i))
    return idx


# ---------------------------------------------------------------------------
# Kernel lowering: conjunctive-threshold extraction
# ---------------------------------------------------------------------------

#: opcode encoding shared with kernels/matchrank
OPCODES = {"<": 0, "<=": 1, ">": 2, ">=": 3, "==": 4, "!=": 5}


@dataclass(frozen=True)
class ConjTerm:
    attr: str
    op: str  # one of OPCODES
    threshold: float
    #: the term passes where the attribute is Undefined — lowered from
    #: ``isUndefined(other.attr) || other.attr OP c``; otherwise an
    #: Undefined attribute fails it, as a requirement fails closed
    undefined_passes: bool = False


@dataclass(frozen=True)
class RankAlternative:
    """One branch of a rank ``ifThenElse`` chain: where every ``gate`` term
    holds (an empty gate always holds), the rank is ``num / den`` — two
    linear forms ``{attr: weight, "": bias}``, ``den`` None for 1."""

    gate: Tuple[ConjTerm, ...]
    num: Dict[str, float]
    den: Optional[Dict[str, float]] = None


def _candidate_attr(expr: Expr, request: ClassAd) -> Optional[str]:
    """The candidate attribute ``expr`` names: ``other.a``, or an
    unqualified ``a`` that the request does not define (the interpreter
    looks an unqualified name up in the request first)."""
    if isinstance(expr, AttrRef) and (
        expr.scope == "other" or (expr.scope is None and expr.name.lower() not in request)
    ):
        return expr.name.lower()
    return None


def _request_only(expr: Expr, request: ClassAd, env, seen: Optional[set] = None) -> bool:
    """True when ``expr`` reads nothing of the candidate, so that it folds
    to one value per request: only literals, the request's own attributes
    (themselves request-only) and unqualified environment names."""
    seen = set() if seen is None else seen
    if isinstance(expr, Literal):
        return True
    if isinstance(expr, AttrRef):
        name = expr.name.lower()
        if expr.scope == "other":
            return False
        if name in request:
            if name in seen:
                return True
            seen.add(name)
            return _request_only(request.lookup_expr(name), request, env, seen)
        return expr.scope is None and name in {k.lower() for k in (env or {})}
    if isinstance(expr, UnaryOp):
        return _request_only(expr.operand, request, env, seen)
    if isinstance(expr, BinOp):
        return _request_only(expr.left, request, env, seen) and _request_only(
            expr.right, request, env, seen
        )
    if isinstance(expr, Ternary):
        return all(_request_only(e, request, env, seen) for e in (expr.cond, expr.then, expr.other))
    if isinstance(expr, FuncCall):
        return all(_request_only(a, request, env, seen) for a in expr.args)
    return False


def _request_value(expr: Expr, request: ClassAd, env) -> Any:
    """A request-only expression's value; ``None`` when it reads the candidate."""
    if not _request_only(expr, request, env):
        return None
    try:
        return evaluate(expr, request, None, env)
    except Exception:
        return Error


def _scalar_of(expr: Expr, request: ClassAd, env) -> Optional[float]:
    """Evaluate an expression that involves only the request/env to a float."""
    v = _request_value(expr, request, env)
    if isinstance(v, bool) or not isinstance(v, (int, float)):
        return None
    return float(v)


def _threshold_term(e: Expr, request: ClassAd, env) -> Optional[ConjTerm]:
    """``other.attr OP scalar`` or ``scalar OP other.attr`` → a term."""
    if not (isinstance(e, BinOp) and e.op in OPCODES):
        return None
    for attr_side, const_side, flip in ((e.left, e.right, False), (e.right, e.left, True)):
        attr = _candidate_attr(attr_side, request)
        if attr is None:
            continue
        c = _scalar_of(const_side, request, env)
        if c is None:
            continue
        op = e.op
        if flip:
            op = {"<": ">", "<=": ">=", ">": "<", ">=": "<=", "==": "==", "!=": "!="}[op]
        return ConjTerm(attr, op, c)
    return None


def _flatten(e: Expr, op: str) -> List[Expr]:
    if isinstance(e, BinOp) and e.op == op:
        return _flatten(e.left, op) + _flatten(e.right, op)
    return [e]


def _is_undefined_of(e: Expr, request: ClassAd) -> Optional[str]:
    """``isUndefined(other.a)`` → ``a``."""
    if isinstance(e, FuncCall) and e.name == "isundefined" and len(e.args) == 1:
        return _candidate_attr(e.args[0], request)
    return None


#: a disjunction that folds to true for this request: no term at all
_ALWAYS = ConjTerm("", "<", 0.0)


def _guarded_clause(e: Expr, request: ClassAd, env) -> Optional[ConjTerm]:
    """A disjunction ``isUndefined(other.a) || <request constants> ||
    other.a OP c`` (any order, any nesting) → one term on ``a`` that an
    Undefined ``a`` passes. A constant disjunct that folds to true makes
    the clause always true (``_ALWAYS``); one that does not cannot make
    it true and drops out. No guard: the plain term. A guard alone: a
    term only an Undefined ``a`` passes. Anything else — two terms, two
    guarded attributes, a guard on another attribute — is None."""
    guards: set = set()
    terms: List[ConjTerm] = []
    for d in _flatten(e, "||"):
        g = _is_undefined_of(d, request)
        if g is not None:
            guards.add(g)
            continue
        t = _threshold_term(d, request, env)
        if t is not None:
            terms.append(t)
            continue
        v = _request_value(d, request, env)
        if v is None:
            return None
        if v is True:
            return _ALWAYS
    if len(terms) > 1 or len(guards) > 1:
        return None
    if not guards:
        return terms[0] if terms else None
    (attr,) = guards
    if not terms:
        return ConjTerm(attr, "<", float("-inf"), undefined_passes=True)
    if terms[0].attr != attr:
        return None
    return ConjTerm(attr, terms[0].op, terms[0].threshold, undefined_passes=True)


def extract_conjunctive_terms(
    expr: Expr, request: ClassAd, *, env=None
) -> Optional[List[ConjTerm]]:
    """If ``expr`` is a conjunction of ``other.attr OP const`` comparisons,
    return the terms for the Pallas kernel path; else None.

    ``const`` may be any request-side scalar expression (e.g.
    ``my.reqdSpace * 2``) — it is folded at extraction time. A conjunct
    may also be a guarded disjunction, ``isUndefined(other.a) || <request
    constants> || other.a OP c``, which becomes one term with
    ``undefined_passes`` (or drops out when a constant folds to true).
    A general ``||`` is not lowered.
    """
    terms: List[ConjTerm] = []

    def walk(e: Expr) -> bool:
        if isinstance(e, BinOp) and e.op == "&&":
            return walk(e.left) and walk(e.right)
        if isinstance(e, BinOp) and e.op == "||":
            t = _guarded_clause(e, request, env)
            if t is None:
                return False
            if t is not _ALWAYS:
                terms.append(t)
            return True
        t = _threshold_term(e, request, env)
        if t is not None:
            terms.append(t)
            return True
        if isinstance(e, Literal) and e.value is True:
            return True
        return False

    return terms if walk(expr) else None


def extract_linear_rank(
    expr: Expr, request: ClassAd, *, env=None
) -> Optional[Dict[str, float]]:
    """If ``rank`` is (a constant multiple / sum of) ``other.attr`` terms,
    return {attr: weight, '': bias} for the kernel's dot-product scorer."""
    weights: Dict[str, float] = {}

    def add(attr: str, w: float) -> None:
        weights[attr] = weights.get(attr, 0.0) + w

    def walk(e: Expr, scale: float) -> bool:
        attr = _candidate_attr(e, request)
        if attr is not None:
            add(attr, scale)
            return True
        if isinstance(e, BinOp) and e.op == "+":
            return walk(e.left, scale) and walk(e.right, scale)
        if isinstance(e, BinOp) and e.op == "-":
            return walk(e.left, scale) and walk(e.right, -scale)
        if isinstance(e, BinOp) and e.op == "*":
            c = _scalar_of(e.left, request, env)
            if c is not None:
                return walk(e.right, scale * c)
            c = _scalar_of(e.right, request, env)
            if c is not None:
                return walk(e.left, scale * c)
            return False
        if isinstance(e, BinOp) and e.op == "/":
            c = _scalar_of(e.right, request, env)
            if c is not None and c != 0:
                return walk(e.left, scale / c)
            return False
        if isinstance(e, UnaryOp) and e.op == "-":
            return walk(e.operand, -scale)
        c = _scalar_of(e, request, env)
        if c is not None:
            add("", scale * c)
            return True
        return False

    return weights if walk(expr, 1.0) else None


#: a gate that folds to false for this request: its branch is never taken
_NEVER: Tuple[ConjTerm, ...] = (ConjTerm("", "<", 0.0),)


def _guarded_gate(e: Expr, request: ClassAd, env) -> Optional[Tuple[ConjTerm, ...]]:
    """An ``ifThenElse`` condition → its terms, all of which must pass.

    The condition must be a conjunction of ``!isUndefined(other.a)``
    guards, thresholds on guarded attributes and request constants. The
    guards keep the condition from ever being Undefined — which would make
    the whole rank Undefined instead of taking the else branch — so it is
    exactly the conjunction of fail-closed terms. A guard with no
    threshold of its own becomes the term ``a >= -inf``, which only a
    defined ``a`` passes. A constant that folds to false gives ``_NEVER``;
    one that folds to anything but a boolean, an unguarded threshold or
    any other shape gives None."""
    guards: set = set()
    terms: List[ConjTerm] = []
    for c in _flatten(e, "&&"):
        if isinstance(c, UnaryOp) and c.op == "!":
            g = _is_undefined_of(c.operand, request)
            if g is not None:
                guards.add(g)
                continue
        t = _threshold_term(c, request, env)
        if t is not None:
            terms.append(t)
            continue
        v = _request_value(c, request, env)
        if v is False:
            return _NEVER
        if v is not True:
            return None
    if any(t.attr not in guards for t in terms):
        return None
    bare = sorted(guards - {t.attr for t in terms})
    return tuple(terms) + tuple(ConjTerm(a, ">=", float("-inf")) for a in bare)


def _rank_value(
    e: Expr, request: ClassAd, env
) -> Optional[Tuple[Dict[str, float], Optional[Dict[str, float]]]]:
    """A rank branch's value: a linear form, or a quotient of two."""
    lin = extract_linear_rank(e, request, env=env)
    if lin is not None:
        return lin, None
    if isinstance(e, BinOp) and e.op == "/":
        num = extract_linear_rank(e.left, request, env=env)
        den = extract_linear_rank(e.right, request, env=env)
        if num is not None and den is not None:
            return num, den
    return None


def extract_rank_alternatives(
    expr: Expr, request: ClassAd, *, env=None
) -> Optional[List[RankAlternative]]:
    """If ``rank`` is a linear form, a quotient of two, or an ``ifThenElse``
    chain of them whose conditions are guarded conjunctions (see
    :func:`_guarded_gate`), return its branches in order — the rank is the
    first branch whose gate holds; the last branch's gate is empty. Else
    None. Branches that can never be taken are left out; branches after
    one that is always taken are unreachable and left out too."""
    alts: List[RankAlternative] = []
    e = expr
    while True:
        if isinstance(e, FuncCall) and e.name == "ifthenelse" and len(e.args) == 3:
            cond, then, other = e.args
        elif isinstance(e, Ternary):
            cond, then, other = e.cond, e.then, e.other
        else:
            value = _rank_value(e, request, env)
            if value is None:
                return None
            alts.append(RankAlternative((), *value))
            return alts
        gate = _guarded_gate(cond, request, env)
        if gate is None:
            return None
        if gate is not _NEVER:
            value = _rank_value(then, request, env)
            if value is None:
                return None
            alts.append(RankAlternative(gate, *value))
            if not gate:
                return alts
        e = other
