"""LRU plan cache: skip ClassAd tree traversal for repeated request shapes.

Fleet traffic is template-heavy: thousands of clients submit requests
minted from the same few helpers (``default_read_request`` et al.), so the
broker keeps re-lowering structurally identical (requirements, rank)
pairs. This cache fronts the two compilation tiers:

  * :func:`repro.kernels.matchrank.ops.lower_request` → ``KernelPlan``
    (the Pallas / batched-kernel tier),
  * :func:`repro.core.compile.compile_program` → ``CompiledProgram`` and
    ``compile_policy`` → policy closures (the columnar tier).

Keys canonicalize the *content* of the request — the source of every
attribute expression (constants like ``reqdSpace = 5G`` are folded into
thresholds at lowering time, so they must key the entry) — plus the
column vocabulary and the evaluation environment. ``CompileError``s are
cached too (negative caching): a request that falls outside a tier's
subset skips the failed traversal on every retry and falls through to
the next tier immediately.
"""

from __future__ import annotations

from collections import OrderedDict
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

from .classads import ClassAd
from .compile import CompileError, CompiledProgram, compile_policy, compile_program

__all__ = ["PlanCache", "request_cache_key"]


def request_cache_key(
    request: ClassAd,
    vocab_key: Tuple[str, ...],
    env: Optional[Dict[str, Any]] = None,
) -> Tuple:
    """Canonical structural identity of (request, vocabulary, env).

    Two requests with identical attribute sources get identical keys even
    if parsed from different ad objects; any constant that lowering would
    fold (e.g. ``my.reqdSpace``) is part of the key by construction.
    """
    attrs = tuple(sorted((name.lower(), repr(expr)) for name, expr in request.items()))
    env_key = tuple(sorted((k.lower(), repr(v)) for k, v in (env or {}).items()))
    return (attrs, tuple(vocab_key), env_key)


class PlanCache:
    """A bounded LRU over compiled request artifacts.

    One instance per broker (decentralized, like the matchmaker) or one
    shared instance per serving process — entries are immutable once
    built, so sharing is safe for concurrent readers.
    """

    def __init__(self, maxsize: int = 256, *, metrics: Any = None):
        self.maxsize = int(maxsize)
        self._entries: "OrderedDict[Tuple, Any]" = OrderedDict()
        self.stats = {
            "hits": 0,
            "misses": 0,
            "evictions": 0,
            "negative_hits": 0,
        }
        # optional mirror into an obs MetricsRegistry (labels: event=...);
        # self.stats stays the source of truth for exact-count consumers
        self._mctr = (
            {
                ev: metrics.counter(
                    "plan_cache_events_total", "plan-cache lookups by outcome", event=ev
                )
                for ev in self.stats
            }
            if metrics is not None
            else None
        )

    def _bump(self, event: str) -> None:
        self.stats[event] += 1
        if self._mctr is not None:
            self._mctr[event].inc()

    # ------------------------------------------------------------- plumbing
    def _get(self, key: Tuple) -> Tuple[bool, Any]:
        if key in self._entries:
            self._entries.move_to_end(key)
            val = self._entries[key]
            self._bump("negative_hits" if isinstance(val, CompileError) else "hits")
            return True, val
        self._bump("misses")
        return False, None

    def _put(self, key: Tuple, val: Any) -> None:
        self._entries[key] = val
        self._entries.move_to_end(key)
        while len(self._entries) > self.maxsize:
            self._entries.popitem(last=False)
            self._bump("evictions")

    def _cached_compile(self, key: Tuple, build: Callable[[], Any]) -> Any:
        hit, val = self._get(key)
        if hit:
            if isinstance(val, CompileError):
                raise CompileError(str(val))
            return val
        try:
            val = build()
        except CompileError as e:
            self._put(key, e)
            raise
        self._put(key, val)
        return val

    # ------------------------------------------------------------ interfaces
    def kernel_plan(
        self,
        request: ClassAd,
        attr_names: Sequence[str],
        *,
        env: Optional[Dict[str, Any]] = None,
    ):
        """Cached :func:`lower_request` → ``KernelPlan`` (raises
        ``CompileError`` — negatively cached — outside the kernel subset)."""
        # deferred: kernels pull in jax/pallas
        from repro.kernels.matchrank.ops import lower_request

        vocab = tuple(n.lower() for n in attr_names)
        key = ("kernel",) + request_cache_key(request, vocab, env)
        return self._cached_compile(
            key, lambda: lower_request(request, vocab, env=env)
        )

    def columnar_program(
        self,
        request: ClassAd,
        vocab_key: Tuple[str, ...],
        *,
        env: Optional[Dict[str, Any]] = None,
    ) -> CompiledProgram:
        """Cached :func:`compile_program` against a named column set."""
        vocab = tuple(n.lower() for n in vocab_key)
        present = frozenset(vocab)
        key = ("columnar",) + request_cache_key(request, vocab, env)
        return self._cached_compile(
            key,
            lambda: compile_program(
                request, column_names=lambda n: n.lower() in present, env=env
            ),
        )

    def policy_fn(
        self,
        policy_src: str,
        request: ClassAd,
        vocab_key: Tuple[str, ...],
        *,
        env: Optional[Dict[str, Any]] = None,
    ) -> Callable:
        """Cached server-policy compile (policy text × request constants)."""
        from .classads import parse as parse_expr

        vocab = tuple(n.lower() for n in vocab_key)
        present = frozenset(vocab)
        key = ("policy", policy_src) + request_cache_key(request, vocab, env)
        return self._cached_compile(
            key,
            lambda: compile_policy(
                parse_expr(policy_src),
                request,
                column_names=lambda n: n.lower() in present,
                env=env,
            ),
        )

    def clear(self) -> None:
        self._entries.clear()

    def __len__(self) -> int:
        return len(self._entries)
