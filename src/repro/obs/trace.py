"""Tracing spans: nested timing scopes exportable as Chrome trace events.

A :class:`Tracer` hands out :class:`Span` scopes via a context manager or
decorator; spans nest (parent/child through an explicit stack, no
thread-locals — the repo is single-controller per host) and the finished
buffer exports as Chrome ``traceEvents`` JSON, loadable in Perfetto or
``chrome://tracing``.

Around kernel dispatch the tracer can additionally enter a
``jax.profiler.TraceAnnotation`` so spans line up with XLA's own traces
(``jax_annotations=True``); the passthrough is best-effort and degrades
to a no-op when the profiler is unavailable.

Every full (generation-2) garbage collection is recorded as a parentless
``broker.gc`` span (:data:`GC_SPAN`): a process event that stalls whatever
span happens to be open, not a child of it.

The span buffer is bounded (``max_spans``): a serving process tracing
every batch keeps the most recent window instead of growing without
bound.
"""

from __future__ import annotations

import functools
import gc
import json
import time
import weakref
from collections import deque
from contextlib import contextmanager
from typing import Any, Callable, Deque, Dict, Iterator, List, Optional, Tuple

__all__ = ["GC_SPAN", "Span", "Tracer"]

#: name of the span a full garbage collection is recorded as
GC_SPAN = "broker.gc"


class Span:
    """One timing scope. ``duration`` is valid after the scope exits."""

    __slots__ = ("name", "span_id", "parent_id", "depth", "t0", "t1", "args")

    def __init__(self, name: str, span_id: int, parent_id: Optional[int],
                 depth: int, t0: float, args: Dict[str, Any]):
        self.name = name
        self.span_id = span_id
        self.parent_id = parent_id
        self.depth = depth
        self.t0 = t0
        self.t1: Optional[float] = None
        self.args = args

    @property
    def duration(self) -> float:
        return (self.t1 - self.t0) if self.t1 is not None else 0.0

    def set(self, **kv: Any) -> None:
        """Attach result attributes mid-scope (batch sizes, cache hits)."""
        self.args.update(kv)

    def to_event(self, epoch: float) -> Dict[str, Any]:
        """Chrome trace-event 'complete' (ph=X) form, µs timestamps."""
        return {
            "name": self.name,
            "cat": "repro",
            "ph": "X",
            "ts": (self.t0 - epoch) * 1e6,
            "dur": self.duration * 1e6,
            "pid": 0,
            "tid": self.depth,
            "args": {"span_id": self.span_id,
                     "parent_id": self.parent_id,
                     **{k: _jsonable(v) for k, v in self.args.items()}},
        }


def _jsonable(v: Any) -> Any:
    if isinstance(v, (str, int, float, bool)) or v is None:
        return v
    return repr(v)


def _gc_hook(ref: "weakref.ref[Tracer]") -> Callable[[str, Dict[str, Any]], None]:
    """A ``gc.callbacks`` entry that holds its tracer weakly, so that the
    registration does not keep the tracer alive."""

    def on_gc(phase: str, info: Dict[str, Any]) -> None:
        tracer = ref()
        if tracer is not None and info["generation"] == 2:
            tracer._on_full_gc(phase, info)

    return on_gc


def _unhook(callback: Callable) -> None:
    try:
        gc.callbacks.remove(callback)
    except ValueError:
        pass


class Tracer:
    """Span factory + bounded buffer of finished spans.

    Parameters
    ----------
    time_fn:
        Timestamp source; defaults to ``time.perf_counter``. Inject a
        deterministic clock's ``now`` for reproducible traces in tests.
    jax_annotations:
        Also enter ``jax.profiler.TraceAnnotation(name)`` for every span
        — used around kernel dispatch so broker spans appear inside
        ``jax.profiler`` traces.
    max_spans:
        Finished-span ring-buffer capacity.

    Full garbage collections land in the buffer as :data:`GC_SPAN` spans
    with no parent; the ``gc.callbacks`` hook goes with the tracer.
    """

    def __init__(
        self,
        *,
        time_fn: Optional[Callable[[], float]] = None,
        jax_annotations: bool = False,
        max_spans: int = 8192,
    ):
        self.time_fn = time_fn or time.perf_counter  # lint: allow-wallclock
        self.jax_annotations = bool(jax_annotations)
        self._annotation: Optional[Callable[[str], Any]] = None
        if self.jax_annotations:
            try:
                from jax.profiler import TraceAnnotation

                self._annotation = TraceAnnotation
            except ImportError:
                pass
        self._spans: Deque[Span] = deque(maxlen=int(max_spans))
        self._stack: List[Span] = []
        self._next_id = 1
        self.epoch = self.time_fn()
        self.dropped = 0
        self._gc_open: Optional[Tuple[Span, Any]] = None
        hook = _gc_hook(weakref.ref(self))
        gc.callbacks.append(hook)
        weakref.finalize(self, _unhook, hook)

    # ------------------------------------------------------------- scoping
    @contextmanager
    def span(self, name: str, **args: Any) -> Iterator[Span]:
        parent = self._stack[-1] if self._stack else None
        s = Span(
            name,
            self._next_id,
            parent.span_id if parent else None,
            len(self._stack),
            self.time_fn(),
            dict(args),
        )
        self._next_id += 1
        self._stack.append(s)
        annotation = self._annotate(name)
        try:
            yield s
        finally:
            if annotation is not None:
                annotation.__exit__(None, None, None)
            s.t1 = self.time_fn()
            self._stack.pop()
            self._finish(s)

    def _annotate(self, name: str) -> Any:
        """The entered ``TraceAnnotation`` for ``name``, or None."""
        if self._annotation is None:
            return None
        annotation = self._annotation(name)
        annotation.__enter__()
        return annotation

    def _finish(self, s: Span) -> None:
        if len(self._spans) == self._spans.maxlen:
            self.dropped += 1
        self._spans.append(s)

    def _on_full_gc(self, phase: str, info: Dict[str, Any]) -> None:
        """Open a :data:`GC_SPAN` at a full collection's start, close it
        at its stop; it takes no parent from the spans open around it."""
        if phase == "start":
            s = Span(GC_SPAN, self._next_id, None, 0, self.time_fn(), {})
            self._next_id += 1
            self._gc_open = (s, self._annotate(GC_SPAN))
        elif self._gc_open is not None:
            s, annotation = self._gc_open
            self._gc_open = None
            if annotation is not None:
                annotation.__exit__(None, None, None)
            s.t1 = self.time_fn()
            s.args["collected"] = info.get("collected", 0)
            self._finish(s)

    def trace(self, name: Optional[str] = None) -> Callable:
        """Decorator form: ``@tracer.trace("phase")``."""

        def deco(fn: Callable) -> Callable:
            span_name = name or fn.__qualname__

            @functools.wraps(fn)
            def wrapper(*a, **kw):
                with self.span(span_name):
                    return fn(*a, **kw)

            return wrapper

        return deco

    # ------------------------------------------------------------- reading
    def spans(self, name: Optional[str] = None) -> List[Span]:
        if name is None:
            return list(self._spans)
        return [s for s in self._spans if s.name == name]

    def clear(self) -> None:
        self._spans.clear()
        self.dropped = 0

    @property
    def depth(self) -> int:
        return len(self._stack)

    # -------------------------------------------------------------- export
    def export_chrome(self) -> Dict[str, Any]:
        """Chrome/Perfetto ``traceEvents`` JSON object."""
        return {
            "displayTimeUnit": "ms",
            "traceEvents": [s.to_event(self.epoch) for s in self._spans],
        }

    def dump_json(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump(self.export_chrome(), f, indent=2)
            f.write("\n")
