"""Coalescing selection scheduler: many concurrent broker selections,
few kernel launches.

Serving replicas, data-pipeline workers, and checkpoint restores all
issue storms of small ``broker.select`` calls that hit the same published
GRIS snapshot. The :class:`BatchScheduler` queues them and flushes the
queue through :meth:`DataBroker.select_many` — one stacked
``matchrank_batched`` launch per flush — under two triggers:

  * **size**: the queue reached ``max_batch`` (a full kernel batch),
  * **latency**: the oldest queued request has waited ``max_delay``
    (checked by :meth:`poll`, driven by the injected deterministic
    clock — nothing here spawns threads),

plus an explicit :meth:`flush`, and an implicit one when a caller forces
a ticket's :meth:`~SelectionTicket.result` (a synchronous caller never
deadlocks waiting on its own unflushed batch).

Each ticket's wait in the queue (submit to the start of its flush, on the
tracer's span clock) adds to ``stats["queue_wait_s"]`` over
``stats["queue_waited"]`` tickets, and to the registry's
``scheduler_queue_wait_seconds`` histogram: the batching delay and any
head-of-line blocking behind a long flush, as the server saw them.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, List, Optional, Sequence, Tuple

from repro.core.broker import BrokerError, DataBroker, RankedReplica
from repro.core.classads import ClassAd

__all__ = ["SelectionTicket", "BatchScheduler"]


class SelectionTicket:
    """A pending selection: filled by the scheduler at flush time."""

    def __init__(self, scheduler: "BatchScheduler", lfn: str, submitted_at: float):
        self._scheduler = scheduler
        self.lfn = lfn
        self.submitted_at = submitted_at  # the scheduler's tracer clock
        self._outcome: Any = None
        self._done = False

    def _fill(self, outcome: Any) -> None:
        self._outcome = outcome
        self._done = True

    @property
    def done(self) -> bool:
        return self._done

    def result(self) -> List[RankedReplica]:
        """The ranked list; forces a flush if still queued. Raises the
        per-request ``BrokerError`` (NoReplica/NoMatch) like ``select``."""
        if not self._done:
            self._scheduler.flush()
        if isinstance(self._outcome, BrokerError):
            raise self._outcome
        return self._outcome


class BatchScheduler:
    """Aggregates concurrent selections into batched kernel launches."""

    def __init__(
        self,
        broker: DataBroker,
        *,
        max_batch: int = 64,
        max_delay: float = 0.005,
        top_k: Optional[int] = None,
        use_kernel: Optional[bool] = None,
        clock=None,
        metrics=None,
    ):
        self.broker = broker
        self.max_batch = int(max_batch)
        self.max_delay = float(max_delay)
        self.top_k = top_k
        self.use_kernel = use_kernel
        self.clock = clock if clock is not None else broker.clock
        self._pending: List[Tuple[str, Optional[ClassAd], SelectionTicket]] = []
        self._oldest_at: Optional[float] = None
        self.stats = {
            "submitted": 0,
            "batches": 0,
            "latency_flushes": 0,
            "size_flushes": 0,
            "max_batch_seen": 0,
            "queue_wait_s": 0.0,
            "queue_waited": 0,
        }
        # obs: share the broker's registry/tracer unless told otherwise;
        # self.stats stays the source of truth for exact-count consumers
        self.metrics = metrics if metrics is not None else broker.metrics
        self.tracer = broker.tracer
        self._c_submitted = self.metrics.counter(
            "scheduler_submitted_total", "selections queued"
        )
        self._c_flush = {
            reason: self.metrics.counter(
                "scheduler_flushes_total", "queue flushes by trigger", reason=reason
            )
            for reason in ("size", "latency", "forced")
        }
        self._g_queue = self.metrics.gauge(
            "scheduler_queue_depth", "selections currently queued"
        )
        self._h_wait = self.metrics.histogram(
            "scheduler_queue_wait_seconds", "submit to the start of the selection's flush"
        )

    # ----------------------------------------------------------- submission
    def submit(self, lfn: str, request: Optional[ClassAd] = None) -> SelectionTicket:
        """Queue one selection; may trigger a size flush."""
        ticket = SelectionTicket(self, lfn, self.tracer.time_fn())
        if not self._pending:
            self._oldest_at = self.clock.now()
        self._pending.append((lfn, request, ticket))
        self.stats["submitted"] += 1
        self._c_submitted.inc()
        self._g_queue.set(len(self._pending))
        if len(self._pending) >= self.max_batch:
            self.stats["size_flushes"] += 1
            self.flush(reason="size")
        return ticket

    def submit_many(
        self, queries: Sequence[Tuple[str, Optional[ClassAd]]]
    ) -> List[SelectionTicket]:
        return [self.submit(lfn, req) for lfn, req in queries]

    def select(self, lfn: str, request: Optional[ClassAd] = None) -> List[RankedReplica]:
        """Synchronous convenience: submit + force the result."""
        return self.submit(lfn, request).result()

    # -------------------------------------------------------------- flushing
    def poll(self) -> bool:
        """Max-latency trigger: flush if the oldest queued selection has
        waited ``max_delay``. Returns True if a flush happened."""
        if self._pending and self.clock.now() - self._oldest_at >= self.max_delay:
            self.stats["latency_flushes"] += 1
            self.flush(reason="latency")
            return True
        return False

    def flush(self, *, reason: str = "forced") -> None:
        """Run every queued selection as one ``select_many`` batch.

        ``reason`` labels the flush trigger ("size" | "latency" |
        "forced") in the metrics registry; submit/poll pass theirs."""
        if not self._pending:
            return
        t = self.tracer.time_fn()
        batch, self._pending = self._pending, []
        self._oldest_at = None
        self.stats["batches"] += 1
        self.stats["max_batch_seen"] = max(self.stats["max_batch_seen"], len(batch))
        for _, _, ticket in batch:
            wait = t - ticket.submitted_at
            self.stats["queue_wait_s"] += wait
            self._h_wait.observe(wait)
        self.stats["queue_waited"] += len(batch)
        self._c_flush.get(reason, self._c_flush["forced"]).inc()
        self._g_queue.set(0)
        with self.tracer.span("scheduler.flush", batch=len(batch), reason=reason):
            outcomes = self.broker.select_many(
                [(lfn, req) for lfn, req, _ in batch],
                top_k=self.top_k,
                use_kernel=self.use_kernel,
                strict=False,
            )
        for (_, _, ticket), outcome in zip(batch, outcomes):
            ticket._fill(outcome)

    @property
    def pending(self) -> int:
        return len(self._pending)

    def coalescing_ratio(self) -> float:
        """Selections per kernel launch — the amortization factor."""
        b = self.stats["batches"]
        return self.stats["submitted"] / b if b else 0.0
