"""Selection-decision audit trail: "why was this replica chosen?".

Every :meth:`DataBroker.select`/:meth:`~DataBroker.select_many` records
one :class:`DecisionRecord` — the candidate set the Search Phase found,
how the request lowered (plan-cache hit/miss, snapshot build/reuse, which
execution tier answered it), every candidate's rank score, the chosen
replica, and — once the Access Phase runs — failovers, straggler
switches, and predicted vs. observed bandwidth. Records are retrievable
by ``request_id`` via :meth:`DataBroker.explain` and dump to JSONL for
offline analysis.

The trail is a bounded ring (``capacity``): a broker serving millions of
selections keeps the most recent window; evicted ids raise ``KeyError``
from :meth:`AuditTrail.get`.
"""

from __future__ import annotations

import json
from collections import OrderedDict
from dataclasses import asdict, dataclass, field
from typing import Any, Dict, IO, Iterable, List, Optional, Union

__all__ = ["CandidateScore", "DecisionRecord", "AuditTrail"]

#: execution tiers a selection can take (DecisionRecord.kernel_path)
PATHS = (
    "interpreter",       # per-ad ClassAd interpreter (reference semantics)
    "vectorized",        # columnar engine inside a sequential select()
    "batched_kernel",    # stacked matchrank_batched launch (Pallas / ref)
    "batched_columnar",  # per-request columnar program over the snapshot
    "batched_interp",    # interpreter fallback inside select_many
)


@dataclass
class CandidateScore:
    """One candidate replica's fate in the Match Phase."""

    endpoint: str
    rank: Optional[float]  # None when the candidate failed requirements
    matched: bool

    def to_dict(self) -> Dict[str, Any]:
        return {"endpoint": self.endpoint, "rank": self.rank, "matched": self.matched}


@dataclass
class DecisionRecord:
    """The complete story of one selection (and its access, if any)."""

    request_id: str
    lfn: str
    mode: str  # "select" | "select_many"
    at: float  # broker clock at selection time

    # --- Match Phase ---
    kernel_path: str = ""  # one of PATHS
    candidates: List[str] = field(default_factory=list)  # endpoint urls found
    scores: List[CandidateScore] = field(default_factory=list)
    chosen: Optional[str] = None  # best-ranked endpoint url
    top_k: Optional[int] = None
    plan_cache: Optional[str] = None  # "hit" | "miss" | None (tier unused)
    snapshot: Optional[str] = None  # "build" | "reuse" | "delta" | None
    error: Optional[str] = None  # BrokerError name when the selection failed
    # request-ad analyzer findings (repro.analysis Diagnostic dicts),
    # recorded when the broker runs with ad_check enabled
    ad_diagnostics: List[Dict[str, Any]] = field(default_factory=list)

    # --- Access Phase (filled by DataBroker.access) ---
    accessed: bool = False
    fetched_from: Optional[str] = None  # endpoint that served the bytes
    attempts: int = 0
    failovers: int = 0
    straggler_switches: int = 0
    predicted_bandwidth: Optional[float] = None
    observed_bandwidth: Optional[float] = None
    nbytes: Optional[int] = None

    def to_dict(self) -> Dict[str, Any]:
        d = asdict(self)
        d["scores"] = [s.to_dict() for s in self.scores]
        return d


class AuditTrail:
    """Bounded, id-addressed ring of :class:`DecisionRecord`\\ s."""

    def __init__(self, capacity: int = 1024, *, id_prefix: str = "req"):
        self.capacity = int(capacity)
        self.id_prefix = id_prefix
        self._records: "OrderedDict[str, DecisionRecord]" = OrderedDict()
        self._next = 1
        self.evicted = 0

    # ------------------------------------------------------------ creation
    def new_id(self) -> str:
        rid = f"{self.id_prefix}-{self._next:08d}"
        self._next += 1
        return rid

    def begin(self, lfn: str, *, mode: str, at: float) -> DecisionRecord:
        """Open a record (assigns the request id) and retain it."""
        rec = DecisionRecord(self.new_id(), lfn, mode, at)
        self._records[rec.request_id] = rec
        while len(self._records) > self.capacity:
            self._records.popitem(last=False)
            self.evicted += 1
        return rec

    # ------------------------------------------------------------- reading
    def get(self, request_id: str) -> DecisionRecord:
        rec = self._records.get(request_id)
        if rec is None:
            raise KeyError(
                f"no decision record for {request_id!r} "
                f"(trail keeps the last {self.capacity})"
            )
        return rec

    def records(self) -> List[DecisionRecord]:
        return list(self._records.values())

    def __len__(self) -> int:
        return len(self._records)

    def __contains__(self, request_id: str) -> bool:
        return request_id in self._records

    # -------------------------------------------------------------- export
    def dump_jsonl(self, path_or_file: Union[str, IO[str]]) -> int:
        """Write one JSON object per record; returns the record count."""
        records = self.records()
        if isinstance(path_or_file, str):
            with open(path_or_file, "w") as f:
                for rec in records:
                    f.write(json.dumps(rec.to_dict()) + "\n")
        else:
            for rec in records:
                path_or_file.write(json.dumps(rec.to_dict()) + "\n")
        return len(records)
