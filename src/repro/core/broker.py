"""The decentralized storage broker (§5.1) — the paper's main artifact.

"The entity that identifies the suitable instance of a replicated file
based on application requirements is referred to as a broker."

Every client that needs a replica runs its *own* broker instance (§5.1.1:
"we have designed a decentralized storage brokering strategy wherein every
client that requires access to a replica performs the selection process").
There is no shared mutable state between brokers: each works from the
replica catalog and the *published* GRIS/GIIS state, so two clients with
the same view reach the same (deterministic) decision.

The broker executes the three phases of §5.1.2:

  Search — catalog lookup for all replicas of the logical file, then a
      per-replica GRIS LDAP query projected to the attributes the request
      references (the broker "uses the application ClassAd to build
      specialized LDAP search queries"), narrowed to this client's own
      per-source bandwidth child.
  Match — LDIF → ClassAds (``ldif.entry_to_classad``), symmetric
      Condor matchmaking against the request ad, rank-ordering. Either the
      faithful interpreted matchmaker or the vectorized columnar engine
      (``core.compile``) can run this phase; both produce identical
      selections (tested).
  Access — fetch through an injected transfer service, with two
      fault-tolerance behaviours layered on the paper's design:
      *failover* (endpoint refused/died → next-ranked replica) and
      *straggler mitigation* (observed mid-transfer bandwidth below
      ``straggler_factor ×`` predicted → abandon and re-select).
"""

from __future__ import annotations

import math
from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Mapping, Optional, Protocol, Sequence, Tuple

from repro.obs import AuditTrail, CandidateScore, MetricsRegistry, Tracer

from .bandwidth import TransferMonitor
from .catalog import PhysicalFile, ReplicaCatalog
from .classads import (
    AttrRef,
    BinOp,
    ClassAd,
    Expr,
    FuncCall,
    Ternary,
    UnaryOp,
    parse as parse_expr,
)
from .gris import Clock, StorageGRIS
from .ldif import Entry, entry_to_classad
from .matchmaker import Matchmaker, MatchResult
from .transferplan import (
    TransferFailure,
    TransferPlan,
    TransferRequest,
    TransferResult,
)

__all__ = [
    "ReplicaView",
    "RankedReplica",
    "SelectionResult",
    "FetchOutcome",
    "TransferService",
    "BrokerError",
    "NoReplicaError",
    "NoMatchError",
    "DataBroker",
    "default_read_request",
    "default_write_request",
]


def _referenced_attrs(expr: Optional[Expr]) -> set:
    """Lower-cased attribute names referenced anywhere in an expression."""
    out: set = set()

    def walk(e):
        if e is None:
            return
        if isinstance(e, AttrRef):
            out.add(e.name.lower())
        elif isinstance(e, UnaryOp):
            walk(e.operand)
        elif isinstance(e, BinOp):
            walk(e.left)
            walk(e.right)
        elif isinstance(e, Ternary):
            walk(e.cond)
            walk(e.then)
            walk(e.other)
        elif isinstance(e, FuncCall):
            for a in e.args:
                walk(a)

    walk(expr)
    return out


#: attributes the Search Phase attaches per (lfn, replica) — present in a
#: sequential select's view but NOT in the shared endpoint snapshot, so a
#: request referencing them must take the per-request interpreter path.
_PER_REPLICA_ATTRS = frozenset({"replicapath", "replicasize"})


@dataclass
class _SnapshotState:
    """The broker's cached view of one published GRIS epoch: tensor
    snapshot + per-row ads, shared by every selection until it expires."""

    snapshot: Any  # core.snapshot.ReplicaSnapshot
    endpoints: Tuple[str, ...]  # row order
    row_of: Dict[str, int]  # endpoint url → row
    entries: List[Entry]
    ads: List[ClassAd]
    table: Any  # core.compile.ColumnTable (f64, live rows)
    built_at: float


def _rows_of(
    replicas: Sequence[PhysicalFile], st: "_SnapshotState"
) -> Dict[int, PhysicalFile]:
    """Snapshot row → replica, for the replicas resident in the snapshot."""
    by_row: Dict[int, PhysicalFile] = {}
    for pfn in replicas:
        r = st.row_of.get(pfn.endpoint)
        if r is not None:
            by_row.setdefault(r, pfn)
    return by_row


def _row_name(st: "_SnapshotState", r: int) -> str:
    """The resource name used as the deterministic rank tiebreak."""
    e = st.entries[r]
    for attr in ("name", "hostname", "endpoint", "url"):
        for k, v in e.items():
            if k.lower() == attr and isinstance(v, str):
                return v
    return f"resource-{r}"


class BrokerError(RuntimeError):
    pass


class NoReplicaError(BrokerError):
    """The catalog has no replicas for the logical file."""


class NoMatchError(BrokerError):
    """Replicas exist but none satisfied the two-sided requirements."""


class AdValidationError(BrokerError):
    """``ad_check="strict"``: the request ad has error-severity findings
    from the static analyzer (undefined attributes, type confusions,
    unsatisfiable requirements) that would silently distort selection."""


@dataclass
class ReplicaView:
    """Search-phase product: a replica plus its GRIS-published state."""

    pfn: PhysicalFile
    entry: Entry  # flattened GRIS view (volume + bw summary + per-source)
    ad: ClassAd  # the converted ClassAd (Match Phase step 1)


@dataclass
class RankedReplica:
    """Match-phase product: a matched replica with its rank value."""

    view: ReplicaView
    rank: float

    @property
    def pfn(self) -> PhysicalFile:
        return self.view.pfn


class SelectionResult(Sequence):
    """The one result shape every selection path produces.

    ``select``, ``select_many`` and ``select_placements`` used to return
    bare ``List[RankedReplica]`` — the caller had to hold the request id,
    re-derive bandwidth predictions, and invent its own striping. A
    SelectionResult *is* the ranked list (it iterates, indexes and
    lengths like one, so ``sel[0].pfn`` keeps working) and additionally
    carries:

      * ``plan`` — the broker's :class:`TransferPlan` (primary + ranked
        backups + predicted bandwidths + stripe bound), executable by
        ``ResilientTransferService.execute``,
      * ``request_id`` — the decision record to ``explain()`` /
        annotate after access,
      * ``scores`` — per-candidate (endpoint, rank, matched) fates.
    """

    __slots__ = ("ranked", "lfn", "request_id", "plan", "scores")

    def __init__(
        self,
        ranked: Sequence[RankedReplica],
        *,
        lfn: Optional[str] = None,
        request_id: Optional[str] = None,
        plan: Optional[TransferPlan] = None,
        scores: Optional[List[CandidateScore]] = None,
    ):
        self.ranked = list(ranked)
        self.lfn = lfn
        self.request_id = request_id
        self.plan = plan
        self.scores = scores or []

    def __len__(self) -> int:
        return len(self.ranked)

    def __iter__(self):
        return iter(self.ranked)

    def __getitem__(self, i):
        return self.ranked[i]

    def __bool__(self) -> bool:
        return bool(self.ranked)

    def __eq__(self, other) -> bool:
        if isinstance(other, SelectionResult):
            return self.ranked == other.ranked
        if isinstance(other, list):
            return self.ranked == other
        return NotImplemented

    def __repr__(self) -> str:
        eps = [rr.pfn.endpoint for rr in self.ranked[:3]]
        more = f", +{len(self.ranked) - 3}" if len(self.ranked) > 3 else ""
        return (
            f"SelectionResult({self.lfn!r}, ranked={eps}{more}, "
            f"request_id={self.request_id!r})"
        )

    @property
    def best(self) -> RankedReplica:
        return self.ranked[0]


@dataclass
class FetchOutcome:
    """Access-phase product."""

    lfn: str
    replica: PhysicalFile
    nbytes: int
    seconds: float
    attempts: int
    switched: int  # straggler-mitigation replica switches
    ranked: List[RankedReplica]
    payload: Any = None

    @property
    def bandwidth(self) -> float:
        return self.nbytes / self.seconds if self.seconds > 0 else 0.0


class TransferService(Protocol):
    """What the Access Phase needs from the storage layer (GridFTP stand-in).

    ``transfer`` executes one :class:`TransferRequest` and returns a
    :class:`TransferResult`; it may raise ``TransferFailure`` (endpoint
    dead / refused). ``transfer_chunks`` yields
    :class:`~repro.core.transferplan.ChunkEvent` increments for
    straggler monitoring and restart markers.
    """

    def transfer(self, request: TransferRequest) -> TransferResult: ...

    def transfer_chunks(self, request: TransferRequest): ...


def default_read_request(
    client_url: str,
    *,
    min_bandwidth: float = 0.0,
    rank: str = "predicted",
) -> ClassAd:
    """The request ad a data-pipeline client submits for a shard read.

    Rank prefers this client's own end-to-end history (Figure 5's
    per-source attributes), falling back to the site-wide average
    (Figure 4), falling back to the static ``diskTransferRate`` for a
    cold-start endpoint — the paper's "simple heuristic of combining past
    observed performance with current load".
    """
    ad = ClassAd()
    ad["clientUrl"] = client_url
    ad["reqdRDBandwidth"] = float(min_bandwidth)
    # Reads consume no space; declared so that space-gating site policies
    # (e.g. the paper's ``other.reqdSpace < 10G``) evaluate defined-True.
    ad["reqdSpace"] = 0
    if rank == "predicted":
        ad.set_expr(
            "rank",
            "ifThenElse(!isUndefined(other.EwmaRDBandwidthToSource) && other.EwmaRDBandwidthToSource > 0,"
            " other.EwmaRDBandwidthToSource,"
            " ifThenElse(!isUndefined(other.AvgRDBandwidth) && other.AvgRDBandwidth > 0,"
            "  other.AvgRDBandwidth,"
            "  other.diskTransferRate / (1 + other.loadFactor)))",
        )
    elif rank == "last":
        ad.set_expr("rank", "other.lastRDBandwidth")
    elif rank == "static":
        ad.set_expr("rank", "other.diskTransferRate / (1 + other.loadFactor)")
    else:
        ad.set_expr("rank", rank)  # caller-supplied expression
    # two clauses: the bandwidth gate, and the resilient layer's circuit-
    # breaker feedback — an endpoint whose breaker THIS client tripped
    # publishes breakerOpenToSource=1 into our per-source GRIS view and is
    # excluded from matchmaking until its half-open probe window (0.5,
    # which passes the < 1 gate so the probe stays selectable).
    ad.set_expr(
        "requirements",
        "(isUndefined(other.MaxRDBandwidth) || my.reqdRDBandwidth <= 0"
        " || other.MaxRDBandwidth >= my.reqdRDBandwidth)"
        " && (isUndefined(other.breakerOpenToSource)"
        " || other.breakerOpenToSource < 1)",
    )
    return ad


def default_write_request(client_url: str, nbytes: int) -> ClassAd:
    """The request ad a checkpoint writer submits for replica placement:
    needs space, ranks by predicted write bandwidth then free space."""
    ad = ClassAd()
    ad["clientUrl"] = client_url
    ad["reqdSpace"] = int(nbytes)
    ad.set_expr(
        "rank",
        "ifThenElse(!isUndefined(other.AvgWRBandwidthToSource) && other.AvgWRBandwidthToSource > 0,"
        " other.AvgWRBandwidthToSource * 1000000000,"
        " ifThenElse(!isUndefined(other.AvgWRBandwidth) && other.AvgWRBandwidth > 0,"
        "  other.AvgWRBandwidth * 1000000000,"
        "  other.diskTransferRate))"
        " + other.availableSpace / 1G",
    )
    ad.set_expr("requirements", "other.availableSpace >= my.reqdSpace")
    return ad


class DataBroker:
    """One client's replica-selection broker.

    Parameters
    ----------
    client_url:
        This client's URL — the per-source key under which endpoints have
        recorded end-to-end history about us.
    catalog:
        The replica catalog (read-only here).
    gris_resolver:
        endpoint URL → StorageGRIS. Usually ``grid.gris_for`` from the
        storage simulation, or a GIIS lookup.
    env:
        ClassAd evaluation environment (deterministic ``now`` etc.).
    use_vectorized:
        Route the Match Phase through the columnar engine
        (:mod:`repro.core.compile`) when the request compiles; falls back
        to the interpreter otherwise. Selections are identical.
    """

    def __init__(
        self,
        client_url: str,
        catalog: ReplicaCatalog,
        gris_resolver: Callable[[str], Optional[StorageGRIS]],
        *,
        env: Optional[Dict[str, Any]] = None,
        clock: Optional[Clock] = None,
        use_vectorized: bool = False,
        straggler_factor: float = 0.35,
        straggler_patience: int = 3,
        max_attempts: int = 4,
        stripe_k: int = 3,
        snapshot_ttl: float = 5.0,
        batch_use_kernel: bool = False,
        batch_use_sparse: bool = False,
        snapshot_shards: int = 0,
        shard_key: Optional[Callable[[str], int]] = None,
        plan_cache_size: int = 256,
        metrics: Optional[MetricsRegistry] = None,
        tracer: Optional[Tracer] = None,
        audit: Optional[AuditTrail] = None,
        audit_capacity: int = 1024,
        ad_check: str = "warn",
    ):
        self.client_url = client_url
        self.catalog = catalog
        self.gris_resolver = gris_resolver
        self.clock = clock or Clock()
        self.env = dict(env or {})
        self.env.setdefault("now", self.clock.now())
        self.matchmaker = Matchmaker(self.env)
        self.use_vectorized = use_vectorized
        self.straggler_factor = straggler_factor
        self.straggler_patience = straggler_patience
        self.max_attempts = max_attempts
        self.stripe_k = stripe_k  # TransferPlan stripe bound
        # batched-selection state: snapshot TTL mirrors the GRIS dynamic-
        # attribute TTL (stale columns would diverge from fresh LDAP reads)
        self.snapshot_ttl = snapshot_ttl
        self.batch_use_kernel = batch_use_kernel
        self.batch_use_sparse = batch_use_sparse
        # sharded matchmaking (DESIGN.md §9): partition the snapshot into
        # this many per-registrant shards (0 = flat snapshot). shard_key
        # maps endpoint → bucket; default is the crc32 hash bucketing.
        self.snapshot_shards = int(snapshot_shards)
        self.shard_key = shard_key
        self._plan_cache = None  # lazily built (pulls in core.plancache)
        self._plan_cache_size = plan_cache_size
        self._snap_state: Optional[_SnapshotState] = None
        # local (client-side) observation history: end-to-end from OUR side
        self.local_monitor = TransferMonitor(None)
        # observability: per-broker registry (decentralized, like the
        # matchmaker); cooperating components (scheduler, engine) share it
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        self.tracer = tracer if tracer is not None else Tracer()
        self.audit = audit if audit is not None else AuditTrail(audit_capacity)
        if ad_check not in ("off", "warn", "strict"):
            raise ValueError(f"ad_check must be off|warn|strict, got {ad_check!r}")
        # request-ad static analysis at select time: "warn" records analyzer
        # findings into the decision record; "strict" additionally refuses
        # error-severity ads. Results are memoized per distinct ad source.
        self.ad_check = ad_check
        self._ad_diag_cache: "OrderedDict[str, List[Dict[str, Any]]]" = OrderedDict()
        self._ad_diag_cache_size = 128
        self.last_request_id: Optional[str] = None
        self.last_request_ids: List[str] = []
        # pre-bound counters: the hot path touches these per call, so the
        # family/child resolution happens once here
        self._ctr = {
            name: self.metrics.counter(f"broker_{name}_total", help)
            for name, help in (
                ("searches", "Search Phase sweeps (catalog + GRIS)"),
                ("matches", "Match Phase runs"),
                ("fetches", "Access Phase fetches"),
                ("failovers", "dead/refused endpoints skipped to next rank"),
                ("straggler_switches", "mid-transfer abandons (slow replica)"),
                ("vectorized_matches", "sequential matches on the columnar engine"),
                ("batch_selects", "select_many batches"),
                ("batched_kernel_requests", "requests answered by the stacked kernel"),
                ("batched_sparse_requests", "requests answered by sparse top-k"),
                ("batched_sharded_requests", "requests answered by the sharded walk+merge"),
                ("batched_columnar_requests", "requests answered columnar per-request"),
                ("batched_interp_requests", "requests answered by the interpreter"),
                ("snapshot_builds", "GRIS snapshot (re)builds"),
                ("snapshot_reuses", "GRIS snapshot TTL reuses"),
                ("snapshot_delta_refreshes", "sharded snapshots refreshed in place (dirty shards only)"),
                ("ad_findings", "request-ad analyzer findings recorded"),
            )
        }
        self._ctr_shard_rows = self.metrics.counter(
            "shard_refresh_rows_total",
            "rows re-pushed to the device by sharded delta refreshes",
        )
        self._shard_hists: Dict[int, Any] = {}
        self._h_gris_query = self.metrics.histogram(
            "broker_gris_query_seconds", "per-endpoint GRIS query latency"
        )
        self._h_fetch_bw = self.metrics.histogram(
            "broker_fetch_bandwidth_mb_per_s",
            "achieved Access Phase bandwidth",
            buckets=(0.1, 0.5, 1, 2, 5, 10, 25, 50, 100, 250, 1000, float("inf")),
        )
        self._h_batch = self.metrics.histogram(
            "broker_select_many_batch_size", "queries per select_many call",
            buckets=(1, 2, 4, 8, 16, 32, 64, 128, 256, float("inf")),
        )

    @property
    def stats(self) -> Dict[str, Any]:
        """Legacy counter view, now backed by the metrics registry. Keys
        and integer values are unchanged from the pre-obs dict."""
        out: Dict[str, Any] = {}
        for k, c in self._ctr.items():
            v = c.value
            out[k] = int(v) if float(v).is_integer() else v
        return out

    @property
    def plan_cache(self):
        if self._plan_cache is None:
            from .plancache import PlanCache

            self._plan_cache = PlanCache(self._plan_cache_size, metrics=self.metrics)
        return self._plan_cache

    def explain(self, request_id: str):
        """The :class:`~repro.obs.DecisionRecord` for a past selection —
        candidates, plan-cache status, kernel path, per-candidate scores,
        chosen replica, and (after access) failovers and bandwidths."""
        return self.audit.get(request_id)

    # ------------------------------------------------------------------ Search
    def search(self, lfn: str, attrs: Optional[Sequence[str]] = None) -> List[ReplicaView]:
        """Search Phase: catalog → per-replica GRIS query → ClassAd views."""
        self._ctr["searches"].inc()
        replicas = self.catalog.lookup(lfn)
        if not replicas:
            raise NoReplicaError(lfn)
        views: List[ReplicaView] = []
        for pfn in replicas:
            gris = self.gris_resolver(pfn.endpoint)
            if gris is None:
                continue  # endpoint unreachable: skip (failover will cover)
            with self.tracer.span("broker.gris_query", endpoint=pfn.endpoint) as sp:
                entry = gris.flattened_view(source=self.client_url)
            self._h_gris_query.observe(sp.duration)
            entry.setdefault("endpoint", pfn.endpoint)
            entry.setdefault("replicaPath", pfn.path)
            entry.setdefault("replicaSize", pfn.size)
            ad = entry_to_classad(entry)
            views.append(ReplicaView(pfn, entry, ad))
        if not views:
            raise NoReplicaError(f"{lfn}: no reachable replicas")
        return views

    # ------------------------------------------------------------------- Match
    def match(self, request: ClassAd, views: Sequence[ReplicaView]) -> List[RankedReplica]:
        """Match Phase: two-sided matchmaking + rank ordering."""
        self._ctr["matches"].inc()
        if self.use_vectorized:
            ranked = self._match_vectorized(request, views)
            if ranked is not None:
                self._ctr["vectorized_matches"].inc()
                return ranked
        results = self.matchmaker.match(request, [v.ad for v in views])
        return [RankedReplica(views[m.index], m.rank) for m in results]

    def _match_vectorized(
        self, request: ClassAd, views: Sequence[ReplicaView]
    ) -> Optional[List[RankedReplica]]:
        # deferred import: core.compile pulls in jax
        try:
            from .compile import vectorized_match
        except Exception:  # pragma: no cover - jax always present here
            return None
        return vectorized_match(request, views, env=self.env)

    def _predicted_bandwidth(self, rr: RankedReplica) -> Optional[float]:
        """The bandwidth we expect from a ranked replica. Only trust
        ``rank`` as a prediction when it comes from observed history; a
        cold static rank (disk rate) can exceed the achievable path
        bandwidth several-fold. Cold endpoints fall back to this client's
        own typical achieved bandwidth (local aggregate), if any."""
        has_history = isinstance(
            rr.view.entry.get("EwmaRDBandwidthToSource"), (int, float)
        ) and rr.view.entry.get("EwmaRDBandwidthToSource", 0) > 0
        if rr.rank > 0 and has_history:
            return rr.rank
        agg = self.local_monitor.aggregate["read"]
        return agg.mean if agg.n >= 3 else None

    def _check_request_ad(self, req: ClassAd, rec) -> None:
        """Static analysis of the request ad (``ad_check``), recorded into
        the decision record. Memoized per distinct ad source — the common
        case (the default read request, a scheduler's fixed template) pays
        the analyzer exactly once per broker."""
        if self.ad_check == "off":
            return
        key = ";".join(f"{k}={e!r}" for k, e in req.items())
        diags = self._ad_diag_cache.get(key)
        if diags is None:
            from repro.analysis.adlint import check_request_ad

            diags = [d.to_dict() for d in check_request_ad(req)]
            self._ad_diag_cache[key] = diags
            if len(self._ad_diag_cache) > self._ad_diag_cache_size:
                self._ad_diag_cache.popitem(last=False)
        else:
            self._ad_diag_cache.move_to_end(key)
        if diags:
            rec.ad_diagnostics = list(diags)
            self._ctr["ad_findings"].inc(len(diags))
            if self.ad_check == "strict" and any(
                d["severity"] == "error" for d in diags
            ):
                msgs = "; ".join(
                    f"{d['rule']}: {d['message']}"
                    for d in diags if d["severity"] == "error"
                )
                rec.error = f"AdValidationError: {msgs}"
                raise AdValidationError(msgs)

    def _result(
        self,
        lfn: str,
        ranked: List[RankedReplica],
        request_id: Optional[str],
        scores: Optional[List[CandidateScore]] = None,
    ) -> SelectionResult:
        """Ranked list → SelectionResult, with the executable plan."""
        plan = TransferPlan(
            lfn=lfn,
            replicas=[rr.pfn for rr in ranked],
            ranks=[rr.rank for rr in ranked],
            predicted=[self._predicted_bandwidth(rr) for rr in ranked],
            stripe_k=self.stripe_k,
            request_id=request_id,
        )
        return SelectionResult(
            ranked, lfn=lfn, request_id=request_id, plan=plan, scores=scores
        )

    def select(
        self,
        lfn: str,
        request: Optional[ClassAd] = None,
        *,
        top_k: Optional[int] = None,
    ) -> SelectionResult:
        """Search + Match in one call, best replica first.

        Returns a :class:`SelectionResult` — iterable like the ranked
        list, plus the executable ``plan`` and the ``request_id`` of the
        decision record :meth:`explain` retrieves."""
        req = request if request is not None else default_read_request(self.client_url)
        if self.snapshot_shards > 0 and top_k:
            # sharded brokers answer sequential selections through the
            # batched sharded tier so they hit the same snapshot + result
            # cache (requests needing per-(lfn,replica) attributes can't:
            # those attrs aren't in the shared snapshot)
            refs = _referenced_attrs(req.lookup_expr("requirements")) | _referenced_attrs(
                req.lookup_expr("rank")
            )
            if not (refs & _PER_REPLICA_ATTRS):
                return self.select_many([(lfn, req)], top_k=top_k)[0]
        rec = self.audit.begin(lfn, mode="select", at=self.clock.now())
        rec.top_k = top_k
        self.last_request_id = rec.request_id
        self._check_request_ad(req, rec)
        try:
            views, ranked, path = self._select_impl(lfn, req)
        except BrokerError as e:
            rec.error = f"{type(e).__name__}: {e}"
            raise
        rec.kernel_path = path
        self._fill_match_audit(rec, [v.pfn.endpoint for v in views], ranked)
        if not ranked:
            rec.error = "NoMatchError"
            raise NoMatchError(lfn)
        if top_k:
            ranked = ranked[:top_k]
        return self._result(lfn, ranked, rec.request_id, scores=rec.scores)

    def _select_impl(
        self, lfn: str, req: ClassAd
    ) -> Tuple[List[ReplicaView], List[RankedReplica], str]:
        """Search + Match without audit bookkeeping (select_many's
        interpreter tier reuses this under its own records)."""
        views = self.search(lfn, None)
        vec_before = self._ctr["vectorized_matches"].value
        ranked = self.match(req, views)
        path = (
            "vectorized"
            if self._ctr["vectorized_matches"].value > vec_before
            else "interpreter"
        )
        return views, ranked, path

    def _fill_match_audit(
        self, rec, candidates: List[str], ranked: Sequence[RankedReplica]
    ) -> None:
        """Candidate set + per-candidate scores + chosen replica."""
        rec.candidates = candidates
        matched = {rr.pfn.endpoint: rr.rank for rr in ranked}
        rec.scores = [
            CandidateScore(ep, matched.get(ep), ep in matched) for ep in candidates
        ]
        rec.chosen = ranked[0].pfn.endpoint if ranked else None

    # --------------------------------------------------------- Batched Match
    def _snapshot_state(self, endpoints: Sequence[str]) -> _SnapshotState:
        """The cached snapshot of the published GRIS epoch, rebuilt when
        the TTL lapses or a new endpoint appears (the 'epoch' boundary)."""
        want = [ep for ep in endpoints if self.gris_resolver(ep) is not None]
        now = self.clock.now()
        st = self._snap_state
        if (
            st is not None
            and now - st.built_at < self.snapshot_ttl
            and all(ep in st.row_of for ep in want)
        ):
            self._ctr["snapshot_reuses"].inc()
            return st
        if self.snapshot_shards > 0:
            return self._snapshot_state_sharded(want, now, st)

        from .snapshot import ReplicaSnapshot

        # keep previously known endpoints resident so the snapshot grows
        # monotonically within a broker's lifetime (stable row space)
        known: List[str] = list(st.endpoints) if st is not None else []
        for ep in want:
            if st is None or ep not in st.row_of:
                known.append(ep)
        rows: List[str] = []
        entries: List[Entry] = []
        ads: List[ClassAd] = []
        for ep in known:
            gris = self.gris_resolver(ep)
            if gris is None:
                continue  # endpoint died: drop its row this epoch
            entry = gris.flattened_view(source=self.client_url)
            entry.setdefault("endpoint", ep)
            rows.append(ep)
            entries.append(entry)
            ads.append(entry_to_classad(entry))
        prev = st.snapshot if st is not None else None
        snapshot = (
            prev.new_epoch(entries, reuse_vocab=False)
            if prev is not None
            else ReplicaSnapshot(entries)
        )
        st = _SnapshotState(
            snapshot=snapshot,
            endpoints=tuple(rows),
            row_of={ep: i for i, ep in enumerate(rows)},
            entries=entries,
            ads=ads,
            table=snapshot.table(),
            built_at=now,
        )
        self._snap_state = st
        self._ctr["snapshot_builds"].inc()
        return st

    def _shard_name(self, ep: str) -> str:
        """Endpoint → shard name. Zero-padded so sorted(shard names) is
        numeric bucket order (the global row space is shard-major)."""
        from .snapshot_sharded import shard_by_hash

        bucket = (
            self.shard_key(ep)
            if self.shard_key is not None
            else shard_by_hash(ep, self.snapshot_shards)
        )
        return f"shard-{int(bucket) % self.snapshot_shards:03d}"

    def _shard_hist(self, g: int):
        """Per-shard rank-walk latency histogram (bounded label set: one
        child per shard index)."""
        h = self._shard_hists.get(g)
        if h is None:
            h = self.metrics.histogram(
                "broker_shard_rank_seconds",
                "per-shard sparse rank-walk latency",
                shard=str(g),
            )
            self._shard_hists[g] = h
        return h

    def _snapshot_state_sharded(
        self, want: Sequence[str], now: float, st: Optional[_SnapshotState]
    ) -> _SnapshotState:
        """Sharded twin of :meth:`_snapshot_state`: endpoints are bucketed
        into per-registrant shards, and a TTL lapse with unchanged
        membership becomes a **delta refresh** — unchanged shards never
        leave the device, changed shards re-push in one scatter — instead
        of a full rebuild (DESIGN.md §9)."""
        from .snapshot_sharded import ShardedSnapshot

        known: List[str] = list(st.endpoints) if st is not None else []
        for ep in want:
            if st is None or ep not in st.row_of:
                known.append(ep)
        by_shard: Dict[str, List[str]] = {}
        shard_entries: Dict[str, List[Entry]] = {}
        for ep in known:
            gris = self.gris_resolver(ep)
            if gris is None:
                continue  # endpoint died: drop its row this epoch
            entry = gris.flattened_view(source=self.client_url)
            entry.setdefault("endpoint", ep)
            name = self._shard_name(ep)
            by_shard.setdefault(name, []).append(ep)
            shard_entries.setdefault(name, []).append(entry)
        if not shard_entries:
            # every endpoint unreachable: an empty flat snapshot keeps the
            # n == 0 handling in select_many uniform
            from .snapshot import ReplicaSnapshot

            empty = ReplicaSnapshot([])
            st = _SnapshotState(
                snapshot=empty,
                endpoints=(),
                row_of={},
                entries=[],
                ads=[],
                table=empty.table(),
                built_at=now,
            )
            self._snap_state = st
            self._ctr["snapshot_builds"].inc()
            return st

        shard_names = sorted(shard_entries)
        prev = st.snapshot if st is not None else None
        snapshot = None
        changed: Optional[List[str]] = None
        if (
            isinstance(prev, ShardedSnapshot)
            and prev.shard_names == shard_names
            and all(
                len(shard_entries[nm]) == len(prev.entries_by_shard[nm])
                for nm in shard_names
            )
        ):
            rows_before = prev.pushed_rows
            try:
                changed = prev.refresh(shard_entries)
                snapshot = prev
            except ValueError:
                snapshot = None  # vocab/shape drift: fall through to rebuild
            if snapshot is not None:
                self._ctr["snapshot_delta_refreshes"].inc()
                self._ctr_shard_rows.inc(int(snapshot.pushed_rows - rows_before))
        if snapshot is None:
            snapshot = ShardedSnapshot(
                shard_entries, epoch=prev.epoch + 1 if prev is not None else 0
            )

        rows = [ep for nm in shard_names for ep in by_shard[nm]]
        entries = [e for nm in shard_names for e in shard_entries[nm]]
        if changed is not None and st is not None:
            # delta: re-convert ads only for shards whose entries moved
            changed_set = set(changed)
            ads: List[ClassAd] = []
            pos = 0
            for nm in shard_names:
                cnt = len(shard_entries[nm])
                if nm in changed_set:
                    ads.extend(entry_to_classad(e) for e in shard_entries[nm])
                else:
                    ads.extend(st.ads[pos : pos + cnt])
                pos += cnt
        else:
            ads = [entry_to_classad(e) for e in entries]
        st = _SnapshotState(
            snapshot=snapshot,
            endpoints=tuple(rows),
            row_of={ep: i for i, ep in enumerate(rows)},
            entries=entries,
            ads=ads,
            table=snapshot.table(),
            built_at=now,
        )
        self._snap_state = st
        if changed is None:
            self._ctr["snapshot_builds"].inc()
        return st

    def invalidate_snapshot(self) -> None:
        self._snap_state = None

    def warm_snapshot(self, endpoints: Sequence[str]) -> Any:
        """Build (or reuse) the batched-selection snapshot over
        ``endpoints`` before traffic arrives — a server's start-up step,
        so that the first flushes neither rebuild the snapshot nor change
        its padded shape (which would recompile the kernel). Returns the
        snapshot."""
        with self.tracer.span("broker.snapshot", endpoints=len(endpoints)):
            return self._snapshot_state(endpoints).snapshot

    def select_many(
        self,
        queries: Sequence[Tuple[str, Optional[ClassAd]]],
        *,
        top_k: Optional[int] = None,
        use_kernel: Optional[bool] = None,
        use_sparse: Optional[bool] = None,
        strict: bool = True,
    ) -> List[Any]:
        """Batched Search+Match: many ``(lfn, request)`` selections against
        ONE device-resident snapshot in (at most) one kernel launch.

        Requests whose plans lower to the kernel subset are stacked into a
        single ``matchrank_batched`` call (or, with ``use_sparse`` and a
        ``top_k``, answered by the rank-order sparse top-k walk when every
        plan canonicalizes); requests that only compile to the columnar
        subset run per-request against the same snapshot table; everything
        else takes the paper-faithful interpreter — all tiers produce
        identical selections (tested; the sparse tier may order exact
        rank-ties at the k-boundary differently, which is why it is
        opt-in).

        Every query gets a decision record (``self.last_request_ids``,
        :meth:`explain`) noting its kernel path, plan-cache and snapshot
        status, and per-candidate scores.

        Returns one :class:`SelectionResult` per query, in query order.
        With ``strict=False``, a query that fails (no replicas / no
        match) yields its exception object in place of a result instead
        of raising — the coalescing scheduler path, where one bad
        request must not poison the batch.
        """
        use_kernel = self.batch_use_kernel if use_kernel is None else use_kernel
        if use_sparse is None:
            # sharded snapshots answer through the per-shard walk + merge
            # tier, which rides the sparse gate
            use_sparse = self.batch_use_sparse or self.snapshot_shards > 0
        self._ctr["batch_selects"].inc()
        n = len(queries)
        self._h_batch.observe(n)
        results: List[Any] = [None] * n
        recs = [
            self.audit.begin(lfn, mode="select_many", at=self.clock.now())
            for lfn, _ in queries
        ]
        for rec in recs:
            rec.top_k = top_k
        self.last_request_ids = [rec.request_id for rec in recs]
        if recs:
            self.last_request_id = recs[-1].request_id

        # ---- Search: one catalog+GRIS sweep for the whole batch ----
        reqs: List[Optional[ClassAd]] = [None] * n
        replica_lists: List[Optional[List[PhysicalFile]]] = [None] * n
        all_endpoints: List[str] = []
        seen = set()
        from .catalog import CatalogError

        with self.tracer.span("broker.batch_search", batch=n):
            for i, (lfn, req) in enumerate(queries):
                reqs[i] = req if req is not None else default_read_request(self.client_url)
                try:
                    self._check_request_ad(reqs[i], recs[i])
                except AdValidationError as e:
                    if strict:
                        raise
                    results[i] = e
                    continue
                try:
                    replicas = self.catalog.lookup(lfn)
                except CatalogError:
                    replicas = None
                if not replicas:
                    results[i] = NoReplicaError(lfn)
                    recs[i].error = f"NoReplicaError: {lfn}"
                    continue
                replica_lists[i] = replicas
                recs[i].candidates = [p.endpoint for p in replicas]
                for pfn in replicas:
                    if pfn.endpoint not in seen:
                        seen.add(pfn.endpoint)
                        all_endpoints.append(pfn.endpoint)
            self._ctr["searches"].inc()
        if not all_endpoints:
            if strict:
                raise NoReplicaError(queries[0][0] if queries else "<empty batch>")
            return results
        builds_before = self._ctr["snapshot_builds"].value
        deltas_before = self._ctr["snapshot_delta_refreshes"].value
        with self.tracer.span("broker.snapshot", endpoints=len(all_endpoints)):
            st = self._snapshot_state(all_endpoints)
        if self._ctr["snapshot_builds"].value > builds_before:
            snap_status = "build"
        elif self._ctr["snapshot_delta_refreshes"].value > deltas_before:
            snap_status = "delta"
        else:
            snap_status = "reuse"
        for i in range(n):
            if results[i] is None:
                recs[i].snapshot = snap_status
        if st.snapshot.n == 0:  # every endpoint unreachable
            for i in range(n):
                if results[i] is None:
                    msg = f"{queries[i][0]}: no reachable replicas"
                    results[i] = NoReplicaError(msg)
                    recs[i].error = f"NoReplicaError: {msg}"
            if strict:
                raise next(r for r in results if isinstance(r, BrokerError))
            return results
        vocab = st.snapshot.vocab_key()

        # ---- per-request lowering through the plan cache (tiered) ----
        from .compile import CompileError

        kernel_batch: List[int] = []  # query indices in the stacked launch
        kernel_plans: List[Any] = []
        columnar: List[int] = []
        interp: List[int] = []
        policy_cache: Dict[Tuple[str, int], Any] = {}

        def policy_pass(i: int) -> Optional[List[float]]:
            """Fold every row's server policy into a [rows] admit vector
            for request i; None ⇒ some policy is outside the columnar
            subset and request i must go to the interpreter."""
            import numpy as np

            admit = np.ones((st.snapshot.n,), dtype=np.float32)
            groups: Dict[str, List[int]] = {}
            for r, ad in enumerate(st.ads):
                pexpr = ad.lookup_expr("requirements")
                if pexpr is None:
                    continue
                groups.setdefault(repr(pexpr), []).append(r)
            for src, rows in groups.items():
                try:
                    fn = self.plan_cache.policy_fn(src, reqs[i], vocab, env=self.env)
                except CompileError:
                    return None
                t = fn(st.table, np)
                ok = t.ok if t.ok is not True else np.ones((st.snapshot.n,), bool)
                pol = np.broadcast_to(np.asarray(t.val), (st.snapshot.n,)) & np.broadcast_to(
                    np.asarray(ok), (st.snapshot.n,)
                )
                for r in rows:
                    if not pol[r]:
                        admit[r] = 0.0
            return admit

        import numpy as np

        admits: Dict[int, np.ndarray] = {}
        with self.tracer.span("broker.lowering"):
            for i in range(n):
                if results[i] is not None:
                    continue
                req = reqs[i]
                refs = _referenced_attrs(
                    req.lookup_expr("requirements")
                ) | _referenced_attrs(req.lookup_expr("rank"))
                if refs & _PER_REPLICA_ATTRS:
                    interp.append(i)  # needs per-(lfn,replica) attrs, not in snapshot
                    continue
                pcs = self.plan_cache.stats
                pc_before = (pcs["hits"], pcs["misses"], pcs["negative_hits"])
                admit = policy_pass(i)
                if admit is None:
                    interp.append(i)
                else:
                    admits[i] = admit
                    try:
                        plan = self.plan_cache.kernel_plan(req, vocab, env=self.env)
                        kernel_batch.append(i)
                        kernel_plans.append(plan)
                    except CompileError:
                        try:
                            self.plan_cache.columnar_program(req, vocab, env=self.env)
                            columnar.append(i)
                        except CompileError:
                            interp.append(i)
                pcs = self.plan_cache.stats
                if pcs["misses"] > pc_before[1]:
                    recs[i].plan_cache = "miss"
                elif pcs["hits"] > pc_before[0] or pcs["negative_hits"] > pc_before[2]:
                    recs[i].plan_cache = "hit"

        # ---- tier 1: one stacked kernel launch for the whole sub-batch ----
        if kernel_batch:
            from repro.kernels.matchrank.ops import (
                matchrank_batched,
                matchrank_batched_topk,
                stack_plans,
            )

            attrs, valid, n_rows = st.snapshot.device_columns()
            admit_mat = np.zeros((len(kernel_batch), n_rows), dtype=np.float32)
            for bi, i in enumerate(kernel_batch):
                row_ok = admits[i]
                for pfn in replica_lists[i]:
                    r = st.row_of.get(pfn.endpoint)
                    if r is not None and row_ok[r] > 0:
                        admit_mat[bi, r] = 1.0
            sparse_done = False
            if use_sparse and top_k:
                from repro.kernels.matchrank.sparse import canonicalize_plans

                from .snapshot_sharded import ShardedSnapshot

                na = len(kernel_plans[0].attr_names)
                iv = canonicalize_plans(kernel_plans, na)
                if iv is not None and isinstance(st.snapshot, ShardedSnapshot):
                    # tier 1a: per-shard walk + hierarchical merge, fronted
                    # by the per-shard-epoch result cache (DESIGN.md §9)
                    self._sharded_topk_tier(
                        st,
                        iv,
                        kernel_batch,
                        replica_lists,
                        reqs,
                        recs,
                        results,
                        admit_mat,
                        top_k,
                        vocab,
                    )
                    sparse_done = True
                elif iv is not None:
                    l_attrs, l_valid = st.snapshot.logical_columns()
                    with self.tracer.span(
                        "broker.sparse_topk",
                        batch=len(kernel_batch),
                        rows=st.snapshot.n,
                        k=top_k,
                    ):
                        ti, ts = matchrank_batched_topk(
                            l_attrs,
                            l_valid,
                            kernel_plans,
                            k=top_k,
                            admit=admit_mat[:, : st.snapshot.n],
                            rank_order=st.snapshot.rank_order,
                        )
                    for bi, i in enumerate(kernel_batch):
                        results[i] = self._ranked_from_topk(
                            replica_lists[i], st, ti[bi], ts[bi]
                        )
                        recs[i].kernel_path = "sparse_topk"
                        self._fill_batched_audit(recs[i], st, results[i])
                        self._ctr["batched_sparse_requests"].inc()
                    sparse_done = True
            if not sparse_done:
                with self.tracer.span(
                    "broker.kernel_launch",
                    batch=len(kernel_batch),
                    rows=n_rows,
                    use_kernel=use_kernel,
                ):
                    mask, score, _, _ = matchrank_batched(
                        attrs,
                        valid,
                        stack_plans(kernel_plans),
                        admit=admit_mat,
                        n_rows=n_rows,
                        use_kernel=use_kernel,
                    )
                for bi, i in enumerate(kernel_batch):
                    results[i] = self._ranked_from_scores(
                        queries[i][0], replica_lists[i], st, mask[bi], score[bi]
                    )
                    recs[i].kernel_path = "batched_kernel"
                    self._fill_batched_audit(
                        recs[i], st, results[i], mask=mask[bi], score=score[bi]
                    )
                    self._ctr["batched_kernel_requests"].inc()

        # ---- tier 2: columnar programs over the shared snapshot table ----
        for i in columnar:
            with self.tracer.span("broker.columnar", lfn=queries[i][0]):
                prog = self.plan_cache.columnar_program(reqs[i], vocab, env=self.env)
                mask, rank = prog.run(st.table, np)
                mask = np.asarray(mask, bool) & (admits[i] > 0)
                row_admit = np.zeros((st.snapshot.n,), bool)
                for pfn in replica_lists[i]:
                    r = st.row_of.get(pfn.endpoint)
                    if r is not None:
                        row_admit[r] = True
                mask &= row_admit
                score = np.asarray(rank, np.float64)
                results[i] = self._ranked_from_scores(
                    queries[i][0], replica_lists[i], st, mask, score
                )
            recs[i].kernel_path = "batched_columnar"
            self._fill_batched_audit(recs[i], st, results[i], mask=mask, score=score)
            self._ctr["batched_columnar_requests"].inc()

        # ---- tier 3: the paper-faithful interpreter, per request ----
        for i in interp:
            with self.tracer.span("broker.interp", lfn=queries[i][0]):
                try:
                    views, ranked, _ = self._select_impl(queries[i][0], reqs[i])
                    self._fill_match_audit(
                        recs[i], [v.pfn.endpoint for v in views], ranked
                    )
                    results[i] = ranked
                except BrokerError as e:
                    recs[i].error = f"{type(e).__name__}: {e}"
                    results[i] = e
            recs[i].kernel_path = "batched_interp"
            self._ctr["batched_interp_requests"].inc()

        # ---- finalize: every successful query becomes a SelectionResult ----
        for i in range(n):
            r = results[i]
            if isinstance(r, list):
                if not r:
                    results[i] = NoMatchError(queries[i][0])
                    recs[i].error = "NoMatchError"
                    continue
                if top_k:
                    r = r[:top_k]
                results[i] = self._result(
                    queries[i][0], r, recs[i].request_id, scores=recs[i].scores
                )
        if strict:
            for r in results:
                if isinstance(r, BrokerError):
                    raise r
        return results

    def _ranked_from_scores(
        self, lfn: str, replicas: Sequence[PhysicalFile], st: _SnapshotState, mask, score
    ) -> List[RankedReplica]:
        """Snapshot rows + per-request scores → the same rank-ordered
        RankedReplica list the interpreter produces (same tiebreak)."""
        by_row = _rows_of(replicas, st)
        rows = [r for r in by_row if bool(mask[r])]
        rows.sort(key=lambda r: (-float(score[r]), _row_name(st, r), r))
        out = []
        for r in rows:
            view = ReplicaView(by_row[r], st.entries[r], st.ads[r])
            out.append(RankedReplica(view, float(score[r])))
        return out

    def _ranked_from_topk(
        self, replicas: Sequence[PhysicalFile], st: _SnapshotState, idx, scores
    ) -> List[RankedReplica]:
        """Sparse top-k winners (row indices + scores) → RankedReplica
        list, re-sorted with the dense tiebreak key."""
        by_row = _rows_of(replicas, st)
        picked: List[Tuple[int, float]] = []
        for r, s in zip(idx, scores):
            r, s = int(r), float(s)
            if r < 0 or (math.isinf(s) and s < 0):
                continue  # empty slot past the request's match count
            if r in by_row:
                picked.append((r, s))
        picked.sort(key=lambda rs: (-rs[1], _row_name(st, rs[0]), rs[0]))
        return [
            RankedReplica(ReplicaView(by_row[r], st.entries[r], st.ads[r]), s)
            for r, s in picked
        ]

    def _sharded_topk_tier(
        self,
        st: _SnapshotState,
        iv: Any,
        kernel_batch: List[int],
        replica_lists: Sequence[Optional[List[PhysicalFile]]],
        reqs: Sequence[Optional[ClassAd]],
        recs: Sequence[Any],
        results: List[Any],
        admit_mat: Any,
        top_k: int,
        vocab: Tuple[str, ...],
    ) -> None:
        """Tier 1a for sharded snapshots: each query is first looked up in
        the per-shard-epoch result cache — valid while every shard its
        candidates live in is unchanged — and only the misses walk the
        per-shard sparse top-k + hierarchical merge (DESIGN.md §9)."""
        import numpy as np
        from contextlib import contextmanager

        from repro.kernels.matchrank.sharded import sharded_sparse_topk
        from repro.kernels.matchrank.sparse import IntervalBatch

        from .plancache import request_cache_key

        snap = st.snapshot
        answers: Dict[int, Tuple[Any, Any]] = {}  # batch slot → (ti, ts)
        shard_sets: List[List[int]] = []
        keys: List[Tuple] = []
        miss_bis: List[int] = []
        for bi, i in enumerate(kernel_batch):
            rows = [
                r
                for pfn in replica_lists[i]
                if (r := st.row_of.get(pfn.endpoint)) is not None
            ]
            shard_sets.append(sorted({snap.shard_of_row(r) for r in rows}))
            key = (
                "sharded_topk",
                recs[i].lfn,
                int(top_k),
                tuple(sorted(p.endpoint for p in replica_lists[i])),
                request_cache_key(reqs[i], vocab, self.env),
                snap.uid,
            )
            keys.append(key)
            hit, val = self.plan_cache.topk_get(key, snap.shard_epochs)
            if hit:
                answers[bi] = val
            else:
                miss_bis.append(bi)
        if miss_bis:
            m = np.asarray(miss_bis, dtype=np.int64)
            batch_m = IntervalBatch(
                lo=iv.lo[m],
                hi=iv.hi[m],
                used=iv.used[m],
                weights=iv.weights[m],
                bias=iv.bias[m],
                undef_rank=iv.undef_rank[m],
            )
            tracer = self.tracer

            @contextmanager
            def observe(g):
                with tracer.span("broker.shard_rank", shard=int(g)) as sp:
                    yield
                self._shard_hist(int(g)).observe(sp.duration)

            shards = [snap.shard_logical_columns(g) for g in range(snap.g)]
            with self.tracer.span(
                "broker.sharded_topk",
                batch=len(miss_bis),
                rows=snap.n,
                shards=snap.g,
                k=top_k,
            ):
                ti, ts = sharded_sparse_topk(
                    shards,
                    batch_m,
                    k=top_k,
                    offsets=snap.offsets,
                    admit=admit_mat[m][:, : snap.n],
                    rank_order=snap.shard_rank_order,
                    observe=observe,
                )
            for j, bi in enumerate(miss_bis):
                val = (ti[j].copy(), ts[j].copy())
                touched = {g: int(snap.shard_epochs[g]) for g in shard_sets[bi]}
                self.plan_cache.topk_put(keys[bi], touched, val)
                answers[bi] = val
        for bi, i in enumerate(kernel_batch):
            ti_row, ts_row = answers[bi]
            results[i] = self._ranked_from_topk(replica_lists[i], st, ti_row, ts_row)
            recs[i].kernel_path = "sharded_topk"
            recs[i].shards = sorted(
                {snap.shard_of_row(int(r)) for r in ti_row if int(r) >= 0}
            )
            self._fill_batched_audit(recs[i], st, results[i])
            self._ctr["batched_sharded_requests"].inc()

    def _fill_batched_audit(
        self, rec, st: _SnapshotState, result: List[RankedReplica], mask=None, score=None
    ) -> None:
        """Per-candidate fates for a snapshot-tier request. Dense tiers
        pass row-level (mask, score); the sparse tier only probed until k
        candidates passed, so non-winners are recorded unmatched/unscored."""
        if mask is not None:
            scores = []
            for ep in rec.candidates:
                r = st.row_of.get(ep)
                ok = r is not None and bool(mask[r])
                scores.append(CandidateScore(ep, float(score[r]) if ok else None, ok))
            rec.scores = scores
        else:
            won = {rr.pfn.endpoint: rr.rank for rr in result}
            rec.scores = [
                CandidateScore(ep, won.get(ep), ep in won) for ep in rec.candidates
            ]
        rec.chosen = result[0].pfn.endpoint if result else None

    # ------------------------------------------------------------------ Access
    def fetch(
        self,
        lfn: str,
        transfer: TransferService,
        request: Optional[ClassAd] = None,
        *,
        monitor_stragglers: bool = True,
    ) -> FetchOutcome:
        """Search+Match+Access in one call (the paper's full loop)."""
        ranked = self.select(lfn, request)
        return self.access(lfn, ranked, transfer, monitor_stragglers=monitor_stragglers)

    def access(
        self,
        lfn: str,
        ranked: "SelectionResult | List[RankedReplica]",
        transfer: TransferService,
        *,
        monitor_stragglers: bool = True,
        request_id: Optional[str] = None,
    ) -> FetchOutcome:
        """Access Phase with failover and straggler mitigation, over a
        pre-computed selection (e.g. from a batched ``select_many``).

        Walks the ranked list; a failed endpoint advances to the next
        (failover); a transfer whose observed chunk bandwidth stays below
        ``straggler_factor × predicted`` for ``straggler_patience`` chunks
        is abandoned mid-flight and the next replica is tried.

        The outcome annotates the selection's decision record — a
        :class:`SelectionResult` carries its own ``request_id``; a bare
        list attaches to ``last_request_id`` when its lfn matches.
        """
        if request_id is None and isinstance(ranked, SelectionResult):
            request_id = ranked.request_id
        with self.tracer.span("broker.access", lfn=lfn):
            return self._access_impl(
                lfn,
                ranked,
                transfer,
                monitor_stragglers=monitor_stragglers,
                request_id=request_id,
            )

    def note_access(self, request_id: Optional[str], result: TransferResult) -> None:
        """Annotate a selection's decision record with an access outcome
        produced *outside* :meth:`access` — the resilient transfer
        service executes the plan itself and reports back here. Also
        feeds the client-side history monitor, keyed by the endpoint
        that contributed the most bytes."""
        self._ctr["fetches"].inc()
        top = None
        if result.per_replica:
            top = max(result.per_replica.items(), key=lambda kv: (kv[1], kv[0]))[0]
            self.local_monitor.observe_transfer(
                "read", top, result.nbytes, result.seconds, self.clock.now()
            )
        self._h_fetch_bw.observe(result.bandwidth / 1e6)
        if result.failovers:
            self._ctr["failovers"].inc(result.failovers)
        if request_id is not None and request_id in self.audit:
            rec = self.audit.get(request_id)
            rec.accessed = True
            rec.fetched_from = top
            rec.attempts = result.stripes + result.failovers
            rec.failovers += result.failovers
            rec.observed_bandwidth = result.bandwidth
            rec.nbytes = int(result.nbytes)

    def _access_impl(
        self,
        lfn: str,
        ranked: "SelectionResult | List[RankedReplica]",
        transfer: TransferService,
        *,
        monitor_stragglers: bool,
        request_id: Optional[str],
    ) -> FetchOutcome:
        if not ranked:
            raise NoMatchError(lfn)
        rid = request_id or self.last_request_id
        rec = None
        if rid is not None and rid in self.audit:
            cand = self.audit.get(rid)
            # implicit attachment only when the record is for this file
            if request_id is not None or cand.lfn == lfn:
                rec = cand
        self._ctr["fetches"].inc()
        attempts = 0
        switched = 0
        errors: List[str] = []
        abandoned: List[RankedReplica] = []  # straggler-abandoned, still alive

        def _finish(
            rr: RankedReplica, payload, nbytes, seconds, predicted
        ) -> FetchOutcome:
            self.local_monitor.observe_transfer(
                "read", rr.pfn.endpoint, nbytes, seconds, self.clock.now()
            )
            bw = nbytes / seconds if seconds > 0 else 0.0
            self._h_fetch_bw.observe(bw / 1e6)
            if rec is not None:
                rec.accessed = True
                rec.fetched_from = rr.pfn.endpoint
                rec.attempts = attempts
                rec.predicted_bandwidth = predicted
                rec.observed_bandwidth = bw
                rec.nbytes = int(nbytes)
            return FetchOutcome(
                lfn, rr.pfn, nbytes, seconds, attempts, switched, ranked, payload
            )

        for rr in ranked:
            if attempts >= self.max_attempts:
                break
            attempts += 1
            predicted = self._predicted_bandwidth(rr)
            try:
                if monitor_stragglers and predicted:
                    result = self._monitored_read(transfer, rr, predicted)
                    if result is None:  # straggler: try next replica
                        switched += 1
                        self._ctr["straggler_switches"].inc()
                        if rec is not None:
                            rec.straggler_switches += 1
                        abandoned.append(rr)
                        continue
                    payload, nbytes, seconds = result
                else:
                    res = transfer.transfer(TransferRequest(rr.pfn, self.client_url))
                    payload, nbytes, seconds = res.payload, res.nbytes, res.seconds
            except TransferFailure as e:
                errors.append(str(e))
                self._ctr["failovers"].inc()
                if rec is not None:
                    rec.failovers += 1
                continue
            return _finish(rr, payload, nbytes, seconds, predicted)
        # Mitigation must never turn a working fetch into a failure: if the
        # list was exhausted by straggler switches, take the best abandoned
        # replica to completion without monitoring.
        for rr in abandoned:
            attempts += 1
            try:
                res = transfer.transfer(TransferRequest(rr.pfn, self.client_url))
                payload, nbytes, seconds = res.payload, res.nbytes, res.seconds
            except TransferFailure as e:
                errors.append(str(e))
                continue
            return _finish(rr, payload, nbytes, seconds, None)
        if rec is not None:
            rec.attempts = attempts
            rec.error = f"AccessFailed: all {attempts} attempt(s) failed"
        raise BrokerError(
            f"all {attempts} attempt(s) to fetch {lfn!r} failed"
            + (f": {errors}" if errors else "")
        )

    def _monitored_read(
        self, transfer: TransferService, rr: RankedReplica, predicted: float
    ) -> Optional[Tuple[Any, int, float]]:
        """Chunked read with mid-transfer bandwidth watch. Returns None if
        abandoned as a straggler."""
        chunks: List[Any] = []
        nbytes = 0
        seconds = 0.0
        slow = 0
        for ev in transfer.transfer_chunks(TransferRequest(rr.pfn, self.client_url)):
            payload, cbytes, csecs = ev.payload, ev.nbytes, ev.seconds
            chunks.append(payload)
            nbytes += cbytes
            seconds += csecs
            bw = cbytes / csecs if csecs > 0 else math.inf
            if bw < self.straggler_factor * predicted:
                slow += 1
                if slow >= self.straggler_patience:
                    return None
            else:
                slow = 0
        merged = b"".join(c for c in chunks if isinstance(c, (bytes, bytearray))) if chunks and isinstance(chunks[0], (bytes, bytearray)) else chunks
        return merged, nbytes, seconds

    # -------------------------------------------------------------- placement
    def select_placements(
        self,
        nbytes: int,
        endpoints: Sequence[str],
        *,
        k: int = 2,
        request: Optional[ClassAd] = None,
    ) -> SelectionResult:
        """Write-side matchmaking: choose ``k`` placement targets for a new
        replica of size ``nbytes`` (checkpoint placement uses this).
        Returns the same :class:`SelectionResult` shape as the read path
        (no transfer plan — writes create replicas, they don't stripe
        reads over them)."""
        req = request if request is not None else default_write_request(self.client_url, nbytes)
        views: List[ReplicaView] = []
        for ep in endpoints:
            gris = self.gris_resolver(ep)
            if gris is None:
                continue
            entry = gris.flattened_view(source=self.client_url)
            entry.setdefault("endpoint", ep)
            pfn = PhysicalFile(ep, "", nbytes)
            views.append(ReplicaView(pfn, entry, entry_to_classad(entry)))
        ranked = self.match(req, views)
        if len(ranked) < 1:
            raise NoMatchError(f"no endpoint admits a {nbytes}-byte replica")
        ranked = ranked[:k]
        matched = {rr.pfn.endpoint: rr.rank for rr in ranked}
        scores = [
            CandidateScore(ep, matched.get(ep), ep in matched) for ep in endpoints
        ]
        return SelectionResult(ranked, lfn=f"<placement:{nbytes}B>", scores=scores)
