"""Served-path smoke: the broker's batched selection, end to end, once.

Builds a grid whose GIIS registers ``endpoints`` storage endpoints and
whose catalog holds ``files`` logical files of ``replicas`` replicas each
(all drawn from ``seed``), then drives the path a serving process takes:

  ``build_demo_grid`` → ``DataGrid.broker_for(..., batch_use_kernel=True)``
  → ``BatchScheduler.submit`` → size flushes → ``DataBroker.select_many``
  → the device-resident snapshot and the fused matchrank kernel → ranked
  ``SelectionResult``\\ s.

One warm-up flush precedes ``flushes`` timed flushes of ``batch`` requests.
Every request lowers to the kernel tier (``rank = other.diskTransferRate``,
conjunctive thresholds drawn per request, ``reqdSpace`` for the sites'
usage policies). The report says which path each request took, whether
the tier-1 program holds a Mosaic ``tpu_custom_call`` (so the kernel is
compiled, not interpreted), and whether every ranking equals that of the
paper-faithful interpreter (a default broker's ``select``) on the same grid.

A second phase then serves the broker's default read ad (requests sent
with no ad) with the branches of its rank chain in play: per-source
transfer history for this client on a third of the endpoints, site
averages on another third, open or half-open breakers on a tenth, all
published through the GRIS per-source and summary paths. After the
snapshot's TTL lapses, :data:`DEFAULT_FLUSHES` size flushes of ``batch``
default-ad requests take the same compiled launch; their rankings must
equal the interpreter's, and each branch of the chain must rank at least
:data:`MIN_BRANCH_SHARE` of the ranked rows.

Times are host wall-clock readings of this one run (each flush ends with
its results on the host), not benchmark metrics.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from repro.core.broker import BrokerError
from repro.core.catalog import PhysicalFile
from repro.core.classads import ClassAd, parse_classad
from repro.serve.scheduler import BatchScheduler
from repro.storage.endpoint import DataGrid, build_demo_grid

__all__ = ["DefaultAdPhase", "SmokeReport", "build_smoke_grid", "publish_history", "run_smoke"]

GiB = 1 << 30
MiB = 1 << 20
CLIENT = "client://smoke"
#: the least share of ranked rows each branch of the default read ad's
#: rank chain must take in the default-ad phase
MIN_BRANCH_SHARE = 0.10
#: size flushes of requests sent with no ad in the default-ad phase
DEFAULT_FLUSHES = 4
#: the chain's branches, by the attribute that ranks a row
BRANCHES = ("EwmaRDBandwidthToSource", "AvgRDBandwidth", "static")


def build_smoke_grid(
    endpoints: int, files: int, replicas: int, *, seed: int = 0
) -> Tuple[DataGrid, List[str]]:
    """``build_demo_grid(endpoints, 16)`` — five disk rates, so equal ranks
    are common and the tie-break is exercised, and a usage policy on every
    third site — with seeded capacity (whole GiB) and load per site, and
    ``files`` logical files of ``replicas`` replicas on distinct sites.
    → (grid, lfns)."""
    rng = np.random.default_rng(seed)
    grid = build_demo_grid(endpoints, 16, seed=seed)
    grid.add_client(CLIENT, zone="zone0")
    urls = list(grid.endpoints)
    caps = rng.integers(1, 65, endpoints)
    loads = rng.integers(0, 8, endpoints)
    for url, cap, load in zip(urls, caps, loads):
        ep = grid.endpoints[url]
        ep.capacity = int(cap) * GiB  # published as availableSpace
        ep.active_transfers = int(load)  # published as loadFactor
    lfns = [f"file-{f:06d}" for f in range(files)]
    for lfn in lfns:
        for e in rng.choice(endpoints, size=replicas, replace=False):
            grid.catalog.register_replica(
                lfn, PhysicalFile(urls[e], f"/data/{lfn}", 64 << 20)
            )
    return grid, lfns


def _requests(rng: np.random.Generator, lfns: List[str], n: int) -> List[Tuple[str, ClassAd]]:
    """``n`` (lfn, request) pairs with per-request thresholds."""
    out = []
    for _ in range(n):
        lfn = lfns[int(rng.integers(len(lfns)))]
        need = int(rng.integers(1, 17)) * GiB
        space = int(rng.integers(0, 40)) * GiB
        load = int(rng.integers(2, 9))
        req = parse_classad(
            f"reqdSpace = {need}; rank = other.diskTransferRate;"
            f" requirements = other.availableSpace > {space}"
            f" && other.loadFactor < {load};"
        )
        out.append((lfn, req))
    return out


def publish_history(grid: DataGrid, seed: int) -> Dict[str, str]:
    """Publish what the default read ad's rank chain reads: this client's
    per-source EWMA on a third of the endpoints, a site summary (average
    and maximum read bandwidth) on another third, and a breaker this
    client tripped (open, 1, or half-open, 0.5) on a tenth drawn apart.
    Bandwidths are whole MiB/s, exact in f32. → endpoint → the branch
    that ranks it."""
    rng = np.random.default_rng([seed, 4])
    urls = list(grid.endpoints)
    order = rng.permutation(len(urls))
    third = len(urls) // 3
    branch = {}
    for k, i in enumerate(order):
        gris = grid.endpoints[urls[i]].gris
        if k < third:
            gris.publish_source_bandwidth(CLIENT, {
                "lastRDBandwidth": 0.0, "lastRDurl": "", "lastWRBandwidth": 0.0,
                "lastWRurl": "",
                "EwmaRDBandwidthToSource": float(rng.integers(64, 1024) * MiB),
            })
            branch[urls[i]] = BRANCHES[0]
        elif k < 2 * third:
            gris.publish_bandwidth_summary({
                "MaxRDBandwidth": float(rng.integers(64, 2048) * MiB),
                "MinRDBandwidth": 0.0,
                "AvgRDBandwidth": float(rng.integers(64, 1024) * MiB),
                "MaxWRBandwidth": 0.0, "MinWRBandwidth": 0.0, "AvgWRBandwidth": 0.0,
            })
            branch[urls[i]] = BRANCHES[1]
        else:
            branch[urls[i]] = BRANCHES[2]
    for i in rng.choice(len(urls), size=len(urls) // 10, replace=False):
        grid.endpoints[urls[i]].gris.publish_source_health(
            CLIENT, {"breakerOpenToSource": float(rng.choice([1.0, 0.5]))}
        )
    return branch


def _outcome(result: Any) -> Tuple[str, List[Tuple[str, float]]]:
    """A selection outcome as (error name or "ok", [(endpoint, rank)])."""
    if isinstance(result, BaseException):
        return type(result).__name__, []
    return "ok", [(rr.pfn.endpoint, float(rr.rank)) for rr in result]


def _same(got, want, rtol: float = 1e-6) -> bool:
    if got[0] != want[0] or len(got[1]) != len(want[1]):
        return False
    return all(
        ge == we and abs(gr - wr) <= rtol * max(1.0, abs(wr))
        for (ge, gr), (we, wr) in zip(got[1], want[1])
    )


@dataclass
class DefaultAdPhase:
    """The default-ad phase: requests sent with no ad, answered by the
    stacked kernel, against the interpreter."""

    requests: int
    guarded_requests: int  # batched_kernel_guarded_requests in the phase
    kernel_launches: int
    paths: Dict[str, int]
    mismatches: List[str] = field(default_factory=list)
    branch_share: Dict[str, float] = field(default_factory=dict)  # of ranked rows

    @property
    def ok(self) -> bool:
        return (
            self.paths == {"batched_kernel": self.requests}
            and self.guarded_requests == self.requests
            and not self.mismatches
            and all(self.branch_share.get(b, 0.0) >= MIN_BRANCH_SHARE for b in BRANCHES)
        )


@dataclass
class SmokeReport:
    endpoints: int
    snapshot_shape: Tuple[int, int]
    requests: int  # all requests, warm-up flush included
    timed_flushes: int
    kernel_requests: int  # the broker's batched_kernel_requests counter
    kernel_launches: int  # timed flushes' broker.kernel_launch spans with use_kernel
    paths: Dict[str, int]  # kernel_path → requests
    matched: int  # requests with at least one ranked replica
    default_ads: DefaultAdPhase
    mismatches: List[str] = field(default_factory=list)
    mosaic: bool = False  # the tier-1 program holds a tpu_custom_call
    compile_s: float = 0.0
    warmup_flush_s: float = 0.0
    flush_s: List[float] = field(default_factory=list)
    span_s: Dict[str, float] = field(default_factory=dict)  # per timed flush
    peak_bytes: Optional[int] = None

    @property
    def ok(self) -> bool:
        """Every request took the kernel tier and matched the interpreter,
        in both phases."""
        return (
            self.paths == {"batched_kernel": self.requests}
            and self.kernel_requests == self.requests
            and self.kernel_launches == self.timed_flushes
            and not self.mismatches
            and self.default_ads.ok
        )

    def lines(self) -> List[str]:
        s, a = self.snapshot_shape
        steady = float(np.median(self.flush_s)) if self.flush_s else 0.0
        return [
            f"grid: {self.endpoints} endpoints; device snapshot [{s}, {a}] f32 x2",
            f"requests: {self.requests} ({self.timed_flushes} timed size flushes"
            f" + 1 warm-up), {self.matched} with a match",
            f"kernel paths: {self.paths}; batched_kernel_requests ="
            f" {self.kernel_requests}; kernel launches in {self.timed_flushes}"
            f" timed flushes: {self.kernel_launches}",
            f"tier-1 program holds tpu_custom_call: {self.mosaic}",
            f"rankings equal the interpreter's: {not self.mismatches}"
            f" ({len(self.mismatches)} differ)",
            f"smoke timing (not a benchmark): compile {self.compile_s} s,"
            f" first flush {self.warmup_flush_s} s, median flush {steady} s"
            f" of {self.flush_s}",
            "host spans per timed flush (s): "
            + ", ".join(f"{k} {v}" for k, v in sorted(self.span_s.items())),
            f"peak_bytes_in_use: {self.peak_bytes}",
        ] + self._default_ad_lines()

    def _default_ad_lines(self) -> List[str]:
        d = self.default_ads
        share = ", ".join(f"{b} {d.branch_share.get(b, 0.0):.3f}" for b in BRANCHES)
        return [
            f"default-ad phase: {d.requests} requests sent with no ad; kernel paths"
            f" {d.paths}; guarded plans {d.guarded_requests}; kernel launches"
            f" {d.kernel_launches}",
            f"default-ad rankings equal the interpreter's: {not d.mismatches}"
            f" ({len(d.mismatches)} differ)",
            f"default-ad ranked rows by branch: {share} (each at least {MIN_BRANCH_SHARE})",
        ]


def run_smoke(
    *,
    endpoints: int = 10_000,
    files: int = 4096,
    replicas: int = 3,
    flushes: int = 4,
    batch: int = 64,
    seed: int = 0,
) -> SmokeReport:
    """Drive the served path once and check it (see the module doc)."""
    import jax

    from repro.kernels.matchrank.ops import lower_matchrank_batched

    grid, lfns = build_smoke_grid(endpoints, files, replicas, seed=seed)
    broker = grid.broker_for(CLIENT, batch_use_kernel=True)
    snap = broker.warm_snapshot(grid.alive_endpoints())
    attrs, valid, n_rows = snap.device_columns()

    rng = np.random.default_rng(seed + 1)
    flights = [_requests(rng, lfns, batch) for _ in range(flushes + 1)]

    # the tier-1 program a flush launches, compiled ahead of the traffic
    plans = [
        broker.plan_cache.kernel_plan(req, snap.vocab_key(), env=broker.env)
        for _, req in flights[0]
    ]
    lowered = lower_matchrank_batched(
        attrs, valid, plans,
        admit=np.zeros((len(plans), n_rows), np.float32), n_rows=n_rows,
    )
    t0 = time.perf_counter()  # lint: allow-wallclock
    compiled = lowered.compile()
    compile_s = time.perf_counter() - t0  # lint: allow-wallclock
    mosaic = "tpu_custom_call" in compiled.as_text()

    sched = BatchScheduler(broker, max_batch=batch, use_kernel=True)
    tickets = []
    paths: Dict[str, int] = {}
    flush_s: List[float] = []
    for f, flight in enumerate(flights):
        t0 = time.perf_counter()  # lint: allow-wallclock
        flight_tickets = [sched.submit(lfn, req) for lfn, req in flight]
        dt = time.perf_counter() - t0  # lint: allow-wallclock
        if not all(t.done for t in flight_tickets):
            raise RuntimeError("a full batch did not trigger a size flush")
        if f == 0:
            warmup_s = dt
            broker.tracer.clear()  # spans from here on cover the timed flushes
        else:
            flush_s.append(dt)
        for rid in broker.last_request_ids:
            p = broker.explain(rid).kernel_path
            paths[p] = paths.get(p, 0) + 1
        tickets.extend(flight_tickets)

    span_s: Dict[str, float] = {}
    for sp in broker.tracer.spans():
        span_s[sp.name] = span_s.get(sp.name, 0.0) + sp.duration / flushes
    # the label batched_kernel also covers the host evaluator: the span
    # says whether the broker launched the kernel itself
    launches = sum(
        bool(sp.args.get("use_kernel"))
        for sp in broker.tracer.spans("broker.kernel_launch")
    )

    reference = grid.broker_for(CLIENT)  # default settings: the interpreter
    mismatches: List[str] = []
    matched = 0
    queries = [q for flight in flights for q in flight]
    for (lfn, req), ticket in zip(queries, tickets):
        got, want = _compare(ticket, reference, lfn, req)
        matched += got[0] == "ok"
        if not _same(got, want):
            mismatches.append(f"{lfn}: kernel {got} != interpreter {want}")
    kernel_requests = broker.stats["batched_kernel_requests"]

    default_ads = _default_ad_phase(grid, broker, sched, reference, lfns, rng, batch, seed)

    stats = jax.devices()[0].memory_stats() or {}
    return SmokeReport(
        endpoints=endpoints,
        snapshot_shape=tuple(attrs.shape),
        requests=len(queries),
        timed_flushes=flushes,
        kernel_requests=kernel_requests,
        kernel_launches=launches,
        paths=paths,
        matched=matched,
        mismatches=mismatches,
        mosaic=mosaic,
        compile_s=compile_s,
        warmup_flush_s=warmup_s,
        flush_s=flush_s,
        span_s=span_s,
        peak_bytes=stats.get("peak_bytes_in_use"),
        default_ads=default_ads,
    )


def _compare(ticket, reference, lfn: str, req: Optional[ClassAd]):
    """(served outcome, the interpreter's outcome) of one request."""
    try:
        got = _outcome(ticket.result())
    except BrokerError as e:
        got = _outcome(e)
    try:
        want = _outcome(reference.select(lfn, req))
    except BrokerError as e:
        want = _outcome(e)
    return got, want


def _default_ad_phase(
    grid: DataGrid, broker, sched: BatchScheduler, reference, lfns: List[str],
    rng: np.random.Generator, batch: int, seed: int,
) -> DefaultAdPhase:
    """Publish the history the rank chain reads, let the snapshot's TTL
    lapse, and flush :data:`DEFAULT_FLUSHES` × ``batch`` requests sent with
    no ad."""
    branch = publish_history(grid, seed)
    grid.clock.advance(broker.snapshot_ttl + 1.0)  # the next flush rebuilds
    guarded0 = broker.stats["batched_kernel_guarded_requests"]
    broker.tracer.clear()
    queries: List[Tuple[str, None]] = []
    tickets = []
    paths: Dict[str, int] = {}
    for _ in range(DEFAULT_FLUSHES):
        flight = [(lfns[int(rng.integers(len(lfns)))], None) for _ in range(batch)]
        flight_tickets = [sched.submit(lfn, None) for lfn, _ in flight]
        if not all(t.done for t in flight_tickets):
            raise RuntimeError("a full batch did not trigger a size flush")
        for rid in broker.last_request_ids:
            p = broker.explain(rid).kernel_path
            paths[p] = paths.get(p, 0) + 1
        queries.extend(flight)
        tickets.extend(flight_tickets)
    launches = sum(
        bool(sp.args.get("use_kernel"))
        for sp in broker.tracer.spans("broker.kernel_launch")
    )
    mismatches: List[str] = []
    ranked = {b: 0 for b in BRANCHES}
    for (lfn, _), ticket in zip(queries, tickets):
        got, want = _compare(ticket, reference, lfn, None)
        if not _same(got, want):
            mismatches.append(f"{lfn}: kernel {got} != interpreter {want}")
        for url, _ in want[1]:
            ranked[branch[url]] += 1
    rows = sum(ranked.values())
    return DefaultAdPhase(
        requests=len(queries),
        guarded_requests=broker.stats["batched_kernel_guarded_requests"] - guarded0,
        kernel_launches=launches,
        paths=paths,
        mismatches=mismatches,
        branch_share={b: ranked[b] / rows if rows else 0.0 for b in BRANCHES},
    )
