"""Unified observability: metrics exposition, span nesting, audit-record
completeness on every execution tier, and GRIS-published broker telemetry."""

import io
import json
import math

import numpy as np
import pytest

from repro.core.broker import DataBroker, NoMatchError, default_read_request
from repro.core.classads import parse_classad
from repro.core.gris import Clock
from repro.obs import (
    AuditTrail,
    BROKER_METRIC,
    BROKER_TELEMETRY,
    BrokerTelemetryGRIS,
    MetricError,
    MetricsRegistry,
    Tracer,
)
from repro.storage.endpoint import build_demo_grid


# --------------------------------------------------------------------- metrics
class TestMetricsRegistry:
    def test_counter_gauge_histogram_basics(self):
        reg = MetricsRegistry()
        c = reg.counter("requests_total", "requests")
        c.inc()
        c.inc(2)
        assert reg.value("requests_total") == 3
        with pytest.raises(MetricError):
            c.inc(-1)

        g = reg.gauge("queue_depth", "depth")
        g.set(5)
        g.dec(2)
        assert reg.value("queue_depth") == 3
        g.set_max(1)
        assert reg.value("queue_depth") == 3

        h = reg.histogram("latency_seconds", "latency", buckets=(0.1, 1, math.inf))
        for v in (0.05, 0.5, 2.0):
            h.observe(v)
        assert h.count == 3
        assert h.sum == pytest.approx(2.55)
        assert [c for _, c in h.cumulative()] == [1, 2, 3]

    def test_labels_and_bounded_cardinality(self):
        reg = MetricsRegistry(max_label_sets=2)
        reg.counter("ops_total", "ops", op="read").inc()
        reg.counter("ops_total", "ops", op="write").inc()
        # third distinct label set collapses into the overflow series
        reg.counter("ops_total", "ops", op="delete").inc()
        reg.counter("ops_total", "ops", op="stat").inc()
        labels = {
            tuple(lbl.items())
            for name, lbl, _metric in reg.samples()
            if name == "ops_total"
        }
        assert (("op", "__other__"),) in labels
        assert reg.value("ops_total", op="__other__") == 2

    def test_kind_and_name_conflicts_rejected(self):
        reg = MetricsRegistry()
        reg.counter("x_total", "x")
        with pytest.raises(MetricError):
            reg.gauge("x_total", "x")
        with pytest.raises(MetricError):
            reg.counter("bad name!", "x")
        with pytest.raises(MetricError):
            reg.counter("y_total", "y", le="0.5")

    def test_prometheus_exposition_format(self):
        reg = MetricsRegistry()
        reg.counter("broker_searches_total", "searches").inc(3)
        reg.gauge("queue_depth", "depth", shard="a b\"c\\d").set(2.5)
        h = reg.histogram("lat_seconds", "lat", buckets=(0.5, math.inf))
        h.observe(0.1)
        h.observe(7.0)
        text = reg.expose_text()
        assert "# HELP broker_searches_total searches\n" in text
        assert "# TYPE broker_searches_total counter\n" in text
        assert "broker_searches_total 3\n" in text
        # label escaping: backslash, quote (Prometheus text format 0.0.4)
        assert 'queue_depth{shard="a b\\"c\\\\d"} 2.5' in text
        assert 'lat_seconds_bucket{le="0.5"} 1\n' in text
        assert 'lat_seconds_bucket{le="+Inf"} 2\n' in text
        assert "lat_seconds_sum 7.1\n" in text
        assert "lat_seconds_count 2\n" in text
        self._parse_exposition(text)

    @staticmethod
    def _parse_exposition(text: str):
        """Minimal format checker: every non-comment line must be
        ``name{labels} value`` with a float-parseable value."""
        import re

        sample = re.compile(
            r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^{}]*\})? [^ ]+$"
        )
        for line in text.strip().splitlines():
            if line.startswith("#"):
                assert line.startswith(("# HELP ", "# TYPE ")), line
                continue
            assert sample.match(line), f"bad exposition line: {line!r}"
            float(line.rsplit(" ", 1)[1])  # value parses

    def test_json_round_trip(self, tmp_path):
        reg = MetricsRegistry()
        reg.counter("a_total", "a", op="r").inc(4)
        reg.gauge("b", "b").set(-1.5)
        h = reg.histogram("c_seconds", "c", buckets=(1, math.inf))
        h.observe(0.5)
        h.observe(3.0)

        clone = MetricsRegistry.from_dict(reg.to_dict())
        assert clone.value("a_total", op="r") == 4
        assert clone.value("b") == -1.5
        assert clone.expose_text() == reg.expose_text()

        path = tmp_path / "metrics.json"
        reg.dump_json(str(path), extra={"run": "t"})
        payload = json.loads(path.read_text())
        assert payload["run"] == "t"
        assert "a_total" in payload["exposition"]
        again = MetricsRegistry.from_dict(payload)
        assert again.expose_text() == reg.expose_text()


# ----------------------------------------------------------------------- spans
class TestTracer:
    def test_nesting_and_chrome_export(self):
        t = [0.0]

        def clock():
            t[0] += 1.0
            return t[0]

        tr = Tracer(time_fn=clock)
        with tr.span("outer", phase="x") as outer:
            with tr.span("inner") as inner:
                pass
            assert tr.depth == 1
        assert tr.depth == 0
        assert inner.parent_id == outer.span_id
        assert inner.depth == 1 and outer.depth == 0
        assert outer.duration > inner.duration

        doc = tr.export_chrome()
        events = doc["traceEvents"]
        assert {e["name"] for e in events} == {"outer", "inner"}
        for e in events:
            assert e["ph"] == "X"
            assert e["dur"] >= 0
        by_name = {e["name"]: e for e in events}
        assert by_name["inner"]["args"]["parent_id"] == outer.span_id
        assert by_name["outer"]["args"]["phase"] == "x"
        json.dumps(doc)  # serializable as-is

    def test_decorator_and_bounded_buffer(self):
        tr = Tracer(max_spans=4)

        @tr.trace("work")
        def work(x):
            return x * 2

        assert work(3) == 6
        assert len(tr.spans("work")) == 1
        for _ in range(10):
            work(1)
        assert len(tr.spans()) == 4
        assert tr.dropped == 7

    def test_span_set_attaches_args(self):
        tr = Tracer()
        with tr.span("s") as s:
            s.set(batch=7)
        assert tr.spans("s")[0].args["batch"] == 7


# ----------------------------------------------------- broker audit trail
def _demo_broker(**kwargs):
    grid = build_demo_grid(4, 3, seed=7)
    grid.add_client("client://c0", zone="zone1")
    grid.replicate("shard-000", b"x" * (2 << 20), ["gsiftp://ep000", "gsiftp://ep002"])
    grid.replicate("shard-001", b"y" * (1 << 20), ["gsiftp://ep001", "gsiftp://ep003"])
    grid.replicate("shard-002", b"z" * (1 << 20), ["gsiftp://ep000", "gsiftp://ep001"])
    broker = grid.broker_for("client://c0", **kwargs)
    return grid, broker


class TestAuditTrail:
    def test_select_records_complete_decision(self):
        grid, b = _demo_broker()
        lfn = sorted(grid.catalog.logical_files())[0]
        ranked = b.select(lfn)
        rid = b.last_request_id
        rec = b.explain(rid)
        assert rec.request_id == rid
        assert rec.lfn == lfn and rec.mode == "select"
        assert rec.kernel_path in ("interpreter", "vectorized")
        assert rec.candidates and rec.chosen == ranked[0].pfn.endpoint
        assert len(rec.scores) == len(rec.candidates)
        winner = next(s for s in rec.scores if s.endpoint == rec.chosen)
        assert winner.matched and winner.rank == pytest.approx(ranked[0].rank)
        assert rec.error is None and not rec.accessed

    def test_select_failure_recorded(self):
        grid, b = _demo_broker()
        lfn = sorted(grid.catalog.logical_files())[0]
        req = parse_classad("requirements = other.loadFactor > 1e12; rank = 1")
        req["clientUrl"] = "client://c0"
        with pytest.raises(NoMatchError):
            b.select(lfn, req)
        rec = b.explain(b.last_request_id)
        assert rec.error == "NoMatchError"
        assert rec.chosen is None
        assert all(not s.matched for s in rec.scores)

    def test_select_many_dense_kernel_audit(self):
        grid, b = _demo_broker(batch_use_kernel=False)
        lfns = sorted(grid.catalog.logical_files())[:3]
        req = parse_classad(
            "reqdSpace = 0; rank = other.diskTransferRate;"
            "requirements = other.availableSpace > 1M;"
        )
        results = b.select_many([(l, req) for l in lfns])
        assert len(b.last_request_ids) == 3
        assert b.stats["batched_kernel_requests"] == 3
        for rid, lfn, res in zip(b.last_request_ids, lfns, results):
            rec = b.explain(rid)
            assert rec.mode == "select_many" and rec.lfn == lfn
            assert rec.kernel_path == "batched_kernel"
            assert rec.snapshot in ("build", "reuse")
            assert rec.plan_cache in ("hit", "miss")
            assert rec.chosen == res[0].pfn.endpoint
            assert any(s.matched for s in rec.scores)
        # first request lowered the plan, the rest hit the cache
        statuses = [b.explain(r).plan_cache for r in b.last_request_ids]
        assert statuses[0] == "miss" and set(statuses[1:]) == {"hit"}

    def test_select_many_kernel_topk_audit(self):
        """A top-k on the kernel path records its tier, its k, and a score
        for every candidate, not only the k winners."""
        grid, b = _demo_broker(batch_use_kernel=True)
        lfns = sorted(grid.catalog.logical_files())[:3]
        req = parse_classad(
            "reqdSpace = 0; rank = other.diskTransferRate;"
            "requirements = other.availableSpace > 1M;"
        )
        full = b.select_many([(l, req) for l in lfns])
        top2 = b.select_many([(l, req) for l in lfns], top_k=2)
        assert b.stats["batched_kernel_requests"] == 6
        for rid, f, res in zip(b.last_request_ids, full, top2):
            assert [rr.pfn.endpoint for rr in res] == [rr.pfn.endpoint for rr in f][:2]
            rec = b.explain(rid)
            assert rec.kernel_path == "batched_kernel"
            assert rec.top_k == 2
            assert rec.chosen == res[0].pfn.endpoint
            assert [sc.endpoint for sc in rec.scores] == rec.candidates
            ranks = {rr.pfn.endpoint: rr.rank for rr in f}
            for sc in rec.scores:
                assert sc.matched == (sc.endpoint in ranks)
                if sc.matched:
                    assert sc.rank == pytest.approx(ranks[sc.endpoint])

    def test_select_many_interp_tier_audit(self):
        grid, b = _demo_broker()
        lfn = sorted(grid.catalog.logical_files())[0]
        # per-replica attribute forces the interpreter tier
        req = default_read_request("client://c0")
        req.set_expr("rank", "other.replicaSize")
        b.select_many([(lfn, req)])
        rec = b.explain(b.last_request_ids[0])
        assert rec.kernel_path == "batched_interp"
        assert rec.chosen is not None

    def test_access_annotates_record(self):
        grid, b = _demo_broker()
        transfer = grid.transfer_service(metrics=b.metrics)
        lfn = sorted(grid.catalog.logical_files())[0]
        out = b.fetch(lfn, transfer)
        rec = b.explain(b.last_request_id)
        assert rec.accessed
        assert rec.fetched_from == out.replica.endpoint
        assert rec.nbytes == out.nbytes
        assert rec.observed_bandwidth == pytest.approx(out.bandwidth)
        assert rec.attempts == out.attempts
        # transfer service shares the registry
        assert b.metrics.value("transfer_total", op="read") >= 1

    def test_trail_ring_eviction_and_dump(self, tmp_path):
        trail = AuditTrail(capacity=2)
        r1 = trail.begin("f1", mode="select", at=0.0)
        trail.begin("f2", mode="select", at=1.0)
        trail.begin("f3", mode="select", at=2.0)
        assert len(trail) == 2 and trail.evicted == 1
        assert r1.request_id not in trail
        with pytest.raises(KeyError):
            trail.get(r1.request_id)

        buf = io.StringIO()
        assert trail.dump_jsonl(buf) == 2
        lines = [json.loads(l) for l in buf.getvalue().splitlines()]
        assert [l["lfn"] for l in lines] == ["f2", "f3"]

    def test_stats_property_backed_by_registry(self):
        grid, b = _demo_broker()
        lfn = sorted(grid.catalog.logical_files())[0]
        b.select(lfn)
        assert b.stats["searches"] == 1 and b.stats["matches"] == 1
        assert isinstance(b.stats["searches"], int)
        assert b.metrics.value("broker_searches_total") == 1
        assert "broker_searches_total 1" in b.metrics.expose_text()


# ----------------------------------------------------- GRIS-published telemetry
class TestBrokerTelemetryGRIS:
    def test_telemetry_subtree_valid_and_searchable(self):
        grid, b = _demo_broker()
        lfns = sorted(grid.catalog.logical_files())[:2]
        b.select_many([(l, None) for l in lfns])
        pub = BrokerTelemetryGRIS("gbt=c0, o=grid", b)

        top = pub.telemetry_entry()
        assert top["objectClass"] == BROKER_TELEMETRY.name
        assert top["searchesTotal"] == float(b.stats["searches"])
        assert top["batchSelectsTotal"] == 1.0
        assert top["auditRecords"] == float(len(b.audit))

        entries = pub.entries()
        assert entries[0] is not top  # materialized per call
        kids = [e for e in entries if e["objectClass"] == BROKER_METRIC.name]
        assert kids, "registry series published as child entries"
        names = {e["metricName"] for e in kids}
        assert "broker_searches_total" in names
        for e in kids:
            assert e["dn"].endswith(pub.dn)

        # LDAP filter over the subtree, like a GIIS query would issue
        hits = pub.search(f"(objectClass={BROKER_TELEMETRY.name})")
        assert len(hits) == 1 and hits[0]["brokerUrl"] == "client://c0"
        proj = pub.search(
            f"(metricName=broker_searches_total)", attrs=["metricValue"]
        )
        assert proj and "metricValue" in proj[0] and "metricType" not in proj[0]

    def test_giis_aggregates_broker_health(self):
        from repro.core.giis import GIIS

        grid, b = _demo_broker()
        b.select(sorted(grid.catalog.logical_files())[0])
        giis = GIIS("o=grid", clock=Clock())
        giis.register("broker-c0", BrokerTelemetryGRIS("gbt=c0, o=grid", b))
        hits = giis.search(f"(objectClass={BROKER_TELEMETRY.name})")
        assert len(hits) == 1
        assert hits[0]["searchesTotal"] >= 1.0

    def test_ldif_dump(self):
        grid, b = _demo_broker()
        b.select(sorted(grid.catalog.logical_files())[0])
        pub = BrokerTelemetryGRIS("gbt=c0, o=grid", b)
        text = pub.to_ldif()
        assert "dn: gbt=c0, o=grid" in text
        assert "objectClass: Grid::Broker::Telemetry" in text


# ----------------------------------------------------------- GRIS ttl metrics
def test_gris_query_metrics_and_ttl_hit_rate():
    grid, b = _demo_broker()
    ep = next(iter(grid.endpoints.values()))
    ep.gris.metrics = b.metrics
    lfn = sorted(grid.catalog.logical_files())[0]
    b.select(lfn)  # same simulated instant: dynamic reads hit the TTL cache
    b.select(lfn)
    assert b.metrics.value("gris_queries_total") >= 1
    stats = ep.gris.ttl_cache_stats()
    assert stats["misses"] >= 1
    assert 0.0 <= b.metrics.value("gris_dynamic_ttl_hit_rate") <= 1.0
