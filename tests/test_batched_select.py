"""Batched device-resident matchmaking: ReplicaSnapshot, PlanCache,
DataBroker.select_many tier parity, and the coalescing BatchScheduler."""

import numpy as np
import pytest

from repro.core.broker import NoMatchError, NoReplicaError, SelectionResult
from repro.core.classads import parse_classad
from repro.core.compile import CompileError
from repro.core.plancache import PlanCache, request_cache_key
from repro.core.snapshot import ReplicaSnapshot, numeric_attr_names
from repro.kernels.matchrank.ops import matchrank
from repro.serve.scheduler import BatchScheduler
from repro.storage.endpoint import build_demo_grid


def make_entries(n, seed=0):
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        out.append(
            {
                "endpoint": f"ep{i:04d}",
                "availableSpace": float(rng.uniform(0, 20 * 1024**3)),
                "maxRDBandwidth": float(rng.uniform(0, 200 * 1024)),
                "avgRDBandwidth": float(rng.uniform(0, 100e6)),
                "loadFactor": float(rng.uniform(0, 8)),
            }
        )
    return out


REQ = parse_classad(
    "reqdSpace = 5G; rank = other.avgRDBandwidth;"
    "requirements = other.availableSpace > 5G && other.maxRDBandwidth >= 50K;"
)


class TestReplicaSnapshot:
    def test_padding_and_vocab(self):
        snap = ReplicaSnapshot(make_entries(37))
        assert snap.n == 37
        assert snap.s_pad % snap.block_s == 0 and snap.s_pad >= 37
        assert snap.a_pad % 128 == 0
        assert snap.attr_names == numeric_attr_names(snap.entries)
        attrs, valid, n = snap.device_columns()
        assert attrs.shape == (snap.s_pad, snap.a_pad)
        # padded rows are invalid everywhere
        host_attrs, host_valid, _ = snap.host_columns()
        assert not host_valid[n:].any()

    def test_matchrank_accepts_resident_columns(self):
        entries = make_entries(50, seed=1)
        snap = ReplicaSnapshot(entries)
        plan_vocab = snap.attr_names
        from repro.kernels.matchrank.ops import lower_request

        plan = lower_request(REQ, plan_vocab)
        attrs, valid, n = snap.device_columns()
        mk, sk, bs, bi = matchrank(attrs, valid, plan, n_rows=n, use_kernel=False)
        # vs the host-padded path over the same columns
        ha, hv, _ = snap.host_columns()
        cols = [snap.attr_names.index(a) for a in plan_vocab]
        mk2, sk2, bs2, bi2 = matchrank(
            ha[:n][:, : len(snap.attr_names)],
            hv[:n][:, : len(snap.attr_names)] > 0.5,
            lower_request(REQ, snap.attr_names),
            use_kernel=False,
        )
        np.testing.assert_array_equal(mk, mk2)
        assert bi == bi2

    def test_update_rows_incremental(self):
        snap = ReplicaSnapshot(make_entries(20, seed=2))
        v0 = snap.version
        snap.update_rows({3: {"loadFactor": 99.0}, 7: {"availableSpace": 0.0}})
        assert snap.version == v0 + 1
        j = snap.attr_names.index("loadfactor")
        attrs, valid, _ = snap.device_columns()
        assert float(np.asarray(attrs)[3, j]) == 99.0
        ha, _, _ = snap.host_columns()
        assert ha[3, j] == 99.0
        with pytest.raises(IndexError):
            snap.update_rows({99: {"loadFactor": 1.0}})

    def test_new_epoch(self):
        snap = ReplicaSnapshot(make_entries(10, seed=3))
        nxt = snap.new_epoch(make_entries(12, seed=4))
        assert nxt.epoch == snap.epoch + 1 and nxt.n == 12

    def test_table_matches_columns(self):
        snap = ReplicaSnapshot(make_entries(9, seed=5))
        tbl = snap.table()
        ha, hv, n = snap.host_columns()
        for name in snap.attr_names:
            j = snap.attr_names.index(name)
            np.testing.assert_allclose(tbl.cols[name], ha[:n, j], rtol=1e-6)


class TestPlanCache:
    def test_hit_and_canonical_key(self):
        pc = PlanCache()
        vocab = ("availablespace", "maxrdbandwidth", "avgrdbandwidth", "loadfactor")
        p1 = pc.kernel_plan(REQ, vocab)
        # a structurally identical but distinct ad hits the same entry
        req2 = parse_classad(
            "reqdSpace = 5G; rank = other.avgRDBandwidth;"
            "requirements = other.availableSpace > 5G && other.maxRDBandwidth >= 50K;"
        )
        p2 = pc.kernel_plan(req2, vocab)
        assert p1 is p2
        assert pc.stats["hits"] == 1 and pc.stats["misses"] == 1

    def test_constants_key_the_entry(self):
        vocab = ("availablespace",)
        a = parse_classad("reqdSpace = 1G; requirements = other.availableSpace >= my.reqdSpace;")
        b = parse_classad("reqdSpace = 9G; requirements = other.availableSpace >= my.reqdSpace;")
        assert request_cache_key(a, vocab) != request_cache_key(b, vocab)
        pc = PlanCache()
        pa = pc.kernel_plan(a, vocab)
        pb = pc.kernel_plan(b, vocab)
        assert pa.thresholds[0] != pb.thresholds[0]

    def test_negative_caching(self):
        pc = PlanCache()
        bad = parse_classad('requirements = other.hostname == "x";')
        for _ in range(3):
            with pytest.raises(CompileError):
                pc.kernel_plan(bad, ("hostname",))
        assert pc.stats["negative_hits"] == 2 and pc.stats["misses"] == 1

    def test_lru_eviction(self):
        pc = PlanCache(maxsize=2)
        vocab = ("loadfactor",)
        for i in range(4):
            pc.kernel_plan(
                parse_classad(f"requirements = other.loadFactor < {i + 1};"), vocab
            )
        assert len(pc) == 2 and pc.stats["evictions"] == 2


@pytest.fixture
def grid():
    g = build_demo_grid(8, 4, seed=7)
    g.add_client("client://host0", zone="zone1")
    g.replicate("shard-000", b"x" * (1 << 20), ["gsiftp://ep000", "gsiftp://ep003", "gsiftp://ep005"])
    g.replicate("shard-001", b"y" * (1 << 20), ["gsiftp://ep001", "gsiftp://ep004"])
    g.replicate("shard-002", b"z" * (1 << 19), ["gsiftp://ep002", "gsiftp://ep006", "gsiftp://ep007"])
    return g


def _urls(ranked):
    return [r.pfn.url for r in ranked]


class TestSelectMany:
    def test_default_request_parity(self, grid):
        b = grid.broker_for("client://host0")
        want = [b.select(f"shard-00{i}") for i in range(3)]
        got = b.select_many([(f"shard-00{i}", None) for i in range(3)])
        for g_, w in zip(got, want):
            assert _urls(g_) == _urls(w)
            for x, y in zip(g_, w):
                assert abs(x.rank - y.rank) <= 1e-6 * max(1.0, abs(y.rank))

    @pytest.mark.parametrize("use_kernel", [False, True])
    def test_kernel_tier_parity(self, grid, use_kernel):
        b = grid.broker_for("client://host0")
        req = parse_classad(
            "reqdSpace = 0; rank = other.diskTransferRate;"
            "requirements = other.availableSpace > 1M;"
        )
        want = [b.select(f"shard-00{i}", req) for i in range(3)]
        got = b.select_many(
            [(f"shard-00{i}", req) for i in range(3)], use_kernel=use_kernel
        )
        assert b.stats["batched_kernel_requests"] == 3
        for g_, w in zip(got, want):
            assert _urls(g_) == _urls(w)

    def test_mixed_tiers_one_batch(self, grid):
        b = grid.broker_for("client://host0")
        conj = parse_classad(
            "reqdSpace = 0; rank = other.diskTransferRate;"
            "requirements = other.availableSpace > 1M;"
        )
        # references a per-replica attribute ⇒ interpreter tier
        per_replica = parse_classad(
            "reqdSpace = 0; rank = other.diskTransferRate;"
            "requirements = other.replicaSize > 0;"
        )
        # a general || ⇒ columnar tier
        general_or = parse_classad(
            "reqdSpace = 0; rank = other.diskTransferRate;"
            "requirements = other.availableSpace > 1M || other.loadFactor < 2;"
        )
        queries = [
            ("shard-000", conj),
            ("shard-001", general_or),
            ("shard-002", per_replica),
            ("shard-001", None),  # the default read ad lowers to the kernel
        ]
        want = [b.select(lfn, req) for lfn, req in queries]
        got = b.select_many(queries)
        assert b.stats["batched_kernel_requests"] == 2
        assert b.stats["batched_kernel_guarded_requests"] == 1
        assert b.stats["batched_columnar_requests"] == 1
        assert b.stats["batched_interp_requests"] == 1
        for g_, w in zip(got, want):
            assert _urls(g_) == _urls(w)

    def test_snapshot_reuse_and_ttl(self, grid):
        b = grid.broker_for("client://host0")
        b.select_many([("shard-000", None)])
        b.select_many([("shard-000", None), ("shard-001", None)])
        assert b.stats["snapshot_builds"] >= 1
        assert b.stats["snapshot_reuses"] >= 0
        builds = b.stats["snapshot_builds"]
        grid.clock.advance(b.snapshot_ttl + 1)
        b.select_many([("shard-000", None)])
        assert b.stats["snapshot_builds"] == builds + 1

    def test_strict_and_nonstrict_errors(self, grid):
        b = grid.broker_for("client://host0")
        out = b.select_many([("no-such", None), ("shard-000", None)], strict=False)
        assert isinstance(out[0], NoReplicaError)
        assert isinstance(out[1], SelectionResult) and out[1]
        assert out[1].plan is not None and out[1].request_id
        with pytest.raises(NoReplicaError):
            b.select_many([("no-such", None)])
        impossible = parse_classad("requirements = other.loadFactor > 1e30;")
        out = b.select_many([("shard-000", impossible)], strict=False)
        assert isinstance(out[0], NoMatchError)

    def test_top_k(self, grid):
        b = grid.broker_for("client://host0")
        (got,) = b.select_many([("shard-000", None)], top_k=2)
        assert len(got) == 2

    def test_plan_cache_warm_across_batches(self, grid):
        b = grid.broker_for("client://host0")
        req = parse_classad(
            "reqdSpace = 0; rank = other.diskTransferRate;"
            "requirements = other.availableSpace > 1M;"
        )
        b.select_many([("shard-000", req)])
        misses = b.plan_cache.stats["misses"]
        b.select_many([("shard-001", req), ("shard-002", req)])
        assert b.plan_cache.stats["misses"] == misses  # all hits
        assert b.plan_cache.stats["hits"] > 0


class TestBatchScheduler:
    def test_coalesces_and_fills(self, grid):
        b = grid.broker_for("client://host0")
        sch = BatchScheduler(b, max_batch=8)
        tickets = sch.submit_many([(f"shard-00{i % 3}", None) for i in range(6)])
        assert all(not t.done for t in tickets)
        sch.flush()
        assert all(t.done for t in tickets)
        assert sch.stats["batches"] == 1 and sch.coalescing_ratio() == 6.0
        want = b.select("shard-000")
        assert _urls(tickets[0].result()) == _urls(want)

    def test_size_flush(self, grid):
        b = grid.broker_for("client://host0")
        sch = BatchScheduler(b, max_batch=2)
        t1 = sch.submit("shard-000")
        assert not t1.done
        sch.submit("shard-001")  # hits max_batch → flush
        assert t1.done and sch.stats["size_flushes"] == 1

    def test_latency_flush(self, grid):
        b = grid.broker_for("client://host0")
        sch = BatchScheduler(b, max_batch=100, max_delay=2.0)
        t = sch.submit("shard-000")
        assert not sch.poll() and not t.done
        grid.clock.advance(2.5)
        assert sch.poll() and t.done
        assert sch.stats["latency_flushes"] == 1

    def test_result_forces_flush_and_errors(self, grid):
        b = grid.broker_for("client://host0")
        sch = BatchScheduler(b, max_batch=100)
        t_ok = sch.submit("shard-000")
        t_bad = sch.submit("no-such")
        assert _urls(t_ok.result()) == _urls(b.select("shard-000"))
        with pytest.raises(NoReplicaError):
            t_bad.result()


class TestRestoreWiring:
    def test_checkpoint_restore_batches_selections(self, grid):
        import jax
        import jax.numpy as jnp

        from repro.checkpoint.manager import CheckpointManager

        b = grid.broker_for("client://host0")
        mgr = CheckpointManager("t", grid, b, replication=2, chunk_bytes=1 << 16)
        state = {"w": np.arange(65536, dtype=np.float32), "b": np.ones(16, np.float32)}
        mgr.save(0, state)
        sch = BatchScheduler(b, max_batch=64)
        restored = mgr.restore(0, jax.eval_shape(lambda: {"w": jnp.zeros(65536, jnp.float32), "b": jnp.zeros(16, jnp.float32)}), scheduler=sch)
        np.testing.assert_array_equal(np.asarray(restored["w"]), state["w"])
        np.testing.assert_array_equal(np.asarray(restored["b"]), state["b"])
        assert sch.stats["submitted"] >= 2
        assert sch.stats["batches"] >= 1
        assert sch.coalescing_ratio() > 1.0
