"""Sharded snapshots (DESIGN.md §9): ShardedSnapshot layout and delta
refresh, its device columns through the candidate launch against a flat
snapshot of the same rows (tie-break included), the GIIS bridge, and a
sharded broker end to end against the interpreter."""

import numpy as np
import pytest

from repro.core.classads import parse_classad
from repro.core.giis import GIIS
from repro.core.gris import Clock, StorageGRIS
from repro.core.snapshot import ReplicaSnapshot
from repro.core.snapshot_sharded import ShardedSnapshot, shard_by_hash
from repro.kernels.matchrank.ops import lower_request, matchrank_batched
from repro.storage.endpoint import build_demo_grid

NAMES = ["availablespace", "maxrdbandwidth", "avgrdbandwidth", "loadfactor"]

REQ_SRCS = [
    "reqdSpace = 5G; rank = other.avgRDBandwidth;"
    "requirements = other.availableSpace > 5G && other.maxRDBandwidth >= 50K;",
    "reqdSpace = 2G; rank = other.maxRDBandwidth;"
    "requirements = other.availableSpace > 2G;",
    "rank = other.avgRDBandwidth - other.loadFactor;"
    "requirements = other.loadFactor < 6;",
    # impossible: every top-k slot is -inf
    "rank = other.avgRDBandwidth; requirements = other.loadFactor > 1e30;",
]


def make_shard_entries(s, g, seed=0, ties=False, missing_frac=0.1):
    """Uneven shards over a shared vocabulary; ``ties=True`` quantizes the
    rank attribute so equal scores are common (tie-break coverage)."""
    rng = np.random.default_rng(seed)
    cols = np.stack(
        [
            rng.uniform(0, 20 * 1024**3, s),
            rng.uniform(0, 200 * 1024, s),
            rng.uniform(0, 100e6, s),
            rng.uniform(0, 8, s),
        ],
        axis=1,
    )
    if ties:
        cols[:, 2] = np.round(cols[:, 2] / 25e6) * 25e6  # ~5 distinct ranks
    drop = rng.random((s, len(NAMES))) < missing_frac
    # uneven split: every shard non-empty, sizes differ
    cuts = np.sort(rng.choice(np.arange(1, s), size=g - 1, replace=False)) if g > 1 else []
    bounds = [0, *map(int, cuts), s]
    out = {}
    for gi in range(g):
        rows = []
        for i in range(bounds[gi], bounds[gi + 1]):
            e = {"endpoint": f"gsiftp://site{gi}/ep{i:05d}"}
            for j, n in enumerate(NAMES):
                if not drop[i, j]:
                    e[n] = float(cols[i, j])
            rows.append(e)
        out[f"shard-{gi:03d}"] = rows
    return out


def make_plans(snap, srcs=REQ_SRCS):
    return [lower_request(parse_classad(src), snap.attr_names) for src in srcs]


def flat_of(snap):
    """A flat snapshot over the same entries, in the sharded snapshot's
    global (shard-major) row order and vocabulary."""
    entries = [e for name in snap.shard_names for e in snap.entries_by_shard[name]]
    return ReplicaSnapshot(entries, snap.attr_names)


def launch(snap, plans, k, *, admit=None, use_kernel=True):
    """``matchrank_batched`` over a snapshot's ``device_columns()``."""
    attrs, valid, n = snap.device_columns()
    return matchrank_batched(
        attrs, valid, plans, admit=admit, n_rows=n, k=k, use_kernel=use_kernel
    )


def assert_launch_equal(snap, plans, k, **kw):
    """The sharded snapshot's launch equals the flat snapshot's bit for
    bit: mask, score and top-k (tie-break included)."""
    got = launch(snap, plans, k, **kw)
    want = launch(flat_of(snap), plans, k, **kw)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    return got


class TestShardedSnapshot:
    def test_layout_and_global_rows(self):
        se = make_shard_entries(123, 5, seed=1)
        snap = ShardedSnapshot(se, device=False)
        assert snap.g == 5 and snap.n == 123
        assert snap.shard_names == sorted(se)
        assert snap.offsets[0] == 0
        np.testing.assert_array_equal(np.diff(snap.offsets), snap.counts[:-1])
        # global rows are the shard-major concat of the per-shard views
        attrs, valid = snap.logical_columns()
        pos = 0
        for gi in range(snap.g):
            a_g, v_g = snap.shard_logical_columns(gi)
            c = int(snap.counts[gi])
            np.testing.assert_array_equal(attrs[pos : pos + c], a_g)
            np.testing.assert_array_equal(valid[pos : pos + c], v_g)
            for r in range(pos, pos + c):
                assert snap.shard_of_row(r) == gi
            pos += c
        with pytest.raises(IndexError):
            snap.shard_of_row(snap.n)
        # shared vocabulary is the lower-cased union across shards
        assert set(NAMES) <= set(snap.attr_names)

    def test_update_rows_delta_accounting(self):
        snap = ShardedSnapshot(make_shard_entries(200, 4, seed=2))
        assert (snap.shard_epochs == 0).all()
        eps0 = snap.shard_epochs.copy()
        # rows 0..4 live in shard 0 only
        changed = snap.update_rows({r: {"loadFactor": 1.5} for r in range(5)})
        assert changed == [0]
        np.testing.assert_array_equal(snap.shard_epochs[1:], eps0[1:])
        assert snap.shard_epochs[0] == eps0[0] + 1
        j = snap.attr_names.index("loadfactor")
        attrs, _ = snap.shard_logical_columns(0)
        np.testing.assert_allclose(attrs[:5, j], 1.5)

    def test_update_rows_case_insensitive_merge(self):
        snap = ShardedSnapshot(make_shard_entries(20, 2, seed=3, missing_frac=0.0))
        name = snap.shard_names[0]
        entry = snap.entries_by_shard[name][0]
        keys_before = set(entry)
        snap.update_rows({0: {"LoadFactor": 7.25}})  # resident spelling differs
        assert set(entry) == keys_before  # merged, not duplicated
        assert entry["loadfactor"] == 7.25
        j = snap.attr_names.index("loadfactor")
        attrs, valid = snap.shard_logical_columns(0)
        assert attrs[0, j] == np.float32(7.25) and valid[0, j]

    def test_update_rows_new_attr_falls_back(self):
        """An update outside the vocabulary can't take the scalar fast
        path; the full row recompute must still be exact for the
        in-vocabulary cells."""
        snap = ShardedSnapshot(make_shard_entries(20, 2, seed=4))
        snap.update_rows({0: {"loadFactor": 2.5, "newAttr": 9.0}})
        j = snap.attr_names.index("loadfactor")
        attrs, valid = snap.shard_logical_columns(0)
        assert attrs[0, j] == np.float32(2.5) and valid[0, j]
        assert "newattr" not in snap.attr_names  # vocab is fixed per snapshot

    def test_update_rows_bounds(self):
        snap = ShardedSnapshot(make_shard_entries(10, 2, seed=5), device=False)
        with pytest.raises(IndexError):
            snap.update_rows({10: {"loadFactor": 1.0}})
        with pytest.raises(IndexError):
            snap.update_rows({-1: {"loadFactor": 1.0}})

    def test_refresh_delta_and_structural_errors(self):
        se = make_shard_entries(60, 3, seed=6)
        snap = ShardedSnapshot(se)
        eps0 = snap.shard_epochs.copy()
        # identical content ⇒ no shard changes, epoch still rolls
        assert snap.refresh({k: [dict(e) for e in v] for k, v in se.items()}) == []
        assert snap.epoch == 1
        np.testing.assert_array_equal(snap.shard_epochs, eps0)
        # one changed shard ⇒ only it is refilled
        name = snap.shard_names[1]
        se2 = {k: [dict(e) for e in v] for k, v in se.items()}
        se2[name][0]["loadfactor"] = 0.125
        assert snap.refresh(se2) == [name]
        np.testing.assert_array_equal(snap.shard_epochs, eps0 + [0, 1, 0])
        # structural changes refuse the delta path
        with pytest.raises(ValueError):
            snap.refresh({k: v for k, v in se2.items() if k != name})
        grown = {k: [dict(e) for e in v] for k, v in se2.items()}
        grown[name] = grown[name] + [dict(grown[name][0])]
        with pytest.raises(ValueError):
            snap.refresh(grown)
        drift = {k: [dict(e) for e in v] for k, v in se2.items()}
        drift[name][0]["brandNew"] = 3.0
        with pytest.raises(ValueError):
            snap.refresh(drift)

    def test_shard_by_hash(self):
        buckets = {shard_by_hash(f"gsiftp://ep{i}", 4) for i in range(64)}
        assert buckets <= set(range(4)) and len(buckets) > 1
        assert shard_by_hash("gsiftp://ep0", 4) == shard_by_hash("gsiftp://ep0", 4)


class TestShardedSnapshotLaunch:
    @pytest.mark.parametrize("g", [1, 3, 8])
    @pytest.mark.parametrize("s", [100, 1000])
    def test_kernel_launch_matches_flat(self, g, s):
        snap = ShardedSnapshot(make_shard_entries(s, g, seed=g * 31 + s))
        mask, _, _, ts = assert_launch_equal(snap, make_plans(snap), 5)
        assert mask.shape == (len(REQ_SRCS), s) and mask[:3].any(axis=1).all()
        assert np.isneginf(ts[3]).all()  # the impossible request

    @pytest.mark.parametrize("g", [1, 3, 8])
    def test_host_launch_matches_flat_s10k(self, g):
        snap = ShardedSnapshot(make_shard_entries(10_000, g, seed=g), device=False)
        assert_launch_equal(snap, make_plans(snap), 3, use_kernel=False)

    def test_tie_break_exact_on_equal_ranks(self):
        """Quantized ranks ⇒ many exact ties; the top-k must name the lowest
        global rows, as a stable sort of the scores does."""
        snap = ShardedSnapshot(make_shard_entries(600, 4, seed=11, ties=True))
        plans = make_plans(snap, REQ_SRCS[:1] * 3)
        _, score, ti, ts = assert_launch_equal(snap, plans, 8)
        for bi in range(len(plans)):
            want = np.argsort(-score[bi], kind="stable")[:8]
            np.testing.assert_array_equal(ti[bi], want)
            np.testing.assert_array_equal(ts[bi], score[bi][want])

    def test_admit_mask_parity(self):
        snap = ShardedSnapshot(make_shard_entries(300, 3, seed=12))
        rng = np.random.default_rng(0)
        admit = (rng.random((len(REQ_SRCS), snap.n)) > 0.5).astype(np.float32)
        mask, _, _, _ = assert_launch_equal(snap, make_plans(snap), 4, admit=admit)
        assert not mask[admit == 0].any()

    def test_parity_after_update_rows_and_refresh(self):
        """A row update and a shard refresh reach the next launch over
        ``device_columns()``, and leave it equal to a flat snapshot of the
        new rows."""
        se = make_shard_entries(200, 4, seed=13)
        snap = ShardedSnapshot(se)
        plans = make_plans(snap)
        before = launch(snap, plans, 5)
        assert snap.update_rows({r: {"avgRDBandwidth": 99e9} for r in range(3)}) == [0]
        name = snap.shard_names[2]
        se2 = {k: [dict(e) for e in v] for k, v in snap.entries_by_shard.items()}
        se2[name][0].update(avgrdbandwidth=98e9, loadfactor=1.0)
        assert snap.refresh(se2) == [name]
        _, _, ti, ts = assert_launch_equal(snap, plans, 5)
        assert not np.array_equal(ti, before[2])
        row = int(snap.offsets[2])
        j = snap.attr_names.index("avgrdbandwidth")
        attrs, _, _ = snap.device_columns()
        assert np.asarray(attrs)[row, j] == np.float32(98e9)
        # REQ_SRCS[2] admits loadFactor < 6 and ranks by avgRDBandwidth
        assert row in ti[2].tolist() and np.float32(98e9 - 1.0) in ts[2]


class TestGIISBridge:
    def _make_giis(self):
        clock = Clock()
        giis = GIIS("o=grid", clock=clock, cache_ttl=5)
        states = []
        for i in range(3):
            g = StorageGRIS(
                f"gss=vol{i}, o=grid",
                {"hostname": f"ep{i}", "mountPoint": "/x",
                 "diskTransferRate": 800e6, "drdTime": 0.004, "dwrTime": 0.005},
                clock=clock,
            )
            state = {"avail": (i + 1) * 1024.0**3}
            g.register_dynamic("totalSpace", lambda: 100.0 * 1024**3, ttl=5)
            g.register_dynamic(
                "availableSpace", lambda st_=state: st_["avail"], ttl=5
            )
            g.register_dynamic("loadFactor", lambda: 0.5, ttl=5)
            giis.register(f"ep{i}", g)
            states.append((g, state))
        return clock, giis, states

    def test_from_giis_and_delta_refresh(self):
        clock, giis, states = self._make_giis()
        snap = ShardedSnapshot.from_giis(giis)
        assert snap.shard_names == ["ep0", "ep1", "ep2"]
        # nothing moved ⇒ no shard is refilled
        assert snap.refresh_from_giis(giis) == []
        assert (snap.shard_epochs == 0).all()
        # one site's dynamic attribute changes after its TTL
        g1, state1 = states[1]
        state1["avail"] = 7.0 * 1024**3
        g1.invalidate("availableSpace")
        clock.advance(6)
        changed = snap.refresh_from_giis(giis)
        assert changed == ["ep1"]
        assert snap.shard_epochs.tolist() == [0, 1, 0]
        j = snap.attr_names.index("availablespace")
        attrs, _ = snap.shard_logical_columns(1)
        assert float(attrs[0, j]) == np.float32(7.0 * 1024**3)


REQ_KERNEL = parse_classad(
    "reqdSpace = 0; rank = other.diskTransferRate;"
    "requirements = other.availableSpace > 1M;"
)


@pytest.fixture
def grid():
    g = build_demo_grid(8, 4, seed=7)
    g.add_client("client://host0", zone="zone1")
    g.replicate("f-000", b"x" * (1 << 20),
                ["gsiftp://ep000", "gsiftp://ep003", "gsiftp://ep005"])
    g.replicate("f-001", b"y" * (1 << 20), ["gsiftp://ep001", "gsiftp://ep004"])
    g.replicate("f-002", b"z" * (1 << 19),
                ["gsiftp://ep002", "gsiftp://ep006", "gsiftp://ep007"])
    return g


def _urls(ranked):
    return [r.pfn.url for r in ranked]


def _ranking(ranked):
    return [(r.pfn.url, r.rank) for r in ranked]


def _assert_same(got, want):
    assert [u for u, _ in got] == [u for u, _ in want]
    for (_, x), (_, y) in zip(got, want):
        assert abs(x - y) <= 1e-6 * max(1.0, abs(y))


class TestShardedBroker:
    def test_parity_with_flat_broker(self, grid):
        flat = grid.broker_for("client://host0")
        sh = grid.broker_for("client://host0", snapshot_shards=4)
        queries = [(f"f-00{i}", REQ_KERNEL) for i in range(3)]
        want = flat.select_many(queries, top_k=2)
        got = sh.select_many(queries, top_k=2)
        assert sh.stats["batched_kernel_requests"] == 3
        for g_, w in zip(got, want):
            _assert_same(_ranking(g_), _ranking(w))

    def test_audit_records_shards_and_path(self, grid):
        b = grid.broker_for("client://host0", snapshot_shards=4)
        (res,) = b.select_many([("f-000", REQ_KERNEL)], top_k=2)
        rec = b.audit.get(res.request_id)
        assert rec.kernel_path == "batched_kernel"
        assert b.stats["batched_kernel_requests"] == 1
        assert isinstance(b._snap_state.snapshot, ShardedSnapshot)
        assert [s.endpoint for s in rec.scores] == rec.candidates
        assert rec.chosen == res[0].pfn.endpoint

    @pytest.mark.parametrize("top_k", [None, 1, 2])
    def test_select_many_matches_interpreter(self, grid, top_k):
        """The kernel (interpret mode here) over a sharded snapshot, against
        the interpreter's select."""
        b = grid.broker_for("client://host0", snapshot_shards=4, batch_use_kernel=True)
        ref = grid.broker_for("client://host0")
        queries = [(f"f-00{i}", REQ_KERNEL) for i in range(3)]
        got = b.select_many(queries, top_k=top_k)
        assert b.stats["batched_kernel_requests"] == 3
        for (lfn, req), res in zip(queries, got):
            want = _ranking(ref.select(lfn, req))
            _assert_same(_ranking(res), want[:top_k] if top_k else want)

    def test_select_equals_flat_broker(self, grid):
        sh = grid.broker_for("client://host0", snapshot_shards=4)
        flat = grid.broker_for("client://host0")
        got = sh.select("f-000", REQ_KERNEL, top_k=2)
        want = flat.select("f-000", REQ_KERNEL, top_k=2)
        _assert_same(_ranking(got), _ranking(want))
        assert sh.explain(got.request_id).kernel_path == "interpreter"

    def test_per_replica_request_takes_interp(self, grid):
        b = grid.broker_for("client://host0", snapshot_shards=4)
        req = parse_classad(
            "reqdSpace = 0; rank = other.diskTransferRate;"
            "requirements = other.replicaSize > 0;"
        )
        (res,) = b.select_many([("f-000", req)], top_k=2)
        assert b.stats["batched_interp_requests"] == 1
        assert b.stats["batched_kernel_requests"] == 0
        assert b.explain(res.request_id).kernel_path == "batched_interp"
        assert _urls(res)  # still answered

    def test_update_rows_on_one_shard_shows_in_next_flush(self, grid):
        b = grid.broker_for("client://host0", snapshot_shards=4, batch_use_kernel=True)
        queries = [(f"f-00{i}", REQ_KERNEL) for i in range(3)]
        first = b.select_many(queries)
        st = b._snap_state
        loser = first[0][-1].pfn.endpoint  # f-000's last-ranked replica
        assert first[0][0].pfn.endpoint != loser
        row = st.row_of[loser]
        shard = st.snapshot.shard_of_row(row)
        assert st.snapshot.update_rows({row: {"diskTransferRate": 2.0**40}}) == [shard]
        (res,) = b.select_many([("f-000", REQ_KERNEL)])
        assert b.explain(res.request_id).snapshot == "reuse"
        assert res[0].pfn.endpoint == loser and res[0].rank == 2.0**40

    def test_snapshot_delta_refresh_across_ttl(self, grid):
        b = grid.broker_for("client://host0", snapshot_shards=4)
        (r0,) = b.select_many([("f-000", REQ_KERNEL)], top_k=2)
        assert b.audit.get(r0.request_id).snapshot == "build"
        grid.clock.advance(b.snapshot_ttl + 1)
        (r1,) = b.select_many([("f-000", REQ_KERNEL)], top_k=2)
        assert b.stats["snapshot_delta_refreshes"] >= 1
        assert b.audit.get(r1.request_id).snapshot == "delta"
        (r2,) = b.select_many([("f-000", REQ_KERNEL)], top_k=2)
        assert b.audit.get(r2.request_id).snapshot == "reuse"
