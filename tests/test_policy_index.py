"""The per-epoch usage-policy row index of the batched selection path: built
once for each snapshot state lowered against, reused by every later request
of that epoch, dropped with the state, and folded into the same admit
vectors and rankings as the interpreter. A tiny grid with two distinct
site policies and rows with none, on the CPU."""

import numpy as np
import pytest

import repro.kernels.matchrank.ops as mr_ops
from repro.core.classads import parse_classad
from repro.core.matchmaker import Matchmaker
from repro.obs import Tracer
from repro.storage.endpoint import build_demo_grid

CLIENT = "client://c0"
TIGHT = "other.reqdSpace <= 2G"  # the second policy; every third endpoint has 10G
FILES = {
    "f0": ["gsiftp://ep000", "gsiftp://ep001", "gsiftp://ep002"],
    "f1": ["gsiftp://ep003", "gsiftp://ep004", "gsiftp://ep005"],
    "f2": ["gsiftp://ep006", "gsiftp://ep007", "gsiftp://ep001"],
    "f3": ["gsiftp://ep004", "gsiftp://ep008", "gsiftp://ep000"],
}
#: reqdSpace: both policies admit, only the 10G one, neither
SPACES = ["1G", "5G", "20G"]


def _request(space):
    req = parse_classad(
        f"reqdSpace = {space}; rank = other.diskTransferRate;"
        "requirements = other.availableSpace > 1M;"
    )
    req["clientUrl"] = CLIENT
    return req


QUERIES = [(lfn, _request(sp)) for sp in SPACES for lfn in FILES]


def _grid(shards):
    grid = build_demo_grid(9, 3, seed=7)  # the 10G policy on ep000, ep003, ep006
    for url in ("gsiftp://ep001", "gsiftp://ep004"):
        grid.endpoints[url].gris.set_static("requirements", TIGHT)
    grid.add_client(CLIENT, zone="zone1")
    for i, (lfn, eps) in enumerate(FILES.items()):
        grid.replicate(lfn, bytes([i]) * (1 << 16), eps)
    broker = grid.broker_for(
        CLIENT, batch_use_kernel=False, tracer=Tracer(), snapshot_shards=shards
    )
    return grid, broker


def _hits(broker):
    return [s.args["hit"] for s in broker.tracer.spans("broker.lowering.policy_groups")]


def _counts(broker):
    return broker.stats["policy_index_builds"], broker.stats["policy_index_reuses"]


def _select_capturing_admit(broker, monkeypatch):
    """select_many over QUERIES → (results, the admit matrix that the
    launch's candidate rows stand for)."""
    seen = []
    orig = mr_ops.matchrank_candidates

    def capture(attrs, valid, plans, rows, **kw):
        seen.append([list(r) for r in rows])
        return orig(attrs, valid, plans, rows, **kw)

    monkeypatch.setattr(mr_ops, "matchrank_candidates", capture)
    out = broker.select_many(QUERIES)
    monkeypatch.setattr(mr_ops, "matchrank_candidates", orig)
    assert broker.explain(broker.last_request_ids[0]).kernel_path == "batched_kernel"
    [rows] = seen
    n_rows = broker._snap_state.snapshot.device_columns()[2]
    admit = np.zeros((len(rows), n_rows), np.float32)
    for q, r in enumerate(rows):
        admit[q, [x for x in r if x < n_rows]] = 1.0  # empty slots lie past every row
    return out, admit


def _interpreter_admit(broker):
    """Each query's admit row by the interpreter: a replica's row is 1 where
    its endpoint publishes no policy or its policy holds for the request."""
    st = broker._snap_state
    mm = Matchmaker(broker.env)
    want = np.zeros((len(QUERIES), st.snapshot.device_columns()[2]), np.float32)
    for q, (lfn, req) in enumerate(QUERIES):
        for ep in FILES[lfn]:
            r = st.row_of[ep]
            ad = st.ads[r]
            if ad.lookup_expr("requirements") is None or mm.one_sided(ad, req):
                want[q, r] = 1.0
    return want


def _same(got, want):
    assert [r.pfn.url for r in got] == [r.pfn.url for r in want]
    assert [r.rank for r in got] == pytest.approx([r.rank for r in want], rel=1e-6)


@pytest.mark.parametrize("shards", [0, 2])
def test_built_once_within_a_ttl(shards):
    _, broker = _grid(shards)
    n = len(QUERIES)
    broker.select_many(QUERIES)
    broker.select_many(QUERIES)
    assert _hits(broker) == [False] + [True] * (2 * n - 1)
    assert _counts(broker) == (1, 2 * n - 1)
    st = broker._snap_state
    # two policies, disjoint row sets, rows without a policy in neither
    assert len(st.policy_index) == 2
    rows = np.concatenate(list(st.policy_index.values()))
    assert len(set(rows.tolist())) == len(rows) == 5
    assert all(g.dtype == np.intp for g in st.policy_index.values())
    # the registry carries the same counts
    samples = {
        name: m.value
        for name, _, m in broker.metrics.samples()
        if name.startswith("broker_policy_index_")
    }
    assert samples["broker_policy_index_builds_total"] == 1
    assert samples["broker_policy_index_reuses_total"] == 2 * n - 1


@pytest.mark.parametrize("shards", [0, 2])
def test_built_again_after_the_ttl(shards):
    grid, broker = _grid(shards)
    n = len(QUERIES)
    broker.select_many(QUERIES)
    first = broker._snap_state
    grid.clock.advance(broker.snapshot_ttl + 1)
    broker.select_many(QUERIES)
    st = broker._snap_state
    assert st is not first and st.policy_index is not first.policy_index
    if shards:
        assert broker.stats["snapshot_delta_refreshes"] >= 1
    assert _hits(broker) == ([False] + [True] * (n - 1)) * 2
    assert _counts(broker) == (2, 2 * (n - 1))


@pytest.mark.parametrize("shards", [0, 2])
def test_admits_and_rankings_match_the_interpreter(shards, monkeypatch):
    _, broker = _grid(shards)
    got, admit = _select_capturing_admit(broker, monkeypatch)
    want = _interpreter_admit(broker)
    np.testing.assert_array_equal(admit, want)
    # the batch mixes refusals by each policy with admissions
    assert 0 < want.sum() < sum(len(FILES[lfn]) for lfn, _ in QUERIES)
    assert [len(r) for r in got] != [len(FILES[lfn]) for lfn, _ in QUERIES]
    for (lfn, req), res in zip(QUERIES, got):
        _same(res, broker.select(lfn, req))


@pytest.mark.parametrize("shards", [0, 2])
def test_republished_policy_is_honoured(shards, monkeypatch):
    grid, broker = _grid(shards)
    broker.select_many(QUERIES)
    # ep001 lifts its policy, ep002 starts refusing large reads
    grid.endpoints["gsiftp://ep001"].gris.set_static("requirements", "other.reqdSpace <= 100G")
    grid.endpoints["gsiftp://ep002"].gris.set_static("requirements", TIGHT)
    broker.select_many(QUERIES)  # inside the TTL: the old epoch and its index
    assert _counts(broker)[0] == 1
    grid.clock.advance(broker.snapshot_ttl + 1)
    got, admit = _select_capturing_admit(broker, monkeypatch)
    assert _counts(broker)[0] == 2
    assert len(broker._snap_state.policy_index) == 3
    np.testing.assert_array_equal(admit, _interpreter_admit(broker))
    row = broker._snap_state.row_of
    for q, (lfn, _) in enumerate(QUERIES):
        if SPACES[q // len(FILES)] != "20G":
            continue  # at 20G ep001 now admits and ep002 now refuses
        if "gsiftp://ep001" in FILES[lfn]:
            assert admit[q, row["gsiftp://ep001"]] == 1.0
        if "gsiftp://ep002" in FILES[lfn]:
            assert admit[q, row["gsiftp://ep002"]] == 0.0
    for (lfn, req), res in zip(QUERIES, got):
        _same(res, broker.select(lfn, req))
