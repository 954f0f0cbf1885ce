"""The per-epoch usage-policy row index of the batched selection path: built
once for each snapshot state lowered against, reused by every later request
of that epoch, dropped with the state, and folded into the same admit
vectors and rankings as the interpreter. A tiny grid with two distinct
site policies and rows with none, on the CPU.

The index is built from the entries' policy-source column, never from the
rows' ads: it must equal the index grouped from every row's converted ad,
on the benchmark's grids and on hand-made entries."""

import importlib.util
import json
import sys
from pathlib import Path

import numpy as np
import pytest

import repro.kernels.matchrank.ops as mr_ops
from repro.core.broker import _policy_index
from repro.core.classads import parse as parse_expr, parse_classad
from repro.core.ldif import entry_to_classad, policy_sources
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


# ------------------------------------------- the index read from the entries
ROOT = Path(__file__).resolve().parents[1]


def _bench_grid_module():
    """The module that makes the benchmark's grids, ``benchmarks/chip/grid.py``."""
    name = "bench_chip_grid"
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(
            name, ROOT / "benchmarks" / "chip" / "grid.py"
        )
        mod = sys.modules[name] = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
    return sys.modules[name]


def _index_from_ads(entries):
    """The index as grouped from every row's converted ad."""
    groups = {}
    for r, e in enumerate(entries):
        pexpr = entry_to_classad(e).lookup_expr("requirements")
        if pexpr is not None:
            groups.setdefault(repr(pexpr), []).append(r)
    return groups


def _assert_same_index(got, want):
    assert list(got) == list(want)  # the same keys, in first-row order
    for src, rows in want.items():
        assert got[src].dtype == np.intp
        assert got[src].tolist() == rows


def _bench_broker(config, shards):
    """A tiny grid of one of the benchmark's configurations."""
    grid_mod = _bench_grid_module()
    with open(ROOT / "benchmarks" / "chip" / "configs" / f"{config}.json") as f:
        cfg = dict(json.load(f), endpoints=48, files=64)
    world = grid_mod.build_world(cfg, 2**31 + 11)
    broker = world.grid.broker_for(
        grid_mod.CLIENT, batch_use_kernel=False, snapshot_shards=shards
    )
    broker.warm_snapshot(world.urls)
    return broker, [(lfn, _request(sp)) for sp in SPACES for lfn in world.lfns[:8]]


class _ExprPolicy:
    """An endpoint's GRIS as the broker reads it, publishing its policy as a
    parsed ``Expr`` (a GRIS's schema holds a string there)."""

    def __init__(self, gris, policy):
        self.gris = gris
        self.policy = parse_expr(policy)

    def flattened_view(self, source=None):
        return dict(self.gris.flattened_view(source=source), requirements=self.policy)


def _hand_made_broker(shards):
    """The module's grid with a policy under a mixed-case key, one given as
    an ``Expr``, a row with both spellings, and rows with none."""
    grid, broker = _grid(shards)
    eps = grid.endpoints
    eps["gsiftp://ep002"].gris.set_static("ReQuirements", TIGHT)
    eps["gsiftp://ep003"].gris.set_static("REQUIREMENTS", "other.reqdSpace <= 4G")
    stub = _ExprPolicy(eps["gsiftp://ep005"].gris, "other.reqdSpace <= 3G")
    resolve = broker.gris_resolver
    broker.gris_resolver = lambda ep: stub if ep == "gsiftp://ep005" else resolve(ep)
    broker.warm_snapshot(list(eps))
    return broker, QUERIES


@pytest.mark.parametrize("shards", [0, 2])
@pytest.mark.parametrize("grid", ["fleet15k", "wlcg_federation", "hand_made"])
def test_index_from_entries_equals_index_from_ads(grid, shards):
    broker, queries = (
        _hand_made_broker(shards) if grid == "hand_made" else _bench_broker(grid, shards)
    )
    st = broker._snap_state
    want = _index_from_ads(st.entries)
    _assert_same_index(_policy_index(st.policy_sources), want)
    # rows with a policy and rows with none; four distinct policies by hand
    assert 0 < sum(map(len, want.values())) < len(st.entries)
    assert len(want) == (4 if grid == "hand_made" else 1)
    # the served path groups the same column, and makes no ad to do it
    broker.select_many(queries, strict=False)
    assert broker._snap_state is st
    _assert_same_index(st.policy_index, want)
    assert broker.stats["snapshot_ads_built"] == 0


#: one row each: (entry, what the policy-source column reads there)
HAND_MADE = {
    "lower_case": ({"requirements": "other.a < 1"}, "(other.a < 1)"),
    "mixed_case": ({"name": "x", "ReqUirements": "other.a < 1"}, "(other.a < 1)"),
    "expr": ({"requirements": parse_expr("other.b >= 2")}, "(other.b >= 2)"),
    "no_policy": ({"name": "x", "availableSpace": 5}, None),
    "last_spelling_wins": (
        {"requirements": "other.a < 1", "name": "x", "REQUIREMENTS": "other.b < 2"},
        "(other.b < 2)",
    ),
    "int_value": ({"requirements": 7}, "7"),
    "none_value": ({"Requirements": None}, "undefined"),
    # a str subclass is stored as a string literal, not parsed
    "str_subclass": ({"requirements": type("Text", (str,), {})("other.a < 1")}, "\"other.a < 1\""),
}


@pytest.mark.parametrize("case", list(HAND_MADE))
def test_policy_source_of_a_row(case):
    entry, want = HAND_MADE[case]
    pexpr = entry_to_classad(entry).lookup_expr("requirements")
    assert (None if pexpr is None else repr(pexpr)) == want
    assert policy_sources([entry]) == [want]


def test_hand_made_entries_index_as_their_ads():
    entries = [e for e, _ in HAND_MADE.values()] * 2
    _assert_same_index(_policy_index(policy_sources(entries)), _index_from_ads(entries))
