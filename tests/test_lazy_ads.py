"""The snapshot's per-row ClassAds are made on first read: served selections
make none, a view's ad is its row's conversion, shared by every view of the
row within an epoch and made anew after a rebuild, and a view built with an
ad in hand keeps it. A tiny grid with two site policies, on the CPU."""

import gc
import weakref

import pytest

from repro.core.broker import ReplicaView, SelectionResult
from repro.core.catalog import PhysicalFile
from repro.core.classads import parse_classad
from repro.core.ldif import entry_to_classad
from repro.obs import Tracer
from repro.storage.endpoint import build_demo_grid

CLIENT = "client://c0"
FILES = {
    "f0": ["gsiftp://ep000", "gsiftp://ep001", "gsiftp://ep002"],
    "f1": ["gsiftp://ep003", "gsiftp://ep004", "gsiftp://ep005"],
    "f2": ["gsiftp://ep006", "gsiftp://ep007", "gsiftp://ep001"],
}
ROWS = 8  # the endpoints FILES names
#: a request for each tier of select_many, and the path it is answered by
TIERS = {
    "kernel": "requirements = other.availableSpace > 1M;",
    "columnar": "requirements = other.availableSpace > 1M || other.loadFactor < 2;",
    "interpreter": "requirements = other.replicaSize > 0;",
}
PATHS = {"kernel": "batched_kernel", "columnar": "batched_columnar", "interpreter": "batched_interp"}


def _request(tier):
    req = parse_classad("reqdSpace = 1G; rank = other.diskTransferRate;" + TIERS[tier])
    req["clientUrl"] = CLIENT
    return req


def _grid(shards):
    grid = build_demo_grid(9, 3, seed=7)  # the 10G policy on ep000, ep003, ep006
    for url in ("gsiftp://ep001", "gsiftp://ep004"):
        grid.endpoints[url].gris.set_static("requirements", "other.reqdSpace <= 2G")
    grid.add_client(CLIENT, zone="zone1")
    for i, (lfn, eps) in enumerate(FILES.items()):
        grid.replicate(lfn, bytes([i]) * (1 << 16), eps)
    broker = grid.broker_for(
        CLIENT, batch_use_kernel=False, tracer=Tracer(), snapshot_shards=shards
    )
    return grid, broker


def _ads_built(broker):
    return broker.stats["snapshot_ads_built"]


@pytest.mark.parametrize("shards", [0, 2])
@pytest.mark.parametrize("tier", list(TIERS))
def test_served_selections_make_no_ads(tier, shards):
    _, broker = _grid(shards)
    queries = [(lfn, _request(tier)) for lfn in FILES]
    out = broker.select_many(queries)
    assert all(isinstance(r, SelectionResult) and r for r in out)
    assert {broker.explain(i).kernel_path for i in broker.last_request_ids} == {PATHS[tier]}
    st = broker._snap_state
    if tier != "interpreter":  # the interpreter reads each replica's own ad
        assert len(st.policy_index) == 2
    assert _ads_built(broker) == 0
    assert broker.stats["snapshot_rows"] == st.snapshot.n == ROWS
    # the registry carries the same counts
    samples = {
        name: m.value
        for name, _, m in broker.metrics.samples()
        if name.startswith("broker_snapshot_")
    }
    assert samples["broker_snapshot_ads_built_total"] == 0
    assert samples["broker_snapshot_rows_total"] == ROWS


@pytest.mark.parametrize("shards", [0, 2])
def test_view_ad_is_made_once_and_shared_in_an_epoch(shards):
    _, broker = _grid(shards)
    req = _request("kernel")
    [first] = broker.select_many([("f0", req)])
    view = first[0].view
    ad = view.ad
    assert _ads_built(broker) == 1
    want = entry_to_classad(view.entry)
    assert sorted(k for k, _ in ad.items()) == sorted(k for k, _ in want.items())
    for k, e in want.items():
        assert repr(ad.lookup_expr(k)) == repr(e)
    assert view.ad is ad and _ads_built(broker) == 1
    # a second view of the row, inside the TTL: the same ad, none made
    [again] = broker.select_many([("f0", req)])
    assert again[0].view is not view and again[0].pfn == first[0].pfn
    assert again[0].view.ad is ad
    assert broker._snap_state.ads[broker._snap_state.row_of[first[0].pfn.endpoint]] is ad
    assert _ads_built(broker) == 1


@pytest.mark.parametrize("shards", [0, 2])
def test_rebuild_makes_a_fresh_ad(shards):
    grid, broker = _grid(shards)
    req = _request("kernel")
    [first] = broker.select_many([("f0", req)])
    ad = first[0].view.ad
    grid.clock.advance(broker.snapshot_ttl + 1)
    [later] = broker.select_many([("f0", req)])
    assert later[0].pfn == first[0].pfn
    fresh = later[0].view.ad
    assert fresh is not ad
    assert repr(fresh.lookup_expr("requirements")) == repr(ad.lookup_expr("requirements"))
    assert _ads_built(broker) == 2
    assert broker.stats["snapshot_rows"] == 2 * len(FILES["f0"])  # the rows f0 names


def test_view_with_an_ad_in_hand_keeps_it():
    entry = {"endpoint": "gsiftp://x", "availableSpace": 5, "requirements": "other.a < 1"}
    ad = entry_to_classad(entry)
    view = ReplicaView(PhysicalFile("gsiftp://x", "/p", 1), entry, ad)
    assert view.ad is ad
    assert view == ReplicaView(view.pfn, dict(entry), entry_to_classad(entry))


def _reachable(obj):
    """The ids of every object reachable from ``obj``."""
    seen, todo = set(), [obj]
    while todo:
        o = todo.pop()
        if id(o) not in seen:
            seen.add(id(o))
            todo.extend(gc.get_referents(o))
    return seen


@pytest.mark.parametrize("shards", [0, 2])
def test_kept_view_does_not_keep_its_epoch(shards):
    grid, broker = _grid(shards)
    req = _request("kernel")
    [kept] = broker.select_many([("f0", req)])
    st = broker._snap_state
    with pytest.raises(TypeError):
        st.ads[0:2]  # indexed by row, not sliced
    # the view holds its own row, not the epoch's columns
    reach = _reachable(kept[0].view)
    assert id(st.entries) not in reach and id(st.ads) not in reach
    epoch = weakref.ref(st)
    del st
    grid.clock.advance(broker.snapshot_ttl + 1)
    broker.select_many([("f0", req)])
    gc.collect()
    assert epoch() is None
    # the kept view still makes its own row's ad
    assert kept[0].view.ad.eval_attr("endpoint") == kept[0].pfn.endpoint
