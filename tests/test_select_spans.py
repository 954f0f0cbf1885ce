"""Spans and counters inside the served selection path: plan lowering,
the kernel launch and the snapshot rebuild, the scheduler's queue wait and
full garbage collections. A tiny grid behind a ``BatchScheduler``, the
kernel tier in interpret mode on the CPU."""

import gc

import pytest

from repro.core.classads import parse_classad
from repro.obs import Tracer
from repro.obs.trace import GC_SPAN
from repro.serve.scheduler import BatchScheduler
from repro.storage.endpoint import build_demo_grid

CLIENT = "client://c0"
LFNS = ["shard-000", "shard-001", "shard-002"]

#: each new span and the span it nests in
PARENTS = {
    "broker.lowering.policy_groups": "broker.lowering",
    "broker.lowering.policy_compile": "broker.lowering",
    "broker.lowering.policy_eval": "broker.lowering",
    "broker.lowering.plan": "broker.lowering",
    "broker.kernel_launch.copy_in": "broker.kernel_launch",
    "broker.kernel_launch.fetch": "broker.kernel_launch",
    "broker.snapshot.gris": "broker.snapshot",
    "broker.snapshot.ads": "broker.snapshot",
    "broker.snapshot.columns": "broker.snapshot",
}
LOWERING = [n for n in PARENTS if n.startswith("broker.lowering.")]
SNAPSHOT = [n for n in PARENTS if n.startswith("broker.snapshot.")]


def _request():
    req = parse_classad(
        "reqdSpace = 0; rank = other.diskTransferRate;"
        "requirements = other.availableSpace > 1M;"
    )
    req["clientUrl"] = CLIENT
    return req


def _served(tracer=None, **kwargs):
    """A grid whose endpoints publish a usage policy on every third one,
    and a kernel-tier broker behind a scheduler."""
    grid = build_demo_grid(6, 3, seed=7)
    grid.add_client(CLIENT, zone="zone1")
    grid.replicate(LFNS[0], b"x" * (1 << 20), ["gsiftp://ep000", "gsiftp://ep002"])
    grid.replicate(LFNS[1], b"y" * (1 << 20), ["gsiftp://ep001", "gsiftp://ep003"])
    grid.replicate(LFNS[2], b"z" * (1 << 20), ["gsiftp://ep004", "gsiftp://ep005"])
    broker = grid.broker_for(CLIENT, batch_use_kernel=True, tracer=tracer or Tracer(), **kwargs)
    return grid, broker, BatchScheduler(broker, max_batch=64)


def _flush(sched):
    req = _request()
    tickets = [sched.submit(lfn, req) for lfn in LFNS]
    sched.flush()
    assert all(tk.result() for tk in tickets)


@pytest.fixture(scope="module")
def served():
    grid, broker, sched = _served()
    _flush(sched)
    return broker, {s.span_id: s for s in broker.tracer.spans()}


@pytest.mark.parametrize("name", sorted(PARENTS))
def test_new_span_nests_in_its_layer(served, name):
    _, spans = served
    mine = [s for s in spans.values() if s.name == name]
    assert mine
    assert {spans[s.parent_id].name for s in mine} == {PARENTS[name]}


def test_lowering_spans_carry_the_request_id(served):
    broker, spans = served
    rids = broker.last_request_ids
    assert len(rids) == len(LFNS)
    for rid in rids:
        assert broker.explain(rid).kernel_path == "batched_kernel"
        named = {s.name for s in spans.values() if s.args.get("request_id") == rid}
        assert named == set(LOWERING)
    for s in spans.values():
        if s.name in ("broker.lowering.policy_compile", "broker.lowering.plan"):
            assert isinstance(s.args["hit"], bool)
    # the first request compiled its policy and plan, the others found them
    plans = [s for s in spans.values() if s.name == "broker.lowering.plan"]
    assert [s.args["hit"] for s in plans] == [False, True, True]
    assert [broker.explain(r).plan_cache for r in rids] == ["miss", "hit", "hit"]


def test_no_new_span_is_a_direct_child_of_the_flush(served):
    _, spans = served
    flush = {sid for sid, s in spans.items() if s.name == "scheduler.flush"}
    assert len(flush) == 1
    direct = {s.name for s in spans.values() if s.parent_id in flush}
    assert direct == {"broker.batch_search", "broker.snapshot", "broker.lowering", "broker.kernel_launch"}


@pytest.mark.parametrize("shards", [0, 2])
def test_snapshot_spans_on_a_rebuild_not_on_a_reuse(shards):
    grid, broker, sched = _served(snapshot_shards=shards)

    def counts():
        return [len(broker.tracer.spans(n)) for n in SNAPSHOT]

    _flush(sched)
    assert counts() == [1, 1, 1]
    _flush(sched)  # inside the TTL: reused
    assert broker.stats["snapshot_reuses"] == 1
    assert counts() == [1, 1, 1]
    grid.clock.advance(broker.snapshot_ttl + 1.0)
    _flush(sched)
    assert counts() == [2, 2, 2]


def test_queue_wait_on_the_span_clock():
    now = [100.0]
    _, broker, sched = _served(Tracer(time_fn=lambda: now[0]))
    req = _request()
    first = sched.submit(LFNS[0], req)
    now[0] = 101.0
    sched.submit(LFNS[1], req)
    now[0] = 103.0
    sched.flush()
    assert first.done
    assert sched.stats["queue_wait_s"] == pytest.approx(3.0 + 2.0)
    assert sched.stats["queue_waited"] == 2
    [h] = [m for n, _, m in sched.metrics.samples() if n == "scheduler_queue_wait_seconds"]
    assert h.count == 2 and h.sum == pytest.approx(5.0)
    assert "scheduler_coalesced_batch_size" not in sched.metrics.families()


def test_full_collection_is_a_parentless_span():
    tracer = Tracer()
    with tracer.span("broker.snapshot"):
        gc.collect(1)
        gc.collect(2)
    [s] = tracer.spans(GC_SPAN)
    assert s.parent_id is None and s.depth == 0
    assert s.t1 >= s.t0
    [outer] = tracer.spans("broker.snapshot")
    assert outer.t0 <= s.t0 and s.t1 <= outer.t1


def test_gc_hook_goes_with_the_tracer():
    before = len(gc.callbacks)
    tracer = Tracer()
    assert len(gc.callbacks) == before + 1
    del tracer
    gc.collect()
    assert len(gc.callbacks) == before
