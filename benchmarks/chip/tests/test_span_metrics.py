"""The readers of the spans and counters inside plan lowering, the kernel
launch, the snapshot rebuild, the scheduler's queue and full garbage
collections: on synthetic run data, and in a tiny traced run on the CPU."""

import gc
import time

import numpy as np
import pytest

from benchmarks.chip import harness, trace
from benchmarks.chip.harness import RunData

from test_harness import tiny_cell  # the tests directory is on the path (rootdir conftest)

#: each new metric and the span it reads
PER_FLUSH = {
    "policy_groups_ms": "broker.lowering.policy_groups",
    "policy_compile_ms": "broker.lowering.policy_compile",
    "policy_eval_ms": "broker.lowering.policy_eval",
    "plan_lookup_ms": "broker.lowering.plan",
    "launch_copy_in_ms": "broker.kernel_launch.copy_in",
    "launch_fetch_ms": "broker.kernel_launch.fetch",
}
PER_SECOND = {
    "snapshot_gris_ms_per_s": "broker.snapshot.gris",
    "snapshot_ads_ms_per_s": "broker.snapshot.ads",
    "snapshot_columns_ms_per_s": "broker.snapshot.columns",
    "gc_ms_per_s": "broker.gc",
}
NEW = [*PER_FLUSH, *PER_SECOND, "queue_wait_ms"]


def _run(spans, flushes=4, seconds=10.0, sched=None):
    """Run data with these spans ((name, t0, t1, parent) in s) and nothing else."""
    return RunData(
        cell=None, seconds=seconds, setup_s=0.0, due=np.zeros(0), fill=np.zeros(0),
        flush_start=np.zeros(0),
        spans=[(n, t0, t1, i + 1, parent, {}) for i, (n, t0, t1, parent) in enumerate(spans)],
        flushes=flushes, sched=sched or {"submitted": 8.0, "batches": 4.0},
        plan_cache={}, broker={},
    )


@pytest.mark.parametrize("metric", sorted(PER_FLUSH))
def test_per_flush_reader(metric):
    span = PER_FLUSH[metric]
    run = _run([("broker.lowering", 0.0, 1.0, None), (span, 0.1, 0.2, 1), (span, 2.0, 2.3, 1)])
    assert harness.load_reader(metric)(run) == pytest.approx(400.0 / 4)
    assert harness.load_reader(metric + ".closed")(run) == pytest.approx(100.0)
    # a program without the span (the parent of these metrics) reads nothing
    assert harness.load_reader(metric)(_run([("broker.lowering", 0.0, 1.0, None)])) is None


@pytest.mark.parametrize("metric", sorted(PER_SECOND))
def test_per_second_reader(metric):
    span = PER_SECOND[metric]
    # the second span starts after the window: the drain does not count
    run = _run([(span, 1.0, 1.5, None), (span, 10.5, 11.0, None)], seconds=10.0)
    assert harness.load_reader(metric)(run) == pytest.approx(50.0)


@pytest.mark.parametrize("metric", sorted(m for m in PER_SECOND if m != "gc_ms_per_s"))
def test_snapshot_reader_without_a_rebuild(metric):
    assert harness.load_reader(metric)(_run([("broker.snapshot", 1.0, 1.5, None)])) is None


def test_gc_reader_without_a_collection(monkeypatch):
    read = harness.load_reader("gc_ms_per_s")
    assert read(_run([])) == 0.0
    # a program whose tracer records no collections reads nothing
    import repro.obs.trace

    monkeypatch.delattr(repro.obs.trace, "GC_SPAN")
    assert read(_run([("broker.gc", 1.0, 1.5, None)])) is None


def test_queue_wait_reader():
    read = harness.load_reader("queue_wait_ms")
    sched = {"submitted": 8.0, "batches": 4.0, "queue_wait_s": 0.2, "queue_waited": 8.0}
    assert read(_run([], sched=sched)) == pytest.approx(25.0)
    assert read(_run([], sched=dict(sched, queue_waited=0.0))) is None
    assert read(_run([])) is None  # a scheduler that does not count the wait


def test_idle_is_charged_to_the_new_children():
    # device busy 0-10 and 90-100 ns; a lowering (10-90 ns) with a policy
    # fold child (20-50 ns) in which a full collection runs (30-40 ns)
    ev = trace.Events(
        {"/device:TPU:0": [("op", 0.0, 10.0), ("op", 90.0, 10.0)]},
        [("broker.lowering", 10.0, 80.0), ("broker.lowering.policy_eval", 20.0, 30.0),
         ("broker.gc", 30.0, 10.0)],
    )
    idle = {k: v * 1e9 for k, v in trace.summarize(ev, (0.0, 100.0)).idle_by_span.items()}
    assert idle == pytest.approx({"broker.lowering": 50.0, "broker.lowering.policy_eval": 20.0,
                                  "broker.gc": 10.0})


def _one_full_collection(s):
    """The window's second flush runs one full collection."""
    orig = s.broker.select_many
    calls = {"n": 0}

    def collecting(queries, **kw):
        calls["n"] += 1
        if calls["n"] == 2:
            gc.collect(2)
        return orig(queries, **kw)

    s.broker.select_many = collecting


@pytest.mark.parametrize("name", ["fleet15k.restore_open", "fleet15k.restore_closed"])
def test_traced_run_reads_every_new_metric(name):
    cell = tiny_cell(name)
    out = harness.run_cell(cell, 2**31 + 99, 1.5, True, t_process=time.perf_counter(),
                           require_chip=False, fault=_one_full_collection)
    assert out["correct"], out["checks"]
    assert out["notes"]["snapshot_builds"] >= 2  # the TTL lapsed in the window
    listed = {m["name"] for m in cell.per_layer}
    suffix = ".closed" if name.endswith("closed") else ""
    want = {m + suffix for m in NEW} & listed
    assert len(want) == (11 if not suffix else 10)
    got = out["metrics"]
    assert want <= set(got), want - set(got)
    assert got["gc_ms_per_s" + suffix]["value"] > 0
    # the children lie inside their parents' time
    for part, whole in (("policy_groups_ms", "lowering_ms"), ("launch_fetch_ms", "kernel_launch_ms"),
                        ("snapshot_ads_ms_per_s", "snapshot_ms_per_s")):
        if whole + suffix in got:
            assert got[part + suffix]["value"] < got[whole + suffix]["value"]
