"""The reader of the per-epoch usage-policy index counters: on synthetic run
data, and in a tiny traced run on the CPU."""

import time

import numpy as np
import pytest

from benchmarks.chip import harness
from benchmarks.chip.harness import RunData

from test_harness import tiny_cell  # the tests directory is on the path (rootdir conftest)


def _run(broker):
    return RunData(
        cell=None, seconds=10.0, setup_s=0.0, due=np.zeros(0), fill=np.zeros(0),
        flush_start=np.zeros(0), spans=[], flushes=4,
        sched={"submitted": 8.0, "batches": 4.0}, plan_cache={}, broker=broker,
    )


@pytest.mark.parametrize("name", ["policy_index_hit_pct", "policy_index_hit_pct.closed"])
def test_reader(name):
    read = harness.load_reader(name)
    assert read(_run({"policy_index_builds": 2.0, "policy_index_reuses": 198.0})) == pytest.approx(99.0)
    assert read(_run({"policy_index_builds": 0.0, "policy_index_reuses": 0.0})) is None
    # a program without the counters (the parent of this metric) reads nothing
    assert read(_run({"snapshot_builds": 2.0})) is None


def test_traced_run_reads_the_hit_share():
    cell = tiny_cell("fleet15k.restore_closed")
    out = harness.run_cell(cell, 2**31 + 101, 1.5, True, t_process=time.perf_counter(),
                           require_chip=False)
    assert out["correct"], out["checks"]
    builds = out["notes"]["snapshot_builds"]
    assert builds >= 2  # the TTL lapsed in the window: one index an epoch
    got = out["metrics"]["policy_index_hit_pct.closed"]
    assert got["unit"] == "%" and 50.0 < got["value"] < 100.0
