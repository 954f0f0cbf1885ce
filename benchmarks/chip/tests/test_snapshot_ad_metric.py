"""The reader of the lazy snapshot-ad counters: on synthetic run data, and
in a tiny traced run on the CPU."""

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


@pytest.mark.parametrize("name", ["snapshot_ad_pct", "snapshot_ad_pct.closed"])
def test_reader(name):
    read = harness.load_reader(name)
    assert read(_run({"snapshot_rows": 30000.0, "snapshot_ads_built": 24.0})) == pytest.approx(0.08)
    assert read(_run({"snapshot_rows": 30000.0})) == pytest.approx(0.0)
    # no rebuild in the window
    assert read(_run({"snapshot_rows": 0.0, "snapshot_ads_built": 0.0})) is None
    # a program without the counters (the parent of this metric) reads nothing
    assert read(_run({"snapshot_builds": 6.0})) is None


def test_traced_run_makes_no_snapshot_ad():
    cell = tiny_cell("wlcg.analysis_zipf")
    out = harness.run_cell(cell, 2**31 + 107, 1.5, True, t_process=time.perf_counter(),
                           require_chip=False)
    assert out["correct"], out["checks"]
    assert out["notes"]["snapshot_builds"] >= 1
    got = out["metrics"]["snapshot_ad_pct"]
    assert got["unit"] == "%" and got["value"] == 0.0
