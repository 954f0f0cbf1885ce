"""The reader of the candidate-launch counters: on synthetic run data, and
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


@pytest.mark.parametrize("name", ["launch_candidate_pct", "launch_candidate_pct.closed"])
def test_reader(name):
    read = harness.load_reader(name)
    assert read(_run({"kernel_launches": 40.0, "kernel_launches_candidate": 30.0})) == pytest.approx(75.0)
    assert read(_run({"kernel_launches": 40.0})) == pytest.approx(0.0)
    assert read(_run({"kernel_launches": 0.0, "kernel_launches_candidate": 0.0})) is None
    # a program without the counters (the parent of this metric) reads nothing
    assert read(_run({"batched_kernel_requests": 8.0})) is None


def test_traced_run_reads_every_launch_as_candidate():
    cell = tiny_cell("wlcg.analysis_zipf")
    out = harness.run_cell(cell, 2**31 + 103, 1.5, True, t_process=time.perf_counter(),
                           require_chip=False)
    assert out["correct"], out["checks"]
    got = out["metrics"]["launch_candidate_pct"]
    assert got["unit"] == "%" and got["value"] == 100.0
