"""The readers of the default-ad cell's tier counters: on synthetic run
data, and in a tiny traced run of ``wlcg.default_ads`` on the CPU."""

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


def test_kernel_tier_pct():
    read = harness.load_reader("kernel_tier_pct")
    assert read(_run({"batched_kernel_requests": 30.0, "batched_columnar_requests": 90.0,
                      "batched_interp_requests": 0.0})) == pytest.approx(25.0)
    # the guarded count is a part of the kernel tier's, not a tier of its own
    assert read(_run({"batched_kernel_requests": 120.0, "batched_kernel_guarded_requests": 90.0,
                      "batched_columnar_requests": 0.0})) == pytest.approx(100.0)
    assert read(_run({"batched_kernel_requests": 0.0})) is None


def test_guarded_kernel_pct():
    read = harness.load_reader("guarded_kernel_pct")
    assert read(_run({"batched_kernel_requests": 120.0, "batched_kernel_guarded_requests": 90.0,
                      "batched_sparse_requests": 0.0})) == pytest.approx(75.0)
    assert read(_run({"batched_kernel_requests": 0.0,
                      "batched_kernel_guarded_requests": 0.0})) is None
    # a program without the counter (the parent of this metric) reads nothing
    assert read(_run({"batched_kernel_requests": 30.0, "batched_columnar_requests": 90.0})) is None


def test_traced_run_reads_the_tier_shares():
    cell = tiny_cell("wlcg.default_ads")
    out = harness.run_cell(cell, 2**31 + 303, 1.5, True, t_process=time.perf_counter(),
                           require_chip=False)
    assert out["correct"], out["checks"]
    assert out["notes"]["compiles_in_window"] == 0
    m = out["metrics"]
    assert m["kernel_tier_pct"]["value"] == pytest.approx(100.0)
    # about 3 of 4 requests are sent with no ad
    assert 50.0 < m["guarded_kernel_pct"]["value"] < 95.0
    assert m["guarded_kernel_pct"]["unit"] == "%"
