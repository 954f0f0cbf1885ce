"""The trace reduction on a recorded trace: 2 s of a ``fleet15k.restore_open``
window on one TPU v5e chip (a snapshot rebuild inside), reduced to event
lists by ``trace.load_events``."""

from pathlib import Path

import numpy as np
import pytest

from benchmarks.chip import trace

DATA = Path(__file__).resolve().parent / "data" / "fleet15k_trace_2s.json"
KERNEL = ("_dispatch_batched", "[tpu_custom_call]")


@pytest.fixture(scope="module")
def recorded():
    ev = trace.read_events(str(DATA))
    (_, w0, dur), = [e for e in ev.host if e[0] == "bench.window"]
    return ev, (w0, w0 + dur)


def test_busy_is_the_union_of_device_ops(recorded):
    ev, (w0, w1) = recorded
    s = trace.summarize(ev, (w0, w1))
    # an independent count: the window in 100 ns cells, a cell busy when an
    # operation covers it
    cells = np.zeros(int((w1 - w0) / 100) + 1, bool)
    for _, st, d in ev.device["/device:TPU:0"]:
        a, b = max(st, w0), min(st + d, w1)
        if b > a:
            cells[int((a - w0) / 100): int(np.ceil((b - w0) / 100))] = True
    assert s.window_s == pytest.approx(2.0)
    assert s.busy_s == pytest.approx(cells.sum() * 100e-9, rel=0.05)
    assert 0 < s.busy_s < s.window_s
    assert s.idle_pct == pytest.approx(100.0 * (1 - s.busy_s / s.window_s))


def test_idle_time_is_charged_to_host_spans(recorded):
    ev, window = recorded
    s = trace.summarize(ev, window)
    assert sum(s.idle_by_span.values()) == pytest.approx(s.window_s - s.busy_s, rel=1e-9)
    top = dict(s.top_idle())
    # the snapshot rebuild and the policy fold hold the device idle here
    assert top["broker.snapshot"] > 0.5 and top["broker.lowering"] > 0.5
    assert "bench.window" not in top


def test_idle_gaps_are_split_between_spans():
    # device busy 0-10 and 90-100 ns; the host in a flush (5-80 ns) whose
    # lowering child runs 20-50 ns, then nothing
    ev = trace.Events(
        {"/device:TPU:0": [("op", 0.0, 10.0), ("op", 90.0, 10.0)]},
        [("scheduler.flush", 5.0, 75.0), ("broker.lowering", 20.0, 30.0)],
    )
    s = trace.summarize(ev, (0.0, 100.0))
    assert s.busy_s == pytest.approx(20e-9)
    idle = {k: v * 1e9 for k, v in s.idle_by_span.items()}
    assert idle == pytest.approx({"scheduler.flush": 40.0, "broker.lowering": 30.0, "host.waiting": 10.0})


def test_kernel_time_by_name(recorded):
    ev, window = recorded
    s = trace.summarize(ev, window)
    k = trace.kernel_seconds(s, *KERNEL)
    assert 0 < k <= s.busy_s
    assert s.top_ops(1)[0][0].endswith("[tpu_custom_call]")
    assert trace.kernel_seconds(s, "no_such_kernel") is None


def test_short_names():
    hlo = ('%_dispatch_batched.1 = (f32[8,15360]{1,0}) custom-call(f32[15360,128]{1,0} %attrs.1),'
           ' custom_call_target="tpu_custom_call", operand_layout_constraints={}')
    assert trace.short_name(hlo) == "_dispatch_batched.1 [tpu_custom_call]"
    assert trace.short_name("%pad.0 = f32[8,15360]{1,0} pad(f32[1,15360]{1,0} %x)") == "pad.0"
