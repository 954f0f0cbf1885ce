"""Kernel launch per flush, host-to-device and device-to-host copies
included: the ``broker.kernel_launch`` spans."""

from benchmarks.chip.metrics_common import per_flush_ms


def read(run):
    return per_flush_ms(run, "broker.kernel_launch")
