"""The kernel launch's output side per flush: the wait for the device and
the copy of mask and score back to the host (``broker.kernel_launch.fetch``)."""

from benchmarks.chip.metrics_common import per_flush_ms


def read(run):
    return per_flush_ms(run, "broker.kernel_launch.fetch")
