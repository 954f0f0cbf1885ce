"""The kernel launch's input side per flush: plan stacking, the ``[B, S]``
admit padding and the host-to-device puts (``broker.kernel_launch.copy_in``)."""

from benchmarks.chip.metrics_common import per_flush_ms


def read(run):
    return per_flush_ms(run, "broker.kernel_launch.copy_in")
