"""Plan lowering and the server-policy fold per flush: ``broker.lowering``."""

from benchmarks.chip.metrics_common import per_flush_ms


def read(run):
    return per_flush_ms(run, "broker.lowering")
