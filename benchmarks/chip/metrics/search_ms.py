"""Search phase per flush: the ``broker.batch_search`` spans."""

from benchmarks.chip.metrics_common import per_flush_ms


def read(run):
    return per_flush_ms(run, "broker.batch_search")
