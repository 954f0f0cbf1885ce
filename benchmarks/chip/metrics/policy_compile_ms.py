"""Server-policy lookups in the plan cache, compiles on a miss included,
per flush: the ``broker.lowering.policy_compile`` spans."""

from benchmarks.chip.metrics_common import per_flush_ms


def read(run):
    return per_flush_ms(run, "broker.lowering.policy_compile")
