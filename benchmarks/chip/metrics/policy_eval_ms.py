"""Server policies evaluated over the snapshot table and folded into each
request's admit vector, per flush: the ``broker.lowering.policy_eval``
spans."""

from benchmarks.chip.metrics_common import per_flush_ms


def read(run):
    return per_flush_ms(run, "broker.lowering.policy_eval")
