"""Grouping of the snapshot rows by their usage policy, per flush: the
``broker.lowering.policy_groups`` spans (one per request lowered)."""

from benchmarks.chip.metrics_common import per_flush_ms


def read(run):
    return per_flush_ms(run, "broker.lowering.policy_groups")
