"""Request plans looked up or compiled (the kernel plan, or the columnar
program where the kernel's subset refuses it), per flush: the
``broker.lowering.plan`` spans."""

from benchmarks.chip.metrics_common import per_flush_ms


def read(run):
    return per_flush_ms(run, "broker.lowering.plan")
