"""Stacked kernel launches that sent candidate rows narrower than the
snapshot's padded rows, over all stacked launches, in the window: the
broker's ``kernel_launches_candidate`` / ``kernel_launches``. A program
without the counters reads nothing."""


def read(run):
    b = run.broker
    if "kernel_launches" not in b:
        return None
    total = b["kernel_launches"]
    return 100.0 * b.get("kernel_launches_candidate", 0.0) / total if total else None
