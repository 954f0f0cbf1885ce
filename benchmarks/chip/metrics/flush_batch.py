"""Requests per flush: ``scheduler.stats`` submitted over batches."""


def read(run):
    b = run.sched.get("batches", 0.0)
    return run.sched["submitted"] / b if b else None
