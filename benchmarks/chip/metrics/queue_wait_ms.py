"""Mean wait of a request in the scheduler's queue as the program counts
it: ``scheduler.stats`` ``queue_wait_s`` over ``queue_waited`` (submit to
the start of its flush, on the broker's span clock)."""


def read(run):
    n = run.sched.get("queue_waited", 0.0)
    return run.sched["queue_wait_s"] * 1e3 / n if n else None
