"""Mean wait in the scheduler's queue: a request's due time to the start
of the ``scheduler.flush`` that answers it (the benchmark's timestamps and
the flush spans)."""

import numpy as np


def read(run):
    if not run.due.size:
        return None
    return float(np.mean(run.flush_start - run.due) * 1e3)
