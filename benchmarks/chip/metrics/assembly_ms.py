"""Result assembly per flush: each ``scheduler.flush`` less its direct
``broker.*`` children (ranked lists, audit records, the plan objects)."""


def read(run):
    flushes = {sid: t1 - t0 for n, t0, t1, sid, _, _ in run.spans if n == "scheduler.flush"}
    if not flushes:
        return None
    own = dict(flushes)
    for n, t0, t1, _, parent, _ in run.spans:
        if parent in own and n.startswith("broker."):
            own[parent] -= t1 - t0
    return sum(own.values()) * 1e3 / len(own)
