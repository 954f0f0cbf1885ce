"""Snapshot time per second of window: the ``broker.snapshot`` spans that
start in the window (TTL rebuilds land here), over the window's seconds."""


def read(run):
    total = sum(t1 - t0 for n, t0, t1, *_ in run.spans if n == "broker.snapshot" and t0 < run.seconds)
    return total * 1e3 / run.seconds
