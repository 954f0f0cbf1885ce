"""GRIS reads in snapshot rebuilds (every endpoint's flattened view), per
second of window: the ``broker.snapshot.gris`` spans that start
in the window. Nothing where the window has no such span."""

SPAN = "broker.snapshot.gris"


def read(run):
    durs = [t1 - t0 for n, t0, t1, *_ in run.spans if n == SPAN and t0 < run.seconds]
    return sum(durs) * 1e3 / run.seconds if durs else None
