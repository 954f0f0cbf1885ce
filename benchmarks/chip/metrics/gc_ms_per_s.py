"""Full garbage collections per second of window: the ``broker.gc`` spans
that start in the window (0 where none ran). Nothing where the program
records no collections."""


def read(run):
    try:
        from repro.obs.trace import GC_SPAN
    except ImportError:
        return None
    total = sum(t1 - t0 for n, t0, t1, *_ in run.spans if n == GC_SPAN and t0 < run.seconds)
    return total * 1e3 / run.seconds
