"""The device's idle share of the traced window: one minus the union of
its operations' intervals (``trace.py``)."""


def read(run):
    return run.trace.idle_pct if run.trace is not None else None
