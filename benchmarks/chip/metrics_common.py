"""Arithmetic shared by the metric readers in ``metrics/``."""

from __future__ import annotations

from typing import Optional


def per_flush_ms(run, span: str) -> Optional[float]:
    """The summed duration of one kind of span, in ms per flush; None where
    the window has no flush or no such span."""
    durs = [t1 - t0 for n, t0, t1, *_ in run.spans if n == span]
    if not durs or not run.flushes:
        return None
    return sum(durs) * 1e3 / run.flushes
