"""Median selection latency: due time to the ticket being filled, over
every request due in the window (the drain after it included)."""

import numpy as np


def read(run):
    lat = (run.fill - run.due) * 1e3
    lat = lat[~np.isnan(lat)]
    return float(np.percentile(lat, 50)) if lat.size else None
