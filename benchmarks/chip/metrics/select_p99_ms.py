"""99th percentile of the selection latency, all requests due in the
window pooled (never a percentile of chunk medians)."""

import numpy as np


def read(run):
    lat = (run.fill - run.due) * 1e3
    lat = lat[~np.isnan(lat)]
    return float(np.percentile(lat, 99)) if lat.size else None
