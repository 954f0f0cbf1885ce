"""Requests answered inside the window, over the window's seconds."""

import numpy as np


def read(run):
    return float(np.count_nonzero(run.fill < run.seconds)) / run.seconds
