"""Median latency of the requests due in the window, from when each was
due; a request that failed counts as waiting until the run gave up on it."""
import math

import numpy as np


def read(run):
    lat = np.sort(run.latency_ms)
    if not lat.size:
        return None
    return float(lat[max(math.ceil(lat.size / 2) - 1, 0)])  # nearest rank
