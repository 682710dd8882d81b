"""95th percentile of the same read latencies."""

import numpy as np


def read(run):
    if run.read_lat_s.size == 0:
        return None
    return float(np.percentile(run.read_lat_s, 95)) * 1e3
