"""Median read latency, from submit to the step that answered it, over
every read completed in the window."""

import numpy as np


def read(run):
    if run.read_lat_s.size == 0:
        return None
    return float(np.percentile(run.read_lat_s, 50)) * 1e3
