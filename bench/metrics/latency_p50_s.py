"""Median latency of the window's requests, from when each was due."""
import numpy as np

from readings import latencies


def read(run):
    lat = latencies(run)
    return float(np.percentile(lat, 50)) if lat.size else None
