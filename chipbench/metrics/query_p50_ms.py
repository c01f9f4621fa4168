"""Median latency of the window's queries, from when each was due to be
sent to its answer."""
import numpy as np


def read(run):
    lat = run.latencies_ms()
    return float(np.percentile(lat, 50)) if len(lat) else None
