"""95th percentile of the latency of every query of the window, from when
it was due to be sent to its answer; a query that failed counts with the
longest wait the run gives it."""
import numpy as np


def read(run):
    lat = run.latencies_ms()
    return float(np.percentile(lat, 95)) if len(lat) else None
