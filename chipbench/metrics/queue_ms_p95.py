"""95th percentile of the time the window's queries waited in the
service's pending queue (``QueryReport.queue_time_s``), in ms."""
import numpy as np


def read(run):
    q = [r.report.queue_time_s for r in run.done]
    return float(np.percentile(q, 95) * 1e3) if q else None
