"""Open loop: Poisson arrivals at ``rate_qps``, each query sent when it is
due whatever the backlog. The gaps are the midpoint quantiles of the
exponential law, shuffled into one fixed order."""
import time

import numpy as np

from chipbench import loadgen


def plan(mix, seconds):
    rng = np.random.default_rng(loadgen.SCHEDULE_SEED)
    n = max(1, round(mix["rate_qps"] * seconds))
    gaps = rng.permutation(-np.log1p(-loadgen.quantiles(n)) / mix["rate_qps"])
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    return loadgen.Plan("open", loadgen.query_sizes(mix["query_rows"], n, rng), due)


def drive(p, send, seconds, start):
    for i, offset in enumerate(p.due_s):
        due = start + float(offset)
        delay = due - time.monotonic()
        if delay > 0:
            time.sleep(delay)
        send(i, due)
    return send.records
