"""Closed loop: ``clients`` threads, each submitting its next query when
its last one returned, for the whole window. ``capacity_rows_per_s`` rows
are planned a second, so that a faster program still finds fresh rows; a
run that uses up its plan fails."""
import math
import threading
import time

import numpy as np

from chipbench import loadgen


def plan(mix, seconds):
    law = mix["query_rows"]

    def schedule(n):
        return loadgen.query_sizes(law, n, np.random.default_rng(loadgen.SCHEDULE_SEED))

    n = math.ceil(mix["capacity_rows_per_s"] * seconds / schedule(64).mean())
    return loadgen.Plan("closed", schedule(n), clients=int(mix["clients"]))


def drive(p, send, seconds, start):
    end = start + seconds
    counter = iter(range(len(p.sizes)))
    lock = threading.Lock()
    errors = []

    def client():
        try:
            while time.monotonic() < end:
                with lock:
                    i = next(counter, None)
                if i is None:
                    raise RuntimeError(
                        f"the plan's {len(p.sizes)} queries ran out before "
                        "the window closed; raise capacity_rows_per_s")
                r = send(i, time.monotonic())
                if r.handle is not None:
                    r.handle.wait(end + loadgen.RESULT_WAIT_S - time.monotonic())
        except BaseException as e:  # reported by the caller
            errors.append(e)

    threads = [threading.Thread(target=client, name=f"cb-client-{c}")
               for c in range(p.clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errors:
        raise errors[0]
    return send.records
