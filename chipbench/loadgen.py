"""The traffic generator: query sizes and arrivals from a mix file.

A mix (``traffic/<mix>.json``) says how queries arrive and how large they
are; the configuration's deployment fills each query with rows. Keys:

* ``loop``: the arrival law, ``loops/<loop>.py``. Its ``plan(mix,
  seconds)`` returns a ``Plan`` and its ``drive(plan, submit, seconds,
  start)`` sends the plan's queries. ``closed``: ``clients`` threads, each
  submitting its next query when the last one returned. ``open``: Poisson
  arrivals at ``rate_qps``, sent on schedule whatever the backlog.
* ``query_rows``: ``{"<law>": argument}``, the size law
  ``sizes/<law>.py``, whose ``sizes(argument, n, rng)`` gives ``n`` query
  sizes: ``{"fixed": n}``, ``{"loguniform": [lo, hi]}``.
* ``capacity_rows_per_s`` (closed loop): rows planned per second of window,
  so that a faster program still finds fresh rows; a run that uses up its
  plan fails.
* ``data``: parameters the deployment reads (shares of passing rows).

A new arrival or size law is a new file under ``loops/`` or ``sizes/``.
Every law draws from ``SCHEDULE_SEED`` alone: every seed gets the same
schedule of sizes and arrivals, and the seed changes only which rows fill
the queries, since an open loop's tail depends on the order of sizes and
gaps (see PERF.md).
"""
from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field
from typing import Callable, List, Optional

import numpy as np

from chipbench import spec
from repro.launch.serve import AdmissionError

RESULT_WAIT_S = 60.0
SCHEDULE_SEED = 0


@dataclass
class Plan:
    loop: str                         # the arrival law that drives it
    sizes: np.ndarray                 # rows of each planned query
    due_s: Optional[np.ndarray] = None  # offsets from window start
    clients: int = 0                  # closed loop


@dataclass
class Record:
    index: int
    rows: int                         # table rows the query scans
    due: float                        # monotonic seconds
    submitted: float
    handle: object = None
    error: str = ""
    report: object = field(default=None, repr=False)

    @property
    def done(self) -> bool:
        return self.report is not None and self.report.state == "DONE"


def quantiles(n: int) -> np.ndarray:
    """The ``n`` midpoint quantiles ``(i + 1/2) / n``."""
    return (np.arange(n) + 0.5) / n


def query_sizes(law: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` query sizes under the mix's ``query_rows`` law."""
    (name, arg), = law.items()
    return np.asarray(spec.size_law(name).sizes(arg, n, rng), np.int64)


def plan(mix: dict, seconds: float) -> Plan:
    return spec.loop(mix["loop"]).plan(mix, seconds)


def drive(p: Plan, submit: Callable[[int], object], seconds: float,
          start: float) -> List[Record]:
    """Run the plan from monotonic time ``start`` for ``seconds``; every
    query submitted in the window is waited for (a minute past the close
    at most). ``submit(i)`` returns a ``QueryHandle``."""
    records = spec.loop(p.loop).drive(p, Sender(p, submit), seconds, start)
    deadline = start + seconds + RESULT_WAIT_S
    for r in records:
        if r.handle is None:
            continue
        if r.handle.wait(max(0.0, deadline - time.monotonic())):
            r.report = r.handle.report
        else:
            r.error = f"no answer {RESULT_WAIT_S} s after the window closed"
    records.sort(key=lambda r: r.index)
    return records


class Sender:
    """Submits query ``i`` of a plan and keeps its record; safe to call
    from several threads. ``records`` holds every query sent."""

    def __init__(self, p: Plan, submit: Callable[[int], object]):
        self.plan = p
        self.submit = submit
        self.records: List[Record] = []
        self._lock = threading.Lock()

    def __call__(self, i: int, due: float) -> Record:
        r = Record(i, int(self.plan.sizes[i]), due, time.monotonic())
        try:
            r.handle = self.submit(i)
        except AdmissionError as e:
            r.error = f"rejected: {e}"
        with self._lock:
            self.records.append(r)
        return r
