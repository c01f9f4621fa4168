"""What every deployment shares: one ``QueryService`` at its defaults (no
reuse cache), queries submitted from the client side, a warm-up query in
set-up. A configuration's ``configs/<config>.py`` subclasses ``Deployment``.
"""
from __future__ import annotations

from typing import Dict, Iterable, List, Tuple

import numpy as np

from repro.core.udf import bucket_rows
from repro.launch.serve import QueryService

WARM_TIMEOUT_S = 600.0


class Deployment:
    """Subclasses set ``predicates`` (``spans.spanned`` ones) in
    ``make_model`` and define ``make_data``, ``query``, ``warm_batches``,
    ``required_work`` and ``check``."""

    def __init__(self, config: dict, mix: dict, seed: int, small: bool = False):
        self.predicates: List = []
        self.config = config
        self.sizes = config["sizes"]
        self.mix = mix
        self.data = mix.get("data", {})
        self.seed = seed
        self.small = small
        self.service = None

    # ---------------------------------------------------------------- set-up
    def make_model(self) -> None:
        raise NotImplementedError

    def make_data(self, plan) -> None:
        raise NotImplementedError

    def warm_batches(self, plan) -> Iterable[Tuple[object, Dict]]:
        """(predicate, columns) for every row bucket the window will launch."""
        raise NotImplementedError

    def warm(self, plan) -> None:
        """Compile every bucket the window uses, then run the warm-up query
        (index ``len(plan.sizes)``, one more than the plan, as small as its
        smallest) through the service, so the window starts with programs
        loaded and the service's statistics seeded."""
        for pred, cols in self.warm_batches(plan):
            pred.udf(cols)
        self.service = QueryService()
        handle = self.submit(len(plan.sizes))
        report = handle.result(timeout=WARM_TIMEOUT_S)
        if report.state != "DONE":
            raise RuntimeError(f"warm-up query ended {report.state}")

    def start_window(self) -> None:
        for p in self.predicates:
            p.bench_recorder.reset()

    # ---------------------------------------------------------------- window
    def query(self, i: int):
        """(predicates, routing-batch iterable, executor options) of query i."""
        raise NotImplementedError

    def submit(self, i: int):
        preds, batches, options = self.query(i)
        return self.service.submit(preds, batches, **options)

    # ---------------------------------------------------------------- after
    def required_work(self) -> Dict:
        raise NotImplementedError

    def close(self) -> None:
        """Stop the service and drop the program's device state."""
        if self.service is not None:
            self.service.close(drain=False)
        self.predicates = []
        self.service = None

    def check(self, records) -> Dict[str, Tuple[float, float]]:
        raise NotImplementedError


def with_warm_query(plan) -> np.ndarray:
    """The plan's query sizes and the warm-up query's."""
    return np.append(plan.sizes, plan.sizes.min())


def buckets(batch_sizes: Iterable[int]) -> List[int]:
    return sorted({bucket_rows(int(n)) for n in batch_sizes if n > 0})


def batch_sizes(passing: np.ndarray, batch_rows: int) -> set:
    """Sizes of the routing batches of queries whose pushdown passes
    ``passing[i]`` rows: full batches and one tail each."""
    out = set()
    for n in np.unique(passing):
        if n >= batch_rows:
            out.add(batch_rows)
        if n % batch_rows:
            out.add(int(n % batch_rows))
    return out


def multiset_diff(a, b) -> int:
    """Rows in one multiset and not the other, counted with multiplicity."""
    va, ca = np.unique(np.asarray(a), return_counts=True)
    vb, cb = np.unique(np.asarray(b), return_counts=True)
    both = np.union1d(va, vb)
    na = np.zeros(len(both), np.int64)
    nb = np.zeros(len(both), np.int64)
    na[np.searchsorted(both, va)] = ca
    nb[np.searchsorted(both, vb)] = cb
    return int(np.abs(na - nb).sum())
