"""Host spans and output records at the layer boundaries the benchmark
can reach from outside the program.

* ``cb.udf:<predicate>``: a call from a Laminar worker into a predicate's
  UDF (``Predicate.evaluate_outputs``): featurize, transfer, launch, sync.
* ``cb.source:<table>``: the scan producing the next chunk of rows.

The spans are ``jax.profiler.TraceAnnotation``s, which cost nothing when no
trace is being taken, so the timed and the traced runs run the same code.
The recorder keeps what each call returned, for the check after the window.
"""
from __future__ import annotations

import threading
from typing import Callable, Iterable, Iterator, List, Optional

import numpy as np
from jax.profiler import TraceAnnotation

from repro.core.udf import Predicate


class Recorder:
    """Calls of one predicate in the window: their count and rows, and the
    (inputs, outputs, extra) of every call (``keep=None``) or of a seeded
    reservoir sample of ``keep`` calls."""

    def __init__(self, seed: int, keep: Optional[int] = None):
        self._rng = np.random.default_rng(seed)
        self._lock = threading.Lock()
        self.keep = keep
        self.reset()

    def reset(self) -> None:
        with self._lock:
            self.calls = 0
            self.rows = 0
            self.sample: List[tuple] = []

    def add(self, data, out, extra) -> None:
        with self._lock:
            n = len(out)
            self.calls += 1
            self.rows += n
            if self.keep is None or len(self.sample) < self.keep:
                self.sample.append((data, out, extra))
            elif self.keep:
                j = int(self._rng.integers(0, self.calls))
                if j < self.keep:
                    self.sample[j] = (data, out, extra)


class SpannedPredicate(Predicate):
    """The program's ``Predicate`` with a span and a record round each call
    into its UDF. ``extra`` (optional) returns what the UDF's function left
    for the check on this thread (see ``ThreadStash``)."""

    def evaluate_outputs(self, data):
        with TraceAnnotation(f"cb.udf:{self.name}"):
            out = Predicate.evaluate_outputs(self, data)
        extra = self.bench_extra() if self.bench_extra else None
        self.bench_recorder.add(data, out, extra)
        return out


def spanned(pred: Predicate, recorder: Recorder,
            extra: Optional[Callable[[], object]] = None) -> SpannedPredicate:
    p = SpannedPredicate(pred.name, pred.udf, pred.compare, pred.cacheable)
    p.bench_recorder = recorder
    p.bench_extra = extra
    return p


class ThreadStash(threading.local):
    """A value a UDF function leaves for its caller on the same thread."""

    value = None

    def take(self):
        v, self.value = self.value, None
        return v


def source_spans(name: str, chunks: Iterable) -> Iterator:
    it = iter(chunks)
    while True:
        with TraceAnnotation(f"cb.source:{name}"):
            try:
                chunk = next(it)
            except StopIteration:
                return
        yield chunk
