"""Device trace -> busy time, kernel time and idle time by host span.

``read_xplane`` turns the profiler's ``.xplane.pb`` into plain events; the
reductions below work on those alone, so the tests can check them on a
small recorded trace (``tests/trace_sample.json.gz``).

An event is ``(kind, name, start_ns, end_ns)``:

* ``kind == "op"``: an operation that ran on a TPU (line ``XLA Ops`` of a
  ``/device:TPU:<n>`` plane);
* ``kind == "span"``: a host span that the benchmark opened
  (``jax.profiler.TraceAnnotation`` named ``cb.<category>:<what>``).

Busy time is the union of op intervals within the window; idle time is the
rest of the window. An idle interval is charged to the host spans that
cover it: to ``udf:<name>`` when a UDF call was in progress, else to
``source:<name>`` when the scan was producing rows, else to
``host:other`` (the service, executor, eddy, Laminar and worker threads).
"""
from __future__ import annotations

import glob
import gzip
import json
from collections import defaultdict
from typing import Dict, Iterable, List, Sequence, Tuple

SPAN_PREFIX = "cb."
OP_LINE = "XLA Ops"
# ops that hold other ops of the same line (a scanned layer stack)
CONTAINER_OPS = ("while", "conditional", "call")

Event = Tuple[str, str, int, int]
Interval = Tuple[int, int]


def read_xplane(log_dir: str) -> List[Event]:
    """Every TPU op and every benchmark span in the newest trace under
    ``log_dir``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True))
    if not paths:
        raise RuntimeError(f"no .xplane.pb under {log_dir}")
    data = ProfileData.from_file(paths[-1])
    events: List[Event] = []
    for plane in data.planes:
        device = plane.name.startswith("/device:TPU:")
        for line in plane.lines:
            if device and line.name != OP_LINE:
                continue
            for e in line.events:
                if device:
                    kind, name = f"op{plane.name[len('/device:TPU:'):]}", op_name(e.name)
                elif e.name.startswith(SPAN_PREFIX):
                    kind, name = "span", e.name
                else:
                    continue
                start = int(e.start_ns)
                events.append((kind, name, start, start + int(e.duration_ns)))
    return events


def op_name(hlo: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def load_events(path: str) -> List[Event]:
    """Events saved as a gzipped JSON list of ``[kind, name, start, end]``."""
    with gzip.open(path, "rt") as f:
        return [tuple(e) for e in json.load(f)]


# --------------------------------------------------------------------------- #
# interval arithmetic                                                         #
# --------------------------------------------------------------------------- #
def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(i for i in intervals if i[1] > i[0]):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def clip(intervals: Iterable[Interval], window: Interval) -> List[Interval]:
    lo, hi = window
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def length(intervals: Iterable[Interval]) -> int:
    return sum(b - a for a, b in intervals)


def complement(merged: Sequence[Interval], window: Interval) -> List[Interval]:
    """The parts of ``window`` that ``merged`` (sorted, disjoint) leaves."""
    out, cur = [], window[0]
    for a, b in merged:
        if a > cur:
            out.append((cur, a))
        cur = max(cur, b)
    if cur < window[1]:
        out.append((cur, window[1]))
    return out


def intersect(xs: Sequence[Interval], ys: Sequence[Interval]) -> List[Interval]:
    """Intersection of two sorted, disjoint interval lists."""
    out, i, j = [], 0, 0
    while i < len(xs) and j < len(ys):
        a, b = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if a < b:
            out.append((a, b))
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return out


def subtract(xs: Sequence[Interval], ys: Sequence[Interval]) -> List[Interval]:
    """``xs`` minus ``ys``, both sorted and disjoint."""
    out, j = [], 0
    for a, b in xs:
        while j < len(ys) and ys[j][1] <= a:
            j += 1
        k = j
        while k < len(ys) and ys[k][0] < b:
            if ys[k][0] > a:
                out.append((a, ys[k][0]))
            a = max(a, ys[k][1])
            k += 1
        if a < b:
            out.append((a, b))
    return out


# --------------------------------------------------------------------------- #
# reductions                                                                  #
# --------------------------------------------------------------------------- #
class TraceSummary:
    """Reductions of one traced window (all times in seconds)."""

    def __init__(self, events: Sequence[Event], window: Interval):
        self.window = window
        self.window_s = (window[1] - window[0]) * 1e-9
        ops_by_chip: Dict[str, List[Event]] = defaultdict(list)
        spans: Dict[str, List[Interval]] = defaultdict(list)
        for kind, name, a, b in events:
            if kind.startswith("op"):
                ops_by_chip[kind].append((kind, name, a, b))
            elif kind == "span":
                spans[name[len(SPAN_PREFIX):]].append((a, b))
        self.chips = len(ops_by_chip)
        self.ops = [e for chip in ops_by_chip.values() for e in chip]
        busy = [union(clip(((a, b) for _, _, a, b in chip), window))
                for chip in ops_by_chip.values()]
        # the first chip carries every UDF here; busy_s averages the chips
        self.busy_union = busy[0] if busy else []
        self.busy_s = (sum(length(b) for b in busy) / len(busy) * 1e-9
                       if busy else 0.0)
        self.idle = complement(self.busy_union, window)
        self.spans = {k: union(clip(v, window)) for k, v in spans.items()}

    def idle_s(self) -> float:
        return length(self.idle) * 1e-9

    def kernel_s(self, prefix: str) -> float:
        """Device seconds of ops whose name starts with ``prefix``."""
        return length(c for _, name, a, b in self.ops if name.startswith(prefix)
                      for c in clip([(a, b)], self.window)) * 1e-9

    def category(self, category: str) -> List[Interval]:
        """Union of the spans ``<category>:*``."""
        return union(iv for name, ivs in self.spans.items()
                     if name.split(":", 1)[0] == category for iv in ivs)

    def idle_in(self, category: str) -> float:
        return length(intersect(self.idle, self.category(category))) * 1e-9

    def idle_outside(self, categories: Sequence[str]) -> float:
        covered = union(iv for c in categories for iv in self.category(c))
        return length(subtract(self.idle, covered)) * 1e-9

    def idle_gaps(self, top: int = 10) -> List[List]:
        """Idle seconds by what the host was doing: the UDF calls in
        progress, else the scan, else ``host:other``. Where calls of several
        UDFs were in progress at once (worker threads), the idle time is
        split evenly among them."""
        marks = []  # (time, order, delta, name); idle edges carry name None
        for name, ivs in self.spans.items():
            if name.split(":", 1)[0] in ("udf", "source"):
                for a, b in ivs:
                    marks += [(a, 1, 1, name), (b, 0, -1, name)]
        for a, b in self.idle:
            marks += [(a, 1, 1, None), (b, 0, -1, None)]
        marks.sort(key=lambda m: (m[0], m[1]))
        active: Dict[str, int] = defaultdict(int)
        idle, last = 0, None
        totals: Dict[str, float] = defaultdict(float)
        for t, _, delta, name in marks:
            if idle and last is not None and t > last:
                names = [n for n, c in active.items() if c and n.startswith("udf:")]
                names = names or [n for n, c in active.items() if c]
                for n in names or ["host:other"]:
                    totals[n] += (t - last) * 1e-9 / max(len(names), 1)
            if name is None:
                idle += delta
            else:
                active[name] += delta
            last = t
        ranked = sorted(totals.items(), key=lambda x: -x[1])[:top]
        return [[n, v] for n, v in ranked]

    def device_ops(self, top: int = 10) -> List[List]:
        """Device seconds by op, per chip; ops that hold others (a while
        loop over layers) are left out, so no time counts twice."""
        total: Dict[str, int] = defaultdict(int)
        for _, name, a, b in self.ops:
            if name.split(".", 1)[0] in CONTAINER_OPS:
                continue
            for c, d in clip([(a, b)], self.window):
                total[name] += d - c
        ranked = sorted(total.items(), key=lambda x: -x[1])[:top]
        return [[n, t * 1e-9 / max(self.chips, 1)] for n, t in ranked]
