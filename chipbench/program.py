"""What the program records inside its query path, as the benchmark reads it.

Counters: each query's ``QueryReport`` carries the dispatcher's deferrals,
the eddy's routing time, each predicate's worker-queue wait and UDF counts,
and the lowering made on the query's threads. The metric readers in
``metrics/`` sum them over the window's queries with ``total`` and
``predicate_total``. A program that keeps no such counter reads None, never
zero.

Spans: the program opens ``jax.profiler.TraceAnnotation``s named
``hydro.<layer>`` and tagged with the query id (``qid``). They land in the
same ``.xplane.pb`` as the TPU ops, so on the device's clock. ``read_spans``
returns them as ``("pspan", name, start_ns, end_ns, thread, qid)``;
``idle_in`` and ``idle_by_span`` charge a ``trace.TraceSummary``'s idle time
to them. The harness reads only the benchmark's ``cb.*`` spans, so

  python3 chipbench/program.py --workload <cell> --seed <n> --seconds <s> --trace 1

runs one cell as ``run.py`` does and adds one stderr line, ``program idle:``:
the trace's size, the device idle inside ``hydro.udf:*`` beside
``udf_idle_pct``'s reading on ``cb.udf``, the idle inside ``hydro.query``,
and the ten program spans with the most idle seconds by self time.
"""
from __future__ import annotations

import glob
import json
import os
import sys
import time
from collections import defaultdict
from typing import Callable, Dict, List, Optional, Sequence, Tuple

STARTED = time.monotonic()

PREFIX = "hydro."
NO_SPAN = "(no program span)"

Span = Tuple[str, str, int, int, str, str]   # ("pspan", name, a, b, thread, qid)


# --------------------------------------------------------------------------- #
# counters                                                                    #
# --------------------------------------------------------------------------- #
def total(run, get: Callable) -> Optional[float]:
    """``get(report)`` summed over the reports of the window's queries;
    None where there is no report or a report lacks the counter."""
    reports = [r.report for r in run.records if r.report is not None]
    try:
        values = [get(rep) for rep in reports]
    except (AttributeError, KeyError):
        return None
    return float(sum(values)) if values else None


def predicate_total(run, key: str) -> Optional[float]:
    """``key`` summed over every predicate entry of every report."""
    return total(run, lambda rep: sum(e.get(key, 0) for e in rep.stats.values()))


def ratio(num: Optional[float], den: Optional[float],
          scale: float = 1.0) -> Optional[float]:
    return None if num is None or not den else scale * num / den


def spins_per_s(run) -> Optional[float]:
    """Dispatcher deferrals of the window's queries per second of the
    window (``Run.span_s``)."""
    return ratio(total(run, lambda rep: rep.dispatch_deferrals), run.span_s)


# --------------------------------------------------------------------------- #
# spans                                                                       #
# --------------------------------------------------------------------------- #
def read_spans(log_dir: str) -> List[Span]:
    """Every program span in the newest trace under ``log_dir``, with the
    host thread it ran on and the query id it carries."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True))
    if not paths:
        raise RuntimeError(f"no .xplane.pb under {log_dir}")
    spans: List[Span] = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        if plane.name.startswith("/device:"):
            continue
        for index, line in enumerate(plane.lines):
            thread = f"{plane.name}/{line.name}#{index}"
            for e in line.events:
                if e.name.startswith(PREFIX):
                    start = int(e.start_ns)
                    qid = next((str(v) for k, v in e.stats if k == "qid"), "")
                    spans.append(("pspan", e.name, start, start + int(e.duration_ns),
                                  thread, qid))
    return spans


def idle_in(summary, spans: Sequence[Span], *prefixes: str) -> float:
    """Idle seconds of ``summary`` inside the program spans whose name
    starts with one of ``prefixes`` (``"hydro.udf:"``, ``"hydro.query"``)."""
    from chipbench import trace as tr

    covered = tr.union(tr.clip(((a, b) for _, name, a, b, *_ in spans
                                if name.startswith(prefixes)), summary.window))
    return tr.length(tr.intersect(summary.idle, covered)) * 1e-9


def idle_by_span(summary, spans: Sequence[Span], top: int = 10) -> List[List]:
    """Idle seconds by program span, charged by self time: at each idle
    instant, to the innermost open span of the threads whose span stack is
    deepest then, split evenly among those threads (as ``idle_gaps`` splits
    among UDF calls); to ``NO_SPAN`` where no program span is open."""
    by_thread: Dict[str, List] = defaultdict(list)
    for _, name, a, b, thread, _ in spans:
        by_thread[thread].append((a, b, name))
    marks = []  # (time, order, delta, (thread, depth, name)); idle: None
    for thread, items in by_thread.items():
        ends: List[int] = []  # ends of the spans open on this thread
        for a, b, name in sorted(items, key=lambda s: (s[0], -s[1])):
            while ends and ends[-1] <= a:
                ends.pop()
            ends.append(b)
            key = (thread, len(ends), name)
            marks += [(a, 1, 1, key), (b, 0, -1, key)]
    for a, b in summary.idle:
        marks += [(a, 1, 1, None), (b, 0, -1, None)]
    marks.sort(key=lambda m: (m[0], m[1]))
    open_: Dict[str, Dict] = defaultdict(lambda: defaultdict(int))
    totals: Dict[str, float] = defaultdict(float)
    idle, last, names = 0, None, []
    for t, _, delta, key in marks:
        if idle and last is not None and t > last:
            for n in names or [NO_SPAN]:
                totals[n] += (t - last) * 1e-9 / max(len(names), 1)
        if key is None:
            idle += delta
        else:
            thread, depth, name = key
            open_[thread][(depth, name)] += delta
            inner = [max(k for k, c in o.items() if c)
                     for o in open_.values() if any(o.values())]
            deepest = max((d for d, _ in inner), default=0)
            names = [n for d, n in inner if d == deepest]
        last = t
    ranked = sorted(totals.items(), key=lambda x: -x[1])[:top]
    return [[n, v] for n, v in ranked]


def idle_line(summary, spans: Sequence[Span], trace_bytes: int) -> str:
    """The ``program idle:`` line: percents of the traced window."""
    pct = lambda s: 100.0 * s / summary.window_s if summary.window_s > 0 else None
    return (f"program idle: trace_bytes={trace_bytes} program_spans={len(spans)} "
            f"udf_idle_pct.cb={pct(summary.idle_in('udf'))!r} "
            f"udf_idle_pct.hydro={pct(idle_in(summary, spans, 'hydro.udf:'))!r} "
            f"query_idle_pct={pct(idle_in(summary, spans, 'hydro.query'))!r} "
            + json.dumps(idle_by_span(summary, spans)))


def main(argv=None, **harness_kwargs) -> int:
    """One run of a cell through ``harness.main`` (``harness_kwargs`` as
    it takes them), reading the program's spans out of the trace before
    the harness deletes it."""
    from chipbench import harness
    from chipbench import trace as tr

    read = tr.read_xplane

    def read_both(log_dir: str):
        events = read(log_dir)
        spans = read_spans(log_dir)
        size = sum(os.path.getsize(p) for p in glob.glob(f"{log_dir}/**/*", recursive=True)
                   if os.path.isfile(p))
        window = [(a, b) for kind, name, a, b in events
                  if kind == "span" and name == "cb.window"]
        if window:
            harness.log(idle_line(tr.TraceSummary(events, window[0]), spans, size))
        return events

    tr.read_xplane = read_both
    try:
        return harness.main(argv, started=harness_kwargs.pop("started", STARTED),
                            **harness_kwargs)
    finally:
        tr.read_xplane = read


if __name__ == "__main__":
    from pathlib import Path

    repo = Path(__file__).resolve().parent.parent
    sys.path[:0] = [str(repo / "src"), str(repo)]
    sys.exit(main(sys.argv[1:]))
