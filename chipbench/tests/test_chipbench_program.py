"""The program's spans and counters as the benchmark reads them: program
spans in a traced window, idle charged to them by self time, and the
per-layer metrics that read the program's counters."""
import json
import sys
import types
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(REPO / "src"), str(REPO)]

from chipbench import program, spec  # noqa: E402
from chipbench import trace as tr  # noqa: E402
from chipbench.harness import Run  # noqa: E402
from chipbench_cellrun import SEED, TINY, run_cell  # noqa: E402


def _events():
    # window 0..100 ns; one chip busy 20..30 and 60..70; idle 0..20,
    # 30..60, 70..100. Thread Q runs query q1 for 5..95; thread W is a
    # worker: eval 10..80 holding prep 15..25 and launch 35..75; thread S
    # routes 40..50 and 85..90. A cb.udf span covers the worker's eval.
    return [
        ("span", "cb.window", 0, 100),
        ("span", "cb.udf:p", 10, 80),
        ("op0", "fusion.1", 20, 30),
        ("op0", "fusion.2", 60, 70),
    ]


SPANS = [
    ("pspan", "hydro.query", 5, 95, "Q", "q1"),
    ("pspan", "hydro.worker:eval", 10, 80, "W", "q1"),
    ("pspan", "hydro.udf:prep", 15, 25, "W", "q1"),
    ("pspan", "hydro.udf:launch", 35, 75, "W", "q1"),
    ("pspan", "hydro.eddy:route", 40, 50, "S", "q1"),
    ("pspan", "hydro.eddy:route", 85, 90, "S", "q1"),
]


def test_program_idle_by_self_time_on_hand_worked_events():
    s = tr.TraceSummary(_events(), (0, 100))
    assert s.idle == [(0, 20), (30, 60), (70, 100)]
    # idle inside hydro.udf:* : 15..20, 35..60, 70..75
    assert program.idle_in(s, SPANS, "hydro.udf:") == pytest.approx(35e-9)
    # idle inside the query: 5..20, 30..60, 70..95
    assert program.idle_in(s, SPANS, "hydro.query") == pytest.approx(70e-9)
    assert program.idle_in(s, SPANS, "hydro.udf:", "hydro.eddy:") == pytest.approx(40e-9)
    gaps = dict(program.idle_by_span(s, SPANS))
    # 0..5 none; 5..10 query (Q alone, depth 1); 10..15 eval (depth 1 on
    # Q and W: split); 15..20 prep (depth 2); 30..35 eval/query split;
    # 35..60 launch (depth 2); 70..75 launch; 75..80 eval/query split;
    # 80..85 query; 85..90 query and route split (both depth 1); 90..95
    # query; 95..100 none
    assert gaps[program.NO_SPAN] == pytest.approx(10e-9)
    assert gaps["hydro.udf:prep"] == pytest.approx(5e-9)
    assert gaps["hydro.udf:launch"] == pytest.approx(30e-9)
    assert gaps["hydro.worker:eval"] == pytest.approx(7.5e-9)
    assert gaps["hydro.query"] == pytest.approx(5e-9 + 7.5e-9 + 5e-9 + 2.5e-9 + 5e-9)
    assert gaps["hydro.eddy:route"] == pytest.approx(2.5e-9)
    assert sum(gaps.values()) == pytest.approx(s.idle_s())


def test_idle_line_sets_program_spans_beside_the_benchmarks():
    s = tr.TraceSummary(_events(), (0, 100))
    line = program.idle_line(s, SPANS, 1234)
    assert line.startswith("program idle: trace_bytes=1234 program_spans=6 ")
    # cb.udf 10..80 holds idle 10..20, 30..60, 70..80: 50%; hydro.udf 35%
    assert "udf_idle_pct.cb=50.0 udf_idle_pct.hydro=35.0 query_idle_pct=70.0 " in line
    assert dict(json.loads(line[line.index("[["):]))["hydro.udf:launch"] == pytest.approx(30e-9)
    assert program.idle_by_span(s, []) == [[program.NO_SPAN, pytest.approx(s.idle_s())]]


def test_counters_absent_from_the_program_read_nothing():
    """A program whose reports lack a counter reads None, not zero."""
    report = types.SimpleNamespace(finished_at=2.0, routing={}, stats={})
    record = types.SimpleNamespace(report=report, done=True, submitted=1.0, rows=4)
    run = Run(records=[record], setup_s=1.0, work={}, peaks=None, trace=None)
    for m in spec.load_benchmark()["per_layer"]:
        if m["source"] != "device_trace" and m["name"] != "queue_ms_p95":
            assert spec.metric_reader(m["name"]).read(run) is None, m["name"]


def test_traced_cpu_window_holds_program_spans(tmp_path):
    import jax

    from repro.core import Predicate, UDF, make_batch
    from repro.launch.serve import QueryService

    udf = UDF("odd", fn=lambda d: d["x"] % 2 == 1, columns=("x",))
    pred = Predicate("odd", udf, compare=lambda o: o)
    ids = np.arange(32)
    batches = [make_batch({"x": ids[i:i + 8]}, ids[i:i + 8]) for i in range(0, 32, 8)]
    jax.profiler.start_trace(str(tmp_path))
    try:
        with QueryService() as svc:
            svc.submit([pred], iter(batches), qid="traced-q").result(timeout=60)
    finally:
        jax.profiler.stop_trace()
    spans = program.read_spans(str(tmp_path))
    names = {e[1] for e in spans}
    assert {"hydro.query", "hydro.service:dispatch", "hydro.source",
            "hydro.eddy:route", "hydro.worker:eval", "hydro.udf:call",
            "hydro.udf:prep"} <= names
    assert all(e[0] == "pspan" and e[2] <= e[3] and e[5] == "traced-q" for e in spans)
    threads = {e[1]: e[4] for e in spans}
    assert threads["hydro.query"] != threads["hydro.worker:eval"]
    assert threads["hydro.worker:eval"] == threads["hydro.udf:prep"]
    # the benchmark's own reading of the trace keeps none of them
    assert not [e for e in tr.read_xplane(str(tmp_path)) if e[1].startswith("hydro.")]


# the metrics that read the program's counters, each in the cells it lists
COUNTERS = ["dispatch_spins_per_s.rows", "dispatch_spins_per_s.crops",
            "dispatch_spins_per_s.query", "route_us_per_batch.crops",
            "worker_wait_ms_mean.rows", "token_pad_x.rows", "lower_ms_per_batch.crops"]


def _counters_of(cell):
    return [m["name"] for m in spec.metrics_of(spec.load_benchmark(), "per_layer", cell)
            if m["name"] in COUNTERS]


@pytest.mark.parametrize("cell", [w["name"] for w in spec.load_benchmark()["workloads"]])
def test_rehearsal_reads_the_program_counters(cell, capsys):
    """Each counter metric reads a number in a traced CPU rehearsal of the
    cells it lists."""
    out = run_cell(cell, capsys, trace=1)
    assert out["correct"], out["check"]
    assert _counters_of(cell)
    for name in _counters_of(cell):
        assert isinstance(out["metrics"][name]["value"], float), name
    if cell.endswith(".scan"):
        assert out["metrics"]["token_pad_x.rows"]["value"] > 1.0


def test_traced_run_prints_the_program_idle_line(capsys):
    """``program.main`` runs a cell as the harness does and adds the
    ``program idle:`` line, read from the spans of the same trace."""
    cell = "uc1-lostdog.case1"
    mix = spec.workload(spec.load_benchmark(), cell)["traffic"]
    rc = program.main(["--workload", cell, "--seed", str(SEED), "--seconds", "1.5",
                       "--trace", "1"], small=True,
                      mix_override={**spec.traffic(mix), **TINY[mix]})
    assert rc == 0
    captured = capsys.readouterr()
    assert json.loads(captured.out.strip().splitlines()[-1])["correct"]
    line = next(x for x in captured.err.splitlines() if "program idle:" in x)
    ranked = dict(json.loads(line[line.index("[["):]))
    assert "hydro.udf:launch" in ranked and "hydro.query" in ranked
