"""Shared by the CPU rehearsals of the cells (``test_chipbench_*_cells``):
one run of a cell at tiny widths and sizes, Pallas in interpret mode, and
the ways of breaking the timed path underneath."""
import json
import os
import sys
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(REPO / "src"), str(REPO)]

from chipbench import harness, loadgen, spec  # noqa: E402
from repro.core import executor as executor_mod  # noqa: E402
from repro.core import udf as udf_mod  # noqa: E402

SEED = 2 ** 31 + 11
# Rows a closed-loop rehearsal plans a second of window. With UDFs that
# answer at once the tiny mixes run at about 12,000 crops/s (UC1) and
# 33,000 rows/s (scan) on a CPU host once the dispatcher waits instead of
# spinning, the ceiling of everything but the UDFs: a faster program still
# finds fresh rows. Planned rows cost set-up little (0.05 s at this rate).
HEADROOM_ROWS_PER_S = 200_000
TINY = {
    "scan": {"query_rows": {"fixed": 80}, "capacity_rows_per_s": HEADROOM_ROWS_PER_S},
    "interactive": {"query_rows": {"loguniform": [16, 96]}, "rate_qps": 2.0},
    "case1": {"query_rows": {"fixed": 32}, "capacity_rows_per_s": HEADROOM_ROWS_PER_S},
    "case2": {"query_rows": {"fixed": 32}, "capacity_rows_per_s": HEADROOM_ROWS_PER_S},
}


def run_cell(cell, capsys, *, trace=0, seconds=1.5, control=False, **override):
    """One small run of ``cell`` at TINY sizes, with the mix's keys
    ``override`` replaced on top."""
    mix = spec.workload(spec.load_benchmark(), cell)["traffic"]
    rc = harness.main(
        ["--workload", cell, "--seed", str(SEED), "--seconds", str(seconds),
         "--trace", str(trace)],
        small=True, control=control,
        mix_override={**spec.traffic(mix), **TINY[mix], **override})
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def planned_queries(cell, seconds=1.5):
    """Queries the TINY plan of ``cell`` holds for a window of ``seconds``."""
    mix = spec.workload(spec.load_benchmark(), cell)["traffic"]
    return len(loadgen.plan({**spec.traffic(mix), **TINY[mix]}, seconds).sizes)


def traced_rehearsal(cell, barrier):
    """One traced run of ``cell`` at TINY sizes, for a process of its own:
    once its trace has started it waits at ``barrier`` for the other runs'
    traces, so that all are open at once. Returns this process's id, the
    directories it read a trace from and its result line."""
    import contextlib
    import io

    import jax
    from chipbench import trace as tr

    start, read, dirs = jax.profiler.start_trace, tr.read_xplane, []

    def start_then_wait(log_dir, **kwargs):
        start(log_dir, **kwargs)
        barrier.wait(timeout=300)

    def read_recorded(log_dir):
        dirs.append(log_dir)
        return read(log_dir)

    jax.profiler.start_trace, tr.read_xplane = start_then_wait, read_recorded
    mix = spec.workload(spec.load_benchmark(), cell)["traffic"]
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = harness.main(
            ["--workload", cell, "--seed", str(SEED), "--seconds", "1.5", "--trace", "1"],
            small=True, mix_override={**spec.traffic(mix), **TINY[mix]})
    assert rc == 0
    return os.getpid(), dirs, json.loads(out.getvalue().strip().splitlines()[-1])


def check_rehearsal(out):
    assert out["correct"], out["check"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert list(out)[-1] == "check"
    if "breakdown" not in out:
        assert "setup_s" in out["metrics"] and len(out["metrics"]) >= 2


@pytest.fixture
def break_answers(monkeypatch):
    """Alter every UDF answer where it is produced: a label moves to the
    next class, a score rises far enough that every row passes."""
    call = udf_mod.UDF.__call__

    def altered(self, data):
        out = np.asarray(call(self, data))
        return out + 1 if out.dtype.kind in "iu" else out + 100.0

    monkeypatch.setattr(udf_mod.UDF, "__call__", altered)


@pytest.fixture
def constant_answers(monkeypatch):
    """Every UDF answers the same whatever its input: label 0, score 0."""
    call = udf_mod.UDF.__call__

    def constant(self, data):
        return np.zeros_like(np.asarray(call(self, data)))

    monkeypatch.setattr(udf_mod.UDF, "__call__", constant)


@pytest.fixture
def instant_answers(monkeypatch):
    """Every UDF answers at once, label 0 or score 0, without running."""

    def instant(self, data):
        return np.zeros(len(next(iter(data.values()))), np.int64)

    monkeypatch.setattr(udf_mod.UDF, "__call__", instant)


@pytest.fixture
def drop_half(monkeypatch):
    """Leave out half of every routing batch the executor emits."""
    run = executor_mod.AQPExecutor.run

    def halved(self, source):
        for b in run(self, source):
            yield b.filter(np.arange(b.rows) % 2 == 1)

    monkeypatch.setattr(executor_mod.AQPExecutor, "run", halved)
