"""Shared by the CPU rehearsals of the cells (``test_chipbench_*_cells``):
one run of a cell at tiny widths and sizes, Pallas in interpret mode, and
the ways of breaking the timed path underneath."""
import json
import sys
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(REPO / "src"), str(REPO)]

from chipbench import harness, spec  # noqa: E402
from repro.core import executor as executor_mod  # noqa: E402
from repro.core import udf as udf_mod  # noqa: E402

SEED = 2 ** 31 + 11
TINY = {
    "scan": {"query_rows": {"fixed": 80}, "capacity_rows_per_s": 2000},
    "interactive": {"query_rows": {"loguniform": [16, 96]}, "rate_qps": 2.0},
    "case1": {"query_rows": {"fixed": 32}, "capacity_rows_per_s": 2000},
    "case2": {"query_rows": {"fixed": 32}, "capacity_rows_per_s": 2000},
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
def drop_half(monkeypatch):
    """Leave out half of every routing batch the executor emits."""
    run = executor_mod.AQPExecutor.run

    def halved(self, source):
        for b in run(self, source):
            yield b.filter(np.arange(b.rows) % 2 == 1)

    monkeypatch.setattr(executor_mod.AQPExecutor, "run", halved)
