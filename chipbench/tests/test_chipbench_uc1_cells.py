"""CPU rehearsal of the uc1 configuration under each of its mixes; the
check fails on a broken timed path and on the control."""
import pytest

from chipbench import spec
from chipbench_cellrun import (  # noqa: F401  (fixtures by name)
    break_answers, check_rehearsal, constant_answers, drop_half, run_cell)

CELL = 'uc1-lostdog.case1'


def _with_limit(config, key, limit):
    config["sizes"]["limits"] = dict(config["sizes"]["limits"], **{key: limit})
    return config


@pytest.mark.parametrize("mix", ['case1', 'case2'])
def test_uc1_rehearsal(mix, capsys):
    check_rehearsal(run_cell(f"uc1-lostdog.{mix}", capsys, trace=int(mix == 'case2')))


def test_uc1_queries_of_any_size(capsys):
    """Query sizes follow the mix's size law, not one fixed size."""
    check_rehearsal(run_cell(CELL, capsys, query_rows={"loguniform": [8, 40]}))


@pytest.mark.parametrize("fault", ["break_answers", "constant_answers", "drop_half"])
def test_uc1_broken_path_is_not_correct(fault, request, capsys):
    request.getfixturevalue(fault)
    out = run_cell(CELL, capsys)
    assert not out["correct"], out["check"]


def test_uc1_control_is_not_correct(capsys, monkeypatch):
    """The control (the reference in the next lower precision, in the
    program's place) reads at least three times what the program reads at
    this size, and a limit set between the two refuses it."""
    program = run_cell(CELL, capsys)["check"]['hist_gap_px']["value"]
    control = run_cell(CELL, capsys, control=True)["check"]['hist_gap_px']["value"]
    assert control >= 3 * program
    limit = (program * control) ** 0.5 if program > 0 else control / 3
    config = spec.config
    monkeypatch.setattr(spec, "config", lambda *a: _with_limit(config(*a), 'hist_gap_px', limit))
    out = run_cell(CELL, capsys, control=True)
    assert not out["correct"], out["check"]
