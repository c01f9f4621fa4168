"""CPU rehearsal of the review configuration under each of its mixes; the
check fails on a broken timed path and on the control."""
import pytest

from chipbench import spec
from chipbench_cellrun import (  # noqa: F401  (fixtures by name)
    break_answers, check_rehearsal, constant_answers, drop_half, run_cell)

CELL = 'review-smollm135m.scan'


def _with_limit(config, key, limit):
    config["sizes"]["limits"] = dict(config["sizes"]["limits"], **{key: limit})
    return config


@pytest.mark.parametrize("mix", ['scan', 'interactive'])
def test_review_rehearsal(mix, capsys):
    check_rehearsal(run_cell(f"review-smollm135m.{mix}", capsys,
                             trace=int(mix == 'interactive')))


@pytest.mark.parametrize("fault", ["break_answers", "drop_half"])
def test_review_broken_path_is_not_correct(fault, request, capsys):
    request.getfixturevalue(fault)
    out = run_cell(CELL, capsys)
    assert not out["correct"], out["check"]


def test_review_constant_scores_are_not_correct(capsys, monkeypatch, request):
    """A UDF that scores every row 0 returns no rows, so ``rows_wrong``
    cannot see it: ``score_gap`` must. At these tiny widths the scores are
    small, so the limit is set as the chip's was, between the program's
    reading and the fault's; at the published widths the reference scores
    spread far wider than ``score_gap``'s limit (see PERF.md)."""
    program = run_cell(CELL, capsys)["check"]['score_gap']["value"]
    request.getfixturevalue("constant_answers")
    fault = run_cell(CELL, capsys)["check"]['score_gap']["value"]
    assert fault >= 3 * program
    limit = (program * fault) ** 0.5 if program > 0 else fault / 3
    config = spec.config
    monkeypatch.setattr(spec, "config", lambda *a: _with_limit(config(*a), 'score_gap', limit))
    out = run_cell(CELL, capsys)
    assert not out["correct"], out["check"]


def test_review_control_is_not_correct(capsys, monkeypatch):
    """The control (the reference in the next lower precision, in the
    program's place) reads at least three times what the program reads at
    this size, and a limit set between the two refuses it."""
    program = run_cell(CELL, capsys)["check"]['score_gap']["value"]
    control = run_cell(CELL, capsys, control=True)["check"]['score_gap']["value"]
    assert control >= 3 * program
    limit = (program * control) ** 0.5 if program > 0 else control / 3
    config = spec.config
    monkeypatch.setattr(spec, "config", lambda *a: _with_limit(config(*a), 'score_gap', limit))
    out = run_cell(CELL, capsys, control=True)
    assert not out["correct"], out["check"]
