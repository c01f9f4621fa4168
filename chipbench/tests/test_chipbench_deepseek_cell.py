"""CPU rehearsal of the DeepSeek-V2-Lite review cell at toy widths: the
run is correct, traced and untraced, its expert counters reach the result
line, and the check refuses the control and a broken path."""
import pytest

from chipbench import spec
from chipbench_cellrun import (  # noqa: F401  (fixtures by name)
    break_answers, check_rehearsal, run_cell)

CELL = "review-deepseek-v2-lite.scan"


@pytest.mark.parametrize("trace", [0, 1])
def test_deepseek_rehearsal(trace, capsys):
    out = run_cell(CELL, capsys, trace=trace)
    check_rehearsal(out)
    if trace:
        # the device metrics need a chip; the program's counters do not
        assert out["metrics"]["expert_pad_x.rows"]["value"] >= 1.0
        assert out["metrics"]["expert_load_x.rows"]["value"] >= 1.0


def test_deepseek_broken_path_is_not_correct(break_answers, capsys):
    out = run_cell(CELL, capsys)
    assert not out["correct"], out["check"]


def test_deepseek_limit_refuses_the_control():
    """The committed score_gap limit lies between the sound readings and
    the fp8 control's at the cell's own sizes, with room on both sides: it
    passes the program and refuses the control."""
    sizes = spec.config(spec.load_benchmark(), "review-deepseek-v2-lite")["sizes"]
    limit = sizes["limits"]["score_gap"]
    read = sizes["limit_readings"]["score_gap"]
    assert 1.5 * read["sound_max"] <= limit <= read["control_min"] / 1.5, read


def test_deepseek_control_is_not_correct(capsys, monkeypatch):
    """At toy widths too the fp8 control reads several times what the
    program reads, and a limit set between the two refuses it."""
    program = run_cell(CELL, capsys)["check"]["score_gap"]["value"]
    control = run_cell(CELL, capsys, control=True)["check"]["score_gap"]["value"]
    assert control >= 3 * program > 0
    limit = (program * control) ** 0.5
    config = spec.config

    def with_limit(*a):
        c = config(*a)
        c["sizes"]["limits"] = dict(c["sizes"]["limits"], score_gap=limit)
        return c

    monkeypatch.setattr(spec, "config", with_limit)
    assert run_cell(CELL, capsys)["correct"]
    out = run_cell(CELL, capsys, control=True)
    assert not out["correct"], out["check"]
