"""The reduction from trace events to busy, idle, kernel and gap times, and
traced runs at once keeping their traces apart."""
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(REPO / "src"), str(REPO)]

from chipbench import harness  # noqa: E402
from chipbench import trace as tr  # noqa: E402
from chipbench_cellrun import traced_rehearsal  # noqa: E402

SAMPLE = Path(__file__).with_name("trace_sample.json.gz")


def test_interval_arithmetic():
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 8), (9, 9)]) == [(0, 3), (5, 8)]
    assert tr.clip([(0, 3), (5, 8)], (2, 6)) == [(2, 3), (5, 6)]
    assert tr.complement([(2, 3), (5, 6)], (0, 10)) == [(0, 2), (3, 5), (6, 10)]
    assert tr.intersect([(0, 4), (6, 9)], [(3, 7), (8, 20)]) == [(3, 4), (6, 7), (8, 9)]
    assert tr.subtract([(0, 10), (12, 15)], [(2, 3), (5, 7), (9, 13)]) == [
        (0, 2), (3, 5), (7, 9), (13, 15)]
    assert tr.subtract([(0, 4)], []) == [(0, 4)]


def _events():
    # window 0..100 ns; ops on one chip; spans on two host threads
    return [
        ("span", "cb.window", 0, 100),
        ("op0", "flash_attention.6", 10, 30),
        ("op0", "fusion.1", 25, 40),          # overlaps the kernel
        ("op0", "hsv_color", 60, 70),
        ("op0", "fusion.1", 95, 110),         # runs past the window
        ("span", "cb.udf:LLM", 5, 50),
        ("span", "cb.udf:LLM", 55, 58),
        ("span", "cb.udf:Color", 45, 75),     # overlaps the first udf span
        ("span", "cb.source:review", 74, 90),
    ]


def test_summary_on_hand_worked_events():
    s = tr.TraceSummary(_events(), (0, 100))
    assert s.chips == 1
    assert s.busy_union == [(10, 40), (60, 70), (95, 100)]
    assert s.busy_s == pytest.approx(45e-9)
    assert s.idle == [(0, 10), (40, 60), (70, 95)]
    assert s.idle_s() == pytest.approx(55e-9)
    assert s.kernel_s("flash_attention") == pytest.approx(20e-9)
    assert s.kernel_s("fusion") == pytest.approx(20e-9)
    # udf spans cover 5..50, 45..75 -> 5..75; idle inside: 5..10, 40..60, 70..75
    assert s.idle_in("udf") == pytest.approx(30e-9)
    # udf or source cover 5..90; idle outside: 0..5, 90..95
    assert s.idle_outside(["udf", "source"]) == pytest.approx(10e-9)
    gaps = dict(s.idle_gaps())
    # LLM alone 5..10, 40..45; both 45..50, 55..58 (split evenly);
    # Color alone 50..55, 58..60, 70..75
    assert gaps["udf:LLM"] == pytest.approx(14e-9)
    assert gaps["udf:Color"] == pytest.approx(16e-9)
    assert gaps["source:review"] == pytest.approx(15e-9)  # 75..90
    assert gaps["host:other"] == pytest.approx(10e-9)
    assert sum(gaps.values()) == pytest.approx(s.idle_s())
    ops = dict(s.device_ops())
    assert ops["fusion.1"] == pytest.approx(20e-9)
    assert ops["flash_attention.6"] == pytest.approx(20e-9)


def test_no_device_reads_nothing():
    s = tr.TraceSummary([e for e in _events() if e[0] == "span"], (0, 100))
    assert s.chips == 0 and s.busy_s == 0.0


def test_recorded_chip_trace():
    """A slice of a traced run of the scan cell on one v5e."""
    events = tr.load_events(str(SAMPLE))
    window = [(a, b) for k, n, a, b in events if n == "cb.window"][0]
    s = tr.TraceSummary(events, window)
    assert s.chips == 1
    assert 0 < s.busy_s <= s.window_s
    assert s.idle_s() == pytest.approx(s.window_s - s.busy_s, rel=1e-9)
    assert 0 < s.kernel_s("flash_attention") < s.busy_s
    gaps = s.idle_gaps(top=100)
    assert sum(g for _, g in gaps) == pytest.approx(s.idle_s(), rel=1e-9)
    assert s.idle_in("udf") + s.idle_outside(["udf"]) == pytest.approx(s.idle_s())


def test_traced_rehearsals_at_once_keep_their_own_traces():
    """Two traced rehearsals in one process pool, their traces open at
    once: each reads its trace from a directory of its own process and
    removes only that one."""
    import multiprocessing
    from concurrent.futures import ProcessPoolExecutor

    ctx = multiprocessing.get_context("spawn")
    with ctx.Manager() as manager, ProcessPoolExecutor(2, mp_context=ctx) as pool:
        barrier = manager.Barrier(2)
        futures = [pool.submit(traced_rehearsal, "review-smollm135m.scan", barrier)
                   for _ in range(2)]
        runs = [f.result(timeout=300) for f in futures]
    assert runs[0][0] != runs[1][0]
    for pid, dirs, out in runs:
        assert dirs == [str(harness.TRACE_ROOT / str(pid))]
        assert not Path(dirs[0]).exists()
        assert out["correct"], out["check"]
        assert "breakdown" in out and out["device"]["window_s"] > 0
