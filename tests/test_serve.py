"""QueryService (launch/serve.py): the always-on multi-tenant layer —
admission control, priority/deadline dispatch, cancellation, name-conflict
serialization, the cross-query live-prior channel, per-query QueryReport
telemetry, and the ``_service`` snapshot key contract."""
import threading
import time
from collections import Counter

import numpy as np
import pytest

from repro.core import AQPExecutor, Predicate, UDF, make_batch
from repro.core.faults import FaultConfig
from repro.core.statstore import StatsStore, fingerprint_of
from repro.launch.serve import (
    AdmissionError,
    QueryHandle,
    QueryReport,
    QueryService,
)


def _pred(name, *, keep_mod=2, sleep=0.0, fingerprint=None):
    """Keeps rows whose id is NOT divisible by ``keep_mod``."""

    def fn(d):
        if sleep:
            time.sleep(sleep)
        return d["x"].astype(np.int64) % keep_mod != 0

    udf = UDF(name + "_udf", fn=fn, columns=("x",), bucket=False,
              fingerprint=fingerprint)
    return Predicate(name, udf, compare=lambda o: o.astype(bool))


def _batches(ids, per=8):
    ids = np.asarray(ids, np.int64)
    return [make_batch({"x": ids[i:i + per].astype(np.float64)},
                       ids[i:i + per])
            for i in range(0, len(ids), per)]


def _expected(ids, keep_mod):
    return Counter(int(i) for i in ids if i % keep_mod != 0)


_EXEC_KW = dict(max_workers=2, warmup=False)


# --------------------------------------------------------------------------- #
# Submit / await / report
# --------------------------------------------------------------------------- #
def test_submit_and_result_exact_multiset():
    ids = np.arange(64)
    with QueryService(max_concurrent=2) as svc:
        h = svc.submit([_pred("p0", keep_mod=3)], iter(_batches(ids)),
                       **_EXEC_KW)
        rep = h.result(timeout=30)
    assert rep.state == "DONE" and h.done()
    assert Counter(map(int, rep.row_ids)) == _expected(ids, 3)
    assert rep.rows == sum(_expected(ids, 3).values())
    assert rep.batches == len(h.output)
    assert rep.queue_time_s >= 0 and rep.eval_time_s > 0
    assert rep.deadline_met is None            # no deadline given
    assert rep.board_predicates == ("p0",)     # only its OWN predicate
    assert "p0" in rep.cache_hit_rates
    assert rep.routing and rep.reverify is None


def test_service_snapshot_counters():
    with QueryService(max_concurrent=1) as svc:
        svc.submit([_pred("p0")], iter(_batches(np.arange(16))),
                   **_EXEC_KW).result(timeout=30)
        snap = svc.snapshot()
    assert snap["submitted"] == 1 and snap["completed"] == 1
    assert snap["pending"] == 0 and snap["running"] == 0
    assert snap["rejected"] == 0 and snap["failed"] == 0
    assert "arbiter" in snap and "rebalances" in snap["arbiter"]


def test_failed_query_raises_and_keeps_report():
    def boom(d):
        raise ValueError("kaboom")

    udf = UDF("b_udf", fn=boom, columns=("x",), bucket=False)
    bad = Predicate("pb", udf, compare=lambda o: o.astype(bool))
    with QueryService(max_concurrent=1) as svc:
        h = svc.submit([bad], iter(_batches(np.arange(8))), **_EXEC_KW)
        with pytest.raises(RuntimeError, match="kaboom"):
            h.result(timeout=30)
    assert h.report.state == "FAILED"
    assert svc.snapshot()["failed"] == 1


def test_report_names_degraded_predicates():
    def broken(d):
        raise RuntimeError("compiled path broken")

    udf = UDF("d_udf", fn=broken, columns=("x",), bucket=False,
              fallback_fn=lambda d: d["x"] >= 0)
    pd = Predicate("pd", udf, compare=lambda o: o.astype(bool))
    cfg = FaultConfig(mode="degrade", max_attempts=4, degrade_after=2,
                      backoff_base_s=0.0, jitter=0.0)
    with QueryService(max_concurrent=1) as svc:
        rep = svc.submit([pd], iter(_batches(np.arange(8))), on_fault=cfg,
                         **_EXEC_KW).result(timeout=30)
    assert rep.state == "DONE"
    assert rep.faults["degraded"] == ["pd"]
    assert rep.faults["failures"] == 2
    assert Counter(map(int, rep.row_ids)) == Counter(range(8))


# --------------------------------------------------------------------------- #
# Admission control / priority / deadline / cancel
# --------------------------------------------------------------------------- #
def _blocker(svc, name="blk", batches=6, sleep=0.05):
    """Submit a slow query and wait until it is RUNNING."""
    ids = np.arange(batches * 8)
    h = svc.submit([_pred(name, sleep=sleep)], iter(_batches(ids)),
                   **_EXEC_KW)
    deadline = time.monotonic() + 10
    while h.state == "PENDING" and time.monotonic() < deadline:
        time.sleep(0.005)
    assert h.state == "RUNNING"
    return h


def test_admission_rejects_when_pending_full():
    with QueryService(max_concurrent=1, max_pending=1) as svc:
        blk = _blocker(svc)
        q2 = svc.submit([_pred("p2")], iter(_batches(np.arange(8))),
                        **_EXEC_KW)
        with pytest.raises(AdmissionError, match="pending queue full"):
            svc.submit([_pred("p3")], iter(_batches(np.arange(8))),
                       **_EXEC_KW)
        assert svc.snapshot()["rejected"] == 1
        assert blk.result(timeout=30).state == "DONE"
        assert q2.result(timeout=30).state == "DONE"


def test_priority_orders_pending_dispatch():
    with QueryService(max_concurrent=1, max_pending=8) as svc:
        blk = _blocker(svc)
        lo = svc.submit([_pred("lo")], iter(_batches(np.arange(8))),
                        priority=1.0, **_EXEC_KW)
        hi = svc.submit([_pred("hi")], iter(_batches(np.arange(8))),
                        priority=5.0, **_EXEC_KW)
        blk.result(timeout=30)
        lo_rep = lo.result(timeout=30)
        hi_rep = hi.result(timeout=30)
    assert hi_rep.started_at < lo_rep.started_at   # hi jumped the queue


def test_pending_query_expires_at_deadline():
    with QueryService(max_concurrent=1, max_pending=8) as svc:
        blk = _blocker(svc, batches=8)
        doomed = svc.submit([_pred("dd")], iter(_batches(np.arange(8))),
                            deadline_s=0.05, **_EXEC_KW)
        rep = doomed.result(timeout=10)            # expired, not run
        assert rep.state == "EXPIRED"
        assert rep.deadline_met is False
        assert rep.started_at is None and rep.rows == 0
        assert svc.snapshot()["expired"] == 1
        blk.result(timeout=30)


def test_deadline_met_recorded_on_finish():
    with QueryService(max_concurrent=1) as svc:
        h = svc.submit([_pred("p0")], iter(_batches(np.arange(16))),
                       deadline_s=60.0, **_EXEC_KW)
        assert h.result(timeout=30).deadline_met is True


def test_cancel_pending_and_running():
    with QueryService(max_concurrent=1, max_pending=8) as svc:
        blk = _blocker(svc, batches=10)
        pend = svc.submit([_pred("pc")], iter(_batches(np.arange(8))),
                          **_EXEC_KW)
        assert pend.cancel()
        assert pend.result(timeout=10).state == "CANCELLED"
        assert blk.cancel()                        # running: stops early
        rep = blk.result(timeout=30)
        assert rep.state == "CANCELLED"
        assert rep.batches < 10                    # did not finish the scan
        assert svc.snapshot()["cancelled"] == 2
    assert not blk.cancel()                        # already terminal


def test_closed_service_rejects_submit():
    svc = QueryService(max_concurrent=1)
    svc.close()
    with pytest.raises(AdmissionError, match="closed"):
        svc.submit([_pred("p0")], iter(_batches(np.arange(8))), **_EXEC_KW)


# --------------------------------------------------------------------------- #
# Name conflicts + the cross-query live-prior channel
# --------------------------------------------------------------------------- #
def test_same_predicate_name_serialized_not_crosswired():
    """Arbiter registrations are name-keyed: two queries sharing a
    predicate NAME must run one-after-the-other, both correctly."""
    ids_a, ids_b = np.arange(32), np.arange(100, 132)
    with QueryService(max_concurrent=2) as svc:
        h1 = svc.submit([_pred("shared", sleep=0.02)],
                        iter(_batches(ids_a)), **_EXEC_KW)
        h2 = svc.submit([_pred("shared")], iter(_batches(ids_b)),
                        **_EXEC_KW)
        r1, r2 = h1.result(timeout=60), h2.result(timeout=60)
    assert r1.state == "DONE" and r2.state == "DONE"
    assert Counter(map(int, r1.row_ids)) == _expected(ids_a, 2)
    assert Counter(map(int, r2.row_ids)) == _expected(ids_b, 2)
    # serialized: the second never overlapped the first
    first, second = sorted((r1, r2), key=lambda r: r.started_at)
    assert second.started_at >= first.finished_at


def test_live_priors_flow_between_concurrent_queries():
    """Query B admitted WHILE query A is mid-flight: A's live board is
    folded into the shared store before B warm-starts, so B's profile
    channel has A's fingerprint before A ever finishes."""
    fp = "kernel|shared-probe|cmv=1"
    with QueryService(max_concurrent=2) as svc:
        a = svc.submit([_pred("qa", sleep=0.03, fingerprint=fp)],
                       iter(_batches(np.arange(80))), **_EXEC_KW)
        deadline = time.monotonic() + 10
        while a.report.batches < 2 and time.monotonic() < deadline:
            time.sleep(0.005)
        assert a.report.batches >= 2           # A is mid-flight, profiled
        b = svc.submit([_pred("qb", fingerprint=fp)],
                       iter(_batches(np.arange(8))), **_EXEC_KW)
        b.result(timeout=30)
        rec = svc.store.get(fp)
        assert rec is not None                 # folded from A's LIVE board
        a.result(timeout=60)
    assert svc.store.get(fp)["cost_per_row"] > 0


def test_finished_query_profile_persists_in_store():
    p = _pred("p0")
    with QueryService(max_concurrent=1) as svc:
        svc.submit([p], iter(_batches(np.arange(32))),
                   **_EXEC_KW).result(timeout=30)
        assert svc.store.get(fingerprint_of(p)) is not None


# --------------------------------------------------------------------------- #
# The _service snapshot key contract
# --------------------------------------------------------------------------- #
def test_standalone_executor_service_key_unmanaged():
    ex = AQPExecutor([_pred("p0")], **_EXEC_KW)
    ex.collect(iter(_batches(np.arange(8))))
    assert ex.stats_snapshot()["_service"] == {"managed": False}


def test_managed_executor_service_key_identifies_query():
    ex = AQPExecutor([_pred("p0")], query="q7", **_EXEC_KW)
    ex.service_info = {"managed": True, "query": "q7",
                       "priority": 2.0, "deadline_s": 5.0}
    ex.collect(iter(_batches(np.arange(8))))
    svc = ex.stats_snapshot()["_service"]
    assert svc["managed"] is True and svc["query"] == "q7"
    assert svc["priority"] == 2.0 and svc["deadline_s"] == 5.0


# --------------------------------------------------------------------------- #
# Multi-tenant isolation under real concurrency
# --------------------------------------------------------------------------- #
def test_concurrent_tenants_exact_multisets_and_no_board_leakage():
    """Four queries in flight on one shared arbiter: every report carries
    exactly its own predicate's board entries and its exact row-id
    multiset — no cross-query statistics or row leakage."""
    specs = [(f"t{i}m{m}", m, np.arange(i * 1000, i * 1000 + 96))
             for i, m in enumerate((2, 3, 5, 7))]
    with QueryService(max_concurrent=4, max_pending=8) as svc:
        handles = [
            (name, m, ids,
             svc.submit([_pred(name, keep_mod=m)], iter(_batches(ids)),
                        **_EXEC_KW))
            for name, m, ids in specs
        ]
        reports = [(name, m, ids, h.result(timeout=60))
                   for name, m, ids, h in handles]
    for name, m, ids, rep in reports:
        assert rep.state == "DONE"
        assert rep.board_predicates == (name,), rep.board_predicates
        assert Counter(map(int, rep.row_ids)) == _expected(ids, m)
