"""Spans and counters inside the query path: the dispatcher's deferrals,
eddy routing time, worker-queue wait, the LLM scorer's real and launched
tokens, lowering charged to the executor whose thread lowered, and answers
that do not change while a profiler trace is taken."""
import threading
import time
from collections import Counter

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import AQPExecutor, Predicate, UDF, make_batch
from repro.kernels import launch, ops
from repro.launch.serve import QueryService, build_llm_udf
from repro.udfs import color_predicate, planted_classifier


def _pred(name, *, sleep=0.0):
    """Keeps rows whose id is odd."""

    def fn(d):
        if sleep:
            time.sleep(sleep)
        return d["x"].astype(np.int64) % 2 != 0

    udf = UDF(name + "_udf", fn=fn, columns=("x",), bucket=False)
    return Predicate(name, udf, compare=lambda o: o.astype(bool))


def _batches(ids, per=8):
    ids = np.asarray(ids, np.int64)
    return [make_batch({"x": ids[i:i + per].astype(np.float64)}, ids[i:i + per])
            for i in range(0, len(ids), per)]


def test_waiting_same_name_query_counts_deferrals():
    """The second query shares the first one's predicate name, so every
    dispatcher pass while the first runs defers it once."""
    with QueryService(max_concurrent=2) as svc:
        first = svc.submit([_pred("p", sleep=0.02)], iter(_batches(np.arange(64))),
                           max_workers=1, warmup=False)
        second = svc.submit([_pred("p")], iter(_batches(np.arange(64, 96))),
                            max_workers=1, warmup=False)
        r1, r2 = first.result(timeout=60), second.result(timeout=60)
    assert r1.dispatch_deferrals == 0
    assert r2.dispatch_deferrals > 0
    assert r2.started_at >= r1.finished_at - 1e-3  # it did wait


@pytest.mark.parametrize("warmup", [False, True])
def test_routed_batches_and_worker_waits(warmup):
    """``routed`` counts every routing decision: one per evaluation, plus
    the warmup's circulations; each evaluation was dequeued once, and its
    wait is a non-negative count of nanoseconds."""
    preds = [_pred("a"), _pred("b")]
    ex = AQPExecutor(preds, max_workers=2, warmup=warmup)
    out = ex.collect(iter(_batches(np.arange(96))))
    snap = ex.stats_snapshot()
    routing = snap["_routing"]
    evaluations = sum(snap[p.name]["batches"] for p in preds)
    assert sum(snap[p.name]["dequeued"] for p in preds) == evaluations
    assert routing["routed"] == evaluations + routing["circulations"]
    assert routing["route_ns"] > 0
    for p in preds:
        assert snap[p.name]["queue_wait_ns"] >= 0
    assert Counter(int(i) for b in out for i in b.row_ids) == Counter(range(1, 96, 2))


def test_coalesced_batches_are_each_dequeued_once():
    p = _pred("a", sleep=0.005)
    ex = AQPExecutor([p], max_workers=1, warmup=False, coalesce=4)
    ex.collect(iter(_batches(np.arange(128), per=4)))
    snap = ex.stats_snapshot()["a"]
    assert snap["dequeued"] == snap["batches"] == 32
    assert snap["queue_wait_ns"] >= 0


def test_llm_scorer_counts_real_and_launched_tokens():
    """Three rows of 5, 2 and 9 real tokens in a 16-wide column launch as
    a bucket of 4 rows: 16 real tokens, 64 launched."""
    cfg = get_config("smollm-135m").reduce_for_smoke()
    udf = build_llm_udf(cfg=cfg)
    pred = Predicate("LLM_is_food", udf, compare=lambda s: s > 0)
    tokens = np.zeros((3, 16), np.int32)
    for row, n in enumerate((5, 2, 9)):
        tokens[row, :n] = np.arange(1, n + 1)
    ex = AQPExecutor([pred], max_workers=1, warmup=False)
    ex.collect([make_batch({"tokens": tokens}, np.arange(3))])
    entry = ex.stats_snapshot()["LLM_is_food"]
    assert entry["tokens_real"] == 16
    assert entry["tokens_launched"] == 4 * 16
    assert entry["dequeued"] == 1


def test_eager_launch_lowering_charged_to_its_executor_only():
    """An eager interpret-mode Pallas launch lowers on the thread that
    makes it, and the lowering is charged to that thread's executor."""
    ex_a = AQPExecutor([_pred("a")])
    ex_b = AQPExecutor([_pred("b")])
    crops = np.random.default_rng(0).uniform(0, 255, (2, 16, 16, 3)).astype(np.float32)

    def launch_on_a():
        with launch.launch_context(ex_a._launch_token):
            jax.block_until_ready(ops.hsv_color_classify(crops, impl="pallas",
                                                         block_rows=16))

    t = threading.Thread(target=launch_on_a)
    t.start()
    t.join(timeout=120)
    assert not t.is_alive()
    a, b = (ex.stats_snapshot()["_compile"] for ex in (ex_a, ex_b))
    ex_a.shutdown()
    ex_b.shutdown()
    assert a["lower_s"] > 0
    assert b == {"lower_s": 0.0, "compiles": 0, "cache_loads": 0}


def _lost_dog_answers(service, crops, breeds):
    preds = [planted_classifier("breed", 0, label_column="breed_gt",
                                pixel_column="crop"),
             color_predicate("black", size=16, name="color")]
    ids = np.arange(len(crops))
    batches = [make_batch({"crop": crops[i:i + 8], "breed_gt": breeds[i:i + 8]},
                          ids[i:i + 8]) for i in range(0, len(crops), 8)]
    report = service.submit(preds, iter(batches), max_workers=2).result(timeout=300)
    return sorted(map(int, report.row_ids)), report


def test_answers_identical_under_a_profiler_trace(tmp_path):
    rng = np.random.default_rng(1)
    crops = rng.uniform(0, 255, (16, 16, 16, 3)).astype(np.float32)
    crops[::2] *= 0.1                     # dark crops: these are black
    breeds = rng.integers(0, 2, 16)
    with QueryService() as svc:
        plain, _ = _lost_dog_answers(svc, crops, breeds)
        jax.profiler.start_trace(str(tmp_path))
        try:
            traced, report = _lost_dog_answers(svc, crops, breeds)
        finally:
            jax.profiler.stop_trace()
    assert traced == plain and plain
    assert report.compile["lower_s"] >= 0
    assert report.stats["color"]["dequeued"] >= 1
