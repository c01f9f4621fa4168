"""Central-queue watermark + deadlock-prevention tests (§3.3)."""
import threading
import time

import pytest

from repro.core.queues import BoundedQueue, CentralQueue, ClosedError


def test_lambda_watermark_blocks_pull():
    q = CentralQueue(capacity=10, lam=0.3)  # pull limit = 3
    assert q.put_pull(1, timeout=0.05)
    assert q.put_pull(2, timeout=0.05)
    assert q.put_pull(3, timeout=0.05)
    assert not q.put_pull(4, timeout=0.05)  # watermark reached


def test_worker_reinsert_always_allowed():
    q = CentralQueue(capacity=10, lam=0.3)
    for i in range(3):
        q.put_pull(i, timeout=0.05)
    # workers may exceed the watermark freely (deadlock prevention)
    for i in range(7):
        q.put_worker(100 + i)
    assert len(q) == 10


def test_no_deadlock_under_full_cycle():
    """Producer at watermark + workers reinserting + consumer draining:
    the cycle must make progress (the paper's deadlock scenario)."""
    q = CentralQueue(capacity=6, lam=0.3)
    done = threading.Event()
    consumed = []

    def producer():
        for i in range(50):
            while not q.put_pull(i, timeout=0.02):
                pass
        done.set()

    def consumer():
        while not (done.is_set() and len(q) == 0):
            try:
                item = q.get(timeout=0.02)
            except TimeoutError:
                continue
            if isinstance(item, int) and item < 1000:
                q.put_worker(item + 1000)  # simulate worker reinsert
            else:
                consumed.append(item)

    t1 = threading.Thread(target=producer)
    t2 = threading.Thread(target=consumer)
    t1.start(); t2.start()
    t1.join(timeout=10)
    t2.join(timeout=10)
    assert done.is_set() and len(consumed) == 50


def test_pull_blocked_until_drained():
    q = CentralQueue(capacity=10, lam=0.3)
    for i in range(3):
        q.put_pull(i)
    ok = []

    def delayed_get():
        time.sleep(0.05)
        q.get()

    t = threading.Thread(target=delayed_get)
    t.start()
    ok.append(q.put_pull(99, timeout=1.0))  # unblocks after the get
    t.join()
    assert ok == [True]


def test_close_raises():
    q = CentralQueue()
    q.close()
    with pytest.raises(ClosedError):
        q.put_pull(1)
    with pytest.raises(ClosedError):
        q.get()


def test_close_drains_remaining():
    q = BoundedQueue(4)
    q.put(1); q.put(2)
    q.close()
    assert q.get() == 1 and q.get() == 2
    with pytest.raises(ClosedError):
        q.get()


def test_bounded_queue_capacity():
    q = BoundedQueue(2)
    assert q.try_put(1) and q.try_put(2)
    assert not q.try_put(3)
    q.get()
    assert q.try_put(3)


# --------------------------------------------------------------------------- #
# Sharded central queue: stealing, concurrency stress, close-while-waiting
# --------------------------------------------------------------------------- #
class _Item:
    """Carries a bid so the sharded queue can compute a home stripe."""

    def __init__(self, bid):
        self.bid = bid

    def __repr__(self):
        return f"_Item({self.bid})"


def test_sharded_get_steals_from_longest_sibling():
    q = CentralQueue(capacity=16, lam=1.0, shards=2)
    for i in range(4):
        q.put_worker(_Item(0))  # all on stripe 0 (bid % 2 == 0)
    # consumer 1's own stripe is empty: it must steal rather than time out
    got = q.get(timeout=0.5, shard=1)
    assert got.bid == 0
    assert q.steals == 1


def test_sharded_steal_vs_get_interleaving_no_loss_no_dup():
    """Two consumers racing their own stripes + steals against a producer:
    every item is consumed exactly once."""
    q = CentralQueue(capacity=8, lam=1.0, shards=2)
    N = 300
    consumed = [[], []]
    stop = threading.Event()

    def consumer(idx):
        while not (stop.is_set() and len(q) == 0):
            try:
                consumed[idx].append(q.get(timeout=0.02, shard=idx).bid)
            except TimeoutError:
                continue
            except ClosedError:
                break

    threads = [threading.Thread(target=consumer, args=(i,)) for i in (0, 1)]
    for t in threads:
        t.start()
    for i in range(N):  # skewed home stripes: ~2/3 of items land on stripe 0
        q.put_worker(_Item(i if i % 3 else 0))
    stop.set()
    for t in threads:
        t.join(timeout=10)
    assert sorted(consumed[0] + consumed[1]) == sorted(
        (i if i % 3 else 0) for i in range(N)
    )


def test_watermark_fairness_under_concurrency():
    """Worker reinserts are NEVER blocked by pull ingest pressure: with the
    pull parked at the watermark, concurrent worker reinserts all land
    immediately (the deadlock-prevention invariant, sharded edition)."""
    q = CentralQueue(capacity=10, lam=0.3, shards=2)  # pull limit = 3
    for i in range(3):
        assert q.put_pull(_Item(i), timeout=0.1)

    blocked = threading.Event()
    admitted = []

    def pull_ingest():
        blocked.set()
        admitted.append(q.put_pull(_Item(99), timeout=5.0))  # parked

    t = threading.Thread(target=pull_ingest)
    t.start()
    blocked.wait(timeout=1.0)

    done = []

    def reinsert(k):
        q.put_worker(_Item(100 + k))
        done.append(k)

    workers = [threading.Thread(target=reinsert, args=(k,)) for k in range(6)]
    t0 = time.monotonic()
    for w in workers:
        w.start()
    for w in workers:
        w.join(timeout=5)
    assert len(done) == 6                    # none of them blocked
    assert time.monotonic() - t0 < 1.0       # ... and none of them waited
    # the watermark counts every queued item: drain 9 -> 2, below the
    # pull limit of 3, and the parked pull is admitted
    for _ in range(7):
        q.get(timeout=0.5, shard=0)
    t.join(timeout=5)
    assert not t.is_alive()
    assert admitted == [True]


def test_close_wakes_pull_blocked_at_watermark():
    q = CentralQueue(capacity=4, lam=0.25, shards=2)  # pull limit = 1
    assert q.put_pull(_Item(0), timeout=0.1)
    results = []

    def blocked_pull():
        try:
            q.put_pull(_Item(1))  # no timeout: a single blocking wait
        except ClosedError:
            results.append("pull-closed")

    t = threading.Thread(target=blocked_pull)
    t.start()
    time.sleep(0.1)  # let it park in the watermark wait
    q.close()
    t.join(timeout=5)
    assert not t.is_alive()
    assert results == ["pull-closed"]


def test_close_wakes_getters_on_all_stripes():
    q = CentralQueue(capacity=4, lam=0.25, shards=2)  # empty: getters park
    results = []

    def blocked_get(shard):
        try:
            while True:
                q.get(timeout=10.0, shard=shard)
        except ClosedError:
            results.append(f"get-{shard}-closed")

    threads = [threading.Thread(target=blocked_get, args=(s,)) for s in (0, 1)]
    for t in threads:
        t.start()
    time.sleep(0.1)  # let both park in their stripe waits
    q.close()
    for t in threads:
        t.join(timeout=5)
    assert not any(t.is_alive() for t in threads)
    assert sorted(results) == ["get-0-closed", "get-1-closed"]


def test_sharded_close_drains_before_raising():
    q = CentralQueue(capacity=8, lam=1.0, shards=2)
    q.put_worker(_Item(0))
    q.put_worker(_Item(1))
    q.close()
    got = {q.get(timeout=0.5, shard=0).bid, q.get(timeout=0.5, shard=0).bid}
    assert got == {0, 1}
    with pytest.raises(ClosedError):
        q.get(timeout=0.5, shard=0)


def test_single_shard_queue_is_fifo_across_producers():
    q = CentralQueue(capacity=8, lam=1.0, shards=1)
    q.put_pull("a")
    q.put_worker("b")
    q.put_pull("c")
    assert [q.get(timeout=0.1) for _ in range(3)] == ["a", "b", "c"]
