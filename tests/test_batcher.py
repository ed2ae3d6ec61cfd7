"""Cross-client commit batching (server/batcher.py).

Ref parity: CommitProxyServer.actor.cpp commitBatcher — concurrent
client commits share a batch, a commit version, and one resolver
dispatch. Three properties under test:

1. thread mode: genuinely concurrent committers get batched (shared
   commit versions), semantics (OCC conflicts, RYW) unchanged;
2. manual mode under the deterministic simulation with the REAL TPU
   resolver backend at realistic batch sizes — the full pipeline
   (batch → kernel → tlog → storage) with cross-actor batches;
3. crash safety: queued commits resolve to commit_unknown_result, never
   hang.
"""

import random
import threading

import pytest

from foundationdb_tpu.core.errors import FDBError
from foundationdb_tpu.server.cluster import Cluster
from foundationdb_tpu.sim.simulation import Simulation
from foundationdb_tpu.sim.workloads import (
    batched_cycle_workload,
    cycle_check,
    cycle_setup,
)

TPU_KNOBS = dict(
    resolver_backend="tpu",
    batch_txn_capacity=64,
    hash_table_bits=14,
    range_ring_capacity=256,
    coarse_buckets_bits=10,
)


def test_thread_mode_batches_concurrent_commits(tmp_path):
    cluster = Cluster(
        commit_pipeline="thread",
        resolver_backend="cpu",
        commit_batch_max=64,
    )
    db = cluster.database()
    n_threads, per_thread = 8, 25
    errors = []
    barrier = threading.Barrier(n_threads)

    def client(tid):
        try:
            barrier.wait()
            for i in range(per_thread):
                db.run(lambda tr: tr.set(b"t%02d/%03d" % (tid, i), b"v"))
        except Exception as e:  # pragma: no cover - surfaced via assert
            errors.append(e)

    threads = [threading.Thread(target=client, args=(t,)) for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    bp = cluster.commit_proxy
    assert bp.txns_batched == n_threads * per_thread
    # concurrency must actually produce multi-txn batches
    assert bp.max_batch_seen > 1, "no cross-client batch ever formed"
    assert bp.batches_committed < bp.txns_batched
    rows = db.get_range(b"t", b"u")
    assert len(rows) == n_threads * per_thread
    bp.close()


def test_thread_mode_preserves_occ_conflicts():
    cluster = Cluster(commit_pipeline="thread", resolver_backend="cpu")
    db = cluster.database()
    db.run(lambda tr: tr.set(b"k", b"0"))
    # two txns read the same key at the same version, then both write it:
    # exactly one may commit (the loser retries in db.run and succeeds)
    attempts = []

    def bump(tr):
        v = int(tr.get(b"k"))
        attempts.append(v)
        tr.set(b"k", b"%d" % (v + 1))

    barrier = threading.Barrier(2)

    def client():
        barrier.wait()
        db.run(bump)

    ts = [threading.Thread(target=client) for _ in range(2)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert db.get(b"k") == b"2"  # both eventually applied, serially
    cluster.close()


def test_sim_manual_batching_with_tpu_resolver(tmp_path):
    """The VERDICT's flagship gap: the TPU resolver exercised end-to-end
    by the live system with real multi-txn batches, not 1-txn pads."""
    sim = Simulation(
        seed=11,
        buggify=False,
        crash_p=0.0,
        datadir=str(tmp_path),
        commit_pipeline="manual",
        commit_flush_after=6,
        **TPU_KNOBS,
    )
    with sim:
        db = sim.db
        cycle_setup(db, 12)
        rng = random.Random(5)
        for a in range(6):
            sim.add_workload(
                f"cycle{a}",
                batched_cycle_workload(db, 12, 10, random.Random(rng.random())),
            )
        sim.run()
        sim.quiesce()
        cycle_check(db, 12)
        bp = sim.cluster.commit_proxy._inner  # unwrap FaultyCommitProxy
        assert bp.max_batch_seen > 1, "sim never formed a multi-txn batch"
        assert bp.txns_batched >= 60


def test_sim_batching_with_faults_and_crashes(tmp_path):
    """Batched commits under BUGGIFY faults + whole-cluster crashes:
    the cycle invariant must hold and no actor may hang on an orphaned
    future."""
    sim = Simulation(
        seed=23,
        buggify=True,
        crash_p=0.004,
        datadir=str(tmp_path),
        commit_pipeline="manual",
        commit_flush_after=4,
        resolver_backend="cpu",
    )
    with sim:
        db = sim.db
        cycle_setup(db, 10)
        rng = random.Random(9)
        for a in range(4):
            sim.add_workload(
                f"cycle{a}",
                batched_cycle_workload(db, 10, 8, random.Random(rng.random())),
            )
        sim.run(max_steps=200_000)
        sim.quiesce()
        cycle_check(db, 10)


def test_manual_sync_commit_rides_pending_batch():
    """A synchronous commit in manual mode flushes the queue: pending
    async submissions land in the SAME batch (shared commit version)."""
    cluster = Cluster(
        commit_pipeline="manual", resolver_backend="cpu", commit_batch_max=32
    )
    db = cluster.database()
    trs = []
    futs = []
    for i in range(5):
        tr = db.create_transaction()
        tr.set(b"a%d" % i, b"x")
        trs.append(tr)
        futs.append(tr.commit_async())
    assert not any(f.done() for f in futs)
    tr = db.create_transaction()
    tr.set(b"sync", b"y")
    tr.commit()  # flushes everything as one batch
    assert all(f.done() for f in futs)
    for tr_i, f in zip(trs, futs):
        tr_i.commit_finish(f)
    versions = {tr_i.get_committed_version() for tr_i in trs}
    assert len(versions) == 1, "async batch did not share a commit version"
    assert cluster.commit_proxy.max_batch_seen == 6


def test_fail_pending_resolves_futures():
    cluster = Cluster(commit_pipeline="manual", resolver_backend="cpu")
    db = cluster.database()
    tr = db.create_transaction()
    tr.set(b"k", b"v")
    fut = tr.commit_async()
    cluster.commit_proxy.fail_pending(FDBError.from_name("commit_unknown_result"))
    assert fut.done()
    with pytest.raises(FDBError) as ei:
        tr.commit_finish(fut)
    assert ei.value.code == 1021


def test_batcher_survives_poisoned_batch():
    """An exception escaping the inner pipeline must fail that chunk's
    futures with 1021 and leave the batcher thread alive for later
    commits — not deadlock every subsequent client (round-2 review
    finding: the re-raise killed the thread)."""
    from foundationdb_tpu.core.errors import FDBError
    from foundationdb_tpu.server.cluster import Cluster
    from tests.conftest import TEST_KNOBS

    c = Cluster(commit_pipeline="thread", commit_flush_after=1, **TEST_KNOBS)
    db = c.database()
    inner = c.commit_proxy.inner
    orig = inner.commit_batch
    state = {"raised": False}

    def boom(reqs):
        if not state["raised"]:
            state["raised"] = True
            raise IOError("disk full (injected)")
        return orig(reqs)

    inner.commit_batch = boom
    tr = db.create_transaction()
    tr.set(b"k", b"1")
    try:
        tr.commit()
        raise AssertionError("expected commit_unknown_result")
    except FDBError as e:
        assert e.code == 1021
    db.set(b"k", b"2")  # the batcher thread must still be draining
    assert db.get(b"k") == b"2"
    assert isinstance(c.commit_proxy.last_batch_error, IOError)
    c.close()


def test_thread_mode_concurrent_range_reads_consistent():
    """Client threads range-read while the batcher thread applies and
    flushes: the storage mutation lock must keep SortedDict iteration
    safe (round-2 review finding: reads raced overlay mutation)."""
    import threading

    from foundationdb_tpu.server.cluster import Cluster
    from tests.conftest import TEST_KNOBS

    c = Cluster(commit_pipeline="thread", commit_flush_after=1, **TEST_KNOBS)
    c.commit_proxy.inner.pump_interval = 2  # flush (engine mutation) often
    db = c.database()
    for i in range(50):
        db.set(b"seed%03d" % i, b"v")
    stop = threading.Event()
    errors = []

    def reader():
        while not stop.is_set():
            try:
                rows = db.get_range(b"seed", b"seee")
                assert len(rows) >= 50, len(rows)
            except Exception as e:  # pragma: no cover — the regression
                errors.append(e)
                return

    threads = [threading.Thread(target=reader) for _ in range(4)]
    for t in threads:
        t.start()
    try:
        for i in range(200):
            db.set(b"w%04d" % i, b"x" * 50)
            if i % 37 == 0:
                db.clear_range(b"w", b"w\x03")
    finally:
        stop.set()
        for t in threads:
            t.join()
    assert not errors, errors[:3]
    c.close()


def test_commit_async_inflight_guards_reuse():
    """While a commit_async is in flight the transaction is 'committing':
    a second commit (or further mutations) must raise used_during_commit
    instead of re-submitting the same mutation log as an independent
    commit (round-2 review finding: a blind ADD applied twice)."""
    import pytest

    from foundationdb_tpu.core.errors import FDBError
    from foundationdb_tpu.server.cluster import Cluster
    from tests.conftest import TEST_KNOBS

    c = Cluster(commit_pipeline="manual", **TEST_KNOBS)
    db = c.database()
    tr = db.create_transaction()
    tr.add(b"ctr", (1).to_bytes(8, "little"))
    fut = tr.commit_async()
    for op in (tr.commit_async, tr.commit, lambda: tr.set(b"x", b"y")):
        with pytest.raises(FDBError) as ei:
            op()
        assert ei.value.code == 2017  # used_during_commit
    c.commit_proxy.flush()
    tr.commit_finish(fut)
    assert int.from_bytes(db.get(b"ctr"), "little") == 1


def test_backlog_dispatches_through_commit_batches():
    """When the batcher drains a backlog larger than one chunk, the
    chunks ride one resolver dispatch (commit_batches) and every future
    resolves with the correct per-txn verdicts."""
    from foundationdb_tpu.server.cluster import Cluster
    from conftest import TEST_KNOBS

    cluster = Cluster(resolver_backend="cpu", commit_pipeline="manual",
                      commit_batch_max=4, **TEST_KNOBS)
    db = cluster.database()
    try:
        db[b"seed"] = b"0"
        futs, trs = [], []
        for i in range(11):  # 3 chunks of <=4: a real backlog
            tr = db.create_transaction()
            tr.get(b"seed")
            tr.set(b"k%02d" % i, b"v%d" % i)
            trs.append(tr)
            futs.append(tr.commit_async())
        calls = []
        orig = cluster.commit_proxy.inner.commit_batches

        def spy(batches):
            calls.append([len(b) for b in batches])
            return orig(batches)

        cluster.commit_proxy.inner.commit_batches = spy
        cluster.commit_proxy.flush()
        for tr, fut in zip(trs, futs):
            tr.commit_finish(fut)
        assert calls == [[4, 4, 3]]
        for i in range(11):
            assert db[b"k%02d" % i] == b"v%d" % i
        # versions differ per chunk (one commit version per batch)
        versions = {tr.get_committed_version() for tr in trs}
        assert len(versions) == 3
    finally:
        cluster.close()


def test_backlog_depth_adapts_to_conflict_rate():
    """AIMD on observed conflicts: a contended workload shrinks the
    backlog depth (deep pipelines of stale read versions explode OCC
    retries); a clean workload grows it back."""
    from foundationdb_tpu.core.errors import FDBError
    from foundationdb_tpu.server.batcher import BatchingCommitProxy

    class FakeInner:
        knobs = type("K", (), {"batch_txn_capacity": 4,
                               "commit_batch_interval_s": 0})()
        conflict = True

        def commit_batch(self, reqs):
            e = FDBError(1020)
            return [e if self.conflict else 1 for _ in reqs]

        def commit_batches(self, batches):
            return [self.commit_batch(r) for r in batches]

    inner = FakeInner()
    bp = BatchingCommitProxy(inner, max_batch=1, mode="manual")
    assert bp._backlog_target == bp.MAX_BACKLOG
    pending = [(object(), __import__(
        "foundationdb_tpu.server.batcher", fromlist=["CommitFuture"]
    ).CommitFuture()) for _ in range(bp.MAX_BACKLOG)]
    bp._run_batch(list(pending))
    assert bp._backlog_target == bp.MAX_BACKLOG // 2  # conflicts halve it
    for _ in range(10):
        bp._run_batch(list(pending))
    assert bp._backlog_target == 1  # keeps shrinking under contention
    inner.conflict = False
    for _ in range(10):
        bp._run_batch(list(pending))
    assert bp._backlog_target == bp.MAX_BACKLOG  # clean traffic regrows


# ───────────── completions: a commit nobody waits for (PR 38) ─────────────
class _WedgedInner:
    """An inner proxy whose ``commit_batch`` stands still until
    released, then commits everything at version 7."""

    def __init__(self, **knobs):
        from foundationdb_tpu.core.options import Knobs

        self.knobs = Knobs(**knobs)
        self.entered = threading.Event()
        self.release = threading.Event()
        self.batches = []

    def commit_batch(self, reqs):
        self.batches.append(len(reqs))
        self.entered.set()
        assert self.release.wait(30)
        return [7] * len(reqs)


def _wait_for(pred, what, timeout=20.0):
    import time

    deadline = time.monotonic() + timeout
    while not pred():
        assert time.monotonic() < deadline, f"waited for {what}"
        time.sleep(0.001)


@pytest.mark.parametrize("registered", ["before", "after"])
def test_completion_runs_once_with_the_first_settlement(registered):
    """``set`` keeps 'first settlement wins'; the completion sees the
    winner, whether it was registered before it or after."""
    from foundationdb_tpu.server.batcher import CommitFuture

    got = []
    fut = CommitFuture()
    if registered == "before":
        assert fut.add_done_callback(got.append) is None
        assert got == []
    fut.set(11)
    fut.set(FDBError.from_name("commit_unknown_result"))  # the loser
    if registered == "after":
        fut.add_done_callback(got.append)  # runs here and now
    fut.set(12)
    assert got == [11] and fut.result(timeout=0) == 11


def test_a_future_takes_one_completion():
    from foundationdb_tpu.server.batcher import CommitFuture

    fut = CommitFuture()
    fut.add_done_callback(lambda r: None)
    with pytest.raises(RuntimeError):
        fut.add_done_callback(lambda r: None)


def test_completion_fires_exactly_once_under_set_racing_set():
    """The watchdog's 1021 against the real result, from two threads at
    a 10 µs switch interval, with the completion registered by a third:
    every future's completion runs once, with the value ``result``
    reads ever after."""
    import sys

    from foundationdb_tpu.server.batcher import CommitFuture

    n = 3000
    unknown = FDBError.from_name("commit_unknown_result")
    futs = [CommitFuture() for _ in range(n)]
    got = [[] for _ in range(n)]
    start = threading.Barrier(3)

    def settle(value):
        start.wait(10)
        for f in futs:
            f.set(value)

    def register():
        start.wait(10)
        for f, g in zip(futs, got):
            f.add_done_callback(g.append)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=settle, args=(7,)),
                   threading.Thread(target=settle, args=(unknown,)),
                   threading.Thread(target=register)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(interval)
    assert all(len(g) == 1 for g in got)
    assert all(g[0] is f.result(timeout=0) for f, g in zip(futs, got))
    assert {type(g[0]) for g in got} <= {int, FDBError}


def test_a_settle_runs_each_flush_once_behind_its_last_completion():
    """What a completion hands back is called once a settle, after
    every future of the batch is set: the hook a served batch's replies
    share a send through."""
    from foundationdb_tpu.server.batcher import BatchingCommitProxy

    inner = _WedgedInner()
    inner.release.set()
    bp = BatchingCommitProxy(inner, mode="manual")
    events = []

    def flush_a():
        events.append("flush_a")

    def flush_b():
        events.append("flush_b")

    futs = [bp.submit(object()) for _ in range(6)]
    for i, fut in enumerate(futs):
        fut.add_done_callback(
            lambda r, i=i: events.append(i) or (flush_a, flush_b)[i % 2])
    plain = bp.submit(object())  # no completion: result() as ever
    bp.flush()
    assert events[:6] == list(range(6))
    assert sorted(events[6:]) == ["flush_a", "flush_b"]
    assert plain.result(timeout=0) == 7
    assert inner.batches == [7] and bp.batches_committed == 1


@pytest.mark.parametrize("how", ["fail_pending", "fail_chunks"])
def test_failed_batches_answer_their_completions(how):
    """A crash before the batch formed (``fail_pending``) and a batch
    the inner proxy threw out of (``_fail_chunks``) both complete the
    commits nobody waits for, with 1021."""
    from foundationdb_tpu.core.options import Knobs
    from foundationdb_tpu.server.batcher import BatchingCommitProxy

    class Inner:
        knobs = Knobs()

        def commit_batch(self, reqs):
            raise IOError("disk full (injected)")

    bp = BatchingCommitProxy(Inner(), mode="manual")
    got, flushes = [], []

    def flush():
        flushes.append(len(got))

    for _ in range(5):
        bp.submit(object()).add_done_callback(
            lambda r: got.append(r) or flush)
    if how == "fail_pending":
        bp.fail_pending(FDBError.from_name("commit_unknown_result"))
    else:
        bp.flush()
        assert isinstance(bp.last_batch_error, IOError)
    assert [r.code for r in got] == [1021] * 5
    assert flushes == [5]  # once, behind the last of them


def test_watchdog_without_a_waiter_settles_a_wedged_batch():
    """No thread blocks in ``result``: whoever holds the futures calls
    ``poll`` now and then. A batch wedged past ``watchdog_s``
    completes every commit of it with 1021, the wedged drive's late
    results change nothing, ``stranded_settled`` counts them."""
    from foundationdb_tpu.server.batcher import BatchingCommitProxy

    inner = _WedgedInner()
    bp = BatchingCommitProxy(inner, interval_s=0.0, mode="thread")
    bp.watchdog_s = 0.2
    got = []
    try:
        futs = [bp.submit(object()) for _ in range(1)]
        assert inner.entered.wait(20)
        for fut in futs:
            fut.add_done_callback(got.append)
        futs[0].poll()  # too early: the batch is young
        assert got == [] and bp.stranded_settled == 0
        _wait_for(lambda: futs[0].poll() or got,
                  "the watchdog to fire")
        assert [r.code for r in got] == [1021]
        assert bp.stranded_settled == 1
        inner.release.set()  # the wedged drive's late set loses
        _wait_for(lambda: bp.batches_committed == 1, "the late settle")
        assert [r.code for r in got] == [1021]
        assert futs[0].result(timeout=0).code == 1021
        late = bp.submit(object())  # the batcher thread lives on
        assert late.result(timeout=20) == 7
    finally:
        inner.release.set()
        bp.close()
