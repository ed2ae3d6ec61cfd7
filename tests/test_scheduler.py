"""Abort-aware intra-batch commit scheduling (server/scheduler.py):
the plan's ordering/restore algebra, the reader-before-writer wins at
the proxy on every commit path, and the decision observability."""

from foundationdb_tpu.core import flatpack
from foundationdb_tpu.core.commit import CommitRequest
from foundationdb_tpu.core.errors import FDBError
from foundationdb_tpu.core.mutations import Mutation, Op
from foundationdb_tpu.server import scheduler
from foundationdb_tpu.server.cluster import Cluster


def req(reads, writes, rv=10, flat=None, mutations=()):
    span = lambda k: k if isinstance(k, tuple) else (k, k + b"\x00")
    r = CommitRequest(
        read_version=rv,
        mutations=list(mutations),
        read_conflict_ranges=[span(k) for k in reads],
        write_conflict_ranges=[span(k) for k in writes],
    )
    if flat:
        r.flat_conflicts = flatpack.encode_conflicts(
            r.read_conflict_ranges, r.write_conflict_ranges, flat
        )
    return r


# ───────────────────────── the pass itself ─────────────────────────
def test_reader_schedules_before_blind_writer():
    """The canonical win: arrival [W(x), T(reads x)] aborts T; the
    scheduled order commits both."""
    plan = scheduler.schedule([req([], [b"x"]), req([b"x"], [b"y"])])
    assert plan.order == (1, 0)
    assert plan.reordered == 2
    assert plan.deferred == 0


def test_restore_maps_results_back_to_request_order():
    plan = scheduler.SchedulePlan(order=(2, 0, 1), reordered=3, deferred=0)
    assert plan.restore(["r2", "r0", "r1"]) == ["r0", "r1", "r2"]


def test_conflict_free_batch_keeps_arrival_order():
    plan = scheduler.schedule(
        [req([b"a"], [b"a"]), req([b"b"], [b"b"]), req([], [b"c"])]
    )
    assert plan is None  # no cross-txn edges: arrival order untouched


def test_pure_rmw_clique_is_left_in_arrival_order():
    """Mutual read+write pairs get no edge: exactly one member commits
    in every order, so scheduling must not scramble arrival order."""
    plan = scheduler.schedule(
        [req([b"d"], [b"d"]) for _ in range(4)]
    )
    assert plan is None


def test_doomed_tail_member_counts_as_deferred():
    """A txn whose read is covered by an EARLIER-placed write (no order
    saves it) is counted deferred — it aborts this window and retries
    at the next commit version."""
    # W blind-writes x; R1 and R2 read x and write x (RMW): R1/R2 must
    # precede W (one-way edges), but between R1 and R2 one is doomed…
    # actually RMW pairs are mutual → no edge; W is the blind writer.
    plan = scheduler.schedule(
        [req([], [b"x"]), req([b"x"], [b"x"]), req([b"x"], [b"x"])]
    )
    # both RMWs precede the blind writer; the second RMW is doomed by
    # the first (mutual pair, no edge, arrival order kept) → deferred
    assert plan is not None
    assert plan.order.index(0) == 2  # blind writer last
    assert plan.deferred == 1


def test_range_read_schedules_before_point_writer():
    plan = scheduler.schedule(
        [req([], [b"m"]), req([(b"a", b"z")], [])]
    )
    # txn 1 reads the range [a, z) which txn 0 writes into
    assert plan is not None and plan.order == (1, 0)


def test_flat_and_legacy_requests_produce_the_same_plan():
    legacy = [req([], [b"x"]), req([b"x"], [b"y"])]
    flat = [req([], [b"x"], flat=8), req([b"x"], [b"y"], flat=8)]
    mixed = [req([], [b"x"], flat=8), req([b"x"], [b"y"])]
    orders = [scheduler.schedule(b).order for b in (legacy, flat, mixed)]
    assert orders == [(1, 0)] * 3


def test_schedule_is_deterministic():
    import random

    rnd = random.Random(7)
    keys = [b"k%02d" % i for i in range(12)]
    batch = [
        req(rnd.sample(keys, 2), rnd.sample(keys, 2))
        for _ in range(40)
    ]
    plans = [scheduler.schedule(batch) for _ in range(3)]
    assert len({p.order if p is not None else None for p in plans}) == 1


def test_small_batch_declines():
    assert scheduler.schedule([req([b"x"], [b"x"])]) is None
    assert scheduler.schedule([]) is None


# ───────────────────── through the commit proxy ────────────────────
def _pair(cluster):
    rv = cluster.grv_proxy.get_read_version()
    w = CommitRequest(
        read_version=rv, mutations=[Mutation(Op.SET, b"x", b"W")],
        read_conflict_ranges=[],
        write_conflict_ranges=[(b"x", b"x\x00")],
    )
    t = CommitRequest(
        read_version=rv, mutations=[Mutation(Op.SET, b"y", b"T")],
        read_conflict_ranges=[(b"x", b"x\x00")],
        write_conflict_ranges=[(b"y", b"y\x00")],
    )
    return w, t


def test_proxy_commit_batch_saves_the_reader_and_restores_order():
    cl = Cluster(resolver_backend="cpu", commit_batch_scheduling=True)
    db = cl.database()
    db.set(b"x", b"0")
    w, t = _pair(cl)
    out = cl.commit_proxy.commit_batch([w, t])
    # both commit, and results are in REQUEST order (same version)
    assert out[0] == out[1]
    assert not any(isinstance(r, FDBError) for r in out)
    assert cl._commit_target().sched_reordered_total == 2
    assert db.get(b"y") == b"T"
    cl.close()


def test_proxy_arrival_order_baseline_aborts_the_reader():
    # knob explicitly off (default flipped ON in the defaults audit):
    # the arrival-order baseline self-inflicts the in-batch abort
    cl = Cluster(resolver_backend="cpu", commit_batch_scheduling=False)
    db = cl.database()
    db.set(b"x", b"0")
    w, t = _pair(cl)
    out = cl.commit_proxy.commit_batch([w, t])
    assert not isinstance(out[0], FDBError)
    assert isinstance(out[1], FDBError) and out[1].code == 1020
    cl.close()


def test_backlog_and_pipelined_paths_schedule_and_restore():
    """commit_batches and the begin/finish pipeline both schedule each
    batch and map results back to request order."""
    cl = Cluster(resolver_backend="cpu", commit_batch_scheduling=True)
    db = cl.database()
    db.set(b"x", b"0")
    proxy = cl._commit_target()
    # backlog route
    w, t = _pair(cl)
    out = proxy.commit_batches([[w, t]])
    assert not any(isinstance(r, FDBError) for r in out[0])
    # pipelined route (begin on one thread, finish FIFO — the batcher's
    # contract, exercised here single-threaded)
    w2, t2 = _pair(cl)
    group = proxy.commit_batches_begin([[w2, t2]])
    res = proxy.commit_batches_finish(group)
    assert not any(isinstance(r, FDBError) for r in res[0])
    assert proxy.sched_batches == 2
    assert proxy.sched_reordered_total == 4
    # registry counters feed the status rollups
    roll = cl.metrics_status()["rollups"]
    assert roll["sched_reordered"] == 4
    assert roll["sched_deferred"] == 0
    cl.close()


def test_scheduling_preserves_per_request_results_under_mixed_fates():
    """A batch where specific members MUST abort: the restore mapping
    has to pin each outcome to the right request."""
    cl = Cluster(resolver_backend="cpu", commit_batch_scheduling=True)
    db = cl.database()
    db.set(b"x", b"0")
    rv = cl.grv_proxy.get_read_version()

    def rmw(key):
        return CommitRequest(
            read_version=rv,
            mutations=[Mutation(Op.SET, key, b"v")],
            read_conflict_ranges=[(key, key + b"\x00")],
            write_conflict_ranges=[(key, key + b"\x00")],
        )

    blind = CommitRequest(
        read_version=rv, mutations=[Mutation(Op.SET, b"x", b"B")],
        read_conflict_ranges=[],
        write_conflict_ranges=[(b"x", b"x\x00")],
    )
    reader = CommitRequest(
        read_version=rv, mutations=[Mutation(Op.SET, b"y", b"R")],
        read_conflict_ranges=[(b"x", b"x\x00")],
        write_conflict_ranges=[(b"y", b"y\x00")],
    )
    a, b = rmw(b"d"), rmw(b"d")  # mutual pair: second must abort
    out = cl.commit_proxy.commit_batch([blind, a, reader, b])
    assert not isinstance(out[0], FDBError)  # blind writer commits
    assert not isinstance(out[1], FDBError)  # first RMW of d commits
    assert not isinstance(out[2], FDBError)  # reader saved by the plan
    assert isinstance(out[3], FDBError) and out[3].code == 1020
    cl.close()


def test_stage_summary_carries_scheduler_counters():
    cl = Cluster(resolver_backend="cpu", commit_pipeline="manual",
                 commit_batch_scheduling=True)
    db = cl.database()
    db.set(b"x", b"0")
    w, t = _pair(cl)
    proxy = cl.commit_proxy  # BatchingCommitProxy (manual mode)
    futs = [proxy.submit(w), proxy.submit(t)]
    proxy.flush()
    assert all(f.done() for f in futs)
    inner = proxy.inner  # the batcher's window reached the proxy's plan
    assert inner.sched_batches == 1
    assert inner.sched_reordered_total == 2
    assert inner.sched_deferred_total == 0
    cl.close()
