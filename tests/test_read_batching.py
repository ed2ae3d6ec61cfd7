"""Async futures read path + multiplexed read batching (ISSUE 11):
future-vs-sync result parity over the wire on both storage engines,
per-key error isolation inside a batch, repair op-log / read cache
correctness through the batched path, FL002 settlement on batcher
teardown, batched==unbatched heat attribution, and same-seed sim
byte-identity with the future-based read path in place."""

import os
import sys
import threading
import time

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(__file__)))

from foundationdb_tpu.core import deterministic  # noqa: E402
from foundationdb_tpu.core.errors import FDBError  # noqa: E402
from foundationdb_tpu.core.keys import KeySelector  # noqa: E402
from foundationdb_tpu.rpc.service import (  # noqa: E402
    RemoteCluster,
    serve_cluster,
)
from foundationdb_tpu.server.cluster import Cluster  # noqa: E402
from foundationdb_tpu.server.kvstore import open_engine  # noqa: E402
from foundationdb_tpu.txn.futures import (  # noqa: E402
    FutureRange,
    FutureValue,
    ReadBatcher,
)

from conftest import TEST_KNOBS  # noqa: E402

# exact attribution for the heat-parity test (same recipe as
# test_heatmap.py): stride-1 sampling, no decay
HEAT_KNOBS = dict(TEST_KNOBS, storage_sample_every=1,
                  heatmap_half_life_s=0.0)


# ───────────────── future-vs-sync parity over the wire ─────────────────
@pytest.fixture(params=["memory", "redwood"])
def remote_db(request, tmp_path):
    """A served cluster on both storage engines: the async read path
    must be value-identical to the sync one whether the bytes live in
    the RAM map or the disk-resident versioned engine."""
    engines = [open_engine(request.param, str(tmp_path / "store.0"))]
    cluster = Cluster(resolver_backend="cpu", commit_pipeline="thread",
                      storage_engines=engines, **TEST_KNOBS)
    server = serve_cluster(cluster)
    rc = RemoteCluster([server.address])
    yield rc.database(), rc
    rc.close()
    server.close()
    cluster.close()


def test_async_reads_match_sync_reads(remote_db):
    db, rc = remote_db
    keys = [b"par%03d" % i for i in range(16)]
    tr0 = db.create_transaction()
    for i, k in enumerate(keys):
        tr0[k] = b"v%03d" % i
    tr0.commit()

    tr = db.create_transaction()
    # issue EVERY async form before consuming any: the batcher may
    # coalesce them, and settlement order must not matter
    futs = [tr.get_async(k) for k in keys]
    fmiss = tr.get_async(b"par-missing")
    fkey = tr.get_key_async(KeySelector.first_greater_or_equal(b"par"))
    frange = tr.get_range_async(b"par", b"par\xff")
    fpre = tr.get_range_startswith_async(b"par00")
    assert isinstance(futs[0], FutureValue)
    assert isinstance(frange, FutureRange)
    got = [f.wait() for f in futs]
    assert got == [b"v%03d" % i for i in range(16)]
    assert fmiss.wait() is None
    assert fkey.wait() == keys[0]
    rows = frange.wait()
    assert fpre.wait() == rows[:10]

    # sync forms are the same machinery (wait() over the future)
    tr2 = db.create_transaction()
    assert [tr2.get(k) for k in keys] == got
    assert tr2.get_key(
        KeySelector.first_greater_or_equal(b"par")) == keys[0]
    assert tr2.get_range(b"par", b"par\xff") == rows
    # repeated waits are memoized, not re-sent
    sent = rc.read_batcher.ops_sent
    assert futs[0].wait() == b"v000"
    assert rc.read_batcher.ops_sent == sent
    assert rc.read_batcher.ops_sent > 0
    assert rc.read_batcher.batches_sent >= 1


def test_a_window_of_async_reads_goes_out_in_fewer_rpcs_than_reads(tmp_path):
    """Over a real ``fdbserver`` process: windows of ``get_async``
    issued before any wait ride multiplexed ``read_batch`` RPCs — the
    client sends fewer batches than reads, the values are the sync
    path's, and the server's rollups saw multi-key batches."""
    import signal
    import subprocess

    import foundationdb_tpu as fdb

    cluster_file = str(tmp_path / "fdb.cluster")
    proc = subprocess.Popen(
        [sys.executable, "-m", "foundationdb_tpu.tools.fdbserver",
         "--listen", "127.0.0.1:0", "--cluster-file", cluster_file,
         "--dir", str(tmp_path / "data"), "--resolver-backend", "cpu"],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    db = None
    try:
        line = proc.stdout.readline()
        assert "FDBD listening" in line, line
        # the thread pipeline is what gives a remote client its flusher
        db = fdb.open(cluster_file=cluster_file, commit_pipeline="thread")
        keys = [b"win%03d" % i for i in range(64)]

        def load(tr):
            for i, k in enumerate(keys):
                tr[k] = b"v%03d" % i

        db.run(load)
        rb = db._cluster.read_batcher
        ops0, batches0 = rb.ops_sent, rb.batches_sent
        for _ in range(4):
            tr = db.create_transaction()
            futs = [tr.get_async(k) for k in keys]
            assert [f.wait() for f in futs] == [
                b"v%03d" % i for i in range(64)]
        ops, batches = rb.ops_sent - ops0, rb.batches_sent - batches0
        assert ops == 4 * len(keys)
        assert 0 < batches < ops
        roll = db.status()["cluster"]["metrics"]["rollups"]
        assert roll["batched_reads"] >= ops
        assert roll["read_batch_coalesce_rate"] > 1.0
        assert roll["read_batch_size_p99"] > 1.0
    finally:
        if db is not None:
            db._cluster.close()
        proc.send_signal(signal.SIGTERM)
        try:
            proc.wait(timeout=10)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait()


def test_async_reads_see_own_writes(remote_db):
    """RYW through the async forms: a key set in this txn resolves
    from the write set without touching the wire."""
    db, rc = remote_db
    db[b"ryw"] = b"old"
    tr = db.create_transaction()
    tr[b"ryw"] = b"new"
    sent = rc.read_batcher.ops_sent if rc._read_batcher else 0
    assert tr.get_async(b"ryw").wait() == b"new"
    now = rc.read_batcher.ops_sent if rc._read_batcher else 0
    assert now == sent  # known locally: no read op left the client
    assert tr.get_range_async(b"ryw", b"ryx").wait() == [(b"ryw", b"new")]


# ──────────────────── per-key error isolation ────────────────────
def test_batch_slots_fail_per_key_not_batch_fatal():
    cluster = Cluster(resolver_backend="cpu", **TEST_KNOBS)
    try:
        db = cluster.database()
        db[b"iso"] = b"ok"
        st = cluster.storages[0]
        rv = st.version
        slots = st.read_batch([
            ("g", b"iso", rv),
            ("g", b"iso", rv + 10**9),  # future_version: fails ALONE
            ("x",),                     # malformed op: fails ALONE
            ("s", KeySelector.last_less_or_equal(b"iso"), rv),
            ("r", b"i", b"j", rv, 0, False),
        ])
        assert slots[0] == b"ok"
        assert isinstance(slots[1], FDBError) and slots[1].code == 1009
        assert isinstance(slots[2], FDBError) and slots[2].code == 2000
        assert slots[3] == b"iso"
        assert slots[4] == [(b"iso", b"ok")]
    finally:
        cluster.close()


# ──────────── repair op-log + read cache via batched path ────────────
def test_repair_oplog_and_read_cache_through_batched_path(remote_db):
    db, rc = remote_db
    db[b"k"] = b"1"
    db[b"c"] = b"const"
    tr = db.create_transaction()
    tr.options.set_transaction_repair()
    assert tr.get_async(b"k").wait() == b"1"
    assert tr.get_async(b"c").wait() == b"const"
    # the finalize callback recorded the op-log entries on the
    # CONSUMING thread — repair replays from exactly these records
    assert tr._repair.point_reads == {b"k": b"1", b"c": b"const"}
    tr[b"out"] = b"x"
    db[b"k"] = b"2"  # concurrent write lands first: tr must conflict
    with pytest.raises(FDBError) as ei:
        tr.commit()
    assert ei.value.code == 1020
    tr.on_error(ei.value)
    # the repaired retry serves resolver-verified keys from the cache:
    # values are current, and NOT ONE read op leaves the client
    sent = rc.read_batcher.ops_sent
    assert tr.get_async(b"c").wait() == b"const"
    assert tr.get_async(b"k").wait() == b"2"
    assert rc.read_batcher.ops_sent == sent


# ──────────────── FL002: teardown settles every waiter ────────────────
def test_close_settles_queued_reads_retryable():
    """close() must settle everything still queued with process_behind
    — a torn-down connection never strands a parked waiter."""
    gate = threading.Event()

    def send(ops):
        gate.wait(5)
        return [b"served"] * len(ops)

    b = ReadBatcher(send, thread=True)
    f1 = FutureValue(batcher=b)
    b.submit(("g", b"k", 1), f1)  # flusher picks this up, blocks in send
    deadline = time.monotonic() + 5
    while b.pending() and time.monotonic() < deadline:
        time.sleep(0.001)
    f2 = FutureValue(batcher=b)
    b.submit(("g", b"k", 1), f2)  # queued behind the in-flight batch
    closer = threading.Thread(target=b.close)
    closer.start()
    while not b._closed and time.monotonic() < deadline:
        time.sleep(0.001)
    gate.set()
    closer.join(timeout=10)
    assert f1.wait() == b"served"  # in-flight batch completed normally
    with pytest.raises(FDBError) as ei:
        f2.wait()
    assert ei.value.code == 1037  # queued op: settled retryable


def test_submit_after_close_settles_immediately():
    b = ReadBatcher(lambda ops: [None] * len(ops), thread=False)
    b.close()
    f = FutureValue(batcher=b)
    b.submit(("g", b"k", 1), f)
    assert f.done()
    with pytest.raises(FDBError) as ei:
        f.wait()
    assert ei.value.code == 1037


def test_cancel_runs_finalize_cleanup():
    seen = []
    f = FutureValue(finalize=lambda v, e: seen.append((v, e)))
    f.cancel()
    assert len(seen) == 1
    assert seen[0][0] is None and seen[0][1].code == 1025
    with pytest.raises(FDBError):
        f.wait()
    assert len(seen) == 1  # finalize ran exactly once


# ──────────────── heat parity: batched == unbatched ────────────────
def _read_heat_delta(batched):
    """Serve the same 48 keys at the same versions from a same-seed
    cluster, batched or one-at-a-time, and return the read heatmap's
    (charges, heat) delta."""
    deterministic.seed(4242)
    cluster = Cluster(resolver_backend="cpu", **HEAT_KNOBS)
    try:
        db = cluster.database()
        keys = [b"heat%03d" % i for i in range(48)]
        for k in keys:
            db[k] = b"v"
        st = cluster.storages[0]
        rv = st.version
        hm = cluster._role_heatmap("storage_read", 0)
        charges0, heat0 = hm.charges, hm.total_heat()
        if batched:
            slots = st.read_batch([("g", k, rv) for k in keys])
            assert all(not isinstance(s, FDBError) for s in slots)
        else:
            for k in keys:
                st.get(k, rv)
        return hm.charges - charges0, hm.total_heat() - heat0
    finally:
        cluster.close()


def test_batched_serve_charges_heat_like_unbatched():
    """Satellite 2: one countdown decrement PER KEY served, never one
    per RPC — a 48-key batch heats the map exactly like 48 gets."""
    sync_delta = _read_heat_delta(batched=False)
    batch_delta = _read_heat_delta(batched=True)
    assert sync_delta == batch_delta
    assert sync_delta[0] > 0  # the workload actually sampled


# ──────────────── determinism: same-seed sims identical ────────────────
def test_same_seed_sims_identical_with_async_read_path(tmp_path):
    """Two same-seed sims must stay byte-identical now that every read
    (sync forms included) routes through the futures machinery —
    in-process storages settle async reads inline, so the schedule
    never depends on flusher timing."""
    import random

    from foundationdb_tpu.sim.simulation import Simulation
    from foundationdb_tpu.sim.workloads import (
        batched_cycle_workload, cycle_check, cycle_setup,
    )

    def run(tag):
        sim = Simulation(
            seed=17, buggify=False, crash_p=0.0,
            datadir=str(tmp_path / tag),
            commit_pipeline="manual", commit_flush_after=4,
            resolver_backend="cpu",
        )
        with sim:
            db = sim.db
            cycle_setup(db, 8)
            for a in range(2):
                sim.add_workload(
                    f"cycle{a}",
                    batched_cycle_workload(db, 8, 6, random.Random(a)),
                )
            sim.run(max_steps=40_000)
            sim.quiesce()
            cycle_check(db, 8)
            # explicit async reads resolve inline in-process
            tr = db.create_transaction()
            vals = tuple(v for _, v in tr.get_range_async(
                b"", b"\xff", limit=8).wait())
            return (sim.schedule_hash,
                    sim.cluster.sequencer.committed_version, vals)

    assert run("a") == run("b")
