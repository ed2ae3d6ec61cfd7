"""Storage engines + the storage server's durable-version tiering."""

import random

import pytest

from foundationdb_tpu.core.errors import FDBError
from foundationdb_tpu.core.keys import KeySelector
from foundationdb_tpu.core.mutations import Mutation, Op
from foundationdb_tpu.server.kvstore import (
    KeyValueStoreMemory,
    KeyValueStoreSQLite,
    open_engine,
)
from foundationdb_tpu.server.storage import StorageServer
from foundationdb_tpu.server.tlog import TLog


@pytest.fixture(params=["memory", "sqlite"])
def engine_factory(request, tmp_path):
    kind = request.param
    counter = [0]

    def make(name=None):
        counter[0] += 1
        path = str(tmp_path / f"{kind}{name or counter[0]}")
        return open_engine(kind, path)

    return make


@pytest.fixture(params=["versioned", "redwood"])
def versioned_factory(request, tmp_path):
    """Both Redwood-role engines: the RAM-chained KeyValueStoreVersioned
    and the disk-resident KeyValueStoreVersionedDisk — one contract,
    every versioned test runs on each."""
    kind = request.param
    counter = [0]

    def make(name=None):
        counter[0] += 1
        path = str(tmp_path / f"{kind}{name or counter[0]}")
        return open_engine(kind, path)

    return make


# ───────────────────────────── engines ──────────────────────────────────
def test_engine_basic_ops(engine_factory):
    e = engine_factory()
    e.set(b"a", b"1")
    e.set(b"b", b"2")
    e.set(b"c", b"3")
    assert e.get(b"b") == b"2"
    assert e.get(b"zz") is None
    assert e.get_range(b"a", b"c") == [(b"a", b"1"), (b"b", b"2")]
    assert e.get_range(b"a", b"z", reverse=True, limit=2) == [(b"c", b"3"), (b"b", b"2")]
    e.clear_range(b"a", b"b\x00")
    assert e.get_range(b"", b"\xff") == [(b"c", b"3")]
    e.commit(42)
    assert e.stored_version() == 42
    e.close()


def test_engine_durability(engine_factory):
    e = engine_factory("dur")
    path = e.path
    for i in range(100):
        e.set(b"k%03d" % i, b"v%d" % i)
    e.clear_range(b"k050", b"k060")
    e.commit(7)
    e.close()
    e2 = open_engine(type(e).__name__ == "KeyValueStoreSQLite" and "sqlite" or "memory", path)
    assert e2.stored_version() == 7
    assert e2.get(b"k000") == b"v0"
    assert e2.get(b"k055") is None
    assert len(e2) == 90
    e2.close()


def test_memory_engine_snapshot_compaction(tmp_path):
    path = str(tmp_path / "m")
    e = KeyValueStoreMemory(path)
    for i in range(10):
        e.set(b"%d" % i, b"x")
    e.commit(1)
    e.compact()
    e.set(b"post", b"y")
    e.commit(2)
    e.close()
    e2 = KeyValueStoreMemory(path)
    assert e2.stored_version() == 2
    assert e2.get(b"post") == b"y"
    assert e2.get(b"0") == b"x"
    e2.close()


def test_memory_engine_torn_tail(tmp_path):
    path = str(tmp_path / "torn")
    e = KeyValueStoreMemory(path)
    e.set(b"a", b"1")
    e.commit(1)
    e.close()
    with open(path + ".oplog", "ab") as f:
        f.write(b"\x00\x00\x00\x99GARBAGE")  # truncated record
    e2 = KeyValueStoreMemory(path)
    assert e2.get(b"a") == b"1"
    assert e2.stored_version() == 1
    e2.close()


# ──────────────────────── storage server tiering ────────────────────────
def _set(k, v):
    return Mutation(Op.SET, k, v)


def _clr(b, e):
    return Mutation(Op.CLEAR_RANGE, b, e)


def test_storage_flush_moves_data_to_engine():
    ss = StorageServer()
    ss.apply(10, [_set(b"a", b"1"), _set(b"b", b"2")])
    ss.apply(20, [_set(b"a", b"1.1"), _clr(b"b", b"c")])
    assert ss.get(b"a", 15) == b"1"
    ss.flush(10)
    assert ss.durable_version == 10
    assert ss.engine.get(b"a") == b"1" and ss.engine.get(b"b") == b"2"
    # reads at/after the durable version still see the overlay
    assert ss.get(b"a", 20) == b"1.1"
    assert ss.get(b"b", 20) is None
    ss.flush()
    assert ss.engine.get(b"a") == b"1.1"
    assert ss.engine.get(b"b") is None
    # read below durable version now rejected
    with pytest.raises(FDBError):
        ss.get(b"a", 5)


def test_storage_clear_range_shadows_engine_keys():
    ss = StorageServer()
    ss.apply(10, [_set(b"k1", b"a"), _set(b"k2", b"b"), _set(b"k3", b"c")])
    ss.flush(10)
    assert ss._overlay == {}
    ss.apply(20, [_clr(b"k1", b"k3")])
    assert ss.get(b"k1", 20) is None
    assert ss.get(b"k2", 20) is None
    assert ss.get(b"k3", 20) == b"c"
    assert ss.get_range(b"", b"\xff", 20) == [(b"k3", b"c")]


def test_storage_range_and_selectors_merge_tiers():
    ss = StorageServer()
    ss.apply(10, [_set(b"a", b"1"), _set(b"c", b"3")])
    ss.flush(10)
    ss.apply(20, [_set(b"b", b"2"), _set(b"a", b"1.1")])
    assert ss.get_range(b"", b"\xff", 20) == [
        (b"a", b"1.1"), (b"b", b"2"), (b"c", b"3")
    ]
    assert ss.get_range(b"", b"\xff", 20, reverse=True, limit=2) == [
        (b"c", b"3"), (b"b", b"2")
    ]
    assert ss.resolve_selector(KeySelector.first_greater_than(b"a"), 20) == b"b"
    assert ss.resolve_selector(KeySelector.last_less_than(b"c"), 20) == b"b"


def test_storage_recovery_from_engine_plus_log(tmp_path):
    eng_path = str(tmp_path / "e")
    wal_path = str(tmp_path / "w")
    engine = KeyValueStoreMemory(eng_path)
    tlog = TLog(wal_path=wal_path)
    ss = StorageServer(engine=engine)
    ss.apply(10, [_set(b"a", b"1")])
    tlog.push(10, [_set(b"a", b"1")])
    ss.flush(10)  # durable
    ss.apply(20, [_set(b"b", b"2")])
    tlog.push(20, [_set(b"b", b"2")])  # in WAL, not yet durable in engine
    engine.close()
    tlog.close()

    # crash + restart: engine at version 10, WAL has everything
    engine2 = KeyValueStoreMemory(eng_path)
    records = TLog.recover(wal_path)
    ss2 = StorageServer.recover(engine2, records)
    assert ss2.durable_version == 10
    assert ss2.version == 20
    assert ss2.get(b"a", 20) == b"1"
    assert ss2.get(b"b", 20) == b"2"


def test_cluster_restart_end_to_end(tmp_path):
    """Full-cluster crash/restart: engine snapshot + WAL replay, version
    authority resumes above everything recovered, old reads fenced."""
    from foundationdb_tpu.server.cluster import Cluster

    wal = str(tmp_path / "wal")
    eng_path = str(tmp_path / "store")
    c1 = Cluster(
        wal_path=wal,
        storage_engines=[KeyValueStoreMemory(eng_path)],
        resolver_backend="cpu",
    )
    db1 = c1.database()
    db1[b"a"] = b"1"
    c1.storage.flush()  # make durable, then write more (WAL-only)
    db1[b"b"] = b"2"
    pre_crash_version = c1.sequencer.committed_version
    tr_old = db1.create_transaction()
    tr_old.get_read_version()  # in-flight across the "crash"
    c1.storage.engine.close()
    c1.tlog.close()

    c2 = Cluster(
        wal_path=wal,
        storage_engines=[KeyValueStoreMemory(eng_path)],
        resolver_backend="cpu",
    )
    db2 = c2.database()
    assert c2.sequencer.committed_version >= pre_crash_version
    assert db2[b"a"] == b"1"
    assert db2[b"b"] == b"2"
    db2[b"c"] = b"3"  # writes resume with monotone versions
    assert db2[b"c"] == b"3"
    # a transaction from the old incarnation is fenced by the new window
    tr = db2.create_transaction()
    tr.set_read_version(pre_crash_version - 1)
    tr.set(b"x", b"y")
    with pytest.raises(FDBError) as ei:
        tr.commit()
    assert ei.value.code == 1007  # transaction_too_old


def test_storage_differential_vs_dict_oracle():
    """Randomized sets/clears/flushes vs a plain dict, reads at latest."""
    rng = random.Random(5)
    ss = StorageServer()
    oracle = {}
    v = 0
    keys = [b"k%02d" % i for i in range(30)]
    for _ in range(300):
        v += 1
        op = rng.random()
        if op < 0.5:
            k = rng.choice(keys)
            val = b"v%d" % rng.randrange(1000)
            ss.apply(v, [_set(k, val)])
            oracle[k] = val
        elif op < 0.7:
            b, e = sorted(rng.sample(keys, 2))
            ss.apply(v, [_clr(b, e)])
            for k in list(oracle):
                if b <= k < e:
                    del oracle[k]
        elif op < 0.85:
            ss.apply(v, [])
        else:
            ss.apply(v, [])
            ss.flush(v - rng.randrange(0, 3))
        got = dict(ss.get_range(b"", b"\xff", ss.version))
        assert got == oracle, f"divergence at version {v}"


# ──────────────── versioned engine (the Redwood role) ───────────────────
def test_versioned_engine_chains_and_prune(versioned_factory):
    e = versioned_factory()
    e.set_versioned(b"a", 10, b"1")
    e.set_versioned(b"a", 20, b"2")
    e.set_versioned(b"a", 30, None)  # tombstone
    e.set_versioned(b"b", 20, b"b2")
    e.commit(30)
    assert e.get_at(b"a", 15) == b"1"
    assert e.get_at(b"a", 25) == b"2"
    assert e.get_at(b"a", 35) is None
    assert e.get_at(b"a", 5) is None  # before first write
    assert list(e.iter_range_at(b"", b"\xff", 20)) == [(b"a", b"2"), (b"b", b"b2")]
    assert list(e.iter_range_at(b"", b"\xff", 31)) == [(b"b", b"b2")]
    # prune keeps the base every admissible read needs
    e.prune(20)
    assert e.get_at(b"a", 20) == b"2"
    assert e.get_at(b"a", 35) is None
    # a tombstone base below the horizon drops the whole chain
    e.prune(31)
    assert e.get_at(b"a", 35) is None
    assert list(e.iter_chains(b"a", b"a\x00")) == []
    e.close()


def test_versioned_engine_recovery(versioned_factory):
    e = versioned_factory("recov")
    for v in (10, 20, 30):
        e.set_versioned(b"k", v, b"%d" % v)
    e.prune(10)
    e.commit(30)
    e.compact()
    e.set_versioned(b"k", 40, b"40")
    e.commit(40)
    e.close()
    e2 = versioned_factory("recov")
    assert e2.stored_version() == 40
    assert e2.oldest_retained == 10
    for v, want in ((10, b"10"), (25, b"20"), (35, b"30"), (45, b"40")):
        assert e2.get_at(b"k", v) == want, v
    e2.close()


def test_storage_versioned_engine_serves_subdurable_reads(versioned_factory):
    """The integration contract: with a versioned engine the durability
    frontier runs ahead of the read floor — reads BELOW durable_version
    still serve from engine history (ref: Redwood extending the MVCC
    window into the durable tier)."""
    ss = StorageServer(engine=versioned_factory())
    assert ss.versioned_engine
    ss.apply(10, [_set(b"a", b"1"), _set(b"b", b"x")])
    ss.apply(20, [_set(b"a", b"2"), _clr(b"b", b"c")])
    ss.apply(30, [_set(b"a", b"3")])
    ss.flush()  # ALL versions go durable
    assert ss.durable_version == 30
    assert ss._overlay == {}
    assert ss.oldest_version == 0  # floor did NOT jump with durability
    # point reads below the durable version
    assert ss.get(b"a", 10) == b"1"
    assert ss.get(b"a", 25) == b"2"
    assert ss.get(b"b", 15) == b"x"
    assert ss.get(b"b", 25) is None
    # range reads below the durable version
    assert ss.get_range(b"", b"\xff", 15) == [(b"a", b"1"), (b"b", b"x")]
    assert ss.get_range(b"", b"\xff", 30) == [(b"a", b"3")]
    # selector walk at a historical version
    assert ss.resolve_selector(KeySelector.first_greater_than(b"a"), 15) == b"b"
    # the floor still advances by policy, pruning history
    ss.advance_window(20)
    with pytest.raises(FDBError):
        ss.get(b"a", 15)
    assert ss.get(b"a", 25) == b"2"  # >= floor still fine


def test_storage_versioned_mixed_tier_reads(versioned_factory):
    """Reads merge overlay (undurable) over engine history correctly."""
    ss = StorageServer(engine=versioned_factory())
    ss.apply(10, [_set(b"a", b"1"), _set(b"c", b"c1")])
    ss.flush(10)
    ss.apply(20, [_set(b"b", b"2"), _set(b"a", b"1.1")])  # overlay only
    assert ss.get_range(b"", b"\xff", 20) == [
        (b"a", b"1.1"), (b"b", b"2"), (b"c", b"c1")
    ]
    assert ss.get_range(b"", b"\xff", 10) == [(b"a", b"1"), (b"c", b"c1")]
    assert ss.get(b"a", 10) == b"1"


def test_storage_versioned_differential_history_oracle(versioned_factory):
    """Randomized sets/clears/flushes vs a full version-history oracle:
    every read at every version >= the floor must match, across flush
    boundaries (the single-version engines can only check latest)."""
    rng = random.Random(11)
    ss = StorageServer(engine=versioned_factory())
    history = {}  # version -> snapshot dict
    snap = {}
    v = 0
    keys = [b"k%02d" % i for i in range(12)]
    for _ in range(120):
        v += 1
        op = rng.random()
        if op < 0.55:
            k = rng.choice(keys)
            val = b"v%d" % rng.randrange(1000)
            ss.apply(v, [_set(k, val)])
            snap[k] = val
        elif op < 0.75:
            b, e = sorted(rng.sample(keys, 2))
            ss.apply(v, [_clr(b, e)])
            for k in list(snap):
                if b <= k < e:
                    del snap[k]
        else:
            ss.apply(v, [])
            if rng.random() < 0.5:
                ss.flush(v - rng.randrange(0, 4))
        history[v] = dict(snap)
    ss.flush()
    for rv in range(1, v + 1):
        got = dict(ss.get_range(b"", b"\xff", rv))
        assert got == history[rv], f"divergence at read version {rv}"


def test_storage_versioned_export_ingest_preserves_history(versioned_factory):
    """Shard export from a versioned storage carries engine-held history,
    so the joiner serves the same sub-durable snapshots as the source."""
    src = StorageServer(engine=versioned_factory("src"))
    src.apply(10, [_set(b"m", b"1")])
    src.apply(20, [_set(b"m", b"2")])
    src.flush()  # history lives in the ENGINE now
    src.apply(30, [_set(b"m", b"3")])  # and a bit in the overlay
    dst = StorageServer(engine=versioned_factory("dst"))
    for v in (10, 20, 30):
        dst.apply(v, [])  # version-synced replica
    dst.ingest_shard(b"m", b"n", src.export_shard(b"m", b"n"))
    assert dst.get(b"m", 15) == b"1"
    assert dst.get(b"m", 25) == b"2"
    assert dst.get(b"m", 30) == b"3"


def test_versioned_open_ended_ranges(versioned_factory):
    """ADVICE r5 (high): the disk engine compared ``k < NULL`` for
    end=None, so iter_chains/erase_range/clear_range silently no-oped on
    the LAST shard's open upper bound. Both Redwood-role engines must
    treat end=None as +infinity, like iter_range_at does."""
    eng = versioned_factory("open")
    eng.set_versioned(b"a", 10, b"1")
    eng.set_versioned(b"m", 10, b"1")
    eng.set_versioned(b"m", 20, b"2")
    eng.set_versioned(b"z", 20, b"z")
    eng.commit(20)
    chains = dict(eng.iter_chains(b"m", None))
    assert chains == {b"m": [(10, b"1"), (20, b"2")],
                      b"z": [(20, b"z")]}
    eng.clear_range(b"z", None)  # tombstone the open-ended tail
    assert eng.get_at(b"z", 20) is None
    eng.erase_range(b"m", None)  # physical eviction of the tail
    assert dict(eng.iter_chains(b"m", None)) == {}
    assert eng.get_at(b"a", 20) == b"1"  # keys below begin untouched


def test_versioned_last_shard_move_open_ended(versioned_factory):
    """Moving the open-ended LAST shard (end=None, as ShardMap's final
    range reports it) between versioned storages: the export must carry
    the engine-held history and the ingest must evict the joiner's stale
    pre-move copy — on both engines (the disk engine silently moved
    nothing before the open-ended range fix)."""
    src = StorageServer(engine=versioned_factory("src"))
    src.apply(10, [_set(b"t/a", b"1")])
    src.apply(20, [_set(b"t/a", b"2")])
    src.flush()  # history now lives in the ENGINE
    src.apply(30, [_set(b"t/b", b"3")])  # plus overlay
    dst = StorageServer(engine=versioned_factory("dst"))
    # stale pre-move copy on the joiner that the ingest must evict
    dst.apply(5, [_set(b"t/a", b"STALE")])
    dst.flush()
    for v in (10, 20, 30):
        dst.apply(v, [])
    dst.ingest_shard(b"t", None, src.export_shard(b"t", None))
    assert dst.get(b"t/a", 15) == b"1"  # engine-held history moved
    assert dst.get(b"t/a", 30) == b"2"
    assert dst.get(b"t/b", 30) == b"3"
    dst.flush()  # fold the ingested chains into the engine
    assert dst.engine.get_at(b"t/a", 30) == b"2"  # stale copy evicted


def test_cluster_versioned_engine_end_to_end(versioned_factory, tmp_path):
    """Cluster on the versioned engine: commits, aggressive durability,
    reads at old versions, crash/restart recovery."""
    from foundationdb_tpu.server.cluster import Cluster

    wal = str(tmp_path / "wal")
    c1 = Cluster(wal_path=wal,
                 storage_engines=[versioned_factory("store")],
                 resolver_backend="cpu")
    c1.commit_proxy.pump_interval = 2  # pump (flush-to-latest) often
    db1 = c1.database()
    tr = db1.create_transaction()
    db1[b"a"] = b"1"
    rv_old = tr.get_read_version()
    for i in range(10):
        db1[b"k%d" % i] = b"v"
    db1[b"a"] = b"2"
    # the pump has flushed past rv_old; the versioned engine still serves it
    assert c1.storage.durable_version > rv_old
    assert tr.get(b"a", snapshot=True) == b"1"
    c1.storage.engine.close()
    c1.tlog.close()
    c2 = Cluster(wal_path=wal,
                 storage_engines=[versioned_factory("store")],
                 resolver_backend="cpu")
    db2 = c2.database()
    assert db2[b"a"] == b"2"
    assert all(db2[b"k%d" % i] == b"v" for i in range(10))
    db2[b"post"] = b"x"
    assert db2[b"post"] == b"x"


def test_versioned_ingest_over_stale_copy_no_chain_corruption(versioned_factory):
    """Regression (round-2 review, confirmed by execution): ingesting a
    shard onto a versioned storage that already held keys in the range
    durably must physically erase the stale copy. A clear_range would
    tombstone at the dst durable version and the next flush would append
    the ingested chain's LOWER versions after it, breaking the ascending
    invariant — reads then silently return wrong values."""
    src = StorageServer(engine=versioned_factory("s"))
    src.apply(5, [_set(b"m", b"x")])
    src.apply(20, [_set(b"m", b"y")])

    dst = StorageServer(engine=versioned_factory("d"))
    dst.apply(50, [_set(b"m", b"stale")])
    dst.flush()  # stale copy durable at 50
    dst.ingest_shard(b"m", b"n", src.export_shard(b"m", b"n"))
    assert dst.get(b"m", 25) == b"y"
    assert dst.get(b"m", 10) == b"x"
    assert dst.get(b"m", 50) == b"y"
    # the next durability round flushes the ingested history down;
    # the engine chain must come out ascending, reads unchanged
    dst.apply(60, [_set(b"m", b"z")])
    dst.flush()
    assert dst._overlay == {}
    chains = dict(dst.engine.iter_chains(b"m", b"n"))
    vs = [v for v, _ in chains[b"m"]]
    assert vs == sorted(vs) == [5, 20, 60], vs
    assert dst.get(b"m", 25) == b"y"
    assert dst.get(b"m", 10) == b"x"
    assert dst.get(b"m", 60) == b"z"


def test_versioned_erase_range_durable(versioned_factory):
    e = versioned_factory("er")
    e.set_versioned(b"a", 10, b"1")
    e.set_versioned(b"b", 10, b"1")
    e.erase_range(b"a", b"b")
    e.commit(10)
    e.close()
    e2 = versioned_factory("er")
    assert e2.get_at(b"a", 10) is None
    assert e2.get_at(b"b", 10) == b"1"
    e2.close()


def test_fsync_path_exercised_end_to_end(tmp_path, monkeypatch):
    """Round-1 verdict: 'durable' meant 'flushed to page cache' — the
    fsync path was never exercised. Cluster(fsync=True) must drive
    os.fsync on every commit's tlog push and on engine commits, and the
    cluster still recovers correctly."""
    import os as os_mod

    from foundationdb_tpu.server.cluster import Cluster
    from tests.conftest import TEST_KNOBS

    calls = {"n": 0}
    real_fsync = os_mod.fsync

    def counting_fsync(fd):
        calls["n"] += 1
        return real_fsync(fd)

    monkeypatch.setattr("os.fsync", counting_fsync)
    wal = str(tmp_path / "wal")
    eng = open_engine("sqlite", str(tmp_path / "store"), fsync=True)
    c = Cluster(wal_path=wal, fsync=True, storage_engines=[eng],
                n_tlogs=3, **TEST_KNOBS)
    db = c.database()
    for i in range(5):
        db[b"k%d" % i] = b"v"
    pushes = calls["n"]
    assert pushes >= 15, pushes  # >= one fsync per tlog replica per commit
    c.storage.flush()
    c.close()
    c2 = Cluster(wal_path=wal, n_tlogs=3,
                 storage_engines=[open_engine("sqlite", str(tmp_path / "store"))],
                 **TEST_KNOBS)
    db2 = c2.database()
    for i in range(5):
        assert db2[b"k%d" % i] == b"v"
    c2.close()


# ── round-3: sqlite engine under stress (VERDICT weak #7) ───────────────
def test_sqlite_large_store_and_range_scans(tmp_path):
    """Tens of thousands of rows through the engine: versioned flushes,
    lazy range iteration, point lookups, clears, reopen — the shapes a
    real storage tier drives, not just the CRUD basics."""
    eng = open_engine("sqlite", str(tmp_path / "big.db"))
    N = 30_000
    for i in range(0, N, 1000):
        for j in range(i, i + 1000):
            eng.set(b"key%08d" % j, b"val%d" % j)
        eng.commit(i + 1000)
    assert len(eng) == N
    assert eng.stored_version() == N
    # bounded scans from arbitrary offsets, forward and reverse
    rows = eng.get_range(b"key00015000", b"key00016000", limit=10)
    assert [k for k, _ in rows] == [b"key%08d" % i for i in range(15000, 15010)]
    rrows = eng.get_range(b"key00015000", b"key00016000", limit=3,
                          reverse=True)
    assert [k for k, _ in rrows] == [b"key%08d" % i
                                     for i in (15999, 15998, 15997)]
    # lazy iterator across a clear
    eng.clear_range(b"key00020000", b"key00021000")
    eng.commit(N + 1)
    seen = sum(1 for _ in eng.iter_range(b"key00019990", b"key00021010"))
    assert seen == 20
    eng.compact()
    eng.close()
    # reopen: everything durable
    eng2 = open_engine("sqlite", str(tmp_path / "big.db"))
    assert len(eng2) == N - 1000
    assert eng2.stored_version() == N + 1
    assert eng2.get(b"key00000042") == b"val42"
    assert eng2.get(b"key00020500") is None
    eng2.close()


def test_sqlite_crash_mid_commit_is_atomic(tmp_path):
    """Kill a PROCESS mid-commit-burst: on reopen the engine must hold
    a consistent versioned state — every row of the stored version
    present, nothing from an unfinished commit (sqlite's WAL contract,
    which the storage tier's durable_version accounting relies on)."""
    import os
    import subprocess
    import sys

    path = str(tmp_path / "crash.db")
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    script = f'''
import os, sys
sys.path.insert(0, {repo_root!r})
from foundationdb_tpu.server.kvstore import open_engine
eng = open_engine("sqlite", {path!r}, fsync=True)
v = eng.stored_version()
while True:
    v += 1
    for j in range(200):
        eng.set(b"k%06d" % j, b"v%d-%d" % (v, j))
    eng.commit(v)
    if v == 3:
        print("READY", flush=True)  # parent kills us mid-burst after this
'''
    p = subprocess.Popen([sys.executable, "-c", script],
                         stdout=subprocess.PIPE, text=True)
    assert p.stdout.readline().strip() == "READY"
    p.kill()
    p.wait()

    eng = open_engine("sqlite", path, fsync=True)
    v = eng.stored_version()
    assert v >= 3
    rows = dict(eng.get_range(b"k", b"l"))
    assert len(rows) == 200
    # atomicity: every surviving row belongs to ONE committed version
    # (no torn mix of committed and uncommitted generations)
    gens = {val.split(b"-")[0] for val in rows.values()}
    assert gens == {b"v%d" % v}, (v, sorted(gens)[:3])
    eng.close()


def test_sqlite_backed_cluster_survives_repeated_crashes(tmp_path):
    """The sqlite engine as a cluster's durable tier through several
    crash/recover cycles with interleaved clears and atomic adds."""
    from tests.conftest import TEST_KNOBS

    from foundationdb_tpu.server.cluster import Cluster

    total = 0
    for incarnation in range(4):
        c = Cluster(
            storage_engines=[open_engine("sqlite", str(tmp_path / "c.db"))],
            wal_path=str(tmp_path / "c.wal"),
            coordination_dir=str(tmp_path / "co"),
            resolver_backend="cpu", **TEST_KNOBS,
        )
        db = c.database()
        for i in range(25):
            db.run(lambda tr: tr.add(b"acc", (1).to_bytes(8, "little")))
            db[b"inc%d/%02d" % (incarnation, i)] = b"x" * 50
        total += 25
        db.run(lambda tr: tr.clear_range(b"inc%d/" % incarnation,
                                         b"inc%d0" % incarnation))
        assert int.from_bytes(db[b"acc"], "little") == total
        for s in c.storages:
            s.flush()
        c.close()  # "crash": recovery replays WAL over the durable store
    c = Cluster(
        storage_engines=[open_engine("sqlite", str(tmp_path / "c.db"))],
        wal_path=str(tmp_path / "c.wal"),
        coordination_dir=str(tmp_path / "co"),
        resolver_backend="cpu", **TEST_KNOBS,
    )
    db = c.database()
    assert int.from_bytes(db[b"acc"], "little") == total
    assert db.run(lambda tr: list(tr.get_range(b"inc", b"ind"))) == []
    c.close()


# ─────────────── disk-resident versioned engine (redwood) ────────────────
def test_redwood_crash_mid_write_rolls_back_to_commit(tmp_path):
    """Kill -9 a process holding uncommitted versioned writes: sqlite's
    WAL must roll the tail back to the last commit(version) atomically —
    the disk engine's crash contract (ref: Redwood recovering to its
    last committed version)."""
    import subprocess
    import sys

    path = str(tmp_path / "rw")
    script = f"""
import os
from foundationdb_tpu.server.kvstore import KeyValueStoreVersionedDisk
e = KeyValueStoreVersionedDisk({path!r})
e.set_versioned(b"a", 10, b"1")
e.set_versioned(b"a", 20, b"2")
e.commit(20)                     # durable point
e.set_versioned(b"a", 30, b"3")  # never committed
e.set_versioned(b"b", 30, b"x")
print("READY", flush=True)
os.kill(os.getpid(), 9)
"""
    r = subprocess.run([sys.executable, "-c", script],
                       capture_output=True, text=True, timeout=120,
                       env={**__import__("os").environ,
                            "JAX_PLATFORMS": "cpu"})
    assert "READY" in r.stdout
    from foundationdb_tpu.server.kvstore import KeyValueStoreVersionedDisk

    e2 = KeyValueStoreVersionedDisk(path)
    assert e2.stored_version() == 20
    assert e2.get_at(b"a", 25) == b"2"
    assert e2.get_at(b"a", 35) == b"2"  # v30 write rolled back
    assert e2.get_at(b"b", 35) is None
    e2.close()


def test_redwood_store_beyond_cache_rss_bounded(tmp_path):
    """The disk engine's reason to exist: a store larger than its page
    cache must NOT ride in process memory (the RAM-chained engine holds
    every chain in Python dicts). Write ~40MB of versioned rows — 10x
    the engine's 4MB page cache — and assert the process's resident-set
    growth stays a small fraction of the data size while versioned
    reads keep serving from disk."""
    import gc

    from foundationdb_tpu.server.kvstore import KeyValueStoreVersionedDisk

    def rss_mb():
        with open("/proc/self/status") as f:
            for ln in f:
                if ln.startswith("VmRSS"):
                    return int(ln.split()[1]) / 1024.0
        return 0.0

    e = KeyValueStoreVersionedDisk(str(tmp_path / "big"))
    gc.collect()
    base = rss_mb()
    val = b"x" * 1000
    n = 40_000  # ~40MB of values (+ keys/overhead)
    for i in range(n):
        e.set_versioned(b"key%08d" % i, 10, val)
        if i % 5000 == 4999:
            e.commit(10)  # bound sqlite's uncommitted-txn memory
    e.commit(10)
    e.compact()
    gc.collect()
    grown = rss_mb() - base
    # stored ~44MB on disk; RSS growth must stay well under the data
    # size (page cache 4MB + sqlite WAL overhead + allocator slack)
    assert grown < 25, f"RSS grew {grown:.1f}MB for a ~44MB store"
    # and the data is really there, versioned, served from disk
    assert e.get_at(b"key%08d" % (n - 1), 15) == val
    assert e.get_at(b"key%08d" % 0, 5) is None
    got = list(e.iter_range_at(b"key00000000", b"key00000005", 15))
    assert len(got) == 5
    import os as _os
    disk = sum(
        _os.path.getsize(str(tmp_path / "big") + suf)
        for suf in ("", "-wal") if _os.path.exists(str(tmp_path / "big") + suf)
    )
    assert disk > 35 * 1024 * 1024, f"store only {disk} bytes on disk"
    e.close()


def test_redwood_prune_reclaims_disk_history(tmp_path):
    """prune() must translate into real row deletion on disk, with the
    first prune after reopen sweeping pre-crash history that has no
    in-memory prunable record."""
    from foundationdb_tpu.server.kvstore import KeyValueStoreVersionedDisk

    path = str(tmp_path / "pr")
    e = KeyValueStoreVersionedDisk(path)
    for v in range(10, 110, 10):
        e.set_versioned(b"hot", v, b"%d" % v)
    e.set_versioned(b"gone", 10, None)  # lone tombstone
    e.commit(100)
    e.close()  # no prune ran: 11 rows on disk

    e2 = KeyValueStoreVersionedDisk(path)
    rows = e2._conn.execute("SELECT COUNT(*) FROM kvv").fetchone()[0]
    assert rows == 11
    e2.prune(95)  # full-table sweep (fresh open, no prunable set)
    e2.commit(100)
    rows = e2._conn.execute("SELECT COUNT(*) FROM kvv").fetchone()[0]
    # hot keeps base@90 + 100; the lone tombstone drops
    assert rows == 2, rows
    assert e2.get_at(b"hot", 95) == b"90"
    assert e2.get_at(b"hot", 200) == b"100"
    assert e2.oldest_retained == 95
    e2.close()
