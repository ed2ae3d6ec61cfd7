"""RPC transport + remote-cluster tests: wire codec round-trips, a served
cluster driven through the unmodified client stack, concurrent clients
over one multiplexed connection, watches across the network, and a real
fdbserver subprocess found through a cluster file."""

import os
import signal
import subprocess
import sys
import threading
import time

import pytest

import foundationdb_tpu as fdb
from foundationdb_tpu.core.errors import FDBError
from foundationdb_tpu.core.keys import KeySelector
from foundationdb_tpu.core.mutations import Mutation, Op
from foundationdb_tpu.rpc import wire
from foundationdb_tpu.rpc.service import (
    RemoteCluster,
    parse_cluster_file,
    serve_cluster,
    write_cluster_file,
)
from foundationdb_tpu.rpc.transport import RpcClient, RpcServer
from foundationdb_tpu.server.cluster import Cluster
from foundationdb_tpu.server.proxy import CommitRequest

from conftest import TEST_KNOBS


# ───────────────────────────── wire codec ─────────────────────────────
def test_wire_roundtrip_primitives():
    values = [
        None, True, False, 0, -1, 2**40, -(2**70), 3.5,
        b"", b"\x00\xff" * 5, "héllo", [], [1, b"x", None],
        (1, (2, 3)), {"a": 1, b"k": [True]},
    ]
    for v in values:
        assert wire.loads(wire.dumps(v)) == v


def test_wire_roundtrip_structs():
    m = wire.loads(wire.dumps(Mutation(Op.ADD, b"k", b"\x01")))
    assert (m.op, m.key, m.param) == (Op.ADD, b"k", b"\x01")
    m2 = wire.loads(wire.dumps(Mutation(Op.CLEAR_RANGE, b"a", b"b")))
    assert (m2.op, m2.key, m2.param) == (Op.CLEAR_RANGE, b"a", b"b")
    s = wire.loads(wire.dumps(KeySelector(b"key", True, -2)))
    assert (s.key, s.or_equal, s.offset) == (b"key", True, -2)
    e = wire.loads(wire.dumps(FDBError(1020)))
    assert isinstance(e, FDBError) and e.code == 1020
    req = CommitRequest(
        read_version=7,
        mutations=[Mutation(Op.SET, b"k", b"v")],
        read_conflict_ranges=[(b"a", b"b")],
        write_conflict_ranges=[(b"k", b"k\x00")],
        report_conflicting_keys=True,
    )
    r2 = wire.loads(wire.dumps(req))
    assert r2.read_version == 7
    assert r2.read_conflict_ranges == [(b"a", b"b")]
    assert r2.write_conflict_ranges == [(b"k", b"k\x00")]
    assert r2.report_conflicting_keys is True
    assert r2.mutations[0].key == b"k"


def test_wire_rejects_unknown_types():
    with pytest.raises(TypeError):
        wire.dumps(object())


# ───────────────────────────── transport ──────────────────────────────
def test_rpc_server_basic_calls_and_errors():
    def boom():
        raise ValueError("nope")

    def fdb_boom():
        raise FDBError(1020)

    server = RpcServer("127.0.0.1", 0, {
        "echo": lambda x: x,
        "add": lambda a, b: a + b,
        "boom": boom,
        "fdb_boom": fdb_boom,
    })
    try:
        client = RpcClient(server.host, server.port)
        assert client.call("echo", b"payload") == b"payload"
        assert client.call("add", 2, 3) == 5
        with pytest.raises(FDBError) as ei:
            client.call("fdb_boom")
        assert ei.value.code == 1020
        from foundationdb_tpu.rpc.transport import RemoteError

        with pytest.raises(RemoteError, match="ValueError"):
            client.call("boom")
        with pytest.raises(RemoteError, match="no such endpoint"):
            client.call("missing")
        client.close()
    finally:
        server.close()


def test_rpc_multiplexed_concurrent_calls():
    server = RpcServer("127.0.0.1", 0, {"double": lambda x: x * 2})
    try:
        client = RpcClient(server.host, server.port)
        results = {}

        def worker(i):
            results[i] = client.call("double", i)

        threads = [threading.Thread(target=worker, args=(i,)) for i in range(32)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert results == {i: i * 2 for i in range(32)}
        client.close()
    finally:
        server.close()


# ─────────────────────────── served cluster ───────────────────────────
@pytest.fixture
def remote_db():
    cluster = Cluster(resolver_backend="cpu", commit_pipeline="thread",
                      **TEST_KNOBS)
    server = serve_cluster(cluster)
    rc = RemoteCluster([server.address])
    yield rc.database(), cluster, server
    rc.close()
    server.close()
    cluster.close()


def test_remote_transactions_end_to_end(remote_db):
    db, _, _ = remote_db
    db[b"a"] = b"1"
    db[b"b"] = b"2"
    db[b"c"] = b"3"
    assert db[b"a"] == b"1"

    def txn(tr):
        tr[b"d"] = tr[b"a"] + tr[b"b"]
        tr.add(b"counter", (5).to_bytes(8, "little"))
        return tr.get_range(b"a", b"z")

    rows = db.run(txn)
    # RYW: the range view includes this txn's own uncommitted writes
    assert [k for k, _ in rows] == [b"a", b"b", b"c", b"counter", b"d"]
    assert db[b"d"] == b"12"
    assert int.from_bytes(db[b"counter"], "little") == 5

    # selectors resolve server-side
    k = db.get_key(KeySelector.first_greater_than(b"a"))
    assert k == b"b"
    db.clear_range(b"a", b"c")
    assert db[b"a"] is None
    assert db[b"c"] == b"3"


def test_remote_conflicts_retry(remote_db):
    db, cluster, _ = remote_db
    local_db = cluster.database()
    db[b"k"] = b"0"
    tr = db.create_transaction()
    _ = tr[b"k"]
    # a competing local write lands first → remote commit must conflict
    local_db[b"k"] = b"other"
    tr[b"k"] = b"mine"
    with pytest.raises(FDBError) as ei:
        tr.commit()
    assert ei.value.code in (1020, 1007)
    assert ei.value.is_retryable


def test_remote_watch_fires_across_clients(remote_db):
    db, _, server = remote_db
    rc2 = RemoteCluster([server.address])
    db2 = rc2.database()
    try:
        db[b"w"] = b"before"
        watch = db.watch(b"w")
        assert not watch.is_set()
        db2[b"w"] = b"after"
        assert watch.wait(timeout=5)
    finally:
        rc2.close()


def test_remote_concurrent_counter_clients(remote_db):
    db, _, server = remote_db
    n_threads, n_each = 8, 10
    clusters = [RemoteCluster([server.address]) for _ in range(n_threads)]

    def worker(rc):
        d = rc.database()
        for _ in range(n_each):
            d.add(b"ctr", (1).to_bytes(8, "little"))

    threads = [threading.Thread(target=worker, args=(c,)) for c in clusters]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for c in clusters:
        c.close()
    assert int.from_bytes(db[b"ctr"], "little") == n_threads * n_each


def test_remote_layers_stack(remote_db):
    """Tuple/subspace/directory layers run unchanged against the wire."""
    db, _, _ = remote_db
    from foundationdb_tpu.layers.directory import DirectoryLayer
    from foundationdb_tpu.layers.tuple import pack

    d = DirectoryLayer()
    app = db.run(lambda tr: d.create_or_open(tr, ("app", "users")))
    db.run(lambda tr: tr.set(app.pack((42,)), b"alice"))
    assert db.run(lambda tr: tr.get(app.pack((42,)))) == b"alice"
    assert db.run(lambda tr: d.exists(tr, ("app", "users")))
    # plain tuple-layer row too
    db[pack(("t", 1))] = b"x"
    assert db[pack(("t", 1))] == b"x"


def test_remote_status_and_knobs(remote_db):
    db, cluster, _ = remote_db
    st = db.status()
    assert st["cluster"]["database_available"]
    assert db._cluster.knobs.batch_txn_capacity == cluster.knobs.batch_txn_capacity


def test_remote_health_status(remote_db):
    """The doctor's RPC surface: RemoteCluster.health_status() returns
    the served cluster's live health document, wire-clean."""
    db, cluster, _ = remote_db
    h = db._cluster.health_status()
    assert h["verdict"] == "healthy"
    assert set(h) >= {"probe", "recovery", "lag", "ratekeeper",
                      "reasons", "messages"}
    # served and local documents agree on the machine-checkable parts
    assert h["verdict"] == cluster.health_status()["verdict"]


def test_commit_unknown_result_on_lost_connection():
    cluster = Cluster(resolver_backend="cpu", **TEST_KNOBS)
    server = serve_cluster(cluster)
    rc = RemoteCluster([server.address])
    db = rc.database()
    db[b"k"] = b"v"
    tr = db.create_transaction()
    assert tr[b"k"] == b"v"  # read version pinned while the server lives
    tr[b"k2"] = b"v2"
    # sever every path before the commit RPC can be delivered
    server.close()
    cluster.close()
    with pytest.raises(FDBError) as ei:
        tr.commit()
    assert ei.value.code == 1021  # commit_unknown_result
    assert ei.value.is_maybe_committed
    rc.close()


# ───────────────────────── cluster files ──────────────────────────────
def test_cluster_file_roundtrip(tmp_path):
    path = str(tmp_path / "fdb.cluster")
    write_cluster_file(path, ["127.0.0.1:4500", "127.0.0.1:4501"],
                       description="test", cluster_id="abc123")
    desc, cid, addrs = parse_cluster_file(path)
    assert (desc, cid) == ("test", "abc123")
    assert addrs == ["127.0.0.1:4500", "127.0.0.1:4501"]


# ─────────────────────── real server subprocess ───────────────────────
@pytest.mark.slow
def test_fdbserver_subprocess(tmp_path):
    cluster_file = str(tmp_path / "fdb.cluster")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.Popen(
        [sys.executable, "-m", "foundationdb_tpu.tools.fdbserver",
         "--listen", "127.0.0.1:0", "--cluster-file", cluster_file,
         "--dir", str(tmp_path / "data"), "--resolver-backend", "cpu"],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, env=env, text=True,
    )
    try:
        line = proc.stdout.readline()
        assert "FDBD listening" in line, line
        db = fdb.open(cluster_file=cluster_file)
        db[b"proc"] = b"alive"
        assert db[b"proc"] == b"alive"

        def txn(tr):
            tr.add(b"n", (7).to_bytes(8, "little"))
            return tr.get_range(b"", b"\xff")

        rows = db.run(txn)
        assert any(k == b"proc" for k, _ in rows)
        assert int.from_bytes(db[b"n"], "little") == 7
        db._cluster.close()
    finally:
        proc.send_signal(signal.SIGTERM)
        try:
            proc.wait(timeout=10)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait()


# ───────────────────────────── transport auth ──────────────────────────
def test_rpc_auth_handshake():
    """With a shared secret both sides authenticate; a wrong or missing
    client secret is rejected before any endpoint is reachable."""
    from foundationdb_tpu.rpc.transport import ConnectionLost

    server = RpcServer("127.0.0.1", 0, {"echo": lambda x: x},
                       secret="hunter2")
    try:
        good = RpcClient(server.host, server.port, secret="hunter2")
        assert good.call("echo", 42) == 42
        good.close()

        # the confirmation frame makes a wrong secret fail at connect
        with pytest.raises(ConnectionLost, match="auth handshake"):
            RpcClient(server.host, server.port, secret="wrong")

        # a secret-less client never answers the challenge: its first
        # request frame is read as the (wrong) proof and the server
        # closes without dispatching anything
        naked = RpcClient(server.host, server.port)
        with pytest.raises(Exception):
            naked.call("echo", 1, timeout=5)
        naked.close()
    finally:
        server.close()


def test_remote_cluster_with_auth():
    cluster = Cluster(resolver_backend="cpu", **TEST_KNOBS)
    server = serve_cluster(cluster, secret="s3cret")
    try:
        remote = RemoteCluster(server.address, secret="s3cret")
        db = remote.database()
        db[b"authed"] = b"yes"
        assert db[b"authed"] == b"yes"
        remote.close()
    finally:
        server.close()
        cluster.close()


def test_grv_coalescing_leader_failure_releases_waiters():
    """Regression (round-5 review): a failed leader GRV round must
    release EVERY registered waiter (they fall back to direct calls) —
    not strand threads waiting on rounds no surviving leader will run."""
    import threading
    import time as _time

    from foundationdb_tpu.rpc.service import _CoalescingGrvProxy

    class FakeRC:
        def __init__(self):
            self.calls = 0
            self.gate = threading.Event()

        def _call(self, method, *args):
            self.calls += 1
            if self.calls == 1:
                self.gate.wait(5)  # hold round 1 until waiters register
                raise OSError("socket died")
            return 42

    rc = FakeRC()
    grv = _CoalescingGrvProxy(rc)
    results, errors = [], []

    def leader():
        try:
            results.append(grv.get_read_version())
        except Exception as e:
            errors.append(e)

    def waiter():
        results.append(grv.get_read_version())

    tl = threading.Thread(target=leader)
    tl.start()
    _time.sleep(0.1)  # leader is mid-flight
    tws = [threading.Thread(target=waiter) for _ in range(3)]
    for t in tws:
        t.start()
    _time.sleep(0.1)  # waiters registered for the next round
    rc.gate.set()  # leader's rpc now fails
    tl.join(timeout=5)
    for t in tws:
        t.join(timeout=5)
        assert not t.is_alive(), "waiter stranded after leader failure"
    assert len(errors) == 1  # the leader saw the failure
    assert results == [42, 42, 42]  # waiters fell back to direct calls
