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


# ─────────── a short request stays on its connection's thread ───────────
DECLARED = pytest.mark.parametrize("declared", [True, False],
                                   ids=["declared", "undeclared"])


def _short_server(declared, extra=None, **kw):
    """``storage_get`` echoes; declared inline or left to the pool."""
    handlers = {"storage_get": lambda k, rv=0: k}
    handlers.update(extra or {})
    return RpcServer(
        "127.0.0.1", 0, handlers,
        inline_methods={"storage_get"} if declared else (), **kw)


def _wait_for(pred, what, timeout=20.0):
    deadline = time.monotonic() + timeout
    while not pred():
        assert time.monotonic() < deadline, f"waited for {what}"
        time.sleep(0.001)


def _counted(server, n):
    """The counters are added after the reply is sent: wait for the
    n-th add, then read them."""
    _wait_for(lambda: sum(server.stats()["requests"].values()) == n,
              f"{n} requests counted")
    return server.stats()


@DECLARED
def test_short_request_is_answered_while_held_commits_fill_the_pool(
        declared):
    """Sixteen commit-class handlers hold all sixteen workers: a read
    declared inline is answered at once on its connection's thread; one
    left to the pool waits for a worker (the parent's behaviour)."""
    release = threading.Event()
    running = threading.Semaphore(0)

    def held():
        running.release()
        release.wait(30)
        return "held"

    server = _short_server(declared, {"commit": held}, max_workers=16)
    client = RpcClient(server.host, server.port)
    try:
        commits = [client.call_async("commit") for _ in range(16)]
        for _ in commits:
            assert running.acquire(timeout=20)
        read = client.call_async("storage_get", b"k")
        if declared:
            assert read.result(20) == b"k"
            assert not any(c.done() for c in commits)
        else:
            _wait_for(lambda: server.stats()["pool"]["queued"] == 1,
                      "the read to queue behind the commits")
            assert not read.done()
        release.set()
        assert read.result(20) == b"k"
        assert [c.result(20) for c in commits] == ["held"] * 16
        doc = _counted(server, 17)
    finally:
        release.set()
        client.close()
        server.close()
    assert doc["requests"]["read"] == 1 and doc["requests"]["commit"] == 16
    assert doc["inline_requests"] == {
        "read": int(declared), "grv": 0, "commit": 0, "admin": 0}


def test_parked_undeclared_endpoint_never_delays_a_declared_one():
    """Head of line: a connection multiplexes its client's threads, so
    the endpoint that parks (undeclared: the pool's) must not stand in
    front of the declared read sent after it on the same connection."""
    parked, release = threading.Event(), threading.Event()

    def park():
        parked.set()
        release.wait(30)
        return "woke"

    server = _short_server(True, {"park": park})
    client = RpcClient(server.host, server.port)
    try:
        first = client.call_async("park")
        assert parked.wait(20)
        for i in range(8):
            assert client.call("storage_get", b"k%d" % i, timeout=20) \
                == b"k%d" % i
        assert not first.done()
        release.set()
        assert first.result(20) == "woke"
        doc = _counted(server, 9)
    finally:
        release.set()
        client.close()
        server.close()
    assert doc["inline_requests"]["read"] == 8
    assert doc["inline_requests"]["admin"] == 0


@DECLARED
def test_handler_that_parks_hands_its_request_to_the_pool(declared):
    """A handler may return ``Park(resume)`` where it finds it has to
    wait after all: declared inline, the request moves to the pool and
    the connection goes on reading; on the pool, resume runs at once.
    Either way the caller gets resume's result, counted not inline."""
    from foundationdb_tpu.rpc.transport import Park

    release = threading.Event()
    resumed_on = []

    def resume():
        resumed_on.append(threading.current_thread().name)
        release.wait(30)
        return "granted"

    server = RpcServer(
        "127.0.0.1", 0,
        {"get_read_version": lambda: Park(resume),
         "storage_get": lambda k: k},
        inline_methods={"get_read_version", "storage_get"}
        if declared else ())
    client = RpcClient(server.host, server.port)
    try:
        grv = client.call_async("get_read_version")
        _wait_for(lambda: resumed_on, "the parked half to start")
        assert client.call("storage_get", b"k", timeout=20) == b"k"
        assert not grv.done()
        release.set()
        assert grv.result(20) == "granted"
        doc = _counted(server, 2)
    finally:
        release.set()
        client.close()
        server.close()
    assert resumed_on[0].startswith("rpc-handler")
    assert doc["requests"]["grv"] == 1
    assert doc["inline_requests"]["grv"] == 0
    assert doc["inline_requests"]["read"] == int(declared)


def test_grv_that_has_to_queue_is_answered_from_the_pool(remote_db):
    """The served cluster's GRV runs inline only on its grant-now path.
    With the ratekeeper denying, the request queues behind the grant
    loop from a pool thread, and a read sent after it on the same
    connection returns first."""
    _, cluster, server = remote_db
    rk = cluster.grv_proxy.inner.ratekeeper
    assert rk is not None
    admit, deny = rk.admit, threading.Event()
    rk.admit = lambda *a, **kw: not deny.is_set() and admit(*a, **kw)
    client = RpcClient(server.host, server.port)
    try:
        rv = client.call("get_read_version", "default", (), timeout=20)
        _wait_for(lambda: server.stats()["requests"]["grv"] >= 1,
                  "the granted GRV counted")
        before = server.stats()
        assert before["inline_requests"]["grv"] == before["requests"]["grv"]
        deny.set()
        queued = client.call_async("get_read_version", "default", ())
        _wait_for(lambda: cluster.grv_proxy._pending == 1,
                  "the GRV to queue")
        assert client.call("storage_get", b"nokey", rv, timeout=20) is None
        assert not queued.done()
        deny.clear()
        assert queued.result(20) >= rv
        _wait_for(lambda: server.stats()["requests"]["grv"]
                  == before["requests"]["grv"] + 1, "the GRV counted")
        after = server.stats()
    finally:
        deny.clear()
        rk.admit = admit
        client.close()
    assert after["inline_requests"]["grv"] == before["inline_requests"]["grv"]
    assert after["inline_requests"]["read"] \
        == before["inline_requests"]["read"] + 1
    assert after["inline_requests"]["commit"] == 0
    assert after["inline_requests"]["admin"] == 0


def test_served_cluster_declares_reads_grv_and_the_deferred_commit(
        remote_db):
    """``commit`` is declared since PR 38 (submitted where it was
    decoded, answered by a completion) and still never counts inline:
    it was not answered on the thread that read it."""
    db, _, server = remote_db
    assert server.inline_methods == {
        "ping", "get_read_version", "storage_get", "get_range",
        "resolve_selector", "read_batch", "commit"}
    assert not server.inline_methods & server.long_methods
    db[b"a"] = b"1"
    assert db[b"a"] == b"1"
    assert [k for k, _ in db.get_range(b"", b"\xff")] == [b"a"]
    _wait_for(lambda: server.stats()["requests"]["commit"] >= 1
              and server.stats()["inline_requests"]["read"] >= 2,
              "the commit and the reads counted")
    doc = server.stats()
    assert doc["inline_requests"]["read"] == doc["requests"]["read"]
    assert doc["inline_requests"]["commit"] == 0
    assert doc["deferred_requests"]["commit"] == doc["requests"]["commit"]
    assert doc["inline_requests"]["admin"] == 0
    assert doc["requests"]["admin"] >= 1  # hello, at least


# ───────────────────────── frames, read in bursts ──────────────────────
def _raw_connect(server):
    import socket

    sock = socket.create_connection((server.host, server.port), 5)
    sock.settimeout(20)
    return sock


def _drain_until_closed(sock):
    """The server closed the connection: end of stream, or a reset
    where it closed with bytes of ours unread."""
    try:
        while sock.recv(65536):
            pass
    except ConnectionResetError:
        pass


def _frame(msg):
    import struct

    payload = wire.dumps(msg)
    return struct.pack(">I", len(payload)) + payload


def _read_frames(sock, n):
    """n whole frames' payloads off a raw socket, by the frame's own
    rule (4-byte length, then exactly that many bytes)."""
    import struct

    def exact(k):
        got = b""
        while len(got) < k:
            chunk = sock.recv(k - len(got))
            assert chunk, "server closed early"
            got += chunk
        return got

    return [exact(struct.unpack(">I", exact(4))[0]) for _ in range(n)]


def _read_replies(sock, n):
    return [wire.loads(frame) for frame in _read_frames(sock, n)]


@DECLARED
def test_frame_split_across_recv_boundaries(declared):
    """A frame that arrives a few bytes at a time (header split too) is
    one request; what follows it in the same segment is the next."""
    server = _short_server(declared)
    sock = _raw_connect(server)
    try:
        data = _frame(("q", 1, "storage_get", (b"x" * 300,))) \
            + _frame(("q", 2, "storage_get", (b"y",)))
        for cut in (1, 3, 4, 7, 150):
            sock.sendall(data[:cut])
            data = data[cut:]
            time.sleep(0.01)
        sock.sendall(data)
        replies = sorted(_read_replies(sock, 2), key=lambda r: r[1])
        doc = _counted(server, 2)
    finally:
        sock.close()
        server.close()
    assert replies == [("r", 1, True, b"x" * 300), ("r", 2, True, b"y")]
    assert doc["recv_calls"] >= 6  # every read that returned bytes
    assert doc["inline_requests"]["read"] == 2 * int(declared)


@DECLARED
def test_replies_of_one_burst_arrive_whole_and_match_their_seq(declared):
    """Forty frames in one segment: forty whole replies, each the
    answer to its own seq, in fewer reads than requests."""
    server = _short_server(declared)
    sock = _raw_connect(server)
    n = 40
    try:
        sock.sendall(b"".join(
            _frame(("q", 100 + i, "storage_get", (b"%d" % i * (i + 1),)))
            for i in range(n)))
        replies = _read_replies(sock, n)
        doc = _counted(server, n)
    finally:
        sock.close()
        server.close()
    assert sorted(r[1] for r in replies) == [100 + i for i in range(n)]
    for kind, seq, ok, payload in replies:
        i = seq - 100
        assert (kind, ok, payload) == ("r", True, b"%d" % i * (i + 1))
    if declared:  # one thread, the burst's order
        assert [r[1] for r in replies] == [100 + i for i in range(n)]
    assert doc["requests"]["read"] == n
    assert 1 <= doc["recv_calls"] < n


@DECLARED
def test_oversized_frame_closes_the_connection_at_its_header(declared):
    import struct

    from foundationdb_tpu.rpc.transport import MAX_FRAME

    server = _short_server(declared)
    sock = _raw_connect(server)
    try:
        # a good request, then a header of MAX_FRAME + 1 and no payload:
        # the request in front of it is served (inline, before the
        # header is looked at again; on the pool its reply races the
        # close, as it always did), then the connection is closed with
        # nothing buffered for the frame
        sock.sendall(_frame(("q", 1, "storage_get", (b"k",)))
                     + struct.pack(">I", MAX_FRAME + 1))
        if declared:
            assert _read_replies(sock, 1) == [("r", 1, True, b"k")]
        _drain_until_closed(sock)
        # a frame of exactly MAX_FRAME is still only a header to wait on
        sock2 = _raw_connect(server)
        sock2.sendall(struct.pack(">I", MAX_FRAME) + b"\x00" * 8)
        sock2.settimeout(0.3)
        with pytest.raises(TimeoutError):
            sock2.recv(1)
        sock2.close()
    finally:
        sock.close()
        server.close()


def test_pre_auth_frame_over_64_bytes_is_refused_unbuffered():
    """Before the HMAC check a peer may send 64 bytes and no more: the
    burst reader starts only behind the handshake."""
    import struct

    server = RpcServer("127.0.0.1", 0, {"storage_get": lambda k: k},
                       inline_methods={"storage_get"}, secret="hunter2")
    try:
        sock = _raw_connect(server)
        assert len(_read_frames(sock, 1)[0]) == 16  # the nonce
        sock.sendall(struct.pack(">I", 65) + b"p" * 65)
        _drain_until_closed(sock)
        sock.close()
        # a wrong proof of a legal size is refused too, and a request
        # sent in the same segment as the proof is never dispatched
        sock = _raw_connect(server)
        _read_frames(sock, 1)
        sock.sendall(struct.pack(">I", 32) + b"p" * 32
                     + _frame(("q", 1, "storage_get", (b"k",))))
        _drain_until_closed(sock)
        sock.close()
        assert sum(server.stats()["requests"].values()) == 0
        good = RpcClient(server.host, server.port, secret="hunter2")
        assert good.call("storage_get", b"k", timeout=20) == b"k"
        good.close()
    finally:
        server.close()


class _ChunkSocket:
    """recv() hands out scripted chunks; a ``None`` is a timeout."""

    def __init__(self, chunks):
        self.chunks = list(chunks)

    def recv(self, n):
        if not self.chunks:
            return b""
        chunk = self.chunks.pop(0)
        if chunk is None:
            raise TimeoutError("tick")
        assert len(chunk) <= n
        return chunk


@pytest.mark.parametrize("cuts", [
    (),                       # everything in one read
    (2,), (4,), (5,),         # inside the header, at it, one byte past
    (1, 2, 3, 4, 5, 6),       # a byte at a time
    (30, 31, 60),             # inside the second and third frames
], ids=lambda c: "cuts=" + "-".join(map(str, c)) if c else "one-read")
def test_frame_reader_parses_bursts_across_any_boundary(cuts):
    """Whatever the segment boundaries, and with a timeout between any
    two segments, the reader returns the same frames in order, each
    burst holding every frame complete so far."""
    from foundationdb_tpu.rpc.transport import ConnectionLost, _FrameReader

    payloads = [b"a" * 20, b"", b"c" * 33, b"d"]
    data = b"".join(len(p).to_bytes(4, "big") + p for p in payloads)
    chunks, last = [], 0
    for cut in cuts:
        chunks += [data[last:cut], None]
        last = cut
    chunks.append(data[last:])
    reader = _FrameReader(_ChunkSocket(chunks))
    got, bursts = [], 0
    while len(got) < len(payloads):
        try:
            frames = reader.recv_burst()
        except TimeoutError:
            continue
        assert frames
        got += frames
        bursts += 1
    assert got == payloads
    assert reader.recvs == len(cuts) + 1
    assert bursts <= reader.recvs
    if not cuts:
        assert bursts == 1
    with pytest.raises(ConnectionLost):
        reader.recv_burst()  # peer closed


@DECLARED
def test_undecodable_frame_fails_the_connection_behind_the_good_ones(
        declared):
    """A burst of two requests and a frame that is no message: the
    requests in front of it are served (a declared one surely: it is
    answered before the connection is failed), then the server closes."""
    import struct

    server = _short_server(declared)
    sock = _raw_connect(server)
    try:
        sock.sendall(_frame(("q", 1, "storage_get", (b"a",)))
                     + _frame(("q", 2, "storage_get", (b"b",)))
                     + struct.pack(">I", 3) + b"\xfe\xfe\xfe")
        if declared:
            assert sorted(_read_replies(sock, 2)) == [
                ("r", 1, True, b"a"), ("r", 2, True, b"b")]
        _drain_until_closed(sock)
        _wait_for(lambda: sum(server.stats()["requests"].values()) == 2,
                  "both requests in front of it counted")
    finally:
        sock.close()
        server.close()


def test_inline_and_pooled_requests_interleaved_under_thread_switching():
    """Four connections × eight threads, more than the cores, a switch
    interval of 10 µs: reads answered on the connections' threads and
    commits on the pool share sockets, send locks and the counters.
    Every caller gets its own answer and every request is counted once,
    where it was answered."""
    import sys

    server = _short_server(True, {"commit": lambda x: ("done", x)})
    clients = [RpcClient(server.host, server.port) for _ in range(4)]
    wrong, per_thread = [], 60
    interval = sys.getswitchinterval()

    def work(client, tid):
        for i in range(per_thread):
            key = b"%d:%d" % (tid, i)
            if client.call("storage_get", key, timeout=30) != key:
                wrong.append(("read", tid, i))
            if i % 3 == 0 and client.call("commit", key, timeout=30) \
                    != ("done", key):
                wrong.append(("commit", tid, i))

    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=work, args=(c, 8 * n + t))
                   for n, c in enumerate(clients) for t in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in threads)
        reads, commits = 32 * per_thread, 32 * (per_thread // 3)
        doc = _counted(server, reads + commits)
    finally:
        sys.setswitchinterval(interval)
        for c in clients:
            c.close()
        server.close()
    assert wrong == []
    assert doc["requests"]["read"] == reads
    assert doc["requests"]["commit"] == commits
    assert doc["inline_requests"] == {"read": reads, "grv": 0, "commit": 0,
                                      "admin": 0}
    assert 1 <= doc["recv_calls"] <= reads + commits


# ─────────────── a deferred reply: a request that holds no thread ───────────────
from foundationdb_tpu.rpc.transport import Deferred  # noqa: E402


class _Registrar:
    """Stands where the batcher stands: takes each deferred request's
    ``complete`` and calls it later, from the test's own thread."""

    def __init__(self):
        from collections import deque

        self.completes = deque()  # appended by the server's threads
        self.polls = 0

    def handler(self, x):
        return Deferred(lambda complete: self.completes.append(
            (x, complete)), self.poll)

    def poll(self):
        self.polls += 1

    def settle(self, n=None):
        """Complete what has registered (the first ``n``) in one row:
        one flush behind the last."""
        n = len(self.completes) if n is None else n
        row = [self.completes.popleft() for _ in range(n)]
        flushes = {complete(("done", x)) for x, complete in row}
        for flush in flushes:
            flush()


def test_deferred_reply_reaches_its_caller_with_every_pool_worker_held():
    """Sixteen admin calls hold all sixteen workers; a declared
    endpoint that defers is answered all the same: its request waits on
    no thread, the pool's or the connection's."""
    release = threading.Event()
    running = threading.Semaphore(0)

    def held():
        running.release()
        release.wait(30)
        return "held"

    reg = _Registrar()
    server = RpcServer("127.0.0.1", 0,
                       {"commit": reg.handler, "status": held,
                        "ping": lambda: "pong"},
                       inline_methods={"commit", "ping"}, max_workers=16)
    client = RpcClient(server.host, server.port)
    try:
        holders = [client.call_async("status") for _ in range(16)]
        for _ in holders:
            assert running.acquire(timeout=20)
        commits = [client.call_async("commit", i) for i in range(5)]
        _wait_for(lambda: len(reg.completes) == 5, "five to register")
        # the connection's thread is free too: it answers a ping
        assert client.call("ping", timeout=20) == "pong"
        doc = server.stats()
        assert doc["deferred_pending"] == 5
        assert doc["pool"]["queued"] == 0
        assert not any(c.done() for c in commits)
        reg.settle()
        assert [c.result(20) for c in commits] == [
            ("done", i) for i in range(5)]
        assert not any(h.done() for h in holders)
        release.set()
        assert [h.result(20) for h in holders] == ["held"] * 16
        doc = _counted(server, 22)
    finally:
        release.set()
        client.close()
        server.close()
    assert doc["deferred_requests"] == {
        "read": 0, "grv": 0, "commit": 5, "admin": 0}
    assert doc["inline_requests"] == {
        "read": 1, "grv": 0, "commit": 0, "admin": 0}
    assert doc["deferred_sends"] == 1 and doc["deferred_pending"] == 0


@DECLARED
def test_deferred_replies_of_one_row_share_a_send_per_connection(declared):
    """Twelve requests from three connections completed in one row:
    three sends. From a pool thread (undeclared) as from a connection's
    own, the deferred request is counted where its reply was sent: not
    inline."""
    reg = _Registrar()
    server = RpcServer("127.0.0.1", 0, {"commit": reg.handler},
                       inline_methods={"commit"} if declared else ())
    clients = [RpcClient(server.host, server.port) for _ in range(3)]
    try:
        futs = [c.call_async("commit", (n, i))
                for n, c in enumerate(clients) for i in range(4)]
        _wait_for(lambda: len(reg.completes) == 12, "twelve to register")
        reg.settle(6)
        reg.settle()
        assert sorted(f.result(20) for f in futs) == [
            ("done", (n, i)) for n in range(3) for i in range(4)]
        doc = _counted(server, 12)
    finally:
        for c in clients:
            c.close()
        server.close()
    assert doc["deferred_requests"]["commit"] == 12
    assert doc["inline_requests"]["commit"] == 0
    # two rows, each over at most three connections
    assert 2 <= doc["deferred_sends"] <= 6
    assert doc["timed_requests"]["commit"] == 3  # every fourth


def test_deferred_result_in_hand_at_registration_is_answered_at_once():
    server = RpcServer(
        "127.0.0.1", 0,
        {"commit": lambda x: Deferred(lambda complete: complete(x + 1))},
        inline_methods={"commit"})
    client = RpcClient(server.host, server.port)
    try:
        assert client.call("commit", 41, timeout=20) == 42
        doc = _counted(server, 1)
    finally:
        client.close()
        server.close()
    assert doc["deferred_requests"]["commit"] == 1
    assert doc["deferred_sends"] == 1


def test_deferred_registration_that_raises_answers_a_remote_failure():
    from foundationdb_tpu.rpc.transport import RemoteError

    def register(complete):
        raise KeyError("no batcher (injected)")

    server = RpcServer("127.0.0.1", 0,
                       {"commit": lambda: Deferred(register)},
                       inline_methods={"commit"})
    client = RpcClient(server.host, server.port)
    try:
        with pytest.raises(RemoteError, match="no batcher"):
            client.call("commit", timeout=20)
        assert server.stats()["deferred_pending"] == 0
    finally:
        client.close()
        server.close()


def test_deferred_poll_runs_while_unanswered_and_not_after(monkeypatch):
    from foundationdb_tpu.rpc import transport

    monkeypatch.setattr(transport, "_DEFERRED_POLL_S", 0.01)
    reg = _Registrar()
    server = RpcServer("127.0.0.1", 0, {"commit": reg.handler},
                       inline_methods={"commit"})
    client = RpcClient(server.host, server.port)
    try:
        futs = [client.call_async("commit", i) for i in range(3)]
        # one poll a tick for the three of them: they share a registrant
        _wait_for(lambda: reg.polls >= 3, "the reply thread to poll")
        reg.settle()
        assert [f.result(20) for f in futs] == [("done", i) for i in range(3)]
        _wait_for(lambda: server.stats()["deferred_pending"] == 0, "answers")
        time.sleep(0.05)
        polls = reg.polls
        time.sleep(0.1)
        assert reg.polls == polls
    finally:
        client.close()
        server.close()


def test_vanished_client_costs_its_row_nothing():
    """A client that left before its request was completed: its reply
    has nowhere to go, the others of the row arrive."""
    reg = _Registrar()
    server = RpcServer("127.0.0.1", 0, {"commit": reg.handler},
                       inline_methods={"commit"})
    gone = RpcClient(server.host, server.port)
    stays = RpcClient(server.host, server.port)
    try:
        gone.call_async("commit", "gone")
        fut = stays.call_async("commit", "stays")
        _wait_for(lambda: len(reg.completes) == 2, "two to register")
        gone.close()
        _wait_for(lambda: len(server._conns) == 1, "the server to notice")
        reg.settle()
        assert fut.result(20) == ("done", "stays")
        doc = _counted(server, 2)
    finally:
        stays.close()
        server.close()
    assert doc["deferred_requests"]["commit"] == 2
    assert doc["deferred_pending"] == 0


def test_close_with_deferred_requests_pending_stops_the_reply_thread():
    reg = _Registrar()
    server = RpcServer("127.0.0.1", 0, {"commit": reg.handler},
                       inline_methods={"commit"})
    client = RpcClient(server.host, server.port)
    fut = client.call_async("commit", 1)
    _wait_for(lambda: len(reg.completes) == 1, "one to register")
    reply_thread = server._reply_thread
    server.close()
    assert reply_thread is not None and not reply_thread.is_alive()
    reg.settle()  # a completion behind the close raises nothing
    with pytest.raises(Exception):
        fut.result(20)
    client.close()


def test_deferred_and_inline_requests_interleaved_under_thread_switching():
    """Four connections × eight threads at a 10 µs switch interval:
    reads answered on the connections' threads, commits deferred and
    completed in rows by a settler thread. Every caller gets its own
    answer; every request is counted once, where it was answered."""
    import sys

    reg = _Registrar()
    server = _short_server(True, {"commit": reg.handler})
    server.inline_methods.add("commit")
    clients = [RpcClient(server.host, server.port) for _ in range(4)]
    wrong, per_thread = [], 40
    stop = threading.Event()
    interval = sys.getswitchinterval()

    def settler():
        while not stop.is_set():
            reg_row = len(reg.completes)
            if reg_row:
                reg.settle(reg_row)
            else:
                time.sleep(0.0005)

    def work(client, tid):
        for i in range(per_thread):
            key = b"%d:%d" % (tid, i)
            if client.call("storage_get", key, timeout=30) != key:
                wrong.append(("read", tid, i))
            if client.call("commit", key, timeout=30) != ("done", key):
                wrong.append(("commit", tid, i))

    sys.setswitchinterval(1e-5)
    try:
        st = threading.Thread(target=settler)
        st.start()
        threads = [threading.Thread(target=work, args=(c, 8 * n + t))
                   for n, c in enumerate(clients) for t in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in threads)
        total = 32 * per_thread
        doc = _counted(server, 2 * total)
    finally:
        stop.set()
        st.join(timeout=10)
        sys.setswitchinterval(interval)
        for c in clients:
            c.close()
        server.close()
    assert wrong == []
    assert doc["requests"]["read"] == total
    assert doc["requests"]["commit"] == total
    assert doc["inline_requests"]["read"] == total
    assert doc["inline_requests"]["commit"] == 0
    assert doc["deferred_requests"]["commit"] == total
    assert 1 <= doc["deferred_sends"] <= total
    assert doc["deferred_pending"] == 0


# ───────────── the served commit: submitted, deferred, answered in a batch ─────────────
class _Gate:
    """Wraps the served cluster's inner ``commit_batch``: a batch stands
    at the gate until released, then runs as it would have."""

    def __init__(self, cluster):
        self.inner = cluster.commit_proxy.inner
        self.orig = self.inner.commit_batch
        self.entered = threading.Event()
        self.release = threading.Event()
        self.sizes = []
        self.inner.commit_batch = self

    def __call__(self, reqs):
        self.sizes.append(len(reqs))
        self.entered.set()
        assert self.release.wait(60)
        return self.orig(reqs)

    def open(self):
        self.release.set()
        self.inner.commit_batch = self.orig


def _blind_set(rv, key, value=b"v"):
    end = key + b"\\x00"
    return CommitRequest(rv, [Mutation(Op.SET, key, value)], [],
                         [(key, end)])


def test_forty_commits_from_four_connections_hold_no_pool_worker(remote_db):
    """Forty commits wait for a held batch: no pool worker is theirs
    (none queued, a ping and an admin call answered meanwhile), and
    when the batches settle the replies leave in at most one send a
    connection a batch."""
    db, cluster, server = remote_db
    db[b"seed"] = b"0"  # the first batch, before the gate
    rv = db.create_transaction().get_read_version()
    before = server.stats()
    batches_before = cluster.commit_proxy.batches_committed
    gate = _Gate(cluster)
    clients = [RpcClient(server.host, server.port) for _ in range(4)]
    try:
        futs = [clients[0].call_async("commit", _blind_set(rv, b"k0.0"))]
        assert gate.entered.wait(20)  # the batcher stands at the gate
        futs += [c.call_async("commit", _blind_set(rv, b"k%d.%d" % (n, i)))
                 for n, c in enumerate(clients) for i in range(10)
                 if (n, i) != (0, 0)]  # forty in all
        _wait_for(lambda: server.stats()["deferred_pending"] == 40,
                  "forty commits submitted")
        doc = server.stats()
        assert doc["pool"]["queued"] == 0
        assert clients[1].call("ping", timeout=20) == "pong"   # inline
        assert "batch_txn_capacity" in clients[2].call(
            "knobs", timeout=20)                               # the pool
        assert not any(f.done() for f in futs)
        gate.open()
        versions = [f.result(30) for f in futs]
        assert all(isinstance(v, int) for v in versions)
        _wait_for(lambda: server.stats()["deferred_requests"]["commit"]
                  - before["deferred_requests"]["commit"] == 40,
                  "forty replies counted")
        after = server.stats()
    finally:
        gate.open()
        for c in clients:
            c.close()
    batches = cluster.commit_proxy.batches_committed - batches_before
    sends = after["deferred_sends"] - before["deferred_sends"]
    # the one held, then the thirty-nine that gathered behind it
    assert gate.sizes[0] == 1 and 2 <= batches <= 40
    assert 1 <= sends <= 4 * batches
    assert batches > 2 or sends <= 5  # 1 + 39 over four connections
    assert after["inline_requests"]["commit"] == 0
    assert after["deferred_requests"]["commit"] == after["requests"]["commit"]
    assert after["deferred_pending"] == 0
    assert db[b"k3.9"] == b"v" and db[b"k0.0"] == b"v"


def test_conflict_verdict_rides_the_deferred_reply_as_a_value(remote_db):
    """1020 is a verdict, not a failure: the wire carries the FDBError
    as a value, as before the reply was deferred."""
    db, _, server = remote_db
    db[b"hot"] = b"0"
    tr = db.create_transaction()
    rv = tr.get_read_version()
    db[b"hot"] = b"1"  # a write behind rv
    client = RpcClient(server.host, server.port)
    try:
        stale = CommitRequest(rv, [Mutation(Op.SET, b"hot", b"2")],
                              [(b"hot", b"hot\\x00")],
                              [(b"hot", b"hot\\x00")])
        verdict = client.call("commit", stale, timeout=20)
    finally:
        client.close()
    assert isinstance(verdict, FDBError) and verdict.code == 1020
    assert db[b"hot"] == b"1"
    # and through the client stack: the transaction retries on it
    assert tr.get(b"hot") == b"0"
    tr[b"hot"] = b"3"
    with pytest.raises(FDBError) as ei:
        tr.commit()
    assert ei.value.code == 1020


def test_client_gone_before_its_batch_settles_fails_no_one_else(remote_db):
    db, cluster, server = remote_db
    rv = db.create_transaction().get_read_version()
    gate = _Gate(cluster)
    gone = RpcClient(server.host, server.port)
    stays = RpcClient(server.host, server.port)
    try:
        gone.call_async("commit", _blind_set(rv, b"gone"))
        assert gate.entered.wait(20)
        fut = stays.call_async("commit", _blind_set(rv, b"stays"))
        _wait_for(lambda: server.stats()["deferred_pending"] == 2,
                  "both submitted")
        gone.close()
        _wait_for(lambda: len(server._conns) == 2, "the server to notice")
        gate.open()
        assert isinstance(fut.result(30), int)
    finally:
        gate.open()
        stays.close()
    # the commit whose client left is committed all the same
    assert db[b"gone"] == b"v" and db[b"stays"] == b"v"
    assert server.stats()["deferred_pending"] == 0


def test_deferred_commit_stamps_cover_the_batch_and_no_queue(remote_db):
    """``handler_wall_us`` of a commit runs from its submit to its
    settlement (the wait for the batch and the batch); nothing waits in
    front of the handler."""
    db, cluster, server = remote_db
    rv = db.create_transaction().get_read_version()
    before = server.stats()
    gate = _Gate(cluster)
    client = RpcClient(server.host, server.port)
    held = 0.2
    try:
        # four, so that one of them is a timed one whatever came before
        futs = [client.call_async("commit", _blind_set(rv, b"t%d" % i))
                for i in range(4)]
        assert gate.entered.wait(20)
        _wait_for(lambda: server.stats()["deferred_pending"] == 4,
                  "four submitted")
        time.sleep(held)
        gate.open()
        assert all(isinstance(f.result(30), int) for f in futs)
        _wait_for(lambda: server.stats()["requests"]["commit"]
                  - before["requests"]["commit"] == 4, "four counted")
        after = server.stats()
    finally:
        gate.open()
        client.close()

    def delta(counter):
        return after[counter]["commit"] - before[counter]["commit"]

    timed = delta("timed_requests")
    assert timed >= 1
    assert delta("handler_wall_us") >= timed * held * 1e6
    assert delta("queue_wait_us") < timed * held * 1e6 / 4
    assert delta("reply_us") < timed * held * 1e6


def test_sync_pipeline_commit_still_runs_on_the_pool_under_its_lock():
    """The ``"sync"`` pipeline has no batcher to submit to: ``commit``
    is not declared, blocks a pool worker under ``_commit_lock`` and is
    answered by it."""
    cluster = Cluster(resolver_backend="cpu", **TEST_KNOBS)
    server = serve_cluster(cluster)
    rc = RemoteCluster([server.address])
    try:
        assert "commit" not in server.inline_methods
        db = rc.database()
        db[b"a"] = b"1"
        assert db[b"a"] == b"1"
        doc = _counted(server, sum(server.stats()["requests"].values()))
        assert doc["requests"]["commit"] == 1
        assert doc["deferred_requests"]["commit"] == 0
        assert doc["inline_requests"]["commit"] == 0
        assert doc["deferred_sends"] == 0
        assert server._reply_thread is None
    finally:
        rc.close()
        server.close()
        cluster.close()


def test_watchdog_settles_a_wedged_served_batch_with_no_thread_waiting(
        remote_db, monkeypatch):
    """The inner proxy wedges past a shortened ``watchdog_s``: every
    deferred commit of the batch is answered 1021 by the reply thread's
    poll, with no thread blocked in ``result`` (the pool is idle), and
    the wedged drive's late results change nothing."""
    from foundationdb_tpu.rpc import transport

    monkeypatch.setattr(transport, "_DEFERRED_POLL_S", 0.02)
    db, cluster, server = remote_db
    db[b"seed"] = b"0"  # starts the reply thread at the shortened tick
    rv = db.create_transaction().get_read_version()
    proxy = cluster.commit_proxy
    proxy.watchdog_s = 0.3
    gate = _Gate(cluster)
    client = RpcClient(server.host, server.port)
    try:
        first = client.call_async("commit", _blind_set(rv, b"w0"))
        assert gate.entered.wait(20)
        verdict = first.result(30)  # well inside the 15 s deadline
        assert isinstance(verdict, FDBError) and verdict.code == 1021
        assert proxy.stranded_settled == 1
        doc = server.stats()
        assert doc["pool"]["queued"] == 0 and doc["deferred_pending"] == 0
        assert not [t for t in threading.enumerate()
                    if t.name.startswith("rpc-handler")
                    and _blocked_in_result(t)]
        gate.open()  # the wedged drive finishes: its set loses
        _wait_for(lambda: db.get(b"w0") == b"v", "the late apply")
        assert proxy.stranded_settled == 1
        counted = server.stats()["deferred_requests"]["commit"]
        time.sleep(0.1)
        assert server.stats()["deferred_requests"]["commit"] == counted
    finally:
        gate.open()
        client.close()


def _blocked_in_result(thread):
    import sys

    frame = sys._current_frames().get(thread.ident)
    while frame is not None:
        if frame.f_code.co_name == "result" \
                and frame.f_code.co_filename.endswith("batcher.py"):
            return True
        frame = frame.f_back
    return False
