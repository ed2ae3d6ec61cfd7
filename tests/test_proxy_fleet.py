"""Commit-proxy / GRV fleet (server/fleet.py, VersionGate in
server/proxy.py): the horizontally scaled transaction frontend.

Ref parity: fdbserver/CommitProxyServer.actor.cpp runs a FLEET of
proxies whose batches interleave into one serial order through the
sequencer's prevVersion chaining (masterserver.actor.cpp getVersion);
resolvers and tlogs process batches strictly in that order. These tests
drive the chaining, the VersionGate turnstiles (including adversarial
schedules and unclaimed-turn wedges), fleet-wide management fan-out
(database lock, tenant mode), txn-system recovery with a fleet, WAL
restart, and cross-proxy serializability under real client threads.
"""

import threading

import pytest

from foundationdb_tpu.core.errors import FDBError
from foundationdb_tpu.server.cluster import Cluster
from foundationdb_tpu.server.proxy import CommitRequest, GateTimeout, VersionGate
from foundationdb_tpu.server.sequencer import Sequencer

from conftest import TEST_KNOBS

FLEET_KNOBS = dict(TEST_KNOBS, gate_timeout_s=2.0)


@pytest.fixture
def fleet_cluster():
    c = Cluster(resolver_backend="cpu", n_commit_proxies=3, **FLEET_KNOBS)
    yield c
    c.close()


def _commit(cluster, proxy, kvs, read_version=None, lock_aware=False):
    """One write-only batch through a SPECIFIC fleet member."""
    if read_version is None:
        read_version = cluster.grv_proxy.get_read_version()
    from foundationdb_tpu.core.mutations import Mutation, Op

    req = CommitRequest(
        read_version=read_version,
        mutations=[Mutation(Op.SET, k, v) for k, v in kvs],
        read_conflict_ranges=[],
        write_conflict_ranges=[(k, k + b"\x00") for k, _ in kvs],
        lock_aware=lock_aware,
    )
    return proxy.commit(req)


# ── sequencer chaining ───────────────────────────────────────────────

def test_chained_grants_form_one_serial_order():
    s = Sequencer()
    pairs = []
    for _ in range(5):
        pairs.extend(s.next_commit_versions(1))
    pairs.extend(s.next_commit_versions(3))  # a backlog's contiguous run
    for (p0, v0), (p1, v1) in zip(pairs, pairs[1:]):
        assert p1 == v0  # every grant names its predecessor, no gaps
        assert v1 > v0


def test_chained_grants_atomic_under_threads():
    s = Sequencer()
    out, mu = [], threading.Lock()

    def grab():
        for _ in range(50):
            got = s.next_commit_versions(2)
            with mu:
                out.extend(got)

    ts = [threading.Thread(target=grab) for _ in range(8)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    out.sort(key=lambda pv: pv[1])
    for (_, v0), (p1, _) in zip(out, out[1:]):
        assert p1 == v0  # the chain is global: no two grants overlap


# ── VersionGate ordering ─────────────────────────────────────────────

@pytest.mark.parametrize("seed", [0, 1, 7])
def test_version_gate_orders_adversarial_schedules(seed):
    """Threads holding shuffled (prev, v) grants pass the gate in
    version order no matter the arrival schedule (the template is the
    GRV _grant_round determinism tests)."""
    import random

    rng = random.Random(seed)
    s = Sequencer()
    grants = s.next_commit_versions(16)
    gate = VersionGate(0, timeout=10.0)
    order, mu = [], threading.Lock()
    shuffled = grants[:]
    rng.shuffle(shuffled)

    def worker(prev, v, delay):
        import time

        time.sleep(delay)
        gate.enter(prev)
        with mu:
            order.append(v)
        gate.advance(v)

    ts = [
        threading.Thread(target=worker, args=(p, v, rng.random() * 0.02))
        for p, v in shuffled
    ]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert order == [v for _, v in grants]


def test_version_gate_timeout_raises_gate_timeout():
    gate = VersionGate(0, timeout=0.05)
    with pytest.raises(GateTimeout):
        gate.enter(5)  # nobody will ever advance to 5


# ── fleet commit paths ───────────────────────────────────────────────

def test_fleet_commits_visible_through_every_member(fleet_cluster):
    c = fleet_cluster
    assert len(c.commit_proxy.inners) == 3
    for i, proxy in enumerate(c.commit_proxy.inners * 2):  # 2 laps
        v = _commit(c, proxy, [(b"k%d" % i, b"v%d" % i)])
        assert not isinstance(v, FDBError)
    db = c.database()
    for i in range(6):
        assert db[b"k%d" % i] == b"v%d" % i
    assert c.commit_proxy.commit_count == 6  # aggregated over the fleet


def test_fleet_concurrent_serializable_increments():
    """The classic lost-update check: N threads × M serializable RMW
    increments through a 3-proxy fleet must sum exactly (conflicts
    retried via the standard loop) — cross-proxy resolution shares one
    conflict history in one version order."""
    c = Cluster(resolver_backend="cpu", n_commit_proxies=3,
                commit_pipeline="thread", **FLEET_KNOBS)
    try:
        db = c.database()
        db[b"ctr"] = b"0"
        N, M = 6, 15

        def bump(tr):
            tr[b"ctr"] = b"%d" % (int(tr[b"ctr"]) + 1)

        def client():
            for _ in range(M):
                db.run(bump)

        ts = [threading.Thread(target=client) for _ in range(N)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert int(db[b"ctr"]) == N * M
    finally:
        c.close()


def test_fleet_transfer_workload_holds_sum_invariant():
    """8 threads moving random amounts between 8 accounts through the
    fleet: the total must never change (serializability across
    members, not just per-member)."""
    import random

    c = Cluster(resolver_backend="cpu", n_commit_proxies=3,
                commit_pipeline="thread", **FLEET_KNOBS)
    try:
        db = c.database()
        for i in range(8):
            db[b"acct%d" % i] = b"100"

        def transfer(rng):
            a, b = rng.sample(range(8), 2)
            amt = rng.randint(1, 10)

            def txn(tr):
                va = int(tr[b"acct%d" % a])
                vb = int(tr[b"acct%d" % b])
                tr[b"acct%d" % a] = b"%d" % (va - amt)
                tr[b"acct%d" % b] = b"%d" % (vb + amt)

            db.run(txn)

        def client(seed):
            rng = random.Random(seed)
            for _ in range(12):
                transfer(rng)

        ts = [threading.Thread(target=client, args=(i,)) for i in range(8)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        total = sum(int(db[b"acct%d" % i]) for i in range(8))
        assert total == 800
    finally:
        c.close()


# ── management fan-out ───────────────────────────────────────────────

def test_lock_fans_out_to_every_member(fleet_cluster):
    c = fleet_cluster
    c.lock_database(b"fleet-lock")
    for proxy in c.commit_proxy.inners:
        res = _commit(c, proxy, [(b"x", b"y")])
        assert isinstance(res, FDBError) and res.code == 1038
    # lock-aware passes through any member
    res = _commit(c, c.commit_proxy.inners[2], [(b"x", b"y")],
                  lock_aware=True)
    assert not isinstance(res, FDBError)
    c.unlock_database()
    for proxy in c.commit_proxy.inners:
        res = _commit(c, proxy, [(b"z", b"w")])
        assert not isinstance(res, FDBError)


def test_tenant_mode_fans_out_to_every_member(fleet_cluster):
    c = fleet_cluster
    c.set_tenant_mode("required")
    for proxy in c.commit_proxy.inners:
        res = _commit(c, proxy, [(b"plain", b"v")])
        assert isinstance(res, FDBError) and res.code == 2130
    c.set_tenant_mode("optional")
    res = _commit(c, c.commit_proxy.inners[0], [(b"plain", b"v")])
    assert not isinstance(res, FDBError)


# ── failure paths ────────────────────────────────────────────────────

def test_resolver_death_skips_log_turn_peers_continue(fleet_cluster):
    """ResolverDown mid-fleet: the batch answers 1020, its log-gate
    turn is consumed (_skip_turns_quiet), and after recruitment the OTHER
    members commit without wedging behind the dead batch's version."""
    c = fleet_cluster
    _commit(c, c.commit_proxy.inners[0], [(b"a", b"1")])
    c.resolvers[0].kill()
    res = _commit(c, c.commit_proxy.inners[1], [(b"b", b"2")])
    assert isinstance(res, FDBError) and res.code == 1020
    c.detect_and_recruit()  # fenced replacement resolver
    rv = c.grv_proxy.get_read_version()
    res = _commit(c, c.commit_proxy.inners[2], [(b"c", b"3")],
                  read_version=rv)
    assert not isinstance(res, FDBError)
    assert c.database()[b"c"] == b"3"


def test_build_exception_consumes_both_gate_turns(fleet_cluster):
    """An exception between the version grant and gate consumption
    (advisor r4 finding): both turns must be skipped, or every
    successor batch wedges behind the leaked version."""
    c = fleet_cluster
    p0, p1 = c.commit_proxy.inners[0], c.commit_proxy.inners[1]
    boom = RuntimeError("packer blew up")
    orig = p0._build_txns
    p0._build_txns = lambda reqs: (_ for _ in ()).throw(boom)
    with pytest.raises(RuntimeError):
        _commit(c, p0, [(b"a", b"1")])
    p0._build_txns = orig
    # peers are NOT wedged: their batches pass the gates immediately
    res = _commit(c, p1, [(b"b", b"2")])
    assert not isinstance(res, FDBError)


def test_resolve_exception_consumes_log_turn(fleet_cluster):
    """A non-ResolverDown exception escaping _resolve advances the
    resolve gate (finally) but must also skip the log-gate turn."""
    c = fleet_cluster
    p0, p1 = c.commit_proxy.inners[0], c.commit_proxy.inners[1]
    orig = p0._resolve
    p0._resolve = lambda *a: (_ for _ in ()).throw(RuntimeError("died"))
    with pytest.raises(RuntimeError):
        _commit(c, p0, [(b"a", b"1")])
    p0._resolve = orig
    res = _commit(c, p1, [(b"b", b"2")])
    assert not isinstance(res, FDBError)


def test_unclaimed_turn_times_out_retryable_then_recovers():
    """A proxy dying between grant and advance strands its turn: peers
    hit GateTimeout → retryable 1021 (NOT a bare RuntimeError), the
    wedged proxy marks itself dead, and the failure monitor's
    txn-system recovery rebuilds fresh gates that work."""
    c = Cluster(resolver_backend="cpu", n_commit_proxies=2,
                **dict(TEST_KNOBS, gate_timeout_s=0.2))
    try:
        p0, p1 = c.commit_proxy.inners
        # steal a grant: its (prev, v) turn will never be claimed —
        # exactly what a proxy death after getVersion looks like
        c.sequencer.next_commit_versions(1)
        res = _commit(c, p1, [(b"a", b"1")])
        assert isinstance(res, FDBError)
        assert res.code == 1021 and res.is_retryable
        assert not p1.alive  # wedged member removed itself
        events = c.detect_and_recruit()
        assert ("txn-system", 0) in events
        res = _commit(c, c.commit_proxy.inners[0], [(b"b", b"2")])
        assert not isinstance(res, FDBError)
        assert c.database()[b"b"] == b"2"
    finally:
        c.close()


def test_txn_system_recovery_rebuilds_whole_fleet(fleet_cluster):
    c = fleet_cluster
    db = c.database()
    for i in range(5):
        db[b"pre%d" % i] = b"v%d" % i
    gen0 = c.generation
    c.commit_proxy.inners[1].kill()  # ONE dead member forces recovery
    # a client talking to the dead member sees retryable 1021
    res = _commit(c, c.commit_proxy.inners[1], [(b"during", b"x")])
    assert isinstance(res, FDBError) and res.code == 1021
    events = c.detect_and_recruit()
    assert ("txn-system", 0) in events
    assert c.generation > gen0
    assert len(c.commit_proxy.inners) == 3  # a FLEET recruits a fleet
    assert all(p.alive for p in c.commit_proxy.inners)
    # data survived; new fleet commits through every member
    for i in range(5):
        assert db[b"pre%d" % i] == b"v%d" % i
    for i, proxy in enumerate(c.commit_proxy.inners):
        res = _commit(c, proxy, [(b"post%d" % i, b"w")])
        assert not isinstance(res, FDBError)
    assert c.consistency_check() == []


def test_sequencer_death_recovers_fleet_with_lock_carried(fleet_cluster):
    c = fleet_cluster
    c.lock_database(b"ops")
    c.sequencer.kill()
    c.detect_and_recruit()
    # the lock fans out to every member of the NEW fleet
    for proxy in c.commit_proxy.inners:
        res = _commit(c, proxy, [(b"x", b"y")])
        assert isinstance(res, FDBError) and res.code == 1038
    c.unlock_database()
    res = _commit(c, c.commit_proxy.inners[1], [(b"x", b"y")])
    assert not isinstance(res, FDBError)


def test_wal_restart_with_fleet(tmp_path):
    wal = str(tmp_path / "fleet.wal")
    c = Cluster(resolver_backend="cpu", n_commit_proxies=2, wal_path=wal,
                **FLEET_KNOBS)
    db = c.database()
    for i in range(10):
        db[b"k%02d" % i] = b"v%d" % i
    c.close()
    c2 = Cluster(resolver_backend="cpu", n_commit_proxies=2, wal_path=wal,
                 **FLEET_KNOBS)
    try:
        db2 = c2.database()
        for i in range(10):
            assert db2[b"k%02d" % i] == b"v%d" % i
        db2[b"after"] = b"restart"  # the recovered fleet commits
        assert db2[b"after"] == b"restart"
    finally:
        c2.close()


def test_fleet_status_json_reports_count(fleet_cluster):
    st = fleet_cluster.status()["cluster"]
    assert st["processes"]["commit_proxy"]["count"] == 3


def test_fleet_over_rpc_with_batched_commits(tmp_path):
    """A commit-proxy FLEET behind a real fdbserver process, driven by
    a remote client with batched commits (commit_batch RPC → the
    fleet's round-robin): concurrent RMW increments must sum exactly
    across members and the wire."""
    import os
    import signal
    import subprocess
    import sys

    import foundationdb_tpu as fdb

    cf = str(tmp_path / "fdb.cluster")
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = subprocess.Popen(
        [sys.executable, "-m", "foundationdb_tpu.tools.fdbserver",
         "--listen", "127.0.0.1:0", "--cluster-file", cf,
         "--commit-proxies", "3", "--resolver-backend", "cpu"],
        stdout=subprocess.PIPE, text=True, env=env)
    try:
        assert "FDBD listening" in p.stdout.readline()
        db = fdb.open(cluster_file=cf, commit_pipeline="thread")
        st = db._cluster.status()["cluster"]
        assert st["processes"]["commit_proxy"]["count"] == 3
        db[b"ctr"] = b"0"

        def bump(tr):
            tr[b"ctr"] = b"%d" % (int(tr[b"ctr"]) + 1)

        ts = [threading.Thread(
            target=lambda: [db.run(bump) for _ in range(10)])
            for _ in range(4)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=60)
        assert db[b"ctr"] == b"40"
        # blind writes ride the lazy-rv + batched path through the fleet
        futs = []
        trs = []
        for i in range(50):
            tr = db.create_transaction()
            tr.set(b"blind%02d" % i, b"v")
            trs.append(tr)
            futs.append(tr.commit_async())
        for tr, fut in zip(trs, futs):
            fut.result(timeout=30)
            tr.commit_finish(fut)
        assert len(db.get_range(b"blind", b"bline")) == 50
        db._cluster.close()  # release the socket so SIGTERM lands clean
    finally:
        p.send_signal(signal.SIGTERM)
        try:
            p.wait(timeout=20)
        except subprocess.TimeoutExpired:
            p.kill()


def test_configure_resizes_fleet_live(fleet_cluster):
    """Ref: fdbcli `configure proxies=N` — a live resize rides the
    txn-system recovery: new fleet size, same storage/logs, data and
    lock state intact."""
    c = fleet_cluster
    db = c.database()
    db[b"before"] = b"1"
    gen0 = c.generation
    c.configure(commit_proxies=5)
    assert c.generation > gen0
    assert len(c.commit_proxy.inners) == 5
    assert db[b"before"] == b"1"
    db[b"after"] = b"2"
    assert db[b"after"] == b"2"
    c.configure(commit_proxies=1)  # shrink to a single proxy
    assert not hasattr(c.commit_proxy, "inners")
    db[b"single"] = b"3"
    assert db[b"single"] == b"3"
    c.configure(commit_proxies=1)  # no-op: same size, no recovery
    gen_now = c.generation
    c.configure(commit_proxies=1)
    assert c.generation == gen_now


def test_configure_over_rpc_and_cli(tmp_path):
    """`configure commit_proxies=N` through fdbcli against a remote
    cluster (the management RPC)."""
    import io
    import os
    import signal
    import subprocess
    import sys

    import foundationdb_tpu as fdb
    from foundationdb_tpu.tools.cli import Cli

    cf = str(tmp_path / "fdb.cluster")
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = subprocess.Popen(
        [sys.executable, "-m", "foundationdb_tpu.tools.fdbserver",
         "--listen", "127.0.0.1:0", "--cluster-file", cf,
         "--resolver-backend", "cpu"],
        stdout=subprocess.PIPE, text=True, env=env)
    try:
        assert "FDBD listening" in p.stdout.readline()
        db = fdb.open(cluster_file=cf)
        out = io.StringIO()
        cli = Cli(db, out=out)
        cli.run_command("writemode on")
        cli.run_command("set k v")
        cli.run_command("configure commit_proxies=3")
        assert "Configuration changed" in out.getvalue()
        st = db._cluster.status()["cluster"]
        assert st["processes"]["commit_proxy"]["count"] == 3
        # a remote resolvers-only resize reports its achieved shape
        shape = db._cluster.configure(resolvers=2)
        assert shape == {"commit_proxies": 3, "resolver_lanes": 2}
        assert db[b"k"] == b"v"  # data survived the live recovery
        db[b"post"] = b"w"
        assert db[b"post"] == b"w"
        db._cluster.close()
    finally:
        p.send_signal(signal.SIGTERM)
        try:
            p.wait(timeout=20)
        except subprocess.TimeoutExpired:
            p.kill()


def test_configure_resizes_resolvers_live(fleet_cluster):
    """Ref: `configure resolvers=N` — fresh resolvers open FENCED at
    the committed version; pre-resize read versions retry TOO_OLD, OCC
    still bites after the resize, data intact."""
    c = fleet_cluster
    db = c.database()
    for i in range(20):
        db[b"k%02d" % i] = b"v"
    stale = db.create_transaction()
    assert stale.get(b"k00") == b"v"  # pins a pre-resize read version
    stale[b"k00"] = b"stale"
    for i in range(5):  # history the fresh resolvers can never check
        db[b"post-pin%d" % i] = b"w"
    c.configure(resolvers=3)
    assert len(c.resolvers) == 3
    assert db[b"k00"] == b"v"
    with pytest.raises(FDBError) as ei:
        stale.commit()  # fenced by the fresh resolvers
    assert ei.value.code in (1007, 1020)
    # OCC across the resized fleet: a classic race still conflicts
    t1 = db.create_transaction()
    t2 = db.create_transaction()
    assert t1.get(b"k01") == t2.get(b"k01") == b"v"
    t1[b"k01"] = b"a"
    t2[b"k01"] = b"b"
    t1.commit()
    with pytest.raises(FDBError) as ei2:
        t2.commit()
    assert ei2.value.code == 1020
    c.configure(resolvers=1)
    assert len(c.resolvers) == 1
    db[b"post"] = b"x"
    assert db[b"post"] == b"x"
