"""The waits as counters of their own (PR 39).

A stage's CPU time beside its wall time: ``utils/span.stage(cpu=True)``
reads the calling thread's CPU clock through the determinism seam
(``core/deterministic.thread_cpu``) and hands ``DeviceProfile.add`` both
numbers, for the dispatching thread's four resolver stages. And counted
acquisition of a named mutex: ``utils/lockdep.counted`` tries the lock
first and reads a clock only where it has to wait; storage's mutex, the
commit mutex and the GRV lock are entered through it and summed into
``cluster.locks`` when status is built.
"""

import json
import os
import random
import sys
import threading
import time

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(__file__)))

import foundationdb_tpu as fdb  # noqa: E402
from foundationdb_tpu.analysis import flowlint  # noqa: E402
from foundationdb_tpu.core import deterministic  # noqa: E402
from foundationdb_tpu.core.options import Knobs  # noqa: E402
from foundationdb_tpu.resolver.meshresolver import MeshResolver  # noqa: E402
from foundationdb_tpu.resolver.resolver import Resolver  # noqa: E402
from foundationdb_tpu.resolver.skiplist import TxnRequest  # noqa: E402
from foundationdb_tpu.rpc.service import serve_cluster  # noqa: E402
from foundationdb_tpu.server.cluster import Cluster  # noqa: E402
from foundationdb_tpu.server.storage import StorageServer  # noqa: E402
from foundationdb_tpu.utils import deviceprofile  # noqa: E402
from foundationdb_tpu.utils import lockdep  # noqa: E402
from foundationdb_tpu.utils import span as span_mod  # noqa: E402
from foundationdb_tpu.utils.deviceprofile import (  # noqa: E402
    CPU_EVERY, CPU_SUMS, STAGE_CPU, DeviceProfile, merged_snapshot,
)
from foundationdb_tpu.utils.trace import global_trace_log  # noqa: E402

from conftest import TEST_KNOBS  # noqa: E402
from test_flowlint_v2 import witness  # noqa: E402,F401 (a fixture)
from test_stage_tracing import _Annotations, _wait_until  # noqa: E402


class _Sink:
    """What a stage hands its ``stats``."""

    def __init__(self):
        self.calls = []

    def add(self, *args):
        self.calls.append(args)


@pytest.fixture
def clocks():
    """An injected pair: ``wall`` and ``cpu`` are lists of the readings
    each clock gives, in order; restored to the real ones after."""
    def install(wall, cpu):
        wall, cpu = iter(wall), iter(cpu)
        deterministic.set_clock(lambda: next(wall))
        deterministic.set_cpu_clock(lambda: next(cpu))
    yield install
    deterministic.registry().reset_clock()


def _raise():
    raise AssertionError("this clock may not be read here")


# ───────────────── stage(cpu=True): wall and CPU, one seam ─────────────────
@pytest.mark.parametrize("cpu,want_cpu,want_off", [
    ((5.0, 5.25), 0.25, 1.5),  # a quarter of the second on the CPU
    ((5.0, 5.0), 0.0, 2.0),    # none of it: all of the wall was a wait
    # a clock that steps past the stage (the chip's host: 10 ms a step)
    # is handed on as it read, and off-CPU stops at 0
    ((5.0, 7.5), 2.5, 0.0),
    ((5.0, 4.0), 0.0, 2.0),    # one that ran backwards: never negative
])
def test_stage_with_cpu_hands_stats_wall_and_cpu(clocks, cpu, want_cpu,
                                                 want_off):
    clocks(wall=(100.0, 101.0), cpu=cpu)
    sink = _Sink()
    with span_mod.stage("resolver.pack", sink, cpu=True) as st:
        pass
    assert sink.calls == [("resolver.pack", 1.0, want_cpu)]
    assert st.seconds == 1.0 and st.cpu_seconds == want_cpu
    # what a profile makes of it, beside the dispatch that took no
    # reading (one in CPU_EVERY does, and stands for the others): off-CPU
    # is what the CPU sum leaves of the wall sum, clamped at 0
    assert CPU_EVERY == 2
    p = DeviceProfile("r")
    p.add(*sink.calls[0])
    p.add("resolver.pack", 1.0)
    snap = p.snapshot()
    assert snap["pack_wall_ms"] == 2000.0
    assert snap["pack_cpu_ms"] == 2 * want_cpu * 1e3
    assert snap["pack_offcpu_ms"] == want_off * 1e3


def test_one_dispatch_in_two_takes_the_cpu_readings():
    p = DeviceProfile("r")
    turns = [p.cpu_turn() for _ in range(6)]
    assert turns == [False, True] * 3 and p.cpu_sampled is True
    deviceprofile.set_enabled(False)
    try:
        assert [p.cpu_turn() for _ in range(4)] == [False] * 4
    finally:
        deviceprofile.set_enabled(True)


def test_a_stepping_cpu_clock_adds_up_over_many_stages():
    """The chip's host steps a thread's CPU clock by 10 ms: one stage of
    1 ms reads 0 or 10 ms, and the sums still split the wall. Eighty
    stages of 1 ms, half of each on the CPU, every second one read: two
    steps fall inside the forty that are."""
    p = DeviceProfile("r")
    for i in range(80):
        if i % 2:
            p.add("resolver.enqueue", 0.001, 0.010 if i % 40 == 39 else 0.0)
        else:
            p.add("resolver.enqueue", 0.001)
    snap = p.snapshot()
    assert (snap["enqueue_wall_ms"], snap["enqueue_cpu_ms"],
            snap["enqueue_offcpu_ms"]) == (80.0, 40.0, 40.0)


def test_a_stage_opened_from_the_one_before_shares_its_closing_reading(
        clocks):
    """pack → enqueue → readback: four reads of the CPU clock, not six
    (each is a slow system call on the chip's host), and no CPU second
    between two stages is lost."""
    clocks(wall=(0.0, 1.0, 1.5, 3.5, 4.0, 4.5), cpu=(10.0, 10.5, 11.0, 11.25))
    sink = _Sink()
    first = span_mod.stage("resolver.pack", sink, cpu=True)
    with first:
        pass
    second = span_mod.stage("resolver.enqueue", sink, cpu=first)
    with second:
        pass
    with span_mod.stage("resolver.readback", sink, cpu=second):
        pass
    assert sink.calls == [("resolver.pack", 1.0, 0.5),
                          ("resolver.enqueue", 2.0, 0.5),
                          ("resolver.readback", 0.5, 0.25)]


def test_stage_without_cpu_never_reads_the_cpu_clock(clocks):
    clocks(wall=(1.0, 3.0), cpu=())
    deterministic.set_cpu_clock(_raise)
    sink = _Sink()
    with span_mod.stage("commit.build", sink):
        pass
    assert sink.calls == [("commit.build", 2.0)]  # two numbers, as ever


def test_the_cpu_clock_follows_an_injected_clock_and_comes_back():
    try:
        deterministic.set_clock(lambda: 42.0)
        # under an injected clock CPU time IS that clock: off-CPU reads 0
        assert deterministic.thread_cpu() == 42.0 == deterministic.now()
        sink = _Sink()
        with span_mod.stage("resolver.enqueue", sink, cpu=True):
            pass
        assert sink.calls == [("resolver.enqueue", 0.0, 0.0)]
    finally:
        deterministic.registry().reset_clock()
    a = deterministic.thread_cpu()
    sum(range(20000))
    assert deterministic.thread_cpu() > a  # the thread's own CPU seconds
    assert deterministic.thread_cpu() < deterministic.now()


def test_a_sampled_stage_span_carries_cpu_ms(clocks):
    global_trace_log().clear()
    prior = span_mod.set_current((7, 9, True))
    try:
        clocks(wall=(10.0, 10.5), cpu=(1.0, 1.125))
        with span_mod.stage("resolver.readback", cpu=True):
            pass
    finally:
        span_mod.set_current(prior)
        deterministic.registry().reset_clock()
    (ev,) = [s for s in global_trace_log().events("Span")
             if s["span"] == "resolver.readback"]
    assert ev["dur_ms"] == 500.0 and ev["cpu_ms"] == 125.0


# ─────────────── the device profile carries the eight sums ───────────────
def test_four_cpu_sums_make_eight_fields_of_a_snapshot():
    assert CPU_SUMS == ("pack_cpu_s", "enqueue_cpu_s", "readback_cpu_s",
                        "route_cpu_s")
    assert set(CPU_SUMS) <= set(deviceprofile.PLAIN_WALLS)  # absorb's list
    snap = DeviceProfile("r").snapshot()
    assert [k for k in snap if "cpu" in k] == [
        "pack_cpu_ms", "pack_offcpu_ms", "enqueue_cpu_ms",
        "enqueue_offcpu_ms", "readback_cpu_ms", "readback_offcpu_ms",
        "route_cpu_ms", "route_offcpu_ms"]


@pytest.mark.parametrize("stage,prefix", sorted(
    (stage, cpu[:-len("_cpu_s")]) for stage, cpu in STAGE_CPU.items()))
def test_profile_absorb_snapshot_and_kill_switch_carry_the_split(
        stage, prefix):
    on, off = prefix + "_cpu_ms", prefix + "_offcpu_ms"
    wall = deviceprofile.STAGE_WALLS[stage][:-2] + "_ms"
    p = DeviceProfile("r")
    p.add(stage, 0.004, 0.001)
    p.add(stage, 0.002, 0.002)
    p.add(stage, 0.001)  # a stage that took no CPU clock adds wall alone
    snap = p.snapshot()
    # each reading counts CPU_EVERY times
    assert (snap[on], snap[off], snap[wall]) == (6.0, 1.0, 7.0)
    others = [k for k in snap if "cpu" in k and k not in (on, off)]
    assert len(others) == 6 and all(snap[c] == 0.0 for c in others)
    q = DeviceProfile("q")
    q.absorb(p)  # a respawn's new profile takes the history
    assert (q.snapshot()[on], q.snapshot()[off]) == (6.0, 1.0)
    assert merged_snapshot([p, q])[on] == 12.0  # cluster.device.aggregate
    deviceprofile.set_enabled(False)
    try:
        p.add(stage, 1.0, 0.5)  # gated like every capture site
        q.absorb(p)  # absorb is not
    finally:
        deviceprofile.set_enabled(True)
    assert p.snapshot()[on] == 6.0 and q.snapshot()[on] == 12.0


def _point_txns(n, rv=10):
    return [TxnRequest(read_version=rv, point_reads=[b"k%d" % i],
                       point_writes=[b"k%d" % i]) for i in range(n)]


def _splits_add_up(snap, stages):
    """Off-CPU is what the CPU sum leaves of the wall sum (status rounds
    each to 0.001 ms), and 0 where a stage of microseconds, whose CPU
    interval opens at the stage before's closing reading, read more CPU
    than wall."""
    for prefix, wall in stages:
        on, off = snap[prefix + "_cpu_ms"], snap[prefix + "_offcpu_ms"]
        assert snap[wall] > 0 and on > 0, prefix
        assert abs(off - max(0.0, snap[wall] - on)) <= 0.002, prefix


ONE_LANE = (("pack", "pack_wall_ms"), ("enqueue", "enqueue_wall_ms"),
            ("readback", "verdict_reduce_wall_ms"))


def test_a_dispatch_splits_its_three_stages_and_reads_four_cpu_stamps():
    r = Resolver(Knobs(**TEST_KNOBS))
    r.resolve(_point_txns(3), 20, 0)  # compiles
    r.resolve(_point_txns(3, rv=20), 30, 0)
    reads = []
    deterministic.set_cpu_clock(
        lambda: reads.append(1) or time.thread_time())
    try:
        for i in range(4):
            r.resolve(_point_txns(3, rv=30 + 10 * i), 40 + 10 * i, 0)
    finally:
        deterministic.registry().reset_clock()
    # two dispatches of the four: pack's two, then one as each stage closes
    assert len(reads) == 2 * 4
    snap = r.profile.snapshot()
    _splits_add_up(snap, ONE_LANE)
    assert snap["route_cpu_ms"] == snap["route_offcpu_ms"] == 0.0


def test_the_backlog_route_feeds_the_readback_through_the_stage():
    """``resolve_many``'s materialize is a ``resolver.readback`` stage
    like the single batch's: wall, split and annotation from one feed."""
    r = Resolver(Knobs(**TEST_KNOBS))
    ann = _Annotations()
    prior = span_mod.set_annotator(ann)
    try:
        for d in range(2):  # the second backlog takes the CPU readings
            out = r.resolve_many([(_point_txns(2, rv=10 + 30 * d),
                                   20 + 30 * d + i, 0) for i in range(3)])
    finally:
        span_mod.set_annotator(prior)
    assert len(out) == 3
    assert ("fdb.resolver.readback", "exit") in ann.events
    snap = r.profile.snapshot()
    _splits_add_up(snap, ONE_LANE[2:])
    assert not hasattr(r.profile, "record_verdict_reduce")


def test_a_mesh_dispatch_splits_the_route_inside_the_enqueue():
    knobs = Knobs(**{**TEST_KNOBS, "resolver_backend": "tpu"})
    mesh = MeshResolver(knobs, n_lanes=4)
    reads = []
    mesh.resolve(_point_txns(3), 20, 0)
    mesh.resolve(_point_txns(3, rv=20), 30, 0)
    deterministic.set_cpu_clock(
        lambda: reads.append(1) or time.thread_time())
    try:
        for i in range(2):
            mesh.resolve(_point_txns(3, rv=30 + 10 * i), 40 + 10 * i, 0)
    finally:
        deterministic.registry().reset_clock()
    # one dispatch of the two: the route's two inside the enqueue's
    assert len(reads) == 6
    snap = mesh.profile.snapshot()
    _splits_add_up(snap, ONE_LANE + (("route", "route_wall_ms"),))
    # resolver.enqueue ⊃ resolver.route on a mesh, so do their CPU sums
    assert snap["enqueue_cpu_ms"] >= snap["route_cpu_ms"] > 0


# ─────────── same-seed sims: identical span streams, opt-in on ───────────
def _sim_stream(seed, datadir):
    from foundationdb_tpu.sim.simulation import Simulation
    from foundationdb_tpu.sim.workloads import cycle_setup, cycle_workload

    log = global_trace_log()
    log.clear()
    # the device backend (JAX on the CPU): its dispatches open the four
    # stages with cpu=True, which the sim's default host backend never does
    sim = Simulation(seed=seed, buggify=True, crash_p=0.0, datadir=datadir,
                     tracing_sample_rate=1.0, resolver_backend="tpu",
                     **TEST_KNOBS)
    try:
        cycle_setup(sim.db, 6)
        sim.add_workload(
            "c0", cycle_workload(sim.db, 6, 6, random.Random(seed)))
        sim.run()
        return "\n".join(json.dumps(e, sort_keys=False, default=repr)
                         for e in log.events("Span"))
    finally:
        sim.close()
        deterministic.unseed()
        deterministic.registry().reset_clock()


def test_same_seed_sims_emit_identical_span_streams_with_cpu_stages(
        tmp_path):
    s1 = _sim_stream(3939, str(tmp_path / "s1"))
    s2 = _sim_stream(3939, str(tmp_path / "s2"))
    assert s1 == s2
    spans = [json.loads(line) for line in s1.splitlines()]
    split = [s for s in spans if s["span"] in STAGE_CPU]
    assert {s["span"] for s in split} == {
        "resolver.pack", "resolver.enqueue", "resolver.readback"}
    # the CPU clock is the injected clock there: nothing of the real one
    # (a dispatch in two takes the readings)
    read = [s for s in split if "cpu_ms" in s]
    assert 0 < len(read) < len(split)
    assert all(s["cpu_ms"] == s["dur_ms"] for s in read)
    assert not any("cpu_ms" in s for s in spans if s["span"] not in STAGE_CPU)


# ───────────────────── counted acquisition of a mutex ─────────────────────
@pytest.fixture
def no_clock():
    """A clock that raises: an uncontended acquisition reads none."""
    deterministic.set_clock(_raise)
    yield
    deterministic.registry().reset_clock()


@pytest.mark.parametrize("make", [lockdep.lock, lockdep.rlock])
def test_an_uncontended_acquisition_counts_and_reads_no_clock(
        no_clock, make):
    mu = make("T._mu")
    c = lockdep.counted(mu, "t_mu")
    with c:
        assert not mu.acquire(False) or make is lockdep.rlock
        if make is lockdep.rlock:
            mu.release()
    assert c.snapshot() == {"acquisitions": 1, "blocked": 0, "wait_us": 0}
    assert mu.acquire(False)  # released on the way out
    mu.release()


def test_an_rlock_reentered_by_its_owner_is_not_blocked(no_clock):
    mu = lockdep.rlock("T._mu")
    c = lockdep.counted(mu, "t_mu")
    with c:
        with c:  # the owner's re-entry can never wait
            with mu:
                pass
    assert c.snapshot() == {"acquisitions": 2, "blocked": 0, "wait_us": 0}


def _hold(mu, held, release):
    with mu:
        held.set()
        release.wait(10)


def test_a_held_lock_counts_blocked_and_the_wait():
    mu = lockdep.lock("T._mu")
    c = lockdep.counted(mu, "t_mu")
    ann = _Annotations()
    prior = span_mod.set_annotator(ann)
    held, release = threading.Event(), threading.Event()
    th = threading.Thread(target=_hold, args=(mu, held, release),
                          name="holder", daemon=True)
    th.start()
    assert held.wait(10)
    hold_s = 0.05
    threading.Timer(hold_s, release.set).start()
    t0 = time.monotonic()
    try:
        with c:
            waited = time.monotonic() - t0
    finally:
        span_mod.set_annotator(prior)
    th.join(10)
    snap = c.snapshot()
    assert snap["acquisitions"] == 1 and snap["blocked"] == 1
    # at least the hold, and what this thread saw of it
    assert hold_s * 1e6 * 0.9 <= snap["wait_us"] <= waited * 1e6 + 1000
    # the wait is fdb.lock.<name> on the host plane, and only the wait
    assert ann.events == [("fdb.lock.t_mu", "enter"),
                          ("fdb.lock.t_mu", "exit")]
    with c:  # free again: counted, no wait added
        pass
    assert c.snapshot() == {**snap, "acquisitions": 2}


def test_counts_hold_under_more_threads_than_cores():
    """The three numbers are written while holding the lock they
    describe: sixteen threads lose no acquisition and no update of what
    the lock guards."""
    mu = lockdep.lock("T._mu")
    c = lockdep.counted(mu, "t_mu")
    guarded = [0]
    threads, each = 2 * (os.cpu_count() or 4), 2000

    def work():
        for _ in range(each):
            with c:
                guarded[0] += 1

    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        ts = [threading.Thread(target=work, name=f"w{i}", daemon=True)
              for i in range(threads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(60)
    finally:
        sys.setswitchinterval(was)
    assert not any(t.is_alive() for t in ts)
    snap = c.snapshot()
    assert snap["acquisitions"] == guarded[0] == threads * each
    assert 0 <= snap["blocked"] <= snap["acquisitions"]
    assert (snap["wait_us"] > 0) == (snap["blocked"] > 0)


def test_sum_counted_adds_a_roles_instances():
    a = lockdep.counted(lockdep.lock("T._a"), "t")
    b = lockdep.counted(lockdep.lock("T._a"), "t")
    with a:
        pass
    with b:
        pass
    b.blocked, b.wait_s = 2, 0.0015
    assert lockdep.sum_counted([a, b]) == {
        "acquisitions": 2, "blocked": 2, "wait_us": 1500}
    assert lockdep.sum_counted([]) == {
        "acquisitions": 0, "blocked": 0, "wait_us": 0}


def _nest(outer, inner):
    with outer:
        with inner:
            pass


def test_the_witness_sees_a_counted_acquisition_as_a_plain_one(witness):
    plain = (witness.rlock("T._mu"), witness.lock("T._inner"))
    _nest(*plain)
    want = witness.edge_set()
    n = witness.acquisition_count()
    assert want == {("T._mu", "T._inner")}
    witness.reset()
    mu, inner = witness.rlock("T._mu"), witness.lock("T._inner")
    _nest(lockdep.counted(mu, "t_mu"), lockdep.counted(inner, "t_inner"))
    assert witness.edge_set() == want  # no edge more, none less
    assert witness.acquisition_count() == n
    assert witness.cycle_count() == 0


FIXTURE = '''
from foundationdb_tpu.utils import lockdep

class Store:
    def __init__(self):
        self._mu = lockdep.rlock("Store._mu")
        self._mu_read = lockdep.counted(self._mu, "store_mu_read")
        self._side = lockdep.lock("Store._side")

    def read(self):
        with self._mu_read:
            with self._side:
                pass
'''


def test_fl006_sees_a_counted_attribute_as_the_lock_it_wraps():
    from foundationdb_tpu.analysis.model import ProgramModel
    from foundationdb_tpu.analysis.rules import fl006_lockorder

    model = ProgramModel([("store.py", FIXTURE)])
    edges, _ = fl006_lockorder.compute_graph(model)
    assert set(edges) == {("Store._mu", "Store._side")}


def test_the_trees_lock_order_is_what_it_was():
    """``analysis/lockorder.txt`` names no counted lock and the tree
    still produces exactly its edges (FL006 on the full tree)."""
    text = open(os.path.join(
        os.path.dirname(flowlint.__file__), "lockorder.txt")).read()
    assert "counted" not in text and "_mu_read" not in text
    from foundationdb_tpu.analysis.rules import BY_ID

    # (FL001 and FL004 over the tree: tests/test_flowlint_tree.py)
    findings = flowlint.lint_paths([flowlint.package_dir()],
                                   rules=[BY_ID["FL006"]])
    assert [(f.path, f.line, f.message) for f in findings] == []


# ───────────────── the three mutexes, and cluster.locks ─────────────────
def test_storage_counts_one_acquisition_a_served_read():
    st = StorageServer()
    st.apply(1, [])
    assert st._mu_apply.snapshot()["acquisitions"] == 1
    st.get(b"a", 1)
    assert st._mu_read.acquisitions == 1
    st.read_batch([("g", b"a", 1), ("g", b"b", 1), ("g", b"c", 1)])
    assert st._mu_read.acquisitions == 2  # one a batch, not one a key
    list(st._iter_live(b"", b"\xff", 1))  # the router's range read
    assert st._mu_read.acquisitions == 3
    assert st._mu_read.blocked == st._mu_apply.blocked == 0


def test_a_batched_point_read_still_passes_through_storage_get(
        monkeypatch):
    """``benchmark/faults.py:alter_read`` patches ``StorageServer.get``:
    a point read served by ``read_batch`` goes through it, under the
    batch's one counted acquisition."""
    seen = []
    real = StorageServer.get

    def watched(self, key, version):
        seen.append(key)
        return real(self, key, version)

    monkeypatch.setattr(StorageServer, "get", watched)
    st = StorageServer()
    st.apply(1, [])
    assert st.read_batch([("g", b"a", 1), ("g", b"b", 1)]) == [None, None]
    assert seen == [b"a", b"b"]
    assert st._mu_read.acquisitions == 1
    # and the batch's thread takes the mutex again once the batch is over,
    # also after a batch that raised
    assert st._batch_thread is None and st.get(b"a", 1) is None
    assert st._mu_read.acquisitions == 2
    with pytest.raises(TypeError):
        st.read_batch([None])
    assert st._batch_thread is None


def _bump(tr):
    v = tr.get(b"counter")
    tr.set(b"counter", b"%d" % (int(v or b"0") + 1))


def test_served_status_carries_cluster_locks_as_integers():
    cluster = Cluster(resolver_backend="cpu", commit_pipeline="thread",
                      **TEST_KNOBS)
    server = serve_cluster(cluster)
    db = fdb.open(address=server.address)
    try:
        for _ in range(5):
            db.run(_bump)
        _wait_until(lambda: db.status()["cluster"]["locks"]["commit_mu"]
                    ["acquisitions"] >= 5, "five commits to be counted")
        locks = db.status()["cluster"]["locks"]
    finally:
        db._cluster.close()
        server.close()
        cluster.close()
    assert set(locks) == {"storage_mu_read", "storage_mu_apply",
                          "commit_mu", "grv_lock"}
    for name, doc in locks.items():
        assert set(doc) == {"acquisitions", "blocked", "wait_us"}, name
        assert all(type(v) is int for v in doc.values()), (name, doc)
        assert doc["acquisitions"] >= 5, (name, doc)
        assert 0 <= doc["blocked"] <= doc["acquisitions"]


def test_a_sync_clusters_grv_proxy_has_no_lock_to_count():
    cluster = Cluster(resolver_backend="cpu", **TEST_KNOBS)
    try:
        cluster.database()[b"k"] = b"v"
        locks = cluster.status()["cluster"]["locks"]
    finally:
        cluster.close()
    assert locks["grv_lock"] == {"acquisitions": 0, "blocked": 0,
                                 "wait_us": 0}
    assert locks["commit_mu"]["acquisitions"] >= 1
    assert locks["storage_mu_apply"]["acquisitions"] >= 1
