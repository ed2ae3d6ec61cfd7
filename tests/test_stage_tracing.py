"""``utils/span.stage`` — one pair of stamps, three sinks — and the
served request path it instruments: the RPC server's per-class
counters, the commit stages on both served routes, the resolver's host
stages in the device profile, and the annotations a profiler would
record."""

import json
import os
import sys
import threading
import time

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(__file__)))

import foundationdb_tpu as fdb  # noqa: E402
from foundationdb_tpu.core import flatpack  # noqa: E402
from foundationdb_tpu.core.commit import CommitRequest  # noqa: E402
from foundationdb_tpu.core.options import Knobs  # noqa: E402
from foundationdb_tpu.resolver.resolver import Resolver  # noqa: E402
from foundationdb_tpu.resolver.skiplist import TxnRequest  # noqa: E402
from foundationdb_tpu.rpc.service import serve_cluster  # noqa: E402
from foundationdb_tpu.rpc.transport import (  # noqa: E402
    RPC_COUNTERS, RpcClient, RpcServer, rpc_class,
)
from foundationdb_tpu.server.cluster import Cluster  # noqa: E402
from foundationdb_tpu.utils import deviceprofile  # noqa: E402
from foundationdb_tpu.utils import metrics as metrics_mod  # noqa: E402
from foundationdb_tpu.utils import span as span_mod  # noqa: E402
from foundationdb_tpu.utils.deviceprofile import DeviceProfile  # noqa: E402
from foundationdb_tpu.utils.trace import (  # noqa: E402
    StageStats, global_trace_log,
)

from conftest import TEST_KNOBS  # noqa: E402
from test_tracing import _sim_span_stream  # noqa: E402

COMMIT_STAGES = ("batch", "build", "resolve", "assemble", "log_push",
                 "storage_apply", "report")


def _spans():
    return global_trace_log().events("Span")


class _Annotations:
    """A stand-in for ``jax.profiler.TraceAnnotation``: records what a
    profiler would, (name, "enter"/"exit"), in order."""

    def __init__(self):
        self.events = []

    def __call__(self, name):
        outer = self

        class _One:
            def __enter__(self):
                outer.events.append((name, "enter"))

            def __exit__(self, *exc):
                outer.events.append((name, "exit"))

        return _One()

    def names(self):
        return {n for n, _ in self.events}


@pytest.fixture
def annotations():
    ann = _Annotations()
    prior = span_mod.set_annotator(ann)
    yield ann
    span_mod.set_annotator(prior)


@pytest.fixture
def ambient():
    """Install a context for the test, restore the thread's after."""
    prior = span_mod.current()
    yield span_mod.set_current
    span_mod.set_current(prior)


# ───────────────────────── the primitive ─────────────────────────
def test_stage_feeds_stage_stats_and_bands_without_dots():
    reg = metrics_mod.MetricsRegistry("t")
    stats = StageStats(registry=reg)
    with span_mod.stage("commit.build", stats) as st:
        pass
    with span_mod.stage("commit.build", stats):
        pass
    assert st.t1 >= st.t0 and st.seconds == st.t1 - st.t0
    assert set(stats.summary()) == {"commit.build"}
    band = reg.get_latency("stage_commit_build")
    assert band is not None and band.count == 2
    assert reg.get_latency("stage_commit.build") is None


@pytest.mark.parametrize("ctx,emits", [
    (None, False),                 # no ambient context
    ((7, 9, False), False),        # an unsampled one
    ((7, 9, True), True),          # a sampled one
])
def test_stage_emits_a_child_span_only_under_a_sampled_context(
        ambient, ctx, emits):
    global_trace_log().clear()
    ambient(ctx)
    before = span_mod.spans_emitted()
    with span_mod.stage("commit.resolve", txns=3) as st:
        inside = span_mod.current()
    assert span_mod.current() == ctx  # restored
    assert span_mod.spans_emitted() - before == (1 if emits else 0)
    if not emits:
        assert inside == ctx  # nothing was built, nothing installed
        return
    (ev,) = [s for s in _spans() if s["span"] == "commit.resolve"]
    assert ev["trace"] == "%016x" % 7 and ev["parent"] == "%016x" % 9
    assert ev["txns"] == 3
    # the span carries the stage's own stamps: one clock, one interval
    assert ev["begin"] == round(st.t0, 6) and ev["end"] == round(st.t1, 6)
    # while open, the stage IS the ambient context: hops nest under it
    assert inside == (7, int(ev["sid"], 16), True)


def test_nested_stages_parent_to_each_other(ambient):
    global_trace_log().clear()
    ambient((1, 2, True))
    with span_mod.stage("commit.batch"):
        with span_mod.stage("commit.log_push"):
            with span_mod.stage("tlog.push"):
                pass
    by = {s["span"]: s for s in _spans()}
    assert by["tlog.push"]["parent"] == by["commit.log_push"]["sid"]
    assert by["commit.log_push"]["parent"] == by["commit.batch"]["sid"]
    assert by["commit.batch"]["parent"] == "%016x" % 2


def test_stage_annotates_with_the_fdb_prefix(annotations):
    with span_mod.stage("resolver.enqueue"):
        assert annotations.events == [("fdb.resolver.enqueue", "enter")]
    assert annotations.events == [("fdb.resolver.enqueue", "enter"),
                                  ("fdb.resolver.enqueue", "exit")]


def test_stage_builds_nothing_with_no_sink(ambient):
    """No annotator, no sampled context, no stats: two clock reads."""
    assert span_mod.set_annotator(None) is None  # none was installed
    ambient(None)
    before = span_mod.spans_emitted()
    with span_mod.stage("rpc.read", method="storage_get") as st:
        pass
    assert st._span is None and st._ann is None
    assert span_mod.spans_emitted() == before


def test_stage_respects_the_metrics_kill_switch():
    stats = StageStats()
    metrics_mod.set_enabled(False)
    try:
        with span_mod.stage("commit.build", stats):
            pass
    finally:
        metrics_mod.set_enabled(True)
    assert stats.summary() == {}


def test_stage_records_the_error_and_still_feeds_every_sink(
        ambient, annotations):
    global_trace_log().clear()
    ambient((3, 4, True))
    stats = StageStats()
    with pytest.raises(ValueError):
        with span_mod.stage("commit.assemble", stats):
            raise ValueError("boom")
    (ev,) = [s for s in _spans() if s["span"] == "commit.assemble"]
    assert ev["error"] == "boom"
    assert "commit.assemble" in stats.summary()
    assert annotations.events[-1] == ("fdb.commit.assemble", "exit")
    assert span_mod.current() == (3, 4, True)


# ────────────────────── the device profile sink ──────────────────────
@pytest.mark.parametrize("stage,field", sorted(
    deviceprofile.STAGE_WALLS.items()))
def test_device_profile_add_maps_stage_to_wall(stage, field):
    p = DeviceProfile("r")
    p.add(stage, 0.002)
    p.add(stage, 0.001)
    snap = p.snapshot()
    key = field[:-2] + "_ms"  # pack_wall_s -> pack_wall_ms
    assert snap[key] == 3.0
    assert sum(snap[f[:-2] + "_ms"]
               for f in deviceprofile.STAGE_WALLS.values()) == 3.0
    q = DeviceProfile("q")
    q.absorb(p)  # carried across respawn like every other total
    assert q.snapshot()[key] == 3.0
    deviceprofile.set_enabled(False)
    try:
        p.add(stage, 1.0)
    finally:
        deviceprofile.set_enabled(True)
    assert p.snapshot()[key] == 3.0


def _flat_txns(L):
    rcr, wcr = [(b"a", b"a\x00")], [(b"b", b"b\x00")]
    req = CommitRequest(10, [], rcr, wcr,
                        flat_conflicts=flatpack.encode_conflicts(
                            rcr, wcr, L))
    return flatpack.build_flat_batch([req], L)


@pytest.mark.parametrize("route", ["flat", "legacy"])
def test_single_batch_tpu_route_fills_the_host_stage_walls(
        route, annotations):
    knobs = Knobs(**TEST_KNOBS)  # resolver_backend defaults to "tpu"
    r = Resolver(knobs)
    txns = _flat_txns(knobs.key_limbs) if route == "flat" else [
        TxnRequest(read_version=10, point_reads=[b"a"],
                   point_writes=[b"b"])]
    r.resolve(txns, 20, 0)  # compiles: the walls below include it
    r.resolve(txns if route == "legacy" else
              _flat_txns(knobs.key_limbs), 30, 0)
    snap = r.profile.snapshot()
    assert snap["dispatches"] == 2
    for key in ("pack_wall_ms", "enqueue_wall_ms",
                "verdict_reduce_wall_ms"):
        assert snap[key] > 0.0, key
    # dispatch keeps its meaning — all of one kernel step: it IS the
    # enqueue plus the readback, from their stamps (0.002: roundings)
    assert abs(snap["dispatch_wall_ms"] - snap["enqueue_wall_ms"]
               - snap["verdict_reduce_wall_ms"]) <= 0.002
    # one dispatch: pack, then enqueue, then readback, nothing nested
    order = [e for e in annotations.events
             if e[0].startswith("fdb.resolver.")][:6]
    assert order == [
        ("fdb.resolver.pack", "enter"), ("fdb.resolver.pack", "exit"),
        ("fdb.resolver.enqueue", "enter"),
        ("fdb.resolver.enqueue", "exit"),
        ("fdb.resolver.readback", "enter"),
        ("fdb.resolver.readback", "exit"),
    ]


# ─────────────────────────── the RPC server ───────────────────────────
@pytest.mark.parametrize("method,cls", [
    ("storage_get", "read"), ("read_batch", "read"),
    ("get_read_version", "grv"), ("commit", "commit"),
    ("commit_batch", "commit"), ("status", "admin"),
    ("no_such_method", "admin"),
])
def test_rpc_class_table(method, cls):
    assert rpc_class(method) == cls


def _wait_until(pred, what, timeout=20.0):
    deadline = time.monotonic() + timeout
    while not pred():
        assert time.monotonic() < deadline, f"waited for {what}"
        time.sleep(0.001)


def test_rpc_server_counts_queue_wait_and_handler_by_class():
    """One worker, held by an Event: the request behind it waits in
    the pool's queue for at least as long as the hold. Reads and GRVs
    are declared inline, as the served cluster declares them, so the
    request that queues is an admin call, and the read sent while the
    worker is held is answered on its connection's thread."""
    started, release = threading.Event(), threading.Event()

    def held():
        started.set()
        release.wait(30)
        return "held"

    server = RpcServer("127.0.0.1", 0, {
        "commit": held,                    # commit class
        "storage_get": lambda k: k,        # read class
        "get_read_version": lambda: 7,     # grv class
        "hello": lambda: "hi",             # unlisted -> admin
    }, max_workers=1, inline_methods={"storage_get", "get_read_version"})
    client = RpcClient(server.host, server.port)
    try:
        first = client.call_async("commit")
        assert started.wait(20)
        behind = client.call_async("hello")
        _wait_until(lambda: server.stats()["pool"]["queued"] == 1,
                    "the second request to be decoded and queued")
        t_queued = span_mod.now()
        # past the held worker and the queued call, with no worker
        assert client.call("storage_get", b"k", timeout=20) == b"k"
        assert not first.done() and not behind.done()
        while span_mod.now() - t_queued < 0.02:
            pass  # the hold: measured, not slept on
        hold_us = (span_mod.now() - t_queued) * 1e6
        release.set()
        assert first.result(20) == "held"
        assert behind.result(20) == "hi"
        assert client.call("get_read_version", timeout=20) == 7
        assert client.call("get_read_version", timeout=20) == 7
        with pytest.raises(Exception):
            client.call("no_such_endpoint", timeout=20)
        # the reply is sent inside the handler's stage, the counters
        # are added after it: wait for the last add
        _wait_until(lambda: sum(server.stats()["requests"].values()) == 6,
                    "six requests counted")
        doc = server.stats()
    finally:
        release.set()
        client.close()
        server.close()
    assert set(RPC_COUNTERS) <= set(doc)
    assert doc["requests"] == {"read": 1, "grv": 2, "commit": 1,
                               "admin": 2}
    # answered where they were decoded: the declared endpoints' requests
    assert doc["inline_requests"] == {"read": 1, "grv": 2, "commit": 0,
                                      "admin": 0}
    # six requests, sent one at a time but for the two in flight
    # together: a socket read brought one whole frame, seldom two
    assert 4 <= doc["recv_calls"] <= 6
    assert doc["queue_wait_us"]["admin"] >= hold_us
    assert doc["queue_wait_us"]["read"] < hold_us  # it never queued
    # the held handler waited on an Event: wall far above its CPU
    assert doc["handler_wall_us"]["commit"] >= hold_us
    # stamps for the first request of a class and every fourth after it
    assert doc["timed_requests"] == {"read": 1, "grv": 1, "commit": 1,
                                     "admin": 1}
    for counter in RPC_COUNTERS:
        assert all(isinstance(v, int) and v >= 0
                   for v in doc[counter].values()), counter
    assert doc["pool"]["workers"] == 1
    assert doc["pool"]["queued"] == 0
    assert doc["pool"]["queued_high_water"] >= 1
    assert doc["process"]["cpu_us"] > 0 and doc["process"]["wall_us"] > 0


def test_rpc_handler_stage_annotates_by_class(annotations):
    server = RpcServer("127.0.0.1", 0, {"get_read_version": lambda: 1})
    client = RpcClient(server.host, server.port)
    try:
        assert client.call("get_read_version", timeout=20) == 1
        _wait_until(lambda: ("fdb.rpc.grv", "exit") in annotations.events,
                    "the handler's stage to close")
    finally:
        client.close()
        server.close()


# ──────────────────── both served routes, end to end ────────────────────
def _increment(tr):
    v = tr.get(b"counter")
    tr.set(b"counter", b"%d" % (int(v or b"0") + 1))


@pytest.mark.parametrize("route", ["commit", "commit_batch"])
def test_served_status_carries_rpc_counters_and_commit_stage_bands(
        route, annotations):
    """``commit`` rides the server's batcher, ``commit_batch`` (a
    client with ``commit_pipeline="thread"``) goes past it; both end
    in the proxy's serial ``commit_batch`` and record every stage."""
    cluster = Cluster(resolver_backend="cpu", commit_pipeline="thread",
                      **TEST_KNOBS)
    server = serve_cluster(cluster)
    kw = {"commit_pipeline": "thread"} if route == "commit_batch" else {}
    db = fdb.open(address=server.address, **kw)
    try:
        for _ in range(5):
            db.run(_increment)
        # a request is counted after its reply is sent (the count
        # shares the add that takes the reply's stamp): the fifth
        # commit's may still be on its way when status is asked for
        _wait_until(lambda: db.status()["cluster"]["rpc"]["requests"]
                    ["commit"] >= 5, "the fifth commit to be counted")
        doc = db.status()["cluster"]
    finally:
        db._cluster.close()
        server.close()
        cluster.close()
    rpc = doc["rpc"]
    assert set(RPC_COUNTERS) | {"pool", "process"} <= set(rpc)
    assert rpc["requests"]["commit"] >= 5
    assert rpc["requests"]["read"] >= 5 and rpc["requests"]["grv"] >= 5
    assert rpc["handler_wall_us"]["commit"] > 0
    bands = doc["processes"]["commit_proxy"]["members"][0]["metrics"][
        "latency_ms"]
    counts = {s: bands["stage_commit_" + s]["count"]
              for s in COMMIT_STAGES}
    assert len(set(counts.values())) == 1 and counts["batch"] >= 5, counts
    # sequential, not overlapping: the six sum to (just under) the batch
    # (status rounds each mean to 0.001 ms)
    parts = sum(bands["stage_commit_" + s]["mean_ms"]
                for s in COMMIT_STAGES[1:])
    assert parts <= bands["stage_commit_batch"]["mean_ms"] + 0.006
    assert (bands["batcher_wait"]["count"] > 0) == (route == "commit")
    roll = doc["metrics"]["rollups"]
    assert roll["hottest_stage_totals_s"], roll
    assert roll["hottest_stage"] in {"commit_" + s
                                     for s in COMMIT_STAGES[1:]}
    assert {"fdb.rpc.commit", "fdb.rpc.read", "fdb.rpc.grv",
            "fdb.commit.batch", "fdb.commit.build", "fdb.commit.resolve",
            "fdb.commit.assemble", "fdb.commit.log_push",
            "fdb.commit.storage_apply", "fdb.commit.report",
            "fdb.resolver.dispatch",  # the host backend's one stage
            "fdb.tlog.push", "fdb.storage.apply",
            "fdb.grv.grant"} <= annotations.names()
    # (the commit_batch client batches in THIS process, with the same
    # batcher class: fdb.batcher.window shows on both routes here)
    assert "fdb.batcher.window" in annotations.names()


def test_in_process_status_has_no_rpc_section():
    cluster = Cluster(resolver_backend="cpu", **TEST_KNOBS)
    try:
        cluster.database()[b"k"] = b"v"
        assert "rpc" not in cluster.status()["cluster"]
    finally:
        cluster.close()


def test_traced_commit_tree_gains_the_stage_hops():
    """A sampled transaction's tree carries the new hops, nested:
    tlog.push under commit.log_push, resolver.scan under
    commit.resolve, and the old hop names are all still there."""
    global_trace_log().clear()
    cluster = Cluster(resolver_backend="cpu", **TEST_KNOBS)
    try:
        tr = cluster.database().create_transaction()
        tr.options.set_trace()
        tr.get(b"hop")
        tr.set(b"hop", b"v")
        tr.commit()
        spans = _spans()
    finally:
        cluster.close()
    by = {s["span"]: s for s in spans}
    assert {"transaction", "txn.grv", "grv.grant", "txn.commit",
            "proxy.batch", "resolver.scan", "tlog.push", "storage.apply",
            "commit.batch", "commit.build", "commit.resolve",
            "commit.assemble", "commit.log_push", "commit.storage_apply",
            "commit.report", "resolver.dispatch"} <= set(by)
    assert len({s["trace"] for s in spans}) == 1
    assert by["commit.batch"]["parent"] == by["txn.commit"]["sid"]
    for child, parent in [("commit.build", "commit.batch"),
                          ("commit.resolve", "commit.batch"),
                          ("resolver.scan", "commit.resolve"),
                          ("resolver.dispatch", "commit.resolve"),
                          ("tlog.push", "commit.log_push"),
                          ("storage.apply", "commit.storage_apply"),
                          ("commit.log_push", "proxy.batch")]:
        assert by[child]["parent"] == by[parent]["sid"], (child, parent)
    assert by["tlog.push"]["mutations"] == 1
    assert by["grv.grant"]["version"] >= 0


# ─────────────── same-seed sims: identical span streams ───────────────
def test_same_seed_sims_emit_identical_span_streams_with_stage_hops(
        tmp_path):
    s1 = _sim_span_stream(4321, str(tmp_path / "s1"))
    s2 = _sim_span_stream(4321, str(tmp_path / "s2"))
    assert s1 == s2
    names = {json.loads(line)["span"] for line in s1.splitlines()}
    assert {"commit.batch", "commit.resolve", "commit.log_push",
            "tlog.push", "storage.apply", "grv.grant"} <= names, names
