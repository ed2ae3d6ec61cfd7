"""The aggregated status document (ISSUE 4 tentpole): a cluster with a
commit-proxy fleet and sharded resolvers serves \\xff\\xff/status/json
with every live role's metrics, monotone latency bands, cluster-level
rollups, and counters that survive a txn-system recovery without going
backwards."""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(__file__)))

from foundationdb_tpu.server.cluster import Cluster  # noqa: E402
from foundationdb_tpu.txn import specialkeys  # noqa: E402

from conftest import TEST_KNOBS  # noqa: E402


def _assert_monotone(bands):
    assert bands["p50_ms"] <= bands["p90_ms"] <= bands["p99_ms"] \
        <= bands["max_ms"], bands


@pytest.fixture
def fleet_db():
    cluster = Cluster(n_commit_proxies=2, n_resolvers=2, n_storage=2,
                      n_tlogs=3, resolver_backend="cpu", **TEST_KNOBS)
    yield cluster.database(), cluster
    cluster.close()


def test_status_json_carries_every_role(fleet_db):
    db, cluster = fleet_db
    for i in range(30):
        db[b"k%02d" % i] = b"v" * 20
    raw = db.run(lambda tr: tr.get(specialkeys.STATUS_JSON))
    st = json.loads(raw)["cluster"]
    procs = st["processes"]
    # every live role appears with a metrics snapshot
    assert len(procs["commit_proxy"]["members"]) == 2
    for m in procs["commit_proxy"]["members"]:
        assert m["alive"]
        assert m["metrics"]["role"] == "commit_proxy"
    assert len(procs["grv_proxies"]) == 2
    assert len(procs["resolvers"]) == 2
    for r in procs["resolvers"]:
        assert r["metrics"]["counters"]["resolve_batches"] > 0
    assert len(procs["storage_servers"]) == 2
    for s in procs["storage_servers"]:
        assert s["metrics"]["counters"]["mutations_applied"] > 0
    assert len(procs["logs"]["replicas"]) == 3
    for log in procs["logs"]["replicas"]:
        assert log["metrics"]["counters"]["pushes"] > 0
    assert procs["ratekeeper"]["metrics"]["gauges"]["target_tps"] > 0
    # rollups exist and every published band is monotone
    roll = st["metrics"]["rollups"]
    assert roll["commit_spans"] > 0
    _assert_monotone(st["metrics"]["commit_latency_bands"])
    _assert_monotone(st["metrics"]["grv_latency_bands"])
    for m in procs["commit_proxy"]["members"]:
        for bands in m["metrics"]["latency_ms"].values():
            _assert_monotone(bands)
    # workload counters reflect the traffic
    assert st["workload"]["transactions"]["committed"]["counter"] >= 30


def test_metrics_json_special_key(fleet_db):
    db, _ = fleet_db
    db[b"a"] = b"b"
    doc = json.loads(db.run(lambda tr: tr.get(specialkeys.METRICS_JSON)))
    assert "rollups" in doc
    assert doc["rollups"]["commit_spans"] >= 1
    _assert_monotone(doc["commit_latency_bands"])


def test_counters_survive_proxy_recovery(fleet_db):
    """Kill the commit-proxy fleet; after the failure monitor recruits
    a new txn-system generation, status counters continue from where
    the dead generation left off — never backwards (the registries are
    cluster-owned, not incarnation-owned)."""
    db, cluster = fleet_db
    for i in range(20):
        db[b"pre%02d" % i] = b"x"
    before = cluster.status()["cluster"]["workload"]["transactions"]
    committed_before = before["committed"]["counter"]
    started_before = before["started"]["counter"]
    assert committed_before >= 20

    cluster._commit_target().kill()
    assert cluster.detect_and_recruit() == [("txn-system", 0)]

    mid = cluster.status()["cluster"]["workload"]["transactions"]
    assert mid["committed"]["counter"] >= committed_before
    assert mid["started"]["counter"] >= started_before

    for i in range(10):
        db[b"post%02d" % i] = b"y"
    after = cluster.status()["cluster"]["workload"]["transactions"]
    assert after["committed"]["counter"] >= committed_before + 10
    assert after["started"]["counter"] >= started_before
    # the commit latency bands kept accumulating across the recovery
    roll = cluster.metrics_status()["rollups"]
    assert roll["commit_spans"] > 0


def test_resolver_respawn_keeps_counters(fleet_db):
    db, cluster = fleet_db
    for i in range(10):
        db[b"r%02d" % i] = b"x"
    before = sum(r.metrics.counter("resolve_batches").value
                 for r in cluster.resolvers)
    assert before > 0
    cluster.resolvers[0].kill()
    assert ("resolver", 0) in cluster.detect_and_recruit()
    db[b"after"] = b"y"
    after = sum(r.metrics.counter("resolve_batches").value
                for r in cluster.resolvers)
    assert after > before
    assert cluster.resolvers[0].metrics.counter("respawns").value == 1


def test_configure_shrink_absorbs_orphan_registries(fleet_db):
    """A fleet resize from 2 → 1 proxies folds the orphaned member's
    totals into member 0: cluster totals never go backwards."""
    db, cluster = fleet_db
    for i in range(16):
        db[b"s%02d" % i] = b"x"
    committed = cluster.status()["cluster"]["workload"]["transactions"][
        "committed"]["counter"]
    cluster.configure(commit_proxies=1)
    st = cluster.status()["cluster"]
    assert st["processes"]["commit_proxy"]["count"] == 1
    assert st["workload"]["transactions"]["committed"]["counter"] \
        >= committed


def test_hottest_stage_attribution_thread_mode():
    """The thread-pipeline batcher feeds stage bands; the rollup names
    the stage with the most total wall time."""
    import threading

    cluster = Cluster(commit_pipeline="thread", resolver_backend="cpu",
                      commit_pipeline_depth=2, **TEST_KNOBS)
    db = cluster.database()
    try:
        def writer(wid):
            for i in range(40):
                db[b"w%d/%03d" % (wid, i)] = b"v" * 10

        ts = [threading.Thread(target=writer, args=(w,)) for w in range(4)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        roll = cluster.metrics_status()["rollups"]
        assert roll["commit_spans"] > 0
        _assert_monotone(cluster.metrics_status()["commit_latency_bands"])
        if roll["hottest_stage"] is not None:
            # the backlog route's four stages, or the serial
            # commit_batch route's six (lone windows take that one)
            assert roll["hottest_stage"] in (
                "pack", "dispatch", "resolve", "apply",
                "commit_build", "commit_resolve", "commit_assemble",
                "commit_log_push", "commit_storage_apply",
                "commit_report",
            )
            assert roll["hottest_stage_totals_s"][roll["hottest_stage"]] > 0
    finally:
        cluster.close()


def test_storage_recruitment_keeps_counters():
    cluster = Cluster(n_storage=3, replication=2, resolver_backend="cpu",
                      **TEST_KNOBS)
    db = cluster.database()
    try:
        for i in range(20):
            db[b"k%02d" % i] = b"v" * 30
        before = cluster.storages[1].metrics.counter(
            "mutations_applied").value
        assert before > 0
        cluster.storages[1].kill()
        assert ("storage", 1) in cluster.detect_and_recruit()
        assert cluster.storages[1].metrics.counter(
            "mutations_applied").value >= before
    finally:
        cluster.close()
