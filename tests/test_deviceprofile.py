"""Device-path execution profiler (utils/deviceprofile.py): dispatch
accounting and pad waste, compile-cache observation, fallback-cause
taxonomy, staging reuse, per-lane walls on the mesh fleet, cluster
lifecycle carryover (respawn / recovery / configure shrink — the PR-4
never-rewind contract), the status / special-key / RPC / fdbcli
surfaces, and same-seed sim determinism of ``cluster.device``."""

import json
import random
import time

import pytest

from foundationdb_tpu.core import deterministic, flatpack
from foundationdb_tpu.core.options import Knobs
from foundationdb_tpu.ops import conflict as ck
from foundationdb_tpu.resolver.resolver import Resolver
from foundationdb_tpu.rpc.service import RemoteCluster, serve_cluster
from foundationdb_tpu.server.cluster import Cluster
from foundationdb_tpu.txn import specialkeys
from foundationdb_tpu.utils import deviceprofile
from foundationdb_tpu.utils.deviceprofile import (
    FALLBACK_CAUSES,
    DeviceProfile,
    merged_snapshot,
)

from conftest import TEST_KNOBS

KNOBS = Knobs(**TEST_KNOBS)  # resolver_backend defaults to "tpu"
L = KNOBS.key_limbs


# ───────────────────── DeviceProfile unit contract ─────────────────────
def test_snapshot_shape_and_taxonomy_zeros():
    snap = DeviceProfile("resolver", index=3).snapshot()
    assert snap["name"] == "resolver" and snap["id"] == 3
    assert snap["dispatches"] == 0
    assert snap["pad_waste_pct"] == 0.0
    assert snap["lane_skew_pct"] == 0.0
    assert snap["staging_reuse_rate"] == 0.0
    # the taxonomy is CLOSED and fully emitted: zeros included, so the
    # doc's shape is stable for readers that diff two status documents
    assert set(snap["fallback_causes"]) == set(FALLBACK_CAUSES)
    assert all(v == 0 for v in snap["fallback_causes"].values())
    json.dumps(snap)  # JSON-ready


def test_pad_waste_and_bucket_histogram():
    p = DeviceProfile("resolver")
    p.record_dispatch(bucket=8, live_batches=3, live_txns=10,
                      txn_slots=40)
    p.record_dispatch(bucket=8, live_batches=8, live_txns=30,
                      txn_slots=40)
    p.record_dispatch(bucket=2, live_batches=2, live_txns=20,
                      txn_slots=20)
    snap = p.snapshot()
    assert snap["dispatches"] == 3
    assert snap["bucket_histogram"] == {"2": 1, "8": 2}
    # 60 live of 100 slots -> 40% of padded slots burned
    assert snap["pad_waste_pct"] == 40.0
    assert snap["batches_live"] == 13 and snap["batch_slots"] == 18


def test_lane_walls_accumulate_and_skew():
    p = DeviceProfile("resolver")
    p.record_lanes([0.1, 0.2])
    p.record_lanes([0.1, 0.2])
    snap = p.snapshot()
    assert snap["lanes"] == 2 and snap["lane_dispatches"] == 2
    assert snap["lane_walls_ms"] == [200.0, 400.0]
    assert snap["lane_skew_pct"] == 50.0


def test_kill_switch_gates_recording_but_not_absorb():
    p = DeviceProfile("resolver")
    deviceprofile.set_enabled(False)
    try:
        p.record_dispatch(bucket=4, live_batches=1, live_txns=1,
                          txn_slots=4)
        p.record_compile(("k",))
        p.record_fallback("flat_to_legacy")
        p.record_staging(hit=True)
        p.record_lanes([0.1])
        p.add("resolver.readback", 0.5)
        assert p.snapshot()["verdict_reduce_wall_ms"] == 0.0
        assert p.snapshot()["dispatches"] == 0
        assert p.snapshot()["recompiles"] == 0
        # absorb BYPASSES the switch: carried history is not overhead
        donor = DeviceProfile("resolver")
        donor.dispatches = 7
        donor.fallback_causes["too_old_rv"] = 2
        p.absorb(donor)
        snap = p.snapshot()
        assert snap["dispatches"] == 7
        assert snap["fallback_causes"]["too_old_rv"] == 2
    finally:
        deviceprofile.set_enabled(True)


def test_merged_snapshot_rolls_up_a_fleet():
    a, b = DeviceProfile("resolver", 0), DeviceProfile("resolver", 1)
    a.record_dispatch(bucket=8, live_batches=2, live_txns=4, txn_slots=8)
    b.record_dispatch(bucket=8, live_batches=1, live_txns=4, txn_slots=8)
    b.record_fallback("over_capacity")
    agg = merged_snapshot([a, b])
    assert agg["name"] == "aggregate"
    assert agg["dispatches"] == 2
    assert agg["txns_live"] == 8 and agg["txn_slots"] == 16
    assert agg["fallback_causes"]["over_capacity"] == 1


# ───────── pack_rows_touched: the flat pack follows live rows ─────────
def test_pack_rows_touched_absorbs_both_ways_and_merges():
    used, fresh = DeviceProfile("resolver", 0), DeviceProfile("resolver", 1)
    used.record_pack_rows(18)
    used.record_pack_rows(9)
    assert used.snapshot()["pack_rows_touched"] == 27
    assert fresh.snapshot()["pack_rows_touched"] == 0
    agg = merged_snapshot([used, fresh])
    assert agg["pack_rows_touched"] == 27
    fresh.absorb(used)  # a respawn's new profile takes the history
    assert fresh.snapshot()["pack_rows_touched"] == 27
    used.absorb(DeviceProfile("resolver", 2))  # and an empty one adds 0
    assert used.snapshot()["pack_rows_touched"] == 27
    deviceprofile.set_enabled(False)
    try:
        used.record_pack_rows(5)  # gated like every capture site
        used.absorb(fresh)  # absorb is not
    finally:
        deviceprofile.set_enabled(True)
    assert used.snapshot()["pack_rows_touched"] == 54


@pytest.mark.parametrize("variant", ["full", "point-only"])
def test_pack_rows_touched_is_prev_plus_now_never_T(variant):
    """Nine transactions at T = 1024: a pack touches the rows its
    set's last pack wrote plus the rows it writes — 9 while the ring
    fills, 18 once it is warm — and never the 1,024 slots."""
    from foundationdb_tpu.core.commit import CommitRequest
    from foundationdb_tpu.resolver.packing import BatchPacker
    from foundationdb_tpu.resolver.resolver import (
        fast_params_of,
        params_from_knobs,
    )

    params = params_from_knobs(Knobs())
    assert params.txns == 1024
    if variant == "point-only":
        params = fast_params_of(params)
    limbs = params.key_width - 1
    packer = BatchPacker(params)
    prof = packer.profile = DeviceProfile("resolver")
    ring = packer.STAGING_RING

    def pack(n, salt):
        reqs = []
        for i in range(n):
            k = b"u%03d%04d" % (salt, i)
            wcr = [(k, k + b"\x00")]
            reqs.append(CommitRequest(
                10, [], [], wcr,
                flat_conflicts=flatpack.encode_conflicts([], wcr, limbs)))
        before = prof.snapshot()["pack_rows_touched"]
        packer.pack_flat(flatpack.build_flat_batch(reqs, limbs), 0,
                         20 + salt, 0)
        return prof.snapshot()["pack_rows_touched"] - before

    deltas = [pack(9, s) for s in range(3 * ring)]
    assert deltas[:ring] == [9] * ring  # fresh sets: nothing to reset
    assert deltas[ring:] == [18] * (2 * ring)  # n_prev + n_now
    # shrink and grow, as after the bulk load: the full batch is paid
    # once when written and once when its set comes round again
    assert pack(1024, 90) == 9 + 1024
    assert [pack(9, 91 + s) for s in range(ring - 1)] == [18] * (ring - 1)
    assert pack(0, 95) == 1024 + 0
    assert [pack(9, 96 + s) for s in range(ring)] == [18] * (ring - 1) + [9]


def test_pack_rows_touched_rides_the_resolver_hook_per_batch_row():
    """Through a Resolver (single-batch route and a padded backlog
    group): the counter rises by what the staging sets held and take,
    beside txn_slots, which rises by T a step."""
    r = Resolver(KNOBS)
    T = KNOBS.batch_txn_capacity

    def flat(n, salt):
        return _flat([
            _req(10, [], [(b"p%02d%02d" % (salt, i),
                           b"p%02d%02d\x00" % (salt, i))])
            for i in range(n)])

    r.resolve(flat(3, 0), 20, 0)
    snap = r.profile.snapshot()
    assert snap["pack_rows_touched"] == 3 and snap["txn_slots"] == T
    # a backlog group: three live batches in a padded bucket, one set
    r.resolve_many([(flat(n, 1 + g), 21 + g, 0)
                    for g, n in enumerate((5, 0, 2))])
    snap = r.profile.snapshot()
    assert snap["pack_rows_touched"] == 3 + 7
    assert snap["txn_slots"] > 2 * T
    assert merged_snapshot([r.profile])["pack_rows_touched"] == 10


def test_count_retraces_observes_new_signatures_only():
    import numpy as np

    calls = []
    fn = ck.count_retraces(lambda x: x, calls.append)
    fn(np.zeros((2, 3), np.uint32))
    fn(np.zeros((2, 3), np.uint32))  # same signature: no new event
    fn(np.zeros((4, 3), np.uint32))  # new shape: one more
    assert len(calls) == 2
    # gate=False arms skip signature hashing entirely (the kill switch
    # must leave ~zero work on the dispatch hot path)
    gated = []
    fn2 = ck.count_retraces(lambda x: x, gated.append, gate=lambda: False)
    fn2(np.zeros((2, 3), np.uint32))
    assert gated == []


# ───────────────── resolver capture (tpu backend) ─────────────────
def _legacy_batches(nb, rv=10, cv0=20):
    from foundationdb_tpu.resolver.skiplist import TxnRequest

    out = []
    for g in range(nb):
        txns = [TxnRequest(read_version=rv,
                           point_writes=[b"dk%02d%02d" % (g, t)])
                for t in range(3)]
        out.append((txns, cv0 + g, 0))
    return out


def test_backlog_dispatch_records_bucket_and_recompiles():
    r = Resolver(KNOBS)
    r.resolve_many(_legacy_batches(3))
    snap = r.profile.snapshot()
    assert snap["dispatches"] == 1
    # the scanned path pads 3 batches into one fixed bucket
    (bucket,) = snap["bucket_histogram"]
    assert int(bucket) >= 3
    assert snap["batches_live"] == 3
    assert snap["txns_live"] == 9
    assert snap["txn_slots"] == int(bucket) * r.params.txns
    assert snap["pad_waste_pct"] > 0  # 9 live txns in a padded scan
    assert snap["transfer_bytes"] > 0
    # entry occupancy: 9 point writes live, per-side slots padded
    assert snap["entries_live"]["pw"] == 9
    assert snap["entry_slots"]["pw"] >= 9
    # first dispatch traced the scan fn once
    assert snap["recompiles"] == 1
    assert len(snap["compile_keys"]) == 1
    # a second same-shape backlog reuses the compile cache
    r.resolve_many(_legacy_batches(3, rv=40, cv0=50))
    snap2 = r.profile.snapshot()
    assert snap2["dispatches"] == 2
    assert snap2["recompiles"] == 1
    # verdict materialization was timed host-side (>= 0 even under a
    # frozen clock; the field exists either way)
    assert snap2["verdict_reduce_wall_ms"] >= 0.0


def test_single_batch_resolve_records_pad_waste():
    from foundationdb_tpu.resolver.skiplist import TxnRequest

    r = Resolver(KNOBS)
    r.resolve([TxnRequest(read_version=10, point_writes=[b"k"])], 20, 0)
    snap = r.profile.snapshot()
    assert snap["dispatches"] == 1
    # one live txn padded to the full batch capacity
    assert snap["txns_live"] == 1
    assert snap["txn_slots"] == r.params.txns
    assert snap["pad_waste_pct"] > 0


def test_host_backend_resolve_records_without_padding():
    from foundationdb_tpu.resolver.skiplist import TxnRequest

    r = Resolver(Knobs(resolver_backend="cpu", **TEST_KNOBS))
    r.resolve([TxnRequest(read_version=10, point_writes=[b"k"])], 20, 0)
    snap = r.profile.snapshot()
    assert snap["dispatches"] == 1
    assert snap["txns_live"] == 1 and snap["txn_slots"] == 1
    assert snap["pad_waste_pct"] == 0.0  # host sets pack nothing


def _flat(reqs):
    return flatpack.build_flat_batch(reqs, L)


def _req(rv, rcr, wcr):
    from foundationdb_tpu.core.commit import CommitRequest

    return CommitRequest(
        rv, [], rcr, wcr,
        flat_conflicts=flatpack.encode_conflicts(rcr, wcr, L),
    )


def test_fallback_cause_too_old_rv():
    r = Resolver(KNOBS, base_version=50)
    flat = _flat([_req(5, [], [(b"k", b"k\x00")])])  # rv 5 < fence 50
    r.resolve(flat, 60, 50)
    assert r.profile.snapshot()["fallback_causes"]["too_old_rv"] == 1


def test_fallback_cause_over_capacity():
    cap = KNOBS.point_writes_per_txn
    over = _flat([_req(5, [], [(b"k%02d" % i, b"k%02d\x00" % i)
                               for i in range(cap + 3)])])
    r = Resolver(KNOBS)
    assert not r.packer.flat_fits(over)
    r.resolve(over, 30, 0)
    assert r.profile.snapshot()["fallback_causes"]["over_capacity"] == 1


def test_fallback_cause_mixed_backlog_decodes_to_legacy():
    from foundationdb_tpu.resolver.skiplist import TxnRequest

    r = Resolver(KNOBS)
    flat = _flat([_req(10, [], [(b"fa", b"fa\x00")])])
    legacy = [TxnRequest(read_version=10, point_writes=[b"fb"])]
    r.resolve_many([(flat, 20, 0), (legacy, 21, 0)])
    snap = r.profile.snapshot()
    assert snap["fallback_causes"]["flat_to_legacy"] == 1


def test_flat_backlog_staging_reuse_hooks_fire():
    r = Resolver(KNOBS)
    # the staging ring keeps STAGING_RING (4) slots per shape alive
    # before reusing one: the first dispatches miss (fresh allocation),
    # later same-shape dispatches hit (a reset of the rows last written)
    for d in range(6):
        batches = [
            (_flat([_req(10 + 10 * d, [],
                         [(b"s%d%02d" % (d, g), b"s%d%02d\x00" % (d, g))])]),
             20 + 10 * d + g, 0)
            for g in range(2)
        ]
        r.resolve_many(batches)
    snap = r.profile.snapshot()
    assert snap["staging_reuse_misses"] >= 1
    assert snap["staging_reuse_hits"] >= 1
    assert 0.0 < snap["staging_reuse_rate"] < 1.0


def test_resolver_respawn_carries_profile_forward():
    r = Resolver(KNOBS)
    r.resolve_many(_legacy_batches(3))
    before = r.profile.snapshot()
    assert before["dispatches"] == 1
    r.kill()
    r2 = r.respawn(base_version=100)
    # the SAME cluster-owned object, not a copy: history never rewinds
    assert r2.profile is r.profile
    after = r2.profile.snapshot()
    assert after["dispatches"] >= before["dispatches"]
    r2.resolve_many(_legacy_batches(3, rv=200, cv0=210))
    assert r2.profile.snapshot()["dispatches"] == after["dispatches"] + 1


# ─────────── satellite 1: decode cost charged to DISPATCH ───────────
def test_flat_decode_cost_lands_in_dispatch_wall(monkeypatch):
    """Regression pin for the stage split: when a mixed/ineligible
    backlog decodes FlatTxnBatches to TxnRequests, that decode is
    charged to ``dispatch_wall_s`` (stage_dispatch_ms) — before the
    fix it silently landed in whichever stage timer was open
    (stage_pack_ms on the batcher thread)."""
    from foundationdb_tpu.core.flatpack import FlatTxnBatch
    from foundationdb_tpu.resolver.skiplist import TxnRequest

    real = FlatTxnBatch.to_txn_requests

    def slow(self):
        time.sleep(0.05)
        return real(self)

    monkeypatch.setattr(FlatTxnBatch, "to_txn_requests", slow)
    r = Resolver(KNOBS)
    flat = _flat([_req(10, [], [(b"da", b"da\x00")])])
    legacy = [TxnRequest(read_version=10, point_writes=[b"db"])]
    d0 = r.dispatch_wall_s
    r.resolve_many([(flat, 20, 0), (legacy, 21, 0)])
    assert r.dispatch_wall_s - d0 >= 0.05


# ─────────────── mesh fleet: per-lane load instruments ───────────────
def test_mesh_resolver_exposes_per_lane_walls():
    # the hash-sharded (replicated-batch) mode keeps the wall-based
    # instrument: each lane's shard blocked in device order
    cluster = Cluster(n_resolvers=4, resolver_backend="tpu",
                      resolver_sharding="hash", **TEST_KNOBS)
    try:
        (r,) = cluster.resolvers
        assert r.n_lanes == 4 and r.sharding == "hash"
        r.resolve_many(_legacy_batches(3))
        snap = r.profile.snapshot()
        assert snap["lanes"] == 4
        assert len(snap["lane_walls_ms"]) == 4
        assert snap["lane_dispatches"] >= 1
        assert all(w >= 0.0 for w in snap["lane_walls_ms"])
        assert 0.0 <= snap["lane_skew_pct"] <= 100.0
        # the cluster doc surfaces the same lanes
        doc = cluster.device_profile_status()
        assert doc["aggregate"]["lanes"] == 4
    finally:
        cluster.close()


def test_mesh_resolver_range_mode_exposes_per_lane_entry_counts():
    # the range-sharded (default) mode knows lane balance at SPLIT
    # time: routed-entry counts per lane, same lane_skew_pct rollup
    cluster = Cluster(n_resolvers=4, resolver_backend="tpu",
                      **TEST_KNOBS)
    try:
        (r,) = cluster.resolvers
        assert r.n_lanes == 4 and r.sharding == "range"
        r.resolve_many(_legacy_batches(3))
        snap = r.profile.snapshot()
        assert snap["lanes"] == 4
        assert snap["lane_walls_ms"] == []  # never mixed units
        assert len(snap["lane_entries"]) == 4
        assert snap["lane_dispatches"] >= 1
        assert sum(snap["lane_entries"]) > 0
        assert 0.0 <= snap["lane_skew_pct"] <= 100.0
        doc = cluster.device_profile_status()
        assert doc["aggregate"]["lanes"] == 4
    finally:
        cluster.close()


# ──────────── cluster lifecycle (never-rewind contract) ────────────
@pytest.fixture
def fleet_db():
    cluster = Cluster(n_commit_proxies=2, n_resolvers=2, n_storage=2,
                      n_tlogs=3, resolver_backend="cpu", **TEST_KNOBS)
    yield cluster.database()
    cluster.close()


def _agg_dispatches(cluster):
    return cluster.device_profile_status()["aggregate"]["dispatches"]


def test_profile_survives_txn_recovery(fleet_db):
    db = fleet_db
    cluster = db._cluster
    db[b"k"] = b"v"
    before = _agg_dispatches(cluster)
    assert before >= 1
    cluster._commit_target().kill()
    assert ("txn-system", 0) in cluster.detect_and_recruit()
    after = _agg_dispatches(cluster)
    assert after >= before  # never rewinds
    db[b"k"] = b"v2"  # the recruited system records into the SAME store
    assert _agg_dispatches(cluster) > after


def test_configure_shrink_folds_orphan_profiles(fleet_db):
    db = fleet_db
    cluster = db._cluster
    for i in range(4):
        db[b"sk%d" % i] = b"v"
    before = _agg_dispatches(cluster)
    assert len(cluster.device_profile_status()["resolvers"]) == 2
    cluster.configure(commit_proxies=1, resolvers=1)
    doc = cluster.device_profile_status()
    # the orphaned member folded into member 0: nothing rewound
    assert doc["aggregate"]["dispatches"] >= before
    db[b"post"] = b"v"
    assert _agg_dispatches(cluster) > doc["aggregate"]["dispatches"]


def test_resolver_kill_recruit_keeps_profile(fleet_db):
    db = fleet_db
    cluster = db._cluster
    db[b"a"] = b"1"
    before = _agg_dispatches(cluster)
    cluster.resolvers[0].kill()
    assert cluster.detect_and_recruit()
    assert _agg_dispatches(cluster) >= before
    db[b"a"] = b"2"
    assert _agg_dispatches(cluster) > before


# ──────────────── surfaces: status / key / RPC / cli ────────────────
def test_status_device_section_and_special_key():
    cluster = Cluster(n_storage=1, resolver_backend="cpu", **TEST_KNOBS)
    try:
        db = cluster.database()
        db[b"x"] = b"1"
        dev = cluster.status()["cluster"]["device"]
        assert dev["enabled"] is True
        assert dev["aggregate"]["dispatches"] >= 1
        assert [p["id"] for p in dev["resolvers"]] == [0]
        # the special key serves the same document, JSON-encoded
        raw = db.run(lambda tr: tr.get(specialkeys.DEVICE))
        doc = json.loads(raw)
        assert doc["aggregate"]["dispatches"] >= 1
        assert set(doc) == {"enabled", "resolvers", "aggregate", "platform",
                            "device_kind", "device_count", "compile"}
        # special reads never add conflict ranges
        tr = db.create_transaction()
        tr.get(specialkeys.DEVICE)
        assert tr._read_conflicts == []
        # and the range read surfaces the row
        rows = db.run(lambda tr: tr.get_range(
            b"\xff\xff/metrics/", b"\xff\xff/metrics0"))
        assert specialkeys.DEVICE in [k for k, _ in rows]
    finally:
        cluster.close()


def test_device_profile_over_rpc():
    cluster = Cluster(n_storage=1, resolver_backend="cpu", **TEST_KNOBS)
    server = serve_cluster(cluster)
    rc = RemoteCluster([server.address])
    try:
        rdb = rc.database()
        rdb[b"rk"] = b"v"
        doc = rc.device_profile_status()
        assert doc["aggregate"]["dispatches"] >= 1
        # the special key round-trips the wire too
        remote = json.loads(rdb.run(
            lambda tr: tr.get(specialkeys.DEVICE)))
        assert remote["aggregate"]["dispatches"] >= 1
    finally:
        rc.close()
        server.close()
        cluster.close()


def test_fdbcli_profile_renders():
    import io

    from foundationdb_tpu.tools.cli import Cli

    cluster = Cluster(n_storage=1, resolver_backend="cpu", **TEST_KNOBS)
    try:
        db = cluster.database()
        db[b"pk"] = b"v"
        out = io.StringIO()
        cli = Cli(db, out=out)
        assert cli.run_command("profile")
        text = out.getvalue()
        assert "Device profile" in text
        assert "pad_waste_pct" in text
        assert "fallback_causes" in text
        assert "resolver 0" in text
        # json form dumps the raw document
        out2 = io.StringIO()
        Cli(db, out=out2).run_command("profile json")
        assert json.loads(out2.getvalue())["aggregate"]["dispatches"] >= 1
        # help advertises it
        out3 = io.StringIO()
        Cli(db, out=out3).run_command("help")
        assert "profile" in out3.getvalue()
    finally:
        cluster.close()


# ───────────────── same-seed determinism (satellite) ─────────────────
def _sim_device_doc(seed, datadir):
    from foundationdb_tpu.sim.simulation import Simulation
    from foundationdb_tpu.sim.workloads import cycle_setup, cycle_workload

    sim = Simulation(seed=seed, buggify=True, crash_p=0.0, datadir=datadir)
    try:
        cycle_setup(sim.db, 8)
        for a in range(3):
            sim.add_workload(
                f"c{a}",
                cycle_workload(sim.db, 8, 10, random.Random(seed * 7 + a)),
            )
        sim.run()
        return json.dumps(sim.cluster.status()["cluster"]["device"],
                          sort_keys=True)
    finally:
        sim.close()
        deterministic.unseed()
        deterministic.registry().reset_clock()


def test_same_seed_sims_produce_identical_device_docs(tmp_path):
    """Two same-seed simulations emit byte-identical device-profile
    docs: every duration rides the sim step clock (0.0 within a step)
    and everything else is integer counters."""
    s1 = _sim_device_doc(4096, str(tmp_path / "d1"))
    s2 = _sim_device_doc(4096, str(tmp_path / "d2"))
    assert s1 == s2
    doc = json.loads(s1)
    # not trivially empty: the workload's commits were dispatched
    assert doc["aggregate"]["dispatches"] > 0
