"""The mesh router under YCSB's keys, and what it counts
(resolver/meshresolver.py ``_split_counted``: stage ``resolver.route``
and utils/deviceprofile.py ``PLAIN_COUNTERS``), on the 8 host devices
conftest forces.

``ShardRouter`` starts from the first limb's uniform split, and a
sample under 4,096 rows cuts nothing (tests/test_lane_bounds.py), so
every ``user%08d`` key has the second of four lanes: the counters say so (the fullest lane
holds every entry), a batch beyond that lane's capacity is cut into
slices, and whatever the four lanes commit the plain reference
(resolver/skiplist.py's exact interval list) commits.
"""

import random

import numpy as np
import pytest

from foundationdb_tpu.core import flatpack
from foundationdb_tpu.core.commit import CommitRequest
from foundationdb_tpu.core.options import Knobs
from foundationdb_tpu.core.status import COMMITTED, CONFLICT, TOO_OLD
from foundationdb_tpu.resolver.meshresolver import MeshResolver
from foundationdb_tpu.resolver.resolver import Resolver
from foundationdb_tpu.resolver.skiplist import CpuConflictSet, TxnRequest
from foundationdb_tpu.utils import deviceprofile

from conftest import TEST_KNOBS

# no ring eviction and a roomy hash table: one lane and four hold the
# same history, so their verdicts can be held equal
KNOBS = Knobs(**{**TEST_KNOBS, "range_reads_per_txn": 2,
                 "range_writes_per_txn": 2, "hash_table_bits": 20,
                 "range_ring_capacity": 512, "resolver_backend": "tpu"})
T = KNOBS.batch_txn_capacity
ROWS = 100_000


def key(i):
    return b"user%08d" % i


def zipfian_ids(seed, n, theta=0.99):
    """A seeded scrambled Zipfian over ROWS record ids (YCSB's shape:
    rank r drawn ∝ 1/r^theta, ranks scattered over the ids)."""
    rng = np.random.default_rng(seed)
    p = 1.0 / np.arange(1, ROWS + 1) ** theta
    return rng.permutation(ROWS)[rng.choice(ROWS, size=n, p=p / p.sum())]


def updates(ids, rv):
    """YCSB-A's update: get + set of one key."""
    return [TxnRequest(read_version=rv, point_reads=[key(i)],
                       point_writes=[key(i)]) for i in ids]


def flat_of(txns):
    reqs = []
    for t in txns:
        rcr = [(k, k + b"\x00") for k in t.point_reads] + t.range_reads
        wcr = [(k, k + b"\x00") for k in t.point_writes] + t.range_writes
        reqs.append(CommitRequest(
            t.read_version, [], rcr, wcr,
            flat_conflicts=flatpack.encode_conflicts(
                rcr, wcr, KNOBS.key_limbs)))
    return flatpack.build_flat_batch(reqs, KNOBS.key_limbs)


def scenario(seed, batches, most, pool=200):
    """Seeded batches of at most ``most`` point and range transactions
    over a pool of ``user`` keys, some with aged reads, some blind →
    [(txns, cv)]."""
    rng = random.Random(seed)
    ids = sorted(rng.sample(range(ROWS - 8), pool))
    out, cv = [], 1000
    for _ in range(batches):
        cv += 10
        txns = []
        for _ in range(rng.randrange(2, most + 1)):
            rv = cv - 10 - rng.choice((0, 0, 0, 0, 10, 30, 80))
            a, b = rng.choice(ids), rng.choice(ids)
            kind = rng.random()
            if kind < 0.5:  # read-modify-write of one key
                t = TxnRequest(rv, point_reads=[key(a)],
                               point_writes=[key(a)])
            elif kind < 0.7:  # read one, write another
                t = TxnRequest(rv, point_reads=[key(a)],
                               point_writes=[key(b)])
            elif kind < 0.8:  # blind write: nothing to check
                t = TxnRequest(rv, point_writes=[key(a), key(b)])
            elif kind < 0.9:  # a scan that updates its first row
                t = TxnRequest(rv, range_reads=[(key(a), key(a + 4000))],
                               point_writes=[key(a)])
            else:  # a clear of a span
                t = TxnRequest(rv, point_reads=[key(b)],
                               range_writes=[(key(a), key(a + 9000))])
            txns.append(t)
        out.append((txns, cv))
    return out


# ── every user key has one lane, and the counters say so ────────────
def test_zipfian_user_keys_all_land_in_one_lane():
    mesh = MeshResolver(KNOBS, n_lanes=4)
    ids = zipfian_ids(3, 8 * 4)
    for d in range(8):  # four updates a dispatch: far below a lane's room
        mesh.resolve(updates(ids[4 * d:4 * d + 4], 1000 + 10 * d),
                     1010 + 10 * d, 0)
    snap = mesh.profile.snapshot()
    # "user" = 0x75736572 lies in the second quarter of the first limb
    assert snap["lane_entries"] == [0, 64, 0, 0]
    assert snap["lane_entries_fullest"] == snap["lane_entries_routed"] == 64
    assert snap["route_dispatches"] == snap["route_slices"] == 8
    assert snap["route_wall_ms"] > 0
    assert snap["lane_skew_pct"] == 100.0


def test_keys_spread_over_the_first_limb_share_the_lanes():
    mesh = MeshResolver(KNOBS, n_lanes=4)
    rng = np.random.default_rng(5)
    for d in range(40):
        keys = [bytes(rng.integers(256, size=12, dtype=np.uint8))
                for _ in range(T)]
        mesh.resolve([TxnRequest(1000 + 10 * d, point_reads=[k],
                                 point_writes=[k]) for k in keys],
                     1010 + 10 * d, 0)
    snap = mesh.profile.snapshot()
    routed = snap["lane_entries_routed"]
    assert routed == sum(snap["lane_entries"]) == 40 * 2 * T
    assert max(snap["lane_entries"]) < 0.32 * routed
    # summed per dispatch the fullest lane reads above its share of the
    # whole: 32 entries over four lanes have a fullest lane by chance
    assert max(snap["lane_entries"]) < snap["lane_entries_fullest"] \
        < 0.5 * routed
    assert snap["route_slices"] == snap["route_dispatches"] == 40


# ── a lane that overflows: slices, and never a missed conflict ──────
def has_reads(t):
    return bool(t.point_reads or t.range_reads)


@pytest.mark.parametrize("seed", [7, 8])
def test_whatever_four_lanes_commit_the_reference_commits(seed):
    """Full batches on one lane's keys overflow it (a lane takes
    1.75·T·K/n entries a side) and ride the scan as slices, which may
    add conflicts (test_shard_split.py) and never loses one."""
    mesh = MeshResolver(KNOBS, n_lanes=4)
    ref = CpuConflictSet()
    conflicts = committed = 0
    for txns, cv in scenario(seed, batches=120, most=T):
        for t, s in zip(txns, mesh.resolve(txns, cv, 0)):
            assert s != TOO_OLD
            # the reference's verdict on the reads alone, over the
            # history of what the mesh committed; then that history
            # gets this transaction's writes, if the mesh committed it
            (want,) = ref.resolve([TxnRequest(
                t.read_version, point_reads=t.point_reads,
                range_reads=t.range_reads)], cv)
            assert not (s == COMMITTED and want == CONFLICT), (t, cv)
            conflicts += want == CONFLICT
            if s == COMMITTED:
                committed += 1
                ref.resolve([TxnRequest(
                    cv, point_writes=t.point_writes,
                    range_writes=t.range_writes)], cv)
    assert conflicts > 100 and committed > 100, (conflicts, committed)
    snap = mesh.profile.snapshot()
    assert snap["route_dispatches"] == 120
    assert snap["route_slices"] > snap["route_dispatches"]  # it overflowed


@pytest.mark.parametrize("route", ["legacy", "flat", "backlog"])
def test_one_lane_and_four_give_the_same_verdicts(route):
    """Batches that fit the fullest lane (k = 1) answer as one lane
    does, on every route into the resolver: every entry went to a lane,
    point entries to exactly one."""
    mesh, one = MeshResolver(KNOBS, n_lanes=4), Resolver(KNOBS)
    batches = scenario(11, batches=60, most=5)
    seen = set()
    for i in range(0, len(batches), 2):
        (a, cva), (b, cvb) = batches[i], batches[i + 1]
        if route == "legacy":
            got = [r.resolve(a, cva, 0) + r.resolve(b, cvb, 0)
                   for r in (mesh, one)]
        elif route == "flat":
            got = [r.resolve(flat_of(a), cva, 0)
                   + r.resolve(flat_of(b), cvb, 0) for r in (mesh, one)]
        else:
            got = [sum(r.resolve_many([(flat_of(a), cva, 0),
                                       (flat_of(b), cvb, 0)]), [])
                   for r in (mesh, one)]
        assert got[0] == got[1], cva
        seen.update(got[0])
    assert seen == {COMMITTED, CONFLICT}
    snap = mesh.profile.snapshot()
    points = sum(len(t.point_reads) + len(t.point_writes)
                 for txns, _ in batches for t in txns)
    ranges = sum(len(t.range_reads) + len(t.range_writes)
                 for txns, _ in batches for t in txns)
    assert points + ranges <= snap["lane_entries_routed"] \
        <= points + 4 * ranges
    assert snap["route_slices"] == snap["route_dispatches"] > 0
    assert snap["route_wall_ms"] > 0


# ── the counters' life: status, respawn, the kill switch ────────────
def test_route_counters_reach_the_aggregate_and_outlive_a_respawn():
    mesh = MeshResolver(KNOBS, n_lanes=4)
    mesh.resolve(updates([1, 2, 3], 1000), 1010, 0)
    new = mesh.respawn(1010)
    assert isinstance(new, MeshResolver) and new.profile is mesh.profile
    new.resolve(updates([4], 1010), 1020, 0)
    other = MeshResolver(KNOBS, n_lanes=2)
    other.resolve(updates([5, 6], 1000), 1010, 0)
    agg = deviceprofile.merged_snapshot([new.profile, other.profile])
    assert agg["route_dispatches"] == agg["route_slices"] == 3
    assert agg["lane_entries_routed"] == agg["lane_entries_fullest"] == 12
    walls = [p.snapshot()["route_wall_ms"]
             for p in (new.profile, other.profile)]
    assert min(walls) > 0
    assert agg["route_wall_ms"] == pytest.approx(sum(walls), abs=0.002)
    # one lane routes nothing: its counters stay at rest
    one = Resolver(KNOBS)
    one.resolve(updates([7], 1000), 1010, 0)
    snap = one.profile.snapshot()
    assert snap["route_dispatches"] == 0 and snap["route_wall_ms"] == 0


def test_the_kill_switch_stops_the_route_counters():
    mesh = MeshResolver(KNOBS, n_lanes=4)
    deviceprofile.set_enabled(False)
    try:
        assert mesh.resolve(updates([1], 1000), 1010, 0) == [COMMITTED]
    finally:
        deviceprofile.set_enabled(True)
    snap = mesh.profile.snapshot()
    assert snap["route_dispatches"] == 0 and snap["route_wall_ms"] == 0
    assert mesh.resolve(updates([1], 1000), 1020, 0) == [CONFLICT]
    assert mesh.profile.snapshot()["route_dispatches"] == 1


# ── the lane bounds' counters (PR 37) ───────────────────────────────
def rebound_at(mesh, ids):
    """Make the next dispatch re-bound at ``ids``' keys: the rule's
    answer stubbed (tests/test_lane_bounds.py holds the rule), the
    fence, the stage and the counters the program's."""
    from foundationdb_tpu.resolver.packing import ShardRouter

    rows = mesh.packer.codec.encode_lower_batch([key(i) for i in ids])
    router = ShardRouter(mesh.params, mesh.n_lanes, bounds=rows)
    share = np.full(mesh.n_lanes, 1.0 / mesh.n_lanes)
    mesh._lanes.due = lambda buckets: True

    def look(buckets, old):
        del mesh._lanes.due, mesh._lanes.look
        return router, share, share

    mesh._lanes.look = look


def scan(a, n, rv):
    """A scan of ``n`` rows that clears its first."""
    return TxnRequest(rv, range_reads=[(key(a), key(a + n))],
                      range_writes=[(key(a), key(a + 1))])


def test_rebound_counters_reach_the_aggregate_and_outlive_a_respawn():
    mesh = MeshResolver(KNOBS, n_lanes=4)
    assert mesh.resolve(updates([10, 60_000], 1000), 1010, 0) == [
        COMMITTED, COMMITTED]
    snap = mesh.profile.snapshot()
    assert snap["rebounds"] == snap["rebound_fenced_txns"] == 0
    assert snap["range_entries_routed"] == snap["range_lane_dups"] == 0
    assert snap["rebound_wall_ms"] == 0
    assert mesh.status()["lane_bounds"] == ["@", "\\x80", "\\xc0"]
    # the bounds move: the batch in hand and an older read are refused,
    # each counted once; a read from after the fence is not
    rebound_at(mesh, [25_000, 50_000, 75_000])
    assert mesh.resolve(updates([1, 2, 3], 1010), 1020, 0) == [TOO_OLD] * 3
    assert mesh.base_version == 1020
    assert mesh.resolve(updates([4], 1015) + updates([5], 1020),
                        1030, 0) == [TOO_OLD, COMMITTED]
    assert mesh.status()["lane_bounds"] == [
        "user00025000", "user00050000", "user00075000"]
    # a scan across a bound has a slot in both lanes; its clear and a
    # scan inside a lane have one: four range entries, one slot more
    got = mesh.resolve([scan(24_990, 20, 1030), scan(60_000, 20, 1030)],
                       1040, 0)
    assert got == [COMMITTED, COMMITTED]
    snap = mesh.profile.snapshot()
    assert snap["rebounds"] == 1 and snap["rebound_fenced_txns"] == 4
    assert snap["range_entries_routed"] == 4
    assert snap["range_lane_dups"] == 1  # the read crosses, the clear not
    assert snap["rebound_wall_ms"] > 0
    assert snap["lane_entries"][0] > 0 and snap["lane_entries"][2] > 0
    # a replacement keeps counting where this one stopped, with its
    # bounds; its own fence is no re-bound's
    new = mesh.respawn(1040)
    assert new.status()["lane_bounds"] == mesh.status()["lane_bounds"]
    assert new.resolve(updates([6], 1030), 1050, 0) == [TOO_OLD]
    other = MeshResolver(KNOBS, n_lanes=2)
    other.resolve([scan(100, 5, 1000)], 1010, 0)
    agg = deviceprofile.merged_snapshot([new.profile, other.profile])
    assert agg["rebounds"] == 1 and agg["rebound_fenced_txns"] == 4
    assert agg["range_entries_routed"] == 6 and agg["range_lane_dups"] == 1
    assert agg["rebound_wall_ms"] == pytest.approx(
        snap["rebound_wall_ms"], abs=0.002)
    # one lane has no bounds: its counters stay at rest
    one = Resolver(KNOBS)
    one.resolve([scan(100, 5, 1000)], 1010, 0)
    snap = one.profile.snapshot()
    assert snap["range_entries_routed"] == snap["rebounds"] == 0
    assert "lane_bounds" not in one.status()
    import dataclasses

    by_hash = dataclasses.replace(KNOBS, resolver_sharding="hash")
    assert MeshResolver(by_hash, n_lanes=2).status()["lane_bounds"] == []


def test_the_kill_switch_stops_the_rebound_counters_not_the_fence():
    mesh = MeshResolver(KNOBS, n_lanes=4)
    rebound_at(mesh, [25_000, 50_000, 75_000])
    deviceprofile.set_enabled(False)
    try:
        assert mesh.resolve(updates([1], 1000), 1010, 0) == [TOO_OLD]
        assert mesh.resolve([scan(24_990, 20, 1010)], 1020, 0) == [COMMITTED]
    finally:
        deviceprofile.set_enabled(True)
    assert mesh.base_version == 1010
    snap = mesh.profile.snapshot()
    assert snap["rebounds"] == snap["rebound_fenced_txns"] == 0
    assert snap["range_entries_routed"] == snap["rebound_wall_ms"] == 0
    assert mesh.resolve(updates([2], 1000), 1030, 0) == [TOO_OLD]
    assert mesh.profile.snapshot()["rebound_fenced_txns"] == 1
