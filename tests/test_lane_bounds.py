"""Lane bounds cut from the keys the resolvers see, behind a fence
(resolver/packing.py ``LaneBounds``, resolver/meshresolver.py
``_maybe_rebound``), on a 4-lane mesh of the host devices conftest
forces.

The plain reference imports nothing of the device path: the exact
interval list of resolver/skiplist.py (tests/test_resolver.py
``oracle_batches`` is the same class, fed whole batches). A table of
300 keys is small enough that a range crosses a lane bound in one
transaction out of three, which four lanes over a real table see once
in two thousand.

What makes a device verdict equal the reference's here, and not only
never laxer: a hash table roomy enough for 300 keys, a ring no stream
fills, and coarse buckets cut from the loaded table before the first
verdict is compared, so that every key has a bucket of its own (a range
ends at ``key + b"\\x00"``, as a ``get_range``'s or a ``clear_range``'s
does, which falls in its last key's bucket).
"""

import random

import numpy as np
import pytest

from foundationdb_tpu.core import flatpack
from foundationdb_tpu.core.commit import CommitRequest
from foundationdb_tpu.core.options import Knobs
from foundationdb_tpu.core.status import COMMITTED, CONFLICT, TOO_OLD
from foundationdb_tpu.ops.conflict import ResolverParams
from foundationdb_tpu.resolver.meshresolver import MeshResolver
from foundationdb_tpu.resolver.packing import (
    CoarseBuckets,
    LaneBounds,
    ShardRouter,
)
from foundationdb_tpu.resolver.resolver import Resolver
from foundationdb_tpu.resolver.skiplist import CpuConflictSet, TxnRequest
from foundationdb_tpu.utils import trace as trace_mod

from conftest import TEST_KNOBS

KNOBS = Knobs(**{**TEST_KNOBS, "range_reads_per_txn": 2,
                 "range_writes_per_txn": 2, "hash_table_bits": 20,
                 "range_ring_capacity": 1024, "coarse_buckets_bits": 10,
                 "resolver_backend": "tpu"})
T = KNOBS.batch_txn_capacity
ROWS = 300
SPAN = 90  # keys a range covers at most: three bounds, 300 keys


def key(i):
    return b"k%08d" % i


def span(a, n):
    """[key(a), just past key(a + n − 1))."""
    return key(a), key(min(a + n, ROWS) - 1) + b"\x00"


def small_rule(mesh, first=128, check=32):
    """The rule's constants at the size of a 300-key table (they are
    class constants; an instance's own shadow them), and a sample that
    holds the whole load and is full with it: the coarse buckets' first
    cut, at the first range, is then their last while the traffic stays
    what it is, and no later fold blurs a verdict."""
    mesh.buckets.capacity = 256
    mesh._lanes.FIRST, mesh._lanes.CHECK, mesh._lanes.FIT = (
        first, check, 4 * first)
    return mesh


def load(resolver, cv):
    """The table in key order, blind sets, as benchmark/run.py loads it
    → the last commit version."""
    for at in range(0, ROWS, T):
        cv += 10
        got = resolver.resolve(
            [TxnRequest(cv - 10, point_writes=[key(i)])
             for i in range(at, min(at + T, ROWS))], cv, 0)
        assert set(got) == {COMMITTED}
    return cv


def traffic(rng, cv, n, lag=(0, 0, 0, 10, 30)):
    """``n`` transactions of mako's range shape and YCSB's point shape
    over the table, committed at ``cv + 10``, some with aged reads."""
    txns = []
    for _ in range(n):
        rv = cv - rng.choice(lag)
        a, b = rng.randrange(ROWS), rng.randrange(ROWS)
        kind = rng.random()
        if kind < 0.35:  # gr + u + cr: mako_range's transaction
            t = TxnRequest(rv, range_reads=[span(a, rng.randrange(2, SPAN))],
                           point_reads=[key(b)], point_writes=[key(b)],
                           range_writes=[span(a, 2)])
        elif kind < 0.6:  # read-modify-write of one key
            t = TxnRequest(rv, point_reads=[key(a)], point_writes=[key(a)])
        elif kind < 0.75:  # a scan that updates a row elsewhere
            t = TxnRequest(rv, range_reads=[span(a, rng.randrange(2, SPAN))],
                           point_writes=[key(b)])
        elif kind < 0.9:  # a clear of a span behind a point read
            t = TxnRequest(rv, point_reads=[key(b)],
                           range_writes=[span(a, rng.randrange(2, SPAN))])
        else:  # blind
            t = TxnRequest(rv, point_writes=[key(a), key(b)])
        txns.append(t)
    return txns, cv + 10


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


def settled_mesh(seed):
    """A 4-lane mesh that loaded the table in key order and then met
    traffic until the rule cut its bounds → (mesh, rng, cv): the fence
    is ``mesh.base_version``, and the coarse buckets were cut (by the
    first range it met) from a sample that held every key."""
    mesh = small_rule(MeshResolver(KNOBS, n_lanes=4))
    cv = load(mesh, 1000)
    assert mesh.profile.snapshot()["rebounds"] == 0  # a load in key order
    rng = random.Random(seed)
    for _ in range(40):
        txns, cv = traffic(rng, cv, 8)
        mesh.resolve(txns, cv, 0)
        if mesh.profile.snapshot()["rebounds"]:
            break
    snap = mesh.profile.snapshot()
    assert snap["rebounds"] == 1 and mesh.base_version == cv
    assert mesh.buckets.cut and snap["rebuckets"] >= 1
    return mesh, rng, cv


def restarted(fence):
    ref = CpuConflictSet()
    ref.window_start = fence
    return ref


# ── (a) ranges across bounds: every verdict is the reference's ──────
@pytest.mark.parametrize("route", ["legacy", "flat", "backlog"])
def test_verdicts_equal_the_reference_with_ranges_across_bounds(route):
    mesh, rng, cv = settled_mesh(21)
    ref = restarted(mesh.base_version)
    before = mesh.profile.snapshot()
    seen = set()
    for _ in range(30):
        a, cva = traffic(rng, cv, rng.randrange(2, T + 1))
        b, cv = traffic(rng, cva, rng.randrange(2, T + 1))
        want = ref.resolve(a, cva, 0) + ref.resolve(b, cv, 0)
        if route == "legacy":
            got = mesh.resolve(a, cva, 0) + mesh.resolve(b, cv, 0)
        elif route == "flat":
            got = (mesh.resolve(flat_of(a), cva, 0)
                   + mesh.resolve(flat_of(b), cv, 0))
        else:
            got = sum(mesh.resolve_many([(flat_of(a), cva, 0),
                                         (flat_of(b), cv, 0)]), [])
        assert got == want, cv
        seen.update(got)
    # (TOO_OLD too: the first aged reads fall below the fence)
    assert seen >= {COMMITTED, CONFLICT}
    snap = mesh.profile.snapshot()
    assert snap["rebounds"] == 1  # the traffic stayed what it was
    ranges = snap["range_entries_routed"] - before["range_entries_routed"]
    dups = snap["range_lane_dups"] - before["range_lane_dups"]
    assert ranges > 200 and dups >= ranges / 5, (ranges, dups)
    # four lanes carry the table now, none more than twice its share
    lanes = np.array(snap["lane_entries"]) - np.array(before["lane_entries"])
    assert lanes.min() > 0 and lanes.max() < 0.5 * lanes.sum(), lanes


# ── (b) re-bounds forced at random steps ────────────────────────────
def force_rebound(mesh, rng):
    """Make the next dispatch re-bound at three keys drawn at random:
    the rule's answer stubbed, the fence and the swap the program's."""
    cuts = sorted(rng.sample(range(10, ROWS - 10), 3))
    rows = mesh.packer.codec.encode_lower_batch([key(i) for i in cuts])
    router = ShardRouter(mesh.params, 4, bounds=rows)
    shares = np.full(4, 0.25)
    mesh._lanes.due = lambda buckets: True

    def look(buckets, old):
        del mesh._lanes.due, mesh._lanes.look  # once
        return router, shares, shares

    mesh._lanes.look = look
    return cuts


@pytest.mark.parametrize("seed,route", [(31, "legacy"), (32, "flat"),
                                        (33, "backlog"), (34, "legacy")])
def test_across_forced_rebounds_no_conflict_is_missed(seed, route):
    mesh, rng, cv = settled_mesh(seed)
    full = CpuConflictSet()  # never restarted: what was really written
    since = restarted(mesh.base_version)  # restarted at every fence
    fences, exact = 0, 0
    for step in range(60):
        if rng.random() < 0.12:
            force_rebound(mesh, rng)
        a, cva = traffic(rng, cv, rng.randrange(2, T + 1))
        b, cv = traffic(rng, cva, rng.randrange(2, T + 1))
        # (statuses, the fence in force once they were given)
        if route == "backlog":
            got = mesh.resolve_many([(flat_of(a), cva, 0),
                                     (flat_of(b), cv, 0)])
            got = [(g, mesh.base_version) for g in got]
        else:
            wrap = flat_of if route == "flat" else list
            got = [(mesh.resolve(wrap(txns), at, 0), mesh.base_version)
                   for txns, at in ((a, cva), (b, cv))]
        for txns, at, (statuses, fence) in ((a, cva, got[0]),
                                            (b, cv, got[1])):
            if fence != since.window_start:  # it fenced
                fences += 1
                since = restarted(fence)
            # TOO_OLD exactly below the last fence (one that fell in
            # this very dispatch refused the batch in hand too)
            for t, s in zip(txns, statuses):
                assert (s == TOO_OLD) == (t.read_version < fence), (t, fence)
                # the reference's verdict on the reads alone, over what
                # the mesh really committed, before and after any fence
                (want,) = full.resolve([TxnRequest(
                    t.read_version, point_reads=t.point_reads,
                    range_reads=t.range_reads)], at)
                assert not (s == COMMITTED and want == CONFLICT), (t, at)
                if s == COMMITTED:
                    full.resolve([TxnRequest(
                        at, point_writes=t.point_writes,
                        range_writes=t.range_writes)], at)
            # and from the fence on, a reference restarted at it
            assert statuses == since.resolve(txns, at, 0), at
            exact += len(txns)
    snap = mesh.profile.snapshot()
    assert fences >= 3 and snap["rebounds"] == 1 + fences
    assert snap["rebound_fenced_txns"] > 0 and exact > 300


# ── (c) written in one lane, read in another ────────────────────────
def test_a_range_written_in_one_lane_is_met_by_a_read_in_another():
    mesh, rng, cv = settled_mesh(41)
    router = mesh._router
    bound = int(mesh.status()["lane_bounds"][0][1:])  # lanes 0 | 1
    assert 40 < bound < 120
    lo = bound - 30  # keys [lo, lo + 10) lie in lane 0 …
    rows = mesh.packer.codec.encode_lower_batch([key(lo), key(lo + 9)])
    assert router.lane_of_points(rows).tolist() == [0, 0]
    w1 = TxnRequest(cv, range_writes=[span(lo, 10)])
    assert mesh.resolve([w1], cv + 10, 0) == [COMMITTED]
    # … and in lane 1 once the bound has moved across them
    cuts = [lo - 20, bound + 60, bound + 120]
    moved = mesh.packer.codec.encode_lower_batch([key(i) for i in cuts])
    mesh._lanes.due = lambda buckets: True
    mesh._lanes.look = lambda buckets, old: (
        ShardRouter(mesh.params, 4, bounds=moved), np.ones(4), np.ones(4))
    old_reader = TxnRequest(cv, range_reads=[span(lo + 5, 3)],
                            point_writes=[key(0)])
    # the re-bound refuses who read before it: the write it can no
    # longer see is never answered COMMITTED
    assert mesh.resolve([old_reader], cv + 20, 0) == [TOO_OLD]
    del mesh._lanes.due, mesh._lanes.look
    assert mesh.base_version == cv + 20
    assert mesh._router.lane_of_points(rows).tolist() == [1, 1]
    assert mesh.status()["lane_bounds"] == [
        key(i).decode() for i in cuts]
    # a reader from after the fence has nothing to fear from w1 …
    fresh = TxnRequest(cv + 20, range_reads=[span(lo + 5, 3)],
                       point_writes=[key(1)])
    assert mesh.resolve([fresh], cv + 30, 0) == [COMMITTED]
    # … and a range written under the new bounds, across the new bound
    # 0 | 1, is met from either side of it
    w2 = TxnRequest(cv + 30, range_writes=[span(lo - 25, 10)])
    assert mesh.resolve([w2], cv + 40, 0) == [COMMITTED]
    left = TxnRequest(cv + 30, range_reads=[span(lo - 30, 8)],
                      point_writes=[key(2)])
    right = TxnRequest(cv + 30, point_reads=[key(lo - 17)],
                       point_writes=[key(3)])
    clear = TxnRequest(cv + 30, range_reads=[span(lo - 14, 8)],
                       point_writes=[key(4)])
    assert mesh.resolve([left, right, clear], cv + 50, 0) == [
        CONFLICT, CONFLICT, COMMITTED]
    snap = mesh.profile.snapshot()
    assert snap["rebounds"] == 2 and snap["rebound_fenced_txns"] >= 1
    events = trace_mod.global_trace_log().events("ResolverLanesRebound")
    assert events and events[-1]["fenced_at"] == cv + 20
    assert events[-1]["lane_bounds"] == mesh.status()["lane_bounds"]


# ── (d) the fold over four lanes' state ─────────────────────────────
def test_a_rebucket_folds_four_lanes_state_as_it_folds_one():
    """The sample is too small for the lane rule (no fence): the coarse
    buckets are cut and cut again, and each cut folds the summaries.
    Replicated summaries on four lanes read what one lane's read, every
    lane's ring entries take the widest buckets, and the verdicts on
    both sides of every fold are the same."""
    mesh, one = MeshResolver(KNOBS, n_lanes=4), Resolver(KNOBS)
    for r in (mesh, one):
        r.buckets.capacity = 128
    rng = random.Random(51)
    cv, verdicts = 1000, set()
    for step in range(60):
        # (eight transactions fit the one lane that has every key under
        # the first limb's bounds: more would ride as slices, which may
        # add conflicts: tests/test_shard_split.py)
        txns, cv = traffic(rng, cv, rng.randrange(2, T // 2 + 1))
        got, want = mesh.resolve(txns, cv, 0), one.resolve(txns, cv, 0)
        assert got == want, cv
        verdicts.update(got)
        assert np.array_equal(np.asarray(mesh.state.point_coarse),
                              np.asarray(one.state.point_coarse))
    assert verdicts == {COMMITTED, CONFLICT}
    snaps = [r.profile.snapshot() for r in (mesh, one)]
    assert snaps[0]["rebuckets"] == snaps[1]["rebuckets"] >= 3
    assert snaps[0]["rebounds"] == 0
    # the mesh's fold, by hand, on its sharded state: every lane's ring
    C = 1 << KNOBS.coarse_buckets_bits
    from foundationdb_tpu.ops import conflict as ck

    folded = ck.make_fold_fn(mesh.params, mesh.state)(mesh.state)
    mesh.state = folded  # (the fold donates what it was given)
    assert folded.ring_lo.shape == (4 * KNOBS.range_ring_capacity,)
    assert not np.asarray(folded.ring_lo).any()
    assert (np.asarray(folded.ring_hi) == C - 1).all()
    assert folded.ht.sharding == mesh._kernel.init_state().ht.sharding
    pc = np.asarray(folded.point_coarse)
    assert (pc == pc.max()).all() and pc.max() > 0
    txns, cv = traffic(rng, cv, T // 2)
    one.state = ck.make_fold_fn(one.params, one.state)(one.state)
    assert mesh.resolve(txns, cv, 0) == one.resolve(txns, cv, 0)


# ── (e) a replacement keeps bounds and sample ───────────────────────
def test_respawn_keeps_the_bounds_and_the_sample(monkeypatch):
    mesh, rng, cv = settled_mesh(61)
    bounds = mesh.status()["lane_bounds"]
    seen = mesh.buckets.seen
    new = mesh.respawn(cv + 5)
    assert new.status()["lane_bounds"] == bounds and len(bounds) == 3
    assert new.buckets is mesh.buckets and new.packer.buckets is new.buckets
    assert new.profile is mesh.profile and new.base_version == cv + 5
    # fenced once, by the respawn: its traffic costs no second fence
    for _ in range(20):
        txns, cv = traffic(rng, cv + 10, 8, lag=(0,))
        assert TOO_OLD not in new.resolve(txns, cv, 0)
    snap = new.profile.snapshot()
    assert snap["rebounds"] == 1 and new.buckets.seen > seen
    # the fence of a respawn is not a re-bound's: nothing counted
    fenced = snap["rebound_fenced_txns"]
    assert new.resolve([TxnRequest(1000, point_reads=[key(1)])],
                       cv + 10, 0) == [TOO_OLD]
    assert new.profile.snapshot()["rebound_fenced_txns"] == fenced
    # a recovery that changes the lanes' number (server/cluster.py) cuts
    # the new fleet's bounds from the old one's sample at once
    monkeypatch.setattr(LaneBounds, "FIRST", 128)
    two = MeshResolver(KNOBS, base_version=cv, n_lanes=2, heir_of=new)
    (mid,) = two.status()["lane_bounds"]
    assert 100 < int(mid[1:]) < 200 and two.buckets is mesh.buckets
    for _ in range(20):
        txns, cv = traffic(rng, cv + 10, 8, lag=(0,))
        assert TOO_OLD not in two.resolve(txns, cv, 0)
    assert min(two.profile.snapshot()["lane_entries"]) > 0


def test_a_cluster_that_changes_its_lanes_hands_the_sample_on():
    from foundationdb_tpu.server.cluster import Cluster

    cluster = Cluster(**{**TEST_KNOBS, "resolver_backend": "tpu",
                         "n_resolvers": 4})
    db = cluster.database()
    (old,) = cluster.resolvers
    assert isinstance(old, MeshResolver) and old.n_lanes == 4
    for i in range(20):
        db[key(i)] = b"v"
    assert old.buckets.seen >= 20
    cluster.configure(resolvers=2)
    (new,) = cluster.resolvers
    assert new is not old and new.n_lanes == 2
    assert new.buckets is old.buckets and new.profile is old.profile
    db[key(1)] = b"w"
    assert db[key(1)] == b"w"


# ── (f) the rule, on synthetic samples ──────────────────────────────
PARAMS = ResolverParams(key_width=5, bucket_bits=8)


def rows_of(ids):
    out = np.zeros((len(ids), 5), np.uint32)
    out[:, 0] = 0x6B000000  # one first limb: the uniform split is blind
    out[:, 1] = np.asarray(ids, np.uint32)
    out[:, 4] = 8
    return out


class Rule:
    """``LaneBounds`` driven as ``MeshResolver._maybe_rebound`` drives
    it, no device: rows noted a dispatch at a time, a look where due."""

    def __init__(self, n=4, capacity=65536):
        self.buckets = CoarseBuckets(PARAMS, capacity=capacity)
        self.lanes = LaneBounds(n)
        self.router = ShardRouter(PARAMS, n)
        self.cuts = []  # rows seen at each cut

    def note(self, ids, a_dispatch=64):
        for at in range(0, len(ids), a_dispatch):
            if self.lanes.due(self.buckets):
                found = self.lanes.look(self.buckets, self.router)
                if found:
                    self.router = found[0]
                    self.cuts.append(self.buckets.seen)
            self.buckets.note_rows(rows_of(ids[at:at + a_dispatch]))

    def shares(self, ids):
        return np.bincount(self.router.lane_of_points(rows_of(ids)),
                           minlength=self.lanes.n) / len(ids)


def zipf(rng, n, size, theta=0.99):
    p = 1.0 / np.arange(1, n + 1) ** theta
    return rng.permutation(n)[rng.choice(n, size=size, p=p / p.sum())]


def test_a_load_in_key_order_then_uniform_traffic_cuts_once():
    rule, rng = Rule(), np.random.default_rng(71)
    rule.note(np.arange(100_000), a_dispatch=4096)
    assert rule.cuts == []  # every new row lies past every row before
    rule.note(rng.integers(100_000, size=30_000))
    assert len(rule.cuts) == 1
    # … in the first thousand rows of traffic, from the load's sample
    assert 100_000 < rule.cuts[0] <= 100_000 + 2 * LaneBounds.CHECK + 64
    shares = rule.shares(rng.integers(100_000, size=20_000))
    assert 0.22 < shares.min() and shares.max() < 0.28, shares


def test_a_sample_under_the_threshold_cuts_nothing():
    rule, rng = Rule(), np.random.default_rng(72)
    rule.note(rng.integers(100_000, size=LaneBounds.FIRST - 64))
    assert rule.cuts == [] and not rule.lanes.due(rule.buckets)
    assert rule.lanes.fresh(rule.buckets.sample()) is None
    assert rule.shares(np.arange(100)).max() == 1.0  # the first limb's
    # and one lane has no bounds to cut, whatever it has seen
    one = Rule(n=1)
    one.note(rng.integers(100_000, size=3 * LaneBounds.FIRST))
    assert one.cuts == [] and one.router.bounds.shape == (0, 5)


@pytest.mark.parametrize("theta,seed", [(0.99, 73), (2.0, 74)])
def test_a_hot_key_is_never_split_and_never_cut_for(theta, seed):
    """Commit attempts under a Zipfian: the hottest key is a large
    share of the rows (most of them at theta 2). Every row of a key
    has one lane, equal bounds leave a lane empty rather than split a
    key, and stationary traffic is cut for once."""
    rule, rng = Rule(), np.random.default_rng(seed)
    rule.note(np.arange(100_000), a_dispatch=4096)
    ids = zipf(rng, 100_000, 60_000, theta)
    rule.note(ids)
    assert len(rule.cuts) == 1, rule.cuts
    hot = np.bincount(ids).argmax()
    share = float((ids == hot).mean())
    assert share > (0.5 if theta > 1 else 0.04)
    lanes = rule.router.lane_of_points(rows_of(ids))
    assert len(set(lanes[ids == hot].tolist())) == 1
    # a bound is a whole key row, sorted
    b = rule.router.bounds
    assert b.shape == (3, 5) and (b[:, 4] == 8).all()
    assert (np.diff(b[:, 1].astype(np.int64)) >= 0).all()
    # the fullest lane holds the hot key and little else
    assert rule.shares(ids).max() < share + 0.3


def test_a_hot_set_that_moves_is_cut_for_again():
    rule, rng = Rule(capacity=8192), np.random.default_rng(75)
    rule.note(rng.integers(100_000, size=20_000))
    assert len(rule.cuts) == 1
    # the writers move to the table's last tenth: one lane has them all
    # until the sample has caught up, then the bounds follow
    late = 90_000 + rng.integers(10_000, size=40_000)
    rule.note(late)
    assert 2 <= len(rule.cuts) <= 4, rule.cuts
    assert rule.shares(late).max() < 0.5
