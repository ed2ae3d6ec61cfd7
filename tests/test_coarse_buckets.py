"""The coarse lanes' bucket function (resolver/packing.py
``CoarseBuckets``) and what changing it does to recorded history
(ops/conflict.py ``fold_coarse``, ``Resolver._maybe_rebucket``).

Until PR 35 a key's coarse bucket was the top bits of its first four
bytes: every ``mako…`` key of a table, every ``user…`` key, every key of
one tuple-layer subspace had one bucket, and a range read conflicted
with any point write anywhere (ISSUE 35's two CPU findings, the first
two scenario tests below). Now the buckets are cut from a sample of the
keys the resolver packs. Held here: the function is weakly monotone in
the whole key on every pack route; the findings are gone; a write
recorded before a rebucket is still seen after it; the accepted set stays
conflict-free across rebuckets (the device errs only by refusing); one
lane and four answer alike; and the counters' life.

The cases of one scenario run inside one test (a loop, not a
``parametrize``): pytest-xdist's ``loadfile`` hands the files with the
most tests out first, and this file compiles a dozen resolver programs;
with thirty items it ran beside ``test_flowlint_v3.py``'s five-second
wall budget and broke it.
"""

import random

import numpy as np
import pytest

from foundationdb_tpu.core import flatpack
from foundationdb_tpu.core.commit import CommitRequest
from foundationdb_tpu.core.options import Knobs
from foundationdb_tpu.core.status import COMMITTED, CONFLICT, TOO_OLD
from foundationdb_tpu.layers import tuple as fdb_tuple
from foundationdb_tpu.ops import conflict as ck
from foundationdb_tpu.resolver.meshresolver import MeshResolver
from foundationdb_tpu.resolver.packing import BatchPacker, CoarseBuckets
from foundationdb_tpu.resolver.resolver import Resolver
from foundationdb_tpu.resolver.skiplist import CpuConflictSet, TxnRequest
from foundationdb_tpu.utils import deviceprofile

from conftest import TEST_KNOBS

SLOTS = 200_000


def mako(i):
    return b"mako%028d" % i


def user(i):
    return b"user%08d" % i


def packed(i):
    return fdb_tuple.pack(("app", "orders", i))


def spread(i):
    return random.Random(i).randbytes(12)


FAMILIES = {"mako": mako, "user": user, "tuple": packed, "random": spread}

# the served default but for the batch: 32 key bytes a row, 2^14 buckets
WIDE = Knobs(resolver_backend="tpu", batch_txn_capacity=16)
# small shapes, a ring that evicts soon
SMALL = Knobs(**{**TEST_KNOBS, "resolver_backend": "tpu", "key_limbs": 8,
                 "range_reads_per_txn": 2, "range_writes_per_txn": 2,
                 "range_ring_capacity": 32})


def flat_of(txns, key_limbs):
    reqs = []
    for t in txns:
        rcr = [(k, k + b"\x00") for k in t.point_reads] + t.range_reads
        wcr = [(k, k + b"\x00") for k in t.point_writes] + t.range_writes
        reqs.append(CommitRequest(
            t.read_version, [], rcr, wcr,
            flat_conflicts=flatpack.encode_conflicts(rcr, wcr, key_limbs)))
    return flatpack.build_flat_batch(reqs, key_limbs)


# ── the function itself ─────────────────────────────────────────────
@pytest.mark.parametrize("route", ["legacy", "native", "flat"])
def test_buckets_are_weakly_monotone_in_the_whole_key(route):
    for family in sorted(FAMILIES):
        _monotone(family, route)


def _monotone(family, route):
    """Keys in key order get buckets in order, a range's begin bucket
    never passes its end bucket, and keys that share their first four
    bytes no longer share one bucket: on the numpy pack, the native pack
    (whose C pass writes the first limb's bits: recomputed) and the flat
    pack alike."""
    params = ck.ResolverParams(txns=64, point_reads=1, point_writes=1,
                               range_reads=1, range_writes=1, key_width=9,
                               bucket_bits=8)
    key = FAMILIES[family]
    rng = random.Random(5)
    ids = sorted(rng.sample(range(SLOTS), 64))
    keys = sorted(key(i) for i in ids)
    buckets = CoarseBuckets(params)
    packer = BatchPacker(params, use_native=route == "native",
                         buckets=buckets)
    if route == "native" and packer._native is None:
        pytest.skip("no native toolchain")
    txns = [TxnRequest(10, point_writes=[k],
                       range_reads=[(k, keys[min(n + 3, 63)])])
            for n, k in enumerate(keys)]

    def pack():
        if route == "flat":
            return packer.pack_flat(flat_of(txns, 8), 0, 20, 0)
        return packer.pack(txns, 0, 20, 0)

    first = pack()  # under the first limb's bits; its rows are the sample
    assert not buckets.cut
    if family != "random":  # the finding: one bucket for a whole table
        assert len(set(first.pw_bucket[:, 0].tolist())) == 1
    sample = [key(i) for i in rng.sample(range(SLOTS), 2000)]
    buckets.note_rows(packer.codec.encode_lower_batch(sample))
    assert buckets.recut_due() and buckets.recut() and buckets.cut
    assert buckets._bounds.shape == (255,)
    cut = pack()
    pw = cut.pw_bucket[:, 0]
    assert (np.diff(pw) >= 0).all(), pw
    assert (cut.rr_lo[:, 0] <= cut.rr_hi[:, 0]).all()
    assert (cut.rr_lo[:, 0] == pw).all()  # a range begins where its key lies
    assert 0 <= pw.min() and pw.max() <= 255
    assert len(set(pw.tolist())) > 24  # 64 keys over 256 buckets


def _a_thin_sample_still_gives_sorted_boundaries():
    """Fewer sampled keys than buckets: the keys themselves are the
    boundaries (repeated), and the map is still monotone."""
    params = ck.ResolverParams(key_width=9, bucket_bits=8)
    buckets = CoarseBuckets(params)
    codec = BatchPacker(params, use_native=False).codec
    buckets.note_rows(codec.encode_lower_batch([mako(5), mako(900), mako(70)]))
    assert buckets.recut()
    got = buckets.of(codec.encode_lower_batch(
        [mako(i) for i in (0, 5, 6, 70, 899, 900, 5000)]))
    assert (np.diff(got) >= 0).all() and got[0] == 0 and got[-1] == 255
    assert len(set(got.tolist())) == 4


def test_the_sample_settles_and_a_full_cut_waits_for_a_stale_bucket():
    """The rule of ``CoarseBuckets``' class text, at capacity 512: a
    thin cut is made again when the rows noted have doubled; a full one
    only when the keys move away under it."""
    _a_thin_sample_still_gives_sorted_boundaries()
    params = ck.ResolverParams(key_width=9, bucket_bits=6)
    buckets = CoarseBuckets(params, capacity=512)
    codec = BatchPacker(params, use_native=False).codec
    rng = random.Random(2)

    def note(n, lo, hi):
        buckets.note_rows(codec.encode_lower_batch(
            [mako(rng.randrange(lo, hi)) for _ in range(n)]))

    assert not buckets.recut_due()  # nothing noted, nothing to cut from
    note(100, 0, 100_000)
    assert buckets.recut_due() and buckets.recut()
    note(99, 0, 100_000)
    assert not buckets.recut_due()
    note(1, 0, 100_000)
    assert buckets.recut_due() and buckets.recut()  # doubled: 200
    note(2000, 0, 100_000)
    assert 512 <= buckets._held <= 1024  # settled: every second row dropped
    assert buckets.recut()  # from a full sample now
    before = buckets._bounds
    note(511, 0, 100_000)
    assert not buckets.recut_due()
    note(1, 0, 100_000)
    # the same keys again: held up, found fresh, left alone
    assert buckets.recut_due() and not buckets.recut()
    assert not buckets.recut_due()
    assert (buckets._bounds == before).all()
    note(512, 150_000, 200_000)  # the traffic moves behind the last bound
    assert buckets.recut_due() and buckets.recut()
    assert not (buckets._bounds == before).all()


# ── ISSUE 35's two findings ─────────────────────────────────────────
def loaded(knobs=WIDE, key=mako):
    """A resolver that has packed a table's load: a blind set of every
    second key slot, four a transaction, as ``benchmark/run.py`` loads."""
    r = Resolver(knobs)
    cv, per = 1000, 4 * knobs.batch_txn_capacity
    ids = list(range(0, SLOTS, 2))
    for i in range(0, len(ids), per):
        cv += 10
        r.resolve([TxnRequest(cv - 10, point_writes=[key(j)
                                                      for j in ids[k:k + 4]])
                   for k in range(i, min(i + per, len(ids)), 4)], cv, 0)
    return r, cv


def test_a_far_point_write_refuses_a_range_read_only_under_the_first_limb(
        monkeypatch):
    _far_point_write("cut", monkeypatch)
    _far_point_write("first_limb", monkeypatch)


def _far_point_write(boundaries, monkeypatch):
    """One set of mako…7, then a range read of [mako…50000, mako…50010)
    from before it. The skip list commits it. Under the first limb's
    bits the device refuses it (the parent's answer, held here by never
    letting the resolver cut); with boundaries cut from the load's keys
    it commits."""
    if boundaries == "first_limb":
        monkeypatch.setattr(CoarseBuckets, "recut_due", lambda self: False)
    r, cv = loaded()
    # the first range the resolver meets: it cuts its boundaries here,
    # and the fold refuses this one reader from before the load's end
    first = r.resolve([TxnRequest(cv - 5, range_reads=[
        (mako(90000), mako(90010))])], cv + 10, 0)
    cv += 10
    read = TxnRequest(cv + 5, range_reads=[(mako(50000), mako(50010))])
    assert r.resolve([TxnRequest(cv, point_writes=[mako(7)])], cv + 10, 0) \
        == [COMMITTED]
    ref = CpuConflictSet()
    ref.resolve([TxnRequest(cv, point_writes=[mako(7)])], cv + 10, 0)
    assert ref.resolve([read], cv + 20, 0) == [COMMITTED]
    got = r.resolve([read], cv + 20, 0)
    snap = r.profile.snapshot()
    if boundaries == "first_limb":
        assert got == [CONFLICT] and snap["rebuckets"] == 0
        assert first == [CONFLICT]  # the load's last sets, anywhere
        assert snap["conflicts_coarse_only"] == 2  # and it says which lane
    else:
        assert got == [COMMITTED] and snap["rebuckets"] == 1
        assert first == [CONFLICT]  # the fold's one round
        assert snap["conflicts_coarse_only"] == 1
        assert snap["rebucket_wall_ms"] > 0


def rmw(rng, rv, key=mako):
    """mako's gr / g / u / i / cr transaction: a range read of 20 rows
    (40 key slots), a get and a set of another key, an insert, a clear
    of two slots from the range's first key."""
    a, b = rng.randrange(SLOTS - 64), rng.randrange(SLOTS)
    return TxnRequest(rv, range_reads=[(key(a), key(a + 40))],
                      point_reads=[key(b)],
                      point_writes=[key(b), key(rng.randrange(SLOTS))],
                      range_writes=[(key(a), key(a + 2))])


def reference_verdict(ref, t, cv, status):
    """The skip list's verdict on ``t``'s reads over the history of what
    the device committed; then that history takes ``t``'s writes if the
    device committed it. The device may refuse more, never less."""
    (want,) = ref.resolve([TxnRequest(
        t.read_version, point_reads=t.point_reads,
        range_reads=t.range_reads)], cv)
    assert not (status == COMMITTED and want == CONFLICT), (t, cv)
    if status == COMMITTED:
        ref.resolve([TxnRequest(cv, point_writes=t.point_writes,
                                range_writes=t.range_writes)], cv)
    return want


def test_uniform_range_rmws_are_refused_about_as_often_as_they_conflict():
    """60 batches of 9 uniform range read-modify-writes over 200,000
    slots, after the table's load. The parent refused 450 of 540 (the
    skip list 10); now at most the skip list's count plus 20: 540
    transactions × 3 buckets a range × ≈ 25 point writes newer than a
    read version / 16,384 buckets ≈ 2–3 coarse refusals expected, so 20
    is room for an unlucky seed, not for a bucket function that fails."""
    r, cv = loaded()
    ref, rng = CpuConflictSet(), random.Random(1)
    device = want = 0
    for _ in range(60):
        cv += 10
        txns = [rmw(rng, cv - 10 - rng.choice((0, 10, 20)))
                for _ in range(9)]
        for t, s in zip(txns, r.resolve(txns, cv, 0)):
            assert s != TOO_OLD
            want += reference_verdict(ref, t, cv, s) == CONFLICT
            device += s == CONFLICT
    snap = r.profile.snapshot()
    assert want <= device <= want + 20, (device, want)
    # what the device refused beyond the reference, a coarse lane refused
    # (the hash table is 2^22 words here: no collision among 100k keys)
    assert device - want <= snap["conflicts_coarse_only"] <= device
    assert snap["rebuckets"] == 1  # once, at the first range it met
    assert snap["bucket_entries_routed"] == 60 * 9 * 2
    assert snap["bucket_entries_fullest"] < 0.5 * snap["bucket_entries_routed"]


# ── history recorded before a rebucket is seen after it ─────────────
def rebucket(r, keys):
    """Force a cut from ``keys`` (and the fold that goes with it)."""
    r._ranges_seen = True
    r.buckets.note_rows(r.packer.codec.encode_lower_batch(keys))
    r.buckets._cut_seen = 0
    before = r.profile.snapshot()["rebuckets"]
    r._maybe_rebucket()
    assert r.profile.snapshot()["rebuckets"] == before + 1


def test_writes_under_old_boundaries_are_seen_after_a_fold():
    for knobs in (SMALL, Knobs(**{**SMALL.__dict__,
                                  "ring_partition_bits": 2})):
        _seen_after_a_fold(knobs)


def _seen_after_a_fold(knobs):
    """A point write, a range write still in the ring and a range write
    evicted from it, all recorded under one set of boundaries; then a
    rebucket; then reads from before those writes: every one is refused.
    A partitioned ring, whose entries sit where their old begin bucket
    put them, is emptied into the summaries by the fold."""
    r = Resolver(knobs)
    rebucket(r, [mako(i) for i in range(0, 4000, 40)])
    assert r.resolve([
        TxnRequest(100, point_writes=[mako(1234)]),
        TxnRequest(100, range_writes=[(mako(2000), mako(2010))]),
    ], 110, 0) == [COMMITTED, COMMITTED]
    # 40 more range writes elsewhere: the first is evicted from the ring
    # of 32 into range_L / range_R under the OLD boundaries …
    cv = 110
    for n in range(40):
        cv += 10
        assert r.resolve([TxnRequest(cv - 10, range_writes=[
            (mako(3000 + 10 * n), mako(3000 + 10 * n + 5))])], cv, 0) \
            == [COMMITTED]
    # … and one more at the end, still in the ring when the fold comes
    assert r.resolve([TxnRequest(cv, range_writes=[
        (mako(3900), mako(3905))])], cv + 10, 0) == [COMMITTED]
    cv += 10
    # new boundaries: quite other keys, so every old index is wrong now
    rebucket(r, [mako(i) for i in range(100_000, 104_000, 3)])
    got = r.resolve([
        TxnRequest(105, range_reads=[(mako(1230), mako(1240))]),  # the point
        TxnRequest(105, point_reads=[mako(2005)]),  # the evicted range
        TxnRequest(105, range_reads=[(mako(2008), mako(2100))]),
        TxnRequest(cv - 5, point_reads=[mako(3902)]),  # the ring's newest
        TxnRequest(cv - 5, range_reads=[(mako(3904), mako(3999))]),
    ], cv + 10, 0)
    assert got == [CONFLICT] * 5
    # and a read from after them all, under the new boundaries, commits
    assert r.resolve([TxnRequest(cv + 10, range_reads=[
        (mako(1230), mako(1240))])], cv + 20, 0) == [COMMITTED]
    # the newest range write is evicted only now, after the fold, with
    # begin / end bucket 0 / C − 1: a read from before it is refused
    for n in range(40):
        cv += 10
        r.resolve([TxnRequest(cv, range_writes=[
            (mako(5000 + 10 * n), mako(5000 + 10 * n + 5))])], cv + 10, 0)
    # a read version from before 3900's write is too old by now for a
    # window that moved; keep the window open and ask the summaries
    assert r.resolve([TxnRequest(115, point_reads=[mako(3902)])],
                     cv + 20, 0) == [CONFLICT]


def test_the_replicated_sharded_step_finds_a_range_write_after_a_rebucket():
    """``resolve_batch(axis_name=…)`` records a range write on the shard
    that owns its begin BUCKET. A rebucket changes which shard that is
    for later writes; the earlier entry stays where it sits, every shard
    checks every read against its own ring exactly, and the verdicts are
    OR-reduced: nothing is lost."""
    knobs = Knobs(**{**SMALL.__dict__, "resolver_sharding": "hash"})
    r = MeshResolver(knobs, n_lanes=4)
    rebucket(r, [mako(i) for i in range(0, 4000, 4)])
    assert r.resolve([TxnRequest(100, range_writes=[
        (mako(3000), mako(3010))])], 110, 0) == [COMMITTED]
    lo = int(r.buckets.of(r.packer.codec.encode_lower_batch([mako(3000)]))[0])
    rebucket(r, [mako(i) for i in range(2990, 400_000, 97)])
    now = int(r.buckets.of(r.packer.codec.encode_lower_batch(
        [mako(3000)]))[0])
    C = 1 << knobs.coarse_buckets_bits
    assert (lo * 4) // C != (now * 4) // C  # another shard owns it now
    assert r.resolve([
        TxnRequest(105, range_reads=[(mako(3005), mako(3100))]),
        TxnRequest(105, point_reads=[mako(3009)]),
        TxnRequest(115, range_reads=[(mako(3005), mako(3100))]),
    ], 120, 0) == [CONFLICT, CONFLICT, COMMITTED]


# ── the invariant, across rebuckets ─────────────────────────────────
def test_the_accepted_set_stays_conflict_free_across_rebuckets():
    for seed in (3, 4, 5, 6):
        _conflict_free_across_rebuckets(seed)


def _conflict_free_across_rebuckets(seed):
    """``tests/test_resolver.py``'s invariant (the device errs only by
    refusing) over traffic whose keys move to another region half way,
    on a sample small enough to be cut many times: by growth, and by a
    bucket gone stale."""
    r = Resolver(SMALL)
    r.buckets.capacity = 128
    ref, rng = CpuConflictSet(), random.Random(seed)
    cv, conflicts, committed = 1000, 0, 0
    for n in range(160):
        cv += 10
        base = 0 if n < 80 else 500_000

        def key(i):
            return mako(base + i)

        txns = []
        for _ in range(rng.randrange(2, 9)):
            rv = cv - 10 - rng.choice((0, 0, 0, 10, 30))
            a, b = rng.randrange(2000), rng.randrange(2000)
            kind = rng.random()
            if kind < 0.3:
                t = TxnRequest(rv, point_reads=[key(a)],
                               point_writes=[key(a)])
            elif kind < 0.6:
                t = TxnRequest(rv, range_reads=[(key(a), key(a + 20))],
                               point_writes=[key(b)],
                               range_writes=[(key(a), key(a + 2))])
            elif kind < 0.8:
                t = TxnRequest(rv, range_reads=[(key(a), key(a + 200))],
                               point_writes=[key(a)])
            else:
                t = TxnRequest(rv, point_reads=[key(b)],
                               range_writes=[(key(a), key(a + 50))])
            txns.append(t)
        got = (r.resolve(flat_of(txns, 8), cv, 0) if n % 2
               else r.resolve(txns, cv, 0))
        for t, s in zip(txns, got):
            assert s != TOO_OLD
            conflicts += reference_verdict(ref, t, cv, s) == CONFLICT
            committed += s == COMMITTED
    assert conflicts > 20 and committed > 200, (conflicts, committed)
    snap = r.profile.snapshot()
    assert snap["rebuckets"] >= 4, snap["rebuckets"]
    assert r.buckets._cut_seen > 128 * 4  # a full sample was cut again


# ── one lane and four ───────────────────────────────────────────────
def test_one_lane_and_four_answer_range_traffic_alike():
    for route in ("legacy", "flat"):
        _one_lane_and_four(route)


def _one_lane_and_four(route):
    """mako's range transaction through one lane and through four forced
    host lanes, both rebucketing as they go: the same verdicts."""
    knobs = Knobs(**{**SMALL.__dict__, "range_ring_capacity": 512,
                     "hash_table_bits": 20})
    one, mesh = Resolver(knobs), MeshResolver(knobs, n_lanes=4)
    rng = random.Random(9)
    cv, seen = 1000, set()
    for _ in range(50):
        cv += 10
        txns = [rmw(rng, cv - 10 - rng.choice((0, 10, 20)),
                    key=lambda i: mako(i % 3000))
                for _ in range(rng.randrange(2, 6))]
        batch = (lambda: flat_of(txns, 8)) if route == "flat" else (
            lambda: txns)
        a, b = one.resolve(batch(), cv, 0), mesh.resolve(batch(), cv, 0)
        assert a == b, cv
        seen.update(a)
    assert seen == {COMMITTED, CONFLICT}
    assert one.profile.snapshot()["rebuckets"] \
        == mesh.profile.snapshot()["rebuckets"] >= 3


# ── the counters' life: aggregate, respawn, the kill switch ─────────
COUNTERS = ("rebuckets", "conflicts_coarse_only", "bucket_entries_routed",
            "bucket_entries_fullest")


def ranged(r, cv):
    """Two dispatches of range traffic: the first is the sample the
    second cuts from."""
    rng = random.Random(cv)
    for n in range(2):
        r.resolve([rmw(rng, cv + 10 * n, key=lambda i: mako(i % 3000))
                   for _ in range(4)], cv + 10 * (n + 1), 0)


def test_bucket_counters_reach_the_aggregate_and_outlive_a_respawn():
    r = Resolver(SMALL)
    ranged(r, 1000)
    first = r.profile.snapshot()
    assert first["rebuckets"] == 1 and first["rebucket_wall_ms"] > 0
    assert first["bucket_entries_routed"] == 8  # the second dispatch's
    new = r.respawn(1020)
    assert new.profile is r.profile and not new.buckets.cut
    ranged(new, 1020)
    other = Resolver(SMALL)
    ranged(other, 1000)
    agg = deviceprofile.merged_snapshot([new.profile, other.profile])
    assert agg["rebuckets"] == 3
    assert agg["bucket_entries_routed"] == 24
    assert 3 <= agg["bucket_entries_fullest"] <= 24
    walls = [p.snapshot()["rebucket_wall_ms"]
             for p in (new.profile, other.profile)]
    assert agg["rebucket_wall_ms"] == pytest.approx(sum(walls), abs=0.002)
    # a resolver of point transactions cuts nothing and counts nothing
    points = Resolver(SMALL)
    for n in range(3):
        points.resolve([TxnRequest(1000, point_reads=[mako(n)],
                                   point_writes=[mako(n)])], 1010 + n, 0)
    snap = points.profile.snapshot()
    assert [snap[c] for c in COUNTERS] == [0, 0, 0, 0]
    assert not points.buckets.cut and points.buckets.seen == 3


def test_the_kill_switch_stops_the_bucket_counters():
    r = Resolver(SMALL)
    deviceprofile.set_enabled(False)
    try:
        ranged(r, 1000)
        # a far point write, then a range read from before it
        far = r.resolve([TxnRequest(1020, point_writes=[mako(2999)])],
                        1030, 0)
    finally:
        deviceprofile.set_enabled(True)
    assert far == [COMMITTED] and r.buckets.cut  # the work was done
    snap = r.profile.snapshot()
    assert [snap[c] for c in COUNTERS] == [0, 0, 0, 0]
    assert snap["rebucket_wall_ms"] == 0
    ranged(r, 1040)
    assert r.profile.snapshot()["bucket_entries_routed"] == 16


def test_the_counters_are_in_status_json():
    import foundationdb_tpu as fdb

    db = fdb.open(resolver_backend="tpu", **{
        k: v for k, v in TEST_KNOBS.items()})
    for n in range(3):
        @fdb.transactional
        def scan_and_clear(tr, n=n):
            list(tr.get_range(mako(10 * n), mako(10 * n + 5)))
            tr.set(mako(10 * n + 7), b"x")
            tr.clear_range(mako(10 * n), mako(10 * n + 2))
        scan_and_clear(db)
    agg = db.status()["cluster"]["device"]["aggregate"]
    assert agg["rebuckets"] >= 1 and agg["rebucket_wall_ms"] > 0
    assert agg["bucket_entries_routed"] >= 1
    assert set(COUNTERS) <= set(agg)
