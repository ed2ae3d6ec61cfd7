"""A range end at a capacity-length key's successor on the flat route
(core/flatpack.py): mako's range transaction over 32-byte keys, judged
against the plain reference.

A ``get_range`` cut short by its limit records ``[first, last + \\x00)``;
for ``mako%028d`` keys at the default eight limbs that end is one byte
past the flat entry's capacity, and it packs as ``last || >I(33)``. The
reference is resolver/skiplist.py's interval list, which compares raw
bytes and imports nothing of the device path. Every backend that takes
the flat batch must give its verdicts: the ``tpu`` step on the CPU, the
native set, and four lanes of the mesh, whose bounds fall on keys the
ranges end at. A history that interleaves legacy batches (whose packer
encodes the same end as the prefix successor ``last + 1``) with flat
ones over the same keys must give them too.

What makes a device verdict equal the reference's, and not only never
laxer: a hash table roomy enough for the table, a ring the traffic does
not lap, and coarse buckets cut from the loaded table before the first
verdict is compared, so that every key slot has a bucket of its own
(tests/test_lane_bounds.py holds the same conditions).
"""

import random

import jax
import pytest

from foundationdb_tpu.core import flatpack
from foundationdb_tpu.core.commit import CommitRequest
from foundationdb_tpu.core.options import Knobs
from foundationdb_tpu.core.status import COMMITTED, CONFLICT
from foundationdb_tpu.native import native_available
from foundationdb_tpu.resolver.skiplist import CpuConflictSet, TxnRequest

from conftest import TEST_KNOBS

LIMBS = 8  # 32 key bytes: a mako key fills the entry
KNOBS = Knobs(**{**TEST_KNOBS, "key_limbs": LIMBS,
                 "range_reads_per_txn": 2, "range_writes_per_txn": 2,
                 "hash_table_bits": 20, "range_ring_capacity": 1024,
                 "coarse_buckets_bits": 10, "resolver_backend": "tpu"})
T = KNOBS.batch_txn_capacity
SLOTS = 600  # records in the even slots, fresh records in the odd ones
ROWS = 20  # a get_range's limit: 20 records span 40 slots


def key(s):
    return b"mako%028d" % s


def mako_txn(rng, rv):
    """mako's gr / g / u / i / cr transaction: 20 records read from an
    even slot, ending at the last one's successor; a get and a set of
    another record; the insert of a fresh record; a clear of two slots
    from the range's first key, ending either at the next key or at the
    second slot's successor (the same keys, the write side's new end)."""
    a = 2 * rng.randrange(SLOTS // 2 - ROWS)
    b = 2 * rng.randrange(SLOTS // 2)
    fresh = 2 * rng.randrange(SLOTS // 2) + 1
    clear_end = key(a + 2) if rng.random() < 0.5 else key(a + 1) + b"\x00"
    return TxnRequest(
        rv, range_reads=[(key(a), key(a + 2 * ROWS - 2) + b"\x00")],
        point_reads=[key(b)], point_writes=[key(b), key(fresh)],
        range_writes=[(key(a), clear_end)])


def flat_of(txns):
    reqs = []
    for t in txns:
        rcr = [(k, k + b"\x00") for k in t.point_reads] + t.range_reads
        wcr = [(k, k + b"\x00") for k in t.point_writes] + t.range_writes
        reqs.append(CommitRequest(
            t.read_version, [], rcr, wcr,
            flat_conflicts=flatpack.encode_conflicts(rcr, wcr, LIMBS)))
    flat = flatpack.build_flat_batch(reqs, LIMBS)
    assert flat is not None  # every range of the shape flattens
    return flat


def make(backend):
    if backend == "native":
        if not native_available():
            pytest.skip("no native toolchain")
        from foundationdb_tpu.resolver.resolver import Resolver

        return Resolver(Knobs(**{**KNOBS.__dict__,
                                 "resolver_backend": "native"}))
    if backend == "tpu":
        from foundationdb_tpu.resolver.resolver import Resolver

        r = Resolver(KNOBS)
    else:
        if len(jax.devices()) < 4:
            pytest.skip("four lanes need four host devices")
        from foundationdb_tpu.resolver.meshresolver import MeshResolver
        from foundationdb_tpu.resolver.packing import ShardRouter

        r = MeshResolver(KNOBS, n_lanes=4)
        assert r.n_lanes == 4
        # bounds on a record, on a fresh slot and on a record again, set
        # before any history (the rule's own first look waits for 4,096
        # sampled rows, which this table never reaches): a range whose
        # last record is a bound's key reaches into that bound's lane
        r._router = ShardRouter(r.params, 4, bounds=r.packer.codec
                                .encode_lower_batch([key(150), key(301),
                                                     key(450)]))
    # the whole load fits the sample and fills it: the cut at the first
    # range is the last while the traffic stays uniform
    r.buckets.capacity = SLOTS - SLOTS // 8
    return r


def load(r, ref, cv):
    """Every slot in key order, blind sets → the last commit version."""
    for at in range(0, SLOTS, T):
        cv += 10
        txns = [TxnRequest(cv - 10, point_writes=[key(s)])
                for s in range(at, min(at + T, SLOTS))]
        assert set(r.resolve(txns, cv, 0)) == {COMMITTED}
        ref.resolve(txns, cv, 0)
    return cv


@pytest.mark.parametrize("history", ["flat", "interleaved"])
@pytest.mark.parametrize("backend", ["tpu", "native", "mesh"])
def test_mako_range_verdicts_equal_the_reference(backend, history):
    r, ref = make(backend), CpuConflictSet()
    cv = load(r, ref, 1000)
    # the first range the resolver meets cuts its buckets, and the fold
    # that goes with it may refuse this one reader: it writes nothing,
    # so both histories stay the same; the fold raised every summary to
    # this commit version, so no compared read version is older
    cv += 10
    warm = [TxnRequest(cv - 10, range_reads=[(key(0), key(38) + b"\x00")])]
    r.resolve(flat_of(warm), cv, 0)
    ref.resolve(warm, cv, 0)
    cv += 30
    if backend != "native":
        assert r.profile.snapshot()["rebuckets"] == 1
    rng = random.Random(46)
    seen = set()
    for step in range(12):
        for half in range(2):
            cv += 10
            txns = [mako_txn(rng, cv - 10 - rng.choice((0, 0, 10, 30)))
                    for _ in range(rng.randrange(2, T + 1))]
            legacy = history == "interleaved" and (step + half) % 2 == 1
            got = r.resolve(txns if legacy else flat_of(txns), cv, 0)
            want = ref.resolve(txns, cv, 0)
            assert got == want, (step, half, legacy)
            seen.update(got)
    assert seen == {COMMITTED, CONFLICT}
    if backend != "native":
        snap = r.profile.snapshot()
        assert snap["rebuckets"] == 1  # the traffic stayed what it was
        # no flat batch fell back to the legacy pack
        assert r.metrics.counter("flat_fallbacks").value == 0
    if backend == "mesh":
        assert r.profile.snapshot()["range_lane_dups"] > 0  # ranges crossed


@pytest.mark.parametrize("backend", ["tpu", "native"])
def test_a_served_range_commit_takes_the_flat_build(backend):
    """Over the wire: mako's range transaction against a served cluster
    builds its batches flat (status counts them), and the table reads
    back as the commits left it."""
    if backend == "native" and not native_available():
        pytest.skip("no native toolchain")
    from foundationdb_tpu.rpc.service import RemoteCluster, serve_cluster
    from foundationdb_tpu.server.cluster import Cluster

    knobs = {**TEST_KNOBS, "key_limbs": LIMBS}
    cluster = Cluster(n_storage=1, resolver_backend=backend, **knobs)
    server = serve_cluster(cluster)
    rc = RemoteCluster([server.address])
    try:
        db = rc.database()
        want = {key(s): b"v%d" % s for s in range(0, 80, 2)}

        def fill(tr):
            for k, v in want.items():
                tr.set(k, v)

        db.run(fill)
        before = rc.status()["cluster"]["metrics"]["rollups"]
        rng = random.Random(7)
        for n in range(6):
            a = 2 * rng.randrange(30)
            b = 2 * rng.randrange(40)
            tr = db.create_transaction()
            # cut short by its limit: the read conflict range ends at
            # the fifth row's successor, 33 bytes
            rows = tr.get_range(key(a), key(SLOTS) + b"\x00", limit=5)
            assert rows == sorted(
                (k, v) for k, v in want.items() if k >= key(a))[:5]
            assert tr.get(key(b)) == want.get(key(b))
            tr.set(key(b), b"u%d" % n)
            tr.set(key(101 + 2 * n), b"i%d" % n)
            tr.clear_range(key(a), key(a + 2))
            tr.commit()
            want[key(b)] = b"u%d" % n
            want[key(101 + 2 * n)] = b"i%d" % n
            want.pop(key(a), None)
        after = rc.status()["cluster"]["metrics"]["rollups"]
        assert after["pack_flat_batches"] - before["pack_flat_batches"] >= 6
        assert after["pack_legacy_batches"] == 0
        got = db.run(lambda tr: tr.get_range(key(0), key(SLOTS)))
        assert dict(got) == want
    finally:
        rc.close()
        server.close()
        cluster.close()
