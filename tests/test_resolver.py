"""Differential tests: TPU conflict kernel vs the exact host ConflictSet.

Strategy (SURVEY.md §4.2): point-only collision-free workloads must match
the oracle EXACTLY (including intra-batch ordering); arbitrary workloads
(ranges, ring eviction, coarse lanes) must keep the serializability
invariant — the accepted set is mutually conflict-free — and may only
ever err by rejecting more (conservative), never by accepting a conflict.
"""

import random

import numpy as np
import pytest

from foundationdb_tpu.ops import conflict as ck
from foundationdb_tpu.resolver.packing import BatchPacker, fnv_hash_np
from foundationdb_tpu.resolver.skiplist import (
    COMMITTED,
    CONFLICT,
    TOO_OLD,
    CpuConflictSet,
    TxnRequest,
)

SMALL = ck.ResolverParams(
    txns=8,
    point_reads=2,
    point_writes=2,
    range_reads=1,
    range_writes=1,
    key_width=3,
    hash_bits=12,
    ring_capacity=16,
    bucket_bits=6,
)


def plain_status(status):
    """A step's statuses as numpy, the device-side CONFLICT_COARSE (a
    refusal only a coarse summary raised; ``Resolver`` counts it on its
    way out) answered as the CONFLICT it is."""
    status = np.asarray(status)
    return np.where(status == ck.CONFLICT_COARSE, CONFLICT, status)


def make_kernel(params=SMALL):
    packer = BatchPacker(params)
    state = ck.init_state(params)
    step = ck.make_resolve_fn(params, donate=False)
    return packer, state, step


def run_batches(batches, params=SMALL, base=0):
    """batches: list of (txns, commit_version, new_window_start).
    Returns per-batch status lists from the device kernel."""
    packer, state, step = make_kernel(params)
    out = []
    for txns, cv, ws in batches:
        b = packer.pack(txns, base, cv, ws)
        status, _acc, state = step(state, b)
        out.append(plain_status(status)[: len(txns)].tolist())
    return out


def oracle_batches(batches):
    cs = CpuConflictSet()
    return [cs.resolve(txns, cv, ws) for txns, cv, ws in batches]


def test_host_device_hash_parity():
    from foundationdb_tpu.ops.intervals import fnv_hash
    import jax.numpy as jnp

    rng = np.random.default_rng(0)
    limbs = rng.integers(0, 2**32, size=(50, 3), dtype=np.uint32)
    np.testing.assert_array_equal(
        fnv_hash_np(limbs), np.asarray(fnv_hash(jnp.asarray(limbs)))
    )


def test_basic_point_conflict():
    t1 = TxnRequest(read_version=10, point_writes=[b"k1"])
    t2 = TxnRequest(read_version=10, point_reads=[b"k1"])  # reads k1 at rv 10
    t3 = TxnRequest(read_version=20, point_reads=[b"k1"])  # reads after commit
    batches = [
        ([t1], 15, 0),  # k1 written at v15
        ([t2, t3], 25, 0),  # t2 conflicts (15 > 10), t3 fine (15 < 20)
    ]
    got = run_batches(batches)
    assert got == [[COMMITTED], [CONFLICT, COMMITTED]]
    assert got == oracle_batches(batches)


def test_intra_batch_order():
    # writer before reader in one batch: reader conflicts; reversed: both commit
    w = TxnRequest(read_version=10, point_writes=[b"hot"])
    r = TxnRequest(read_version=10, point_reads=[b"hot"])
    assert run_batches([([w, r], 20, 0)]) == [[COMMITTED, CONFLICT]]
    assert run_batches([([r, w], 20, 0)]) == [[COMMITTED, COMMITTED]]
    assert oracle_batches([([w, r], 20, 0)]) == [[COMMITTED, CONFLICT]]
    assert oracle_batches([([r, w], 20, 0)]) == [[COMMITTED, COMMITTED]]


def test_kill_chain_revives_downstream():
    # t0 writes a; t1 reads a (killed by t0) and writes b; t2 reads b —
    # t1 died, so t2 must COMMIT. Exercises the Jacobi fixpoint depth>1.
    t0 = TxnRequest(read_version=10, point_writes=[b"a"])
    t1 = TxnRequest(read_version=10, point_reads=[b"a"], point_writes=[b"b"])
    t2 = TxnRequest(read_version=10, point_reads=[b"b"])
    batches = [([t0, t1, t2], 20, 0)]
    expect = [[COMMITTED, CONFLICT, COMMITTED]]
    assert run_batches(batches) == expect
    assert oracle_batches(batches) == expect


def test_too_old():
    t = TxnRequest(read_version=5, point_reads=[b"x"])
    batches = [([TxnRequest(read_version=10)], 12, 8), ([t], 20, 8)]
    got = run_batches(batches)
    assert got[1] == [TOO_OLD]
    assert got == oracle_batches(batches)


def test_range_write_vs_point_read():
    w = TxnRequest(read_version=10, range_writes=[(b"a", b"m")])
    r_in = TxnRequest(read_version=10, point_reads=[b"c"])
    r_out = TxnRequest(read_version=10, point_reads=[b"z"])
    batches = [([w], 15, 0), ([r_in, r_out], 20, 0)]
    got = run_batches(batches)
    assert got == [[COMMITTED], [CONFLICT, COMMITTED]]
    assert got == oracle_batches(batches)


def test_range_read_vs_point_write():
    w = TxnRequest(read_version=10, point_writes=[b"f"])
    r = TxnRequest(read_version=10, range_reads=[(b"a", b"m")])
    batches = [([w], 15, 0), ([r], 20, 0)]
    got = run_batches(batches)
    assert got == [[COMMITTED], [CONFLICT]]  # may be coarse, must still flag


def test_ring_eviction_stays_conservative():
    # overflow the 16-slot ring with range writes; a read that conflicts
    # with an early (evicted) range write must STILL be flagged.
    batches = []
    v = 10
    for i in range(40):
        batches.append(
            ([TxnRequest(read_version=v, range_writes=[(bytes([i]), bytes([i + 1]))])], v + 5, 0)
        )
        v += 5
    old_read = TxnRequest(read_version=12, point_reads=[b"\x00"])  # vs write at v15
    batches.append(([old_read], v + 5, 0))
    got = run_batches(batches)
    assert got[-1] == [CONFLICT]


def rand_txn(rng, nkeys, rv):
    def k():
        return b"k%04d" % rng.randrange(nkeys)

    t = TxnRequest(read_version=rv)
    for _ in range(rng.randrange(0, 3)):
        t.point_reads.append(k())
    for _ in range(rng.randrange(0, 3)):
        t.point_writes.append(k())
    return t


def test_randomized_point_only_exact_match():
    rng = random.Random(42)
    # pick 50 keys whose 12-bit table slots are collision-free, so the
    # hash lane is exact and the oracle must match bit-for-bit
    packer = BatchPacker(SMALL)
    keys, seen = [], set()
    for i in range(200):
        k = b"k%04d" % i
        h = int(
            fnv_hash_np(packer.codec.encode_lower(k)[None])[0]
            & np.uint32((1 << SMALL.hash_bits) - 1)
        )
        if h not in seen:
            seen.add(h)
            keys.append(k)
        if len(keys) == 50:
            break
    key_ids = [int(k[1:]) for k in keys]

    version = 100
    batches = []
    for _ in range(30):
        n = rng.randrange(1, SMALL.txns + 1)
        txns = []
        for _ in range(n):
            t = TxnRequest(read_version=version - rng.randrange(0, 30))
            for _ in range(rng.randrange(0, 3)):
                t.point_reads.append(b"k%04d" % rng.choice(key_ids))
            for _ in range(rng.randrange(0, 3)):
                t.point_writes.append(b"k%04d" % rng.choice(key_ids))
            txns.append(t)
        version += rng.randrange(1, 10)
        window = max(0, version - 60)
        batches.append((txns, version, window))
    assert run_batches(batches) == oracle_batches(batches)


def exact_serializability_check(batches, statuses):
    """Replay device-accepted txns through an exact checker: every accepted
    txn's reads must miss every accepted newer write. This is the hard
    correctness invariant (false positives allowed, false negatives not)."""
    accepted_writes = []  # (begin, end, commit_version)
    for (txns, cv, _ws), st in zip(batches, statuses):
        new_writes = []
        for txn, s in zip(txns, st):
            if s != COMMITTED:
                continue
            for rb, re_ in txn.read_ranges():
                for wb, we, wv in accepted_writes + new_writes:
                    assert not (
                        wv > txn.read_version and rb < we and wb < re_
                    ), f"accepted txn read {rb!r}..{re_!r}@{txn.read_version} overlaps accepted write {wb!r}..{we!r}@{wv}"
            for wr in txn.write_ranges():
                new_writes.append((*wr, cv))
        accepted_writes.extend(new_writes)


def test_randomized_mixed_serializability():
    rng = random.Random(7)
    version = 100
    batches = []
    for _ in range(25):
        n = rng.randrange(1, SMALL.txns + 1)
        txns = []
        for _ in range(n):
            t = rand_txn(rng, 30, version - rng.randrange(0, 20))
            if rng.random() < 0.3:
                a, b = sorted([b"k%04d" % rng.randrange(30), b"k%04d" % rng.randrange(30)])
                t.range_reads.append((a, b + b"\xff"))
            if rng.random() < 0.3:
                a, b = sorted([b"k%04d" % rng.randrange(30), b"k%04d" % rng.randrange(30)])
                t.range_writes.append((a, b + b"\xff"))
            txns.append(t)
        version += rng.randrange(1, 8)
        batches.append((txns, version, max(0, version - 50)))
    statuses = run_batches(batches)
    exact_serializability_check(batches, statuses)
    # and the device must never accept less than... (it may: conservative)
    # but it must accept SOMETHING on conflict-free workloads:
    flat = [s for b in statuses for s in b]
    assert flat.count(COMMITTED) > 0


def test_resolver_wrapper_backends():
    from foundationdb_tpu.core.options import Knobs
    from foundationdb_tpu.resolver.resolver import Resolver

    for backend in ("cpu", "tpu"):
        knobs = Knobs(
            resolver_backend=backend,
            batch_txn_capacity=8,
            point_reads_per_txn=2,
            point_writes_per_txn=2,
            range_reads_per_txn=1,
            range_writes_per_txn=1,
            key_limbs=2,
            hash_table_bits=12,
            range_ring_capacity=16,
            coarse_buckets_bits=6,
        )
        r = Resolver(knobs)
        w = TxnRequest(read_version=10, point_writes=[b"k"])
        rd = TxnRequest(read_version=10, point_reads=[b"k"])
        assert r.resolve([w], 15, 0) == [COMMITTED]
        assert r.resolve([rd], 20, 0) == [CONFLICT]
        rd2 = TxnRequest(read_version=16, point_reads=[b"k"])
        assert r.resolve([rd2], 25, 0) == [COMMITTED]


def test_version_rebase_preserves_conflicts():
    from foundationdb_tpu.core.options import Knobs
    from foundationdb_tpu.core.versions import REBASE_THRESHOLD
    from foundationdb_tpu.resolver.resolver import Resolver

    knobs = Knobs(
        batch_txn_capacity=8,
        point_reads_per_txn=2,
        point_writes_per_txn=2,
        range_reads_per_txn=1,
        range_writes_per_txn=1,
        key_limbs=2,
        hash_table_bits=12,
        range_ring_capacity=16,
        coarse_buckets_bits=6,
    )
    r = Resolver(knobs)
    thr = REBASE_THRESHOLD
    # below threshold: write k at thr-50, advance window to thr-100
    w = TxnRequest(read_version=thr - 60, point_writes=[b"k"])
    assert r.resolve([w], thr - 50, thr - 100) == [COMMITTED]
    # next batch crosses the threshold -> host rebases device offsets
    rd_stale = TxnRequest(read_version=thr - 55, point_reads=[b"k"])  # < write v
    rd_fresh = TxnRequest(read_version=thr - 45, point_reads=[b"k"])  # > write v
    assert r.resolve([rd_stale, rd_fresh], thr + 10, thr - 100) == [CONFLICT, COMMITTED]
    assert r.base_version == thr - 100  # rebase actually happened
    # and ancient reads are rejected rather than wrapped
    assert (
        r.resolve([TxnRequest(read_version=100, point_reads=[b"k"])], thr + 20, thr - 100)
        == [TOO_OLD]
    )


def test_ring_capacity_validation():
    with pytest.raises(ValueError):
        ck.make_resolve_fn(ck.ResolverParams(txns=64, range_writes=2, ring_capacity=64))


def test_pallas_ring_lanes_match_jnp_lanes():
    """The Pallas VMEM ring kernel (ops/pallas_ring.py) replaces only the
    exact ring lanes; its verdicts must be bit-identical to the jnp
    broadcast lanes on arbitrary mixed workloads (interpret mode off-TPU)."""
    rng = random.Random(11)
    version = 100
    batches = []
    for _ in range(12):
        n = rng.randrange(1, SMALL.txns + 1)
        txns = []
        for _ in range(n):
            t = rand_txn(rng, 25, version - rng.randrange(0, 20))
            if rng.random() < 0.5:
                a, b = sorted([b"k%04d" % rng.randrange(25), b"k%04d" % rng.randrange(25)])
                t.range_reads.append((a, b + b"\xff"))
            if rng.random() < 0.5:
                a, b = sorted([b"k%04d" % rng.randrange(25), b"k%04d" % rng.randrange(25)])
                t.range_writes.append((a, b + b"\xff"))
            txns.append(t)
        version += rng.randrange(1, 8)
        batches.append((txns, version, max(0, version - 50)))
    plain = run_batches(batches, SMALL)
    pallas = run_batches(batches, SMALL._replace(use_pallas=True))
    assert plain == pallas
    exact_serializability_check(batches, pallas)


def test_point_fast_path_history_visible_to_full_kernel():
    """The point-only specialized variant records the hash table AND the
    coarse point summary, so a later range read (full kernel) conflicts
    with point writes that were resolved on the fast path."""
    from foundationdb_tpu.core.options import Knobs
    from foundationdb_tpu.resolver.resolver import Resolver

    knobs = Knobs(
        resolver_backend="tpu", batch_txn_capacity=8, point_reads_per_txn=2,
        point_writes_per_txn=2, range_reads_per_txn=1, range_writes_per_txn=1,
        key_limbs=2, hash_table_bits=12, range_ring_capacity=16,
        coarse_buckets_bits=6,
    )
    r = Resolver(knobs)
    assert r._fast is not None
    # batch 1: pure point writes — must take the fast variant
    t1 = TxnRequest(read_version=10, point_writes=[b"k5"])
    assert r.resolve([t1], 20, 0) == [COMMITTED]
    assert not r._range_history
    # batch 2: a range read covering k5 at an OLD read version — the full
    # kernel must see the fast path's write and reject it
    t2 = TxnRequest(read_version=15, range_reads=[(b"k0", b"k9")])
    t3 = TxnRequest(read_version=25, range_reads=[(b"k0", b"k9")])
    assert r.resolve([t2, t3], 30, 0) == [CONFLICT, COMMITTED]
    # batch 3: a range write makes range history sticky
    t4 = TxnRequest(read_version=25, range_writes=[(b"a", b"b")])
    assert r.resolve([t4], 40, 0) == [COMMITTED]
    assert r._range_history
    # ...and a point read under it must now conflict via the full kernel
    t5 = TxnRequest(read_version=35, point_reads=[b"a5"])
    assert r.resolve([t5], 50, 0) == [CONFLICT]


def test_point_write_spill_disables_fast_path_stickily():
    """A txn whose point writes overflow the lanes is recorded by the
    packer as a RING range-write — so the fast variant (ring statically
    off) must never run again, or a later point read misses the spilled
    write (regression: serializability violation)."""
    from foundationdb_tpu.core.options import Knobs
    from foundationdb_tpu.resolver.resolver import Resolver

    knobs = Knobs(
        resolver_backend="tpu", batch_txn_capacity=8, point_reads_per_txn=2,
        point_writes_per_txn=2, range_reads_per_txn=2, range_writes_per_txn=2,
        key_limbs=2, hash_table_bits=12, range_ring_capacity=32,
        coarse_buckets_bits=6,
    )
    r = Resolver(knobs)
    # 3 point writes > pw cap 2: k3 spills into the ring lanes
    t1 = TxnRequest(read_version=10, point_writes=[b"k1", b"k2", b"k3"])
    assert r.resolve([t1], 20, 0) == [COMMITTED]
    assert r._range_history  # spill = ring history; fast path is done
    # pure point read of the SPILLED key at an old read version
    t2 = TxnRequest(read_version=15, point_reads=[b"k3"])
    assert r.resolve([t2], 30, 0) == [CONFLICT]


def test_resolve_many_matches_sequential():
    """resolve_many (backlog scan dispatch) must produce the exact
    statuses AND leave the same history as sequential resolve calls."""
    from foundationdb_tpu.core.options import Knobs
    from foundationdb_tpu.resolver.resolver import Resolver

    knobs = Knobs(
        resolver_backend="tpu", batch_txn_capacity=8, point_reads_per_txn=2,
        point_writes_per_txn=2, range_reads_per_txn=2, range_writes_per_txn=2,
        key_limbs=2, hash_table_bits=12, range_ring_capacity=32,
        coarse_buckets_bits=6,
    )
    rng = random.Random(21)
    version = 100

    def make_batches():
        nonlocal version
        out = []
        for _ in range(7):  # odd count: exercises power-of-two padding
            n = rng.randrange(1, 8)
            txns = []
            for _ in range(n):
                t = rand_txn(rng, 20, version - rng.randrange(0, 15))
                if rng.random() < 0.3:
                    a, b = sorted([b"k%04d" % rng.randrange(20),
                                   b"k%04d" % rng.randrange(20)])
                    t.range_writes.append((a, b + b"\xff"))
                txns.append(t)
            version += rng.randrange(1, 6)
            out.append((txns, version, max(0, version - 50)))
        return out

    batches = make_batches()
    seq = Resolver(knobs)
    seq_statuses = [seq.resolve(t, cv, ws) for t, cv, ws in batches]
    many = Resolver(knobs)
    many_statuses = many.resolve_many(batches)
    assert many_statuses == seq_statuses
    # history equivalence: a follow-up batch resolves identically
    version += 3
    follow = ([rand_txn(rng, 20, version - 5) for _ in range(5)],
              version, max(0, version - 50))
    assert (seq.resolve(*follow) == many.resolve(*follow))


def test_resolve_many_point_only_uses_fast_variant():
    from foundationdb_tpu.core.options import Knobs
    from foundationdb_tpu.resolver.resolver import Resolver

    knobs = Knobs(
        resolver_backend="tpu", batch_txn_capacity=8, point_reads_per_txn=2,
        point_writes_per_txn=2, range_reads_per_txn=1, range_writes_per_txn=1,
        key_limbs=2, hash_table_bits=12, range_ring_capacity=16,
        coarse_buckets_bits=6,
    )
    r = Resolver(knobs)
    batches = [
        ([TxnRequest(read_version=10, point_writes=[b"a%d" % i])], 20 + i, 0)
        for i in range(3)
    ]
    out = r.resolve_many(batches)
    assert out == [[COMMITTED]] * 3
    assert (False, 8) not in r._scan_fns  # fixed B=8 bucket, fast variant
    assert (True, 8) in r._scan_fns
    # writes recorded: an old point read of a1 through resolve() conflicts
    assert r.resolve(
        [TxnRequest(read_version=15, point_reads=[b"a1"])], 40, 0
    ) == [CONFLICT]


def test_resolve_many_chunks_oversized_backlog():
    """A backlog deeper than BACKLOG_B chunks into BACKLOG_B-wide scan
    dispatches (never per-batch round trips) and still matches
    sequential resolution exactly."""
    from foundationdb_tpu.core.options import Knobs
    from foundationdb_tpu.resolver.resolver import BACKLOG_B, Resolver

    knobs = Knobs(
        resolver_backend="tpu", batch_txn_capacity=8, point_reads_per_txn=2,
        point_writes_per_txn=2, range_reads_per_txn=2, range_writes_per_txn=2,
        key_limbs=2, hash_table_bits=12, range_ring_capacity=32,
        coarse_buckets_bits=6,
    )
    rng = random.Random(77)
    version = 100
    batches = []
    for _ in range(BACKLOG_B * 2 + 3):  # 19: two full chunks + remainder
        txns = [
            rand_txn(rng, 20, version - rng.randrange(0, 15))
            for _ in range(rng.randrange(1, 8))
        ]
        version += rng.randrange(1, 6)
        batches.append((txns, version, max(0, version - 60)))

    seq = Resolver(knobs)
    seq_statuses = [seq.resolve(t, cv, ws) for t, cv, ws in batches]
    many = Resolver(knobs)
    resolved = {"n": 0}
    orig = Resolver.resolve

    def counting_resolve(self, *a, **kw):
        resolved["n"] += 1
        return orig(self, *a, **kw)

    try:
        Resolver.resolve = counting_resolve
        many_statuses = many.resolve_many(batches)
    finally:
        Resolver.resolve = orig
    assert many_statuses == seq_statuses
    # the 3-batch remainder chunk may legitimately ride resolve() when
    # small, but the two full chunks must NOT have fallen back per-batch
    assert resolved["n"] <= 3


RING_KNOBS = dict(
    resolver_backend="tpu", batch_txn_capacity=8, point_reads_per_txn=2,
    point_writes_per_txn=2, range_reads_per_txn=1, range_writes_per_txn=1,
    key_limbs=2, hash_table_bits=12, range_ring_capacity=32,
    coarse_buckets_bits=6,
)


def _ring_resolver(mode, **over):
    from foundationdb_tpu.core.options import Knobs
    from foundationdb_tpu.resolver.resolver import Resolver

    return Resolver(Knobs(**dict(RING_KNOBS, **over), pallas_ring=mode))


def test_forced_lowering_error_lands_in_pallas_to_jit(monkeypatch):
    """A ring kernel that fails to build engages the fenced fallback:
    the in-flight batch answers TOO_OLD, the failure is counted once
    under the pallas_to_jit cause, the Pallas flag strips, and the
    resolver goes on resolving on the jnp lanes with the verdicts of
    the host oracle (resolver/skiplist.py)."""
    from foundationdb_tpu.ops import pallas_ring

    def boom(*a, **kw):
        raise NotImplementedError("forced mosaic lowering failure")

    monkeypatch.setattr(pallas_ring, "ring_hits", boom)
    r = _ring_resolver("on")
    assert r.params.use_pallas
    # a range write forces the FULL variant (the only one with Pallas)
    first = [TxnRequest(read_version=100, range_writes=[(b"a", b"b")])]
    assert r.resolve(first, 110, 0) == [TOO_OLD]
    assert not r.params.use_pallas
    assert r.profile.snapshot()["fallback_causes"]["pallas_to_jit"] == 1
    # fenced at the failed batch's commit version; from there on the
    # jnp lanes answer as the oracle does, range lanes included
    oracle = CpuConflictSet()
    oracle.window_start = 110
    later = [
        ([TxnRequest(read_version=110, point_writes=[b"hot"]),
          TxnRequest(read_version=110, range_writes=[(b"r0", b"r5")])],
         120, 0),
        ([TxnRequest(read_version=110, point_reads=[b"hot"]),
          TxnRequest(read_version=120, point_reads=[b"hot"]),
          TxnRequest(read_version=110, range_reads=[(b"r1", b"r2")]),
          TxnRequest(read_version=120, range_reads=[(b"r1", b"r2")]),
          TxnRequest(read_version=105, point_reads=[b"cold"])],
         130, 0),
    ]
    got = [r.resolve(*b) for b in later]
    assert got == [oracle.resolve(*b) for b in later]
    assert got[1] == [CONFLICT, COMMITTED, CONFLICT, COMMITTED, TOO_OLD]
    snap = r.profile.snapshot()
    assert snap["fallback_causes"]["pallas_to_jit"] == 1  # counted once
    assert "pallas_ring" not in snap["kernel_routes"]


def test_ring_overflow_conservative_direction():
    """Overflowing the version ring may only ever ABORT MORE (the
    evicted entries fall into the coarse lanes): a stale read
    overlapping an evicted range write must CONFLICT, and the ring
    kernel must match the jnp lanes exactly while doing so."""

    def run(mode):
        r = _ring_resolver(mode, range_ring_capacity=16)
        v = 100
        # 3 batches x 8 txns x 2 range writes = 48 ring entries >> 16
        for b in range(3):
            txns = [
                TxnRequest(
                    read_version=v,
                    range_writes=[
                        (b"w%02d" % (b * 16 + 2 * i),
                         b"w%02d" % (b * 16 + 2 * i + 1)),
                        (b"x%02d" % (b * 16 + 2 * i),
                         b"x%02d" % (b * 16 + 2 * i + 1)),
                    ],
                )
                for i in range(8)
            ]
            v += 5
            r.resolve(txns, v, 0)
        # stale reader overlapping the FIRST (long-evicted) write span
        stale = TxnRequest(read_version=100, range_reads=[(b"w00", b"w01")])
        fresh = TxnRequest(read_version=v, range_reads=[(b"w00", b"w01")])
        return r, r.resolve([stale, fresh], v + 5, 0)

    r_on, got_on = run("on")
    assert got_on == run("off")[1]
    assert got_on[0] == CONFLICT  # never a missed conflict
    assert got_on[1] == COMMITTED  # read version above every write
    snap = r_on.profile.snapshot()
    assert snap["kernel_routes"].get("pallas_ring", 0) > 0
    assert snap["fallback_causes"]["pallas_to_jit"] == 0


def test_scanned_backlog_never_engages_the_ring_kernel(monkeypatch):
    """``resolve_many`` scans a backlog on the jnp lanes whatever
    ``pallas_ring`` says: with the ring kernel made to fail, a backlog
    that carries ranges resolves, nothing falls back and every batch is
    counted under the ``jit`` route."""
    from foundationdb_tpu.ops import pallas_ring

    def boom(*a, **kw):
        raise NotImplementedError("the scan must not call the ring kernel")

    monkeypatch.setattr(pallas_ring, "ring_hits", boom)
    r = _ring_resolver("on")
    mk = lambda v: [TxnRequest(read_version=v, range_writes=[(b"a", b"b")]),
                    TxnRequest(read_version=v, range_reads=[(b"a", b"b")]),
                    TxnRequest(read_version=v, point_writes=[b"p"])]
    backlog = [(mk(100), 110, 0), (mk(105), 115, 0), (mk(115), 120, 0)]
    oracle = CpuConflictSet()
    assert r.resolve_many(backlog) == [oracle.resolve(*b) for b in backlog]
    assert r.params.use_pallas  # still requested for the single step
    snap = r.profile.snapshot()
    assert snap["kernel_routes"] == {"jit": len(backlog)}
    assert snap["fallback_causes"]["pallas_to_jit"] == 0


def test_partitioned_ring_serializability_and_liveness():
    """The bucket-partitioned ring (ring_partition_bits > 0): exact
    sub-ring checks for a query's end partitions, conservative
    per-partition max for middles, spanning writes folded to coarse —
    the hard invariant (never a missed conflict) must hold on mixed
    workloads with short AND wide ranges, and conflict-free workloads
    must still commit."""
    params_p = SMALL._replace(ring_partition_bits=2)  # 4 sub-rings of 4
    rng = random.Random(23)
    version = 100
    batches = []
    for _ in range(30):
        txns = []
        for _ in range(rng.randrange(1, SMALL.txns + 1)):
            t = rand_txn(rng, 30, version - rng.randrange(0, 20))
            roll = rng.random()
            if roll < 0.25:  # short span: single-partition fast path
                a = b"k%04d" % rng.randrange(30)
                t.range_writes.append((a, a + b"\x05"))
            elif roll < 0.4:  # wide span: spanning-write coarse path
                a, b = sorted([b"k%04d" % rng.randrange(30),
                               b"k%04d" % rng.randrange(30)])
                t.range_writes.append((a, b + b"\xff"))
            if rng.random() < 0.4:
                a, b = sorted([b"k%04d" % rng.randrange(30),
                               b"k%04d" % rng.randrange(30)])
                t.range_reads.append((a, b + b"\xff"))
            txns.append(t)
        version += rng.randrange(1, 8)
        batches.append((txns, version, max(0, version - 50)))
    statuses = run_batches(batches, params_p)
    exact_serializability_check(batches, statuses)
    flat = [s for b in statuses for s in b]
    assert flat.count(COMMITTED) > 0

    # point-only streams never touch the ring: the partitioned kernel
    # must be verdict-identical to the FLAT ring on them (both share
    # whatever conservative caveats the point lanes already have)
    rng2 = random.Random(5)
    v = 100
    pbatches = []
    for _ in range(10):
        txns = [rand_txn(rng2, 40, v - rng2.randrange(0, 10))
                for _ in range(rng2.randrange(1, SMALL.txns + 1))]
        v += rng2.randrange(1, 6)
        pbatches.append((txns, v, max(0, v - 40)))
    assert run_batches(pbatches, params_p) == run_batches(pbatches, SMALL)


def test_partitioned_ring_eviction_and_spanning_stay_conservative():
    """Sub-ring eviction folds to coarse; spanning writes never enter a
    sub-ring — reads conflicting with either must STILL be flagged."""
    params_p = SMALL._replace(ring_partition_bits=2)
    batches = []
    v = 10
    # flood one key's partition so early entries evict to coarse
    for i in range(40):
        a = b"k%04d" % (i % 4)
        batches.append(
            ([TxnRequest(read_version=v, range_writes=[(a, a + b"\x02")])],
             v + 5, 0)
        )
        v += 5
    old = TxnRequest(read_version=12, point_reads=[b"k0001"])
    batches.append(([old], v + 5, 0))
    got = run_batches(batches, params_p)
    assert got[-1] == [CONFLICT]

    # a spanning write (wide clear) committed at cv=20 vs a reader whose
    # read version 15 PRECEDES it: the spanning entry lives only in the
    # coarse summaries, which must still flag the conflict
    batches2 = [
        ([TxnRequest(read_version=10,
                     range_writes=[(b"k0000", b"k0029\xff")])], 20, 0),
        ([TxnRequest(read_version=15, point_reads=[b"k0015"])], 30, 0),
    ]
    got2 = run_batches(batches2, params_p)
    assert got2[1] == [CONFLICT]


def test_partitioned_ring_under_scan_and_resolver():
    """The partitioned ring through the Resolver wrapper (knob) and the
    backlog scan path: verdicts match the flat ring sequential run on
    the same stream."""
    from foundationdb_tpu.core.options import Knobs
    from foundationdb_tpu.resolver.resolver import Resolver

    base = dict(
        resolver_backend="tpu", batch_txn_capacity=8, point_reads_per_txn=2,
        point_writes_per_txn=2, range_reads_per_txn=2, range_writes_per_txn=2,
        key_limbs=2, hash_table_bits=12, range_ring_capacity=32,
        coarse_buckets_bits=6,
    )
    rng = random.Random(31)
    version = 100
    batches = []
    for _ in range(9):
        txns = []
        for _ in range(rng.randrange(1, 8)):
            t = rand_txn(rng, 20, version - rng.randrange(0, 15))
            if rng.random() < 0.4:
                a = b"k%04d" % rng.randrange(20)
                t.range_writes.append((a, a + b"\x03"))
            txns.append(t)
        version += rng.randrange(1, 6)
        batches.append((txns, version, max(0, version - 50)))

    flat = Resolver(Knobs(**base))
    flat_statuses = [flat.resolve(t, cv, ws) for t, cv, ws in batches]
    part = Resolver(Knobs(ring_partition_bits=2, **base))
    part_statuses = part.resolve_many(batches)  # scan path, chunked
    # NOTE: not verdict-equality with the flat ring — all test keys
    # share one coarse bucket, so every range write lands in ONE
    # sub-ring (capacity KR/4) whose earlier evictions fold to coarse
    # and legally add conservative conflicts (which then legally flip
    # later intra-stream verdicts either way). The HARD contracts:
    # serializability (never a missed conflict) and liveness.
    exact_serializability_check(batches, flat_statuses)
    exact_serializability_check(batches, part_statuses)
    assert any(s == COMMITTED for b in part_statuses for s in b)
