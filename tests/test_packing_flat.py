"""Flat columnar commit packing (core/flatpack.py) — differential
parity against the legacy path.

The contract under test: for every batch the flat lane agrees to serve,
``BatchPacker.pack_flat(_group)`` produces BIT-IDENTICAL arrays to the
legacy ``pack``/``pack_empty``+stack route, and the native backend's
``resolve_flat`` returns the same statuses as legacy resolution; any
batch the flat lane can't serve (over-capacity keys, lane overflow,
too-old read versions) falls back to legacy with identical results.
"""

import numpy as np
import pytest

import jax

from foundationdb_tpu.core import flatpack
from foundationdb_tpu.core.commit import CommitRequest
from foundationdb_tpu.core.options import Knobs
from foundationdb_tpu.native import native_available
from foundationdb_tpu.resolver.packing import BatchPacker
from foundationdb_tpu.resolver.resolver import Resolver, params_from_knobs
from foundationdb_tpu.resolver.skiplist import CpuConflictSet, TxnRequest

from conftest import TEST_KNOBS

KNOBS = Knobs(**TEST_KNOBS)
L = KNOBS.key_limbs  # capacity 4*L = 16 bytes


def _req(rv, rcr, wcr, idmp=None):
    return CommitRequest(
        rv, [], rcr, wcr, idempotency_id=idmp,
        flat_conflicts=flatpack.encode_conflicts(rcr, wcr, L),
    )


def _legacy_txn(r):
    """The proxy's legacy split (point = [k, k+\\x00))."""
    def split(ranges):
        pts, rgs = [], []
        for b, e in ranges:
            if len(e) == len(b) + 1 and e[-1] == 0 and e.startswith(b):
                pts.append(b)
            else:
                rgs.append((b, e))
        return pts, rgs

    pr, rr = split(r.read_conflict_ranges)
    pw, rw = split(r.write_conflict_ranges)
    return TxnRequest(read_version=r.read_version, point_reads=pr,
                      point_writes=pw, range_reads=rr, range_writes=rw)


# the differential fixtures the ISSUE names: point-only, range-only,
# mixed, empty-batch (plus oversize cases further down)
POINT_ONLY = [
    _req(5, [(b"a", b"a\x00")], [(b"b", b"b\x00")]),
    _req(6, [], [(b"ab", b"ab\x00"), (b"cd", b"cd\x00")]),
]
RANGE_ONLY = [
    _req(5, [(b"a", b"c")], [(b"d", b"e")]),
    _req(7, [(b"", b"\xff")], [(b"x", b"x\xff\xff")]),
]
MIXED = [
    _req(5, [(b"a", b"a\x00"), (b"m", b"q")], [(b"b", b"b\x00")]),
    _req(6, [], []),
    _req(8, [(b"k" * 16, b"k" * 15 + b"l")], [(b"z", b"z\x00")]),
]
EMPTY = []


def _assert_batches_equal(a, b):
    for name in a._fields:
        av, bv = np.asarray(getattr(a, name)), np.asarray(getattr(b, name))
        assert av.dtype == bv.dtype, (name, av.dtype, bv.dtype)
        assert np.array_equal(av, bv), name


@pytest.mark.parametrize("reqs", [POINT_ONLY, RANGE_ONLY, MIXED, EMPTY],
                         ids=["point", "range", "mixed", "empty"])
def test_pack_flat_bit_identical_single_batch(reqs):
    packer = BatchPacker(params_from_knobs(KNOBS))
    flat = flatpack.build_flat_batch(reqs, L)
    assert flat is not None and packer.flat_fits(flat)
    legacy = packer.pack([_legacy_txn(r) for r in reqs], 0, 30, 7)
    flatb = packer.pack_flat(flat, 0, 30, 7)
    _assert_batches_equal(legacy, flatb)


def test_pack_flat_group_matches_stacked_legacy_with_pads():
    """Backlog-pad groups: a 3-batch group padded to B=8 must equal the
    legacy per-batch pack + pack_empty pads + np.stack, bitwise."""
    packer = BatchPacker(params_from_knobs(KNOBS))
    groups = [POINT_ONLY, MIXED, EMPTY]
    metas = [(30, 7), (31, 7), (32, 8)]
    legacy = [
        packer.pack([_legacy_txn(r) for r in reqs], 0, cv, ws)
        for reqs, (cv, ws) in zip(groups, metas)
    ]
    pad = packer.pack_empty(0, 32, 8)
    legacy.extend([pad] * (8 - len(legacy)))
    stacked_legacy = jax.tree.map(lambda *xs: np.stack(xs), *legacy)
    flats = [flatpack.build_flat_batch(reqs, L) for reqs in groups]
    stacked_flat = packer.pack_flat_group(flats, metas, 0, B=8)
    _assert_batches_equal(stacked_legacy, stacked_flat)


def test_pack_flat_staging_reuse_is_clean():
    """A reused staging slot must show no trace of the previous group
    (dirty slots were the whole risk of buffer reuse)."""
    packer = BatchPacker(params_from_knobs(KNOBS))
    big = flatpack.build_flat_batch(MIXED, L)
    small = flatpack.build_flat_batch(POINT_ONLY, L)
    for _ in range(packer.STAGING_RING):  # force a full ring cycle
        packer.pack_flat_group([big, big], [(30, 7), (31, 7)], 0, B=4)
    reused = packer.pack_flat_group([small], [(40, 9)], 0, B=4)
    legacy = [packer.pack([_legacy_txn(r) for r in POINT_ONLY], 0, 40, 9)]
    legacy.extend([packer.pack_empty(0, 40, 9)] * 3)
    _assert_batches_equal(
        jax.tree.map(lambda *xs: np.stack(xs), *legacy), reused
    )
    assert packer.flat_reuse_hits > 0


# ── staging reuse: the pack resets and writes only live rows ─────────
# One packer driven through more than 2 × STAGING_RING packs, so every
# set of the ring is reused at least once after holding something else;
# each result must equal a fresh packer's and the legacy pack + stack.
DEFAULT_KNOBS = Knobs()  # T = 1024: the served deployment's shapes
_COUNTS = [1024, 9, 0, 300, 1]  # as after the bulk load: shrink and grow
_KINDS = ["point", "range", "mixed", "empty"]


def _gen(kind, n, salt, limbs):
    """n transactions of one kind (``empty``: none), keys unique to
    ``salt`` so a row left over from another pack cannot pass."""
    def req(i):
        k = b"%c%03d%04d" % (97 + salt % 26, salt, i)
        pts = [(k, k + b"\x00")]
        rgs = [(k + b"a", k + b"q")]
        want_pt = kind == "point" or (kind == "mixed" and i % 3 != 1)
        want_rg = kind == "range" or (kind == "mixed" and i % 3 != 0)
        rcr = (pts if want_pt else []) + (rgs if want_rg else [])
        wcr = ([(k + b"w", k + b"w\x00")] if want_pt else []) + (
            [(k + b"r", k + b"s")] if want_rg and i % 2 else [])
        return CommitRequest(
            5 + i % 7, [], rcr, wcr,
            flat_conflicts=flatpack.encode_conflicts(rcr, wcr, limbs))

    return [] if kind == "empty" else [req(i) for i in range(n)]


def _schedule(case):
    """(params, B, steps); a step is the [(kind, n)] of its live
    batches. Every schedule is longer than 2 × STAGING_RING."""
    full = params_from_knobs(DEFAULT_KNOBS)
    if case == "counts":
        return full, 1, [[("mixed", n)] for n in _COUNTS * 2]
    if case == "counts-point-only":
        from foundationdb_tpu.resolver.resolver import fast_params_of

        return fast_params_of(full), 1, [
            [("point", n)] for n in _COUNTS * 2]
    if case == "sides":
        return full, 1, [[(k, 9)] for k in _KINDS * 3]
    if case == "sides-small":
        return params_from_knobs(KNOBS), 1, [
            [(k, n)] for k in _KINDS for n in (16, 3, 1)]
    nb = {"group-nb1": [1], "group-nb3": [3], "group-nb8": [8],
          "group-nb-varies": [8, 1, 3]}[case]
    steps = []
    for s in range(10):
        n = nb[s % len(nb)]
        steps.append([
            (_KINDS[(s + g) % 4], _COUNTS[(s + 2 * g) % 5])
            for g in range(n)])
    return full, 8, steps


@pytest.mark.parametrize("case", [
    "counts", "counts-point-only", "sides", "sides-small",
    "group-nb1", "group-nb3", "group-nb8", "group-nb-varies",
])
def test_pack_flat_reuse_follows_live_rows_bit_identical(case):
    params, B, steps = _schedule(case)
    assert len(steps) > 2 * BatchPacker.STAGING_RING
    limbs = params.key_width - 1
    shared = BatchPacker(params)
    base = 3
    for s, step in enumerate(steps):
        groups = [_gen(kind, n, 10 * s + g, limbs)
                  for g, (kind, n) in enumerate(step)]
        metas = [(30 + 10 * s + g, 7 + s) for g in range(len(groups))]
        flats = [flatpack.build_flat_batch(reqs, limbs) for reqs in groups]
        assert all(shared.flat_fits(f) for f in flats)
        got = shared.pack_flat_group(flats, metas, base, B=B)
        fresh = BatchPacker(params).pack_flat_group(flats, metas, base, B=B)
        _assert_batches_equal(fresh, got)
        legacy = [
            shared.pack([_legacy_txn(r) for r in reqs], base, cv, ws)
            for reqs, (cv, ws) in zip(groups, metas)
        ]
        legacy.extend(
            [shared.pack_empty(base, *metas[-1])] * (B - len(legacy)))
        _assert_batches_equal(
            jax.tree.map(lambda *xs: np.stack(xs), *legacy), got)
    assert shared.flat_reuse_hits == len(steps) - shared.STAGING_RING


def test_warm_pack_flat_takes_its_ramps_from_the_cached_iota(monkeypatch):
    """``np.arange`` gives the interpreter lock up at any size (PERF.md,
    PR 29: on the served dispatch thread each give-up is a wait behind
    the request threads), so a pack calls it only to grow the cache."""
    from foundationdb_tpu.resolver import packing

    packer = BatchPacker(params_from_knobs(DEFAULT_KNOBS))
    limbs = packer.params.key_width - 1
    flats = [flatpack.build_flat_batch(_gen("mixed", n, n, limbs), limbs)
             for n in (9, 300, 1024)]
    calls = []
    real = np.arange
    monkeypatch.setattr(
        packing.np, "arange",
        lambda *a, **k: calls.append(a) or real(*a, **k))
    for f in flats:
        packer.pack_flat(f, 0, 30, 7)
    packer.pack_flat_group(flats, [(30, 7)] * 3, 0, B=8)
    assert calls == []
    # a batch beyond the cached ramp grows it once, not once a pack
    small = params_from_knobs(KNOBS)
    many = flatpack.build_flat_batch(_gen("point", 16, 1, L), L)
    want = BatchPacker(small).pack_flat(many, 0, 30, 7)
    grown = BatchPacker(small)
    grown._iota = real(2)
    del calls[:]
    for _ in range(3):
        _assert_batches_equal(want, grown.pack_flat(many, 0, 30, 7))
    assert len(calls) == 1 and len(grown._iota) == 32


def test_encode_conflicts_rejects_over_capacity_keys():
    cap = 4 * L
    assert flatpack.encode_conflicts(
        [(b"k" * (cap + 1), b"k" * (cap + 1) + b"\x00")], [], L
    ) is None
    assert flatpack.encode_conflicts(
        [], [(b"a", b"z" * (cap + 1))], L
    ) is None
    # exactly-capacity keys flatten fine (the length word supplies the
    # point end's \x00)
    f = flatpack.encode_conflicts(
        [(b"k" * cap, b"k" * cap + b"\x00")], [], L
    )
    assert f is not None and f.read_points == 1


# ── a capacity-length key's successor as a range end ────────────────
# (a get_range cut short by its limit records [first, last + \x00): for
# mako's 32-byte keys at 8 limbs that end is one byte past capacity)
def _succ_case(limbs):
    cap = 4 * limbs
    k = b"k" * cap
    return cap, k, [(b"a", k + b"\x00")]


@pytest.mark.parametrize("limbs", [L, 8])
@pytest.mark.parametrize("side", ["read", "write"])
def test_encode_conflicts_takes_a_capacity_keys_successor_as_a_range_end(
        limbs, side):
    cap, k, rng = _succ_case(limbs)
    f = flatpack.encode_conflicts(
        rng if side == "read" else [], rng if side == "write" else [], limbs)
    assert f is not None
    n, blob = ((f.read_ranges, f.read_range_blob) if side == "read"
               else (f.write_ranges, f.write_range_blob))
    w = flatpack.entry_width(limbs)
    assert n == 1 and len(blob) == 2 * w
    assert blob[w:] == k + (cap + 1).to_bytes(4, "big")
    assert f.read_points == f.write_points == 0


@pytest.mark.parametrize("shape", ["begin_over", "end_no_nul", "end_over2"])
@pytest.mark.parametrize("side", ["read", "write"])
def test_encode_conflicts_still_refuses_other_over_capacity_ranges(
        shape, side):
    """A begin past capacity, an end past it that does not end in
    ``\x00``, and an end two bytes past it stay on the legacy route."""
    cap = 4 * L
    k = b"k" * cap
    rng = {"begin_over": (k + b"\x00", k + b"\x01"),
           "end_no_nul": (b"a", k + b"\x01"),
           "end_over2": (b"a", k + b"\x00\x00")}[shape]
    assert flatpack.encode_conflicts(
        [rng] if side == "read" else [], [rng] if side == "write" else [],
        L) is None


@pytest.mark.parametrize("limbs", [L, 8])
def test_a_successor_end_decodes_back_exactly(limbs):
    """FlatTxnBatch[i], decode_side and heatmap.entry_key give back
    ``k + b"\x00"``, byte for byte."""
    from foundationdb_tpu.utils import heatmap

    cap, k, rng = _succ_case(limbs)
    point = (b"p", b"p\x00")
    reqs = [CommitRequest(5, [], [point] + rng, rng,
                          flat_conflicts=flatpack.encode_conflicts(
                              [point] + rng, rng, limbs)),
            CommitRequest(6, [], rng, [],
                          flat_conflicts=flatpack.encode_conflicts(
                              rng, [], limbs))]
    flat = flatpack.build_flat_batch(reqs, limbs)
    for i in range(2):
        assert flat[i].range_reads == rng
    assert flat[0].point_reads == [b"p"] and flat[0].range_writes == rng
    f = reqs[0].flat_conflicts
    assert flatpack.decode_side(f.read_point_blob, f.read_range_blob,
                                limbs) == [point] + rng
    w = flatpack.entry_width(limbs)
    assert heatmap.entry_key(f.read_range_blob[w:]) == k + b"\x00"
    # the scheduler's decode of a mixed-width batch reads it the same way
    from foundationdb_tpu.server import scheduler

    assert scheduler._entries_ranges(f.read_range_blob, limbs) == rng


@pytest.mark.parametrize("limbs", [L, 8])
def test_a_successor_entry_sorts_between_its_key_and_the_next(limbs):
    """entry(k) < entry(k + \x00) < entry(next capacity-length key), as
    raw bytes (the scheduler's order) and as limb rows (the packer's and
    the router's): and every key of at most C bytes sorts against the
    successor exactly as its bytes do."""
    from foundationdb_tpu.resolver.packing import _rows_sortable

    cap = 4 * limbs
    k = b"m" * (cap - 2) + b"\x07\x00"
    nxt = b"m" * (cap - 2) + b"\x07\x01"
    f = flatpack.encode_conflicts([(b"a", k + b"\x00")], [], limbs)
    succ = f.read_range_blob[flatpack.entry_width(limbs):]
    hi = flatpack.point_limbs(f.read_range_blob, limbs)[1::2]
    probes = [k, nxt, k[:-1], k[:-2], k[:-1] + b"\x01", b"m" * (cap - 2),
              b"m" * (cap - 2) + b"\x08", b"", b"\xff" * cap]
    for x in probes:
        ex = flatpack.encode_entry(x, limbs)
        want = x < k + b"\x00"
        assert (ex < succ) == want, x
        row = flatpack.point_limbs(ex, limbs)
        assert (_rows_sortable(row)[0] < _rows_sortable(hi)[0]) == want, x
    assert flatpack.encode_entry(k, limbs) < succ \
        < flatpack.encode_entry(nxt, limbs)


def test_a_successor_end_spans_the_lanes_the_legacy_end_spans():
    """A four-lane router's span of a range that ends at a key's
    successor: it reaches the lane whose bound IS that key and no
    further, under the flat entry and under the legacy prefix successor
    alike."""
    from foundationdb_tpu.core.keys import KeyCodec
    from foundationdb_tpu.resolver.packing import ShardRouter

    params = params_from_knobs(Knobs(**{**TEST_KNOBS, "key_limbs": 8}))
    codec = KeyCodec(8)

    def key(i):
        return b"mako%028d" % i

    router = ShardRouter(params, 4, bounds=codec.encode_lower_batch(
        [key(150), key(301), key(450)]))
    for first, last, lanes in ((100, 149, (0, 0)), (100, 150, (0, 1)),
                               (150, 300, (1, 1)), (151, 301, (1, 2)),
                               (0, 599, (0, 3))):
        rng = (key(first), key(last) + b"\x00")
        f = flatpack.encode_conflicts([rng], [], 8)
        rows = flatpack.point_limbs(f.read_range_blob, 8)
        lo, hi = rows[0::2], rows[1::2]
        got = router.lane_span(lo, hi)
        assert (int(got[0][0]), int(got[1][0])) == lanes, rng
        legacy = router.lane_span(codec.encode_lower_batch([rng[0]]),
                                  codec.encode_upper(rng[1])[None])
        assert (int(legacy[0][0]), int(legacy[1][0])) == lanes, rng


def test_a_request_without_conflict_ranges_joins_a_flat_batch():
    """A commit the cluster makes itself (the consistency scan's cursor)
    carries no blobs and no conflict range: it is an empty entry, not a
    reason to build the whole batch legacy. One with ranges and no blobs
    still is."""
    system = CommitRequest(9, [], [], [])
    assert system.flat_conflicts is None
    reqs = MIXED + [system]
    flat = flatpack.build_flat_batch(reqs, L)
    assert flat is not None and len(flat) == len(reqs)
    assert flat.rv[-1] == 9
    assert (flat.prc[-1], flat.pwc[-1], flat.rrc[-1], flat.rwc[-1]) == (
        0, 0, 0, 0)
    packer = BatchPacker(params_from_knobs(KNOBS))
    _assert_batches_equal(
        packer.pack([_legacy_txn(r) for r in reqs], 0, 30, 7),
        packer.pack_flat(flat, 0, 30, 7))
    # and beside an id-carrying request, on the per-request build
    idk = b"\xff\x02/i"
    with_id = flatpack.build_flat_batch(
        [_req(5, [], [(b"a", b"a\x00")], idmp=b"x"), system], L,
        lambda r: idk if r.idempotency_id is not None else None)
    assert list(with_id.pwc) == [2, 0] and list(with_id.prc) == [1, 0]
    unencoded = CommitRequest(9, [], [(b"a", b"b")], [])
    assert flatpack.build_flat_batch(MIXED + [unencoded], L) is None


def test_flat_decode_roundtrip():
    flat = flatpack.build_flat_batch(MIXED, L)
    for i, r in enumerate(MIXED):
        t = flat[i]
        oracle = _legacy_txn(r)
        assert t.read_version == r.read_version
        assert list(t.point_reads) == list(oracle.point_reads)
        assert list(t.point_writes) == list(oracle.point_writes)
        assert list(t.range_reads) == list(oracle.range_reads)
        assert list(t.range_writes) == list(oracle.range_writes)


def _statuses_oracle(batches):
    cset = CpuConflictSet()
    return [
        cset.resolve([_legacy_txn(r) for r in reqs], cv, ws)
        for reqs, cv, ws in batches
    ]


def _contended(rv_new):
    """Point/range/mixed traffic where later batches genuinely conflict
    with earlier writes."""
    return [
        (POINT_ONLY + MIXED, 30, 7),
        ([
            _req(rv_new, [(b"b", b"b\x00")], [(b"q", b"q\x00")]),  # pt cfl
            _req(rv_new, [(b"c", b"f")], []),                # range clear
            _req(rv_new, [(b"d", b"e")], []),                # vs MIXED rw?
            _req(2, [(b"nn", b"nn\x00")], []),               # too old
        ], 40, 9),
    ]


@pytest.mark.skipif(not native_available(), reason="no native toolchain")
def test_native_resolve_flat_matches_legacy():
    from foundationdb_tpu.native import NativeConflictSet

    batches = _contended(rv_new=31)
    oracle = _statuses_oracle(batches)
    flat_set = NativeConflictSet()
    got = [
        flat_set.resolve_flat(flatpack.build_flat_batch(reqs, L), cv, ws)
        for reqs, cv, ws in batches
    ]
    assert got == oracle
    legacy_set = NativeConflictSet()
    got_legacy = [
        legacy_set.resolve([_legacy_txn(r) for r in reqs], cv, ws)
        for reqs, cv, ws in batches
    ]
    assert got_legacy == oracle


def test_tpu_backend_flat_statuses_match_legacy():
    """Resolver(tpu) fed FlatTxnBatches — via resolve and the scanned
    resolve_many — agrees with a twin fed legacy TxnRequests."""
    flat_r = Resolver(KNOBS)
    legacy_r = Resolver(KNOBS)
    batches = _contended(rv_new=31)
    flat_handle = flat_r.resolve_many([
        (flatpack.build_flat_batch(reqs, L), cv, ws)
        for reqs, cv, ws in batches
    ])
    legacy_handle = legacy_r.resolve_many([
        ([_legacy_txn(r) for r in reqs], cv, ws)
        for reqs, cv, ws in batches
    ])
    assert flat_handle == legacy_handle
    # single-batch path too (the sync commit_batch route)
    single = [_req(40, [(b"b", b"b\x00")], [])]
    assert flat_r.resolve(flatpack.build_flat_batch(single, L), 50, 10) \
        == legacy_r.resolve([_legacy_txn(r) for r in single], 50, 10)


def test_lane_overflow_falls_back_to_legacy_same_statuses():
    """A txn with more ops than the packed lanes: flat_fits refuses,
    the resolver decodes to TxnRequests, and _normalize's spill path
    produces the same verdicts as feeding legacy directly."""
    cap = KNOBS.point_writes_per_txn
    many = [
        _req(5, [], [(b"k%02d" % i, b"k%02d\x00" % i)
                     for i in range(cap + 3)])
    ]
    flat = flatpack.build_flat_batch(many, L)
    packer = BatchPacker(params_from_knobs(KNOBS))
    assert not packer.flat_fits(flat)
    flat_r = Resolver(KNOBS)
    legacy_r = Resolver(KNOBS)
    assert flat_r.resolve(flat, 30, 7) \
        == legacy_r.resolve([_legacy_txn(r) for r in many], 30, 7)
    # the spilled writes are real history on both resolvers
    probe = [_req(6, [(b"k%02d" % (cap + 2), b"k%02d\x00" % (cap + 2))],
                  [])]
    assert flat_r.resolve(flatpack.build_flat_batch(probe, L), 40, 8) \
        == legacy_r.resolve([_legacy_txn(r) for r in probe], 40, 8)


@pytest.mark.parametrize("backend", ["tpu", "native", "cpu"])
def test_cluster_flat_vs_legacy_commit_parity(backend):
    """End to end through a live cluster: the same workload under
    commit_pack_path=flat and =legacy commits the same rows, and the
    pack-path counters prove which lane ran."""
    if backend == "native" and not native_available():
        pytest.skip("no native toolchain")
    from foundationdb_tpu.server.cluster import Cluster

    finals = {}
    for path in ("flat", "legacy"):
        c = Cluster(resolver_backend=backend, commit_pack_path=path,
                    **TEST_KNOBS)
        try:
            db = c.database()
            for i in range(12):
                tr = db.create_transaction()
                if i % 3 == 0:
                    tr.get(b"row%02d" % ((i + 1) % 12))
                tr.set(b"row%02d" % i, b"v%d" % i)
                if i % 4 == 0:
                    tr.clear_range(b"tmp", b"tmq")
                tr.commit()
            finals[path] = db.get_range(b"", b"\xff")
            proxy = c.commit_proxy
            inner = getattr(proxy, "inner", proxy)
            if path == "flat" and backend in ("tpu", "native"):
                assert inner.pack_flat_batches > 0
                assert inner.pack_legacy_batches == 0
            else:
                assert inner.pack_flat_batches == 0
        finally:
            c.close()
    assert finals["flat"] == finals["legacy"]


def test_idempotency_id_rides_flat_path():
    """An id-carrying request packs its idmp system row into the flat
    point lanes exactly like legacy _idmp_point — and the proxy dedupe
    still answers a resubmit the original version."""
    from foundationdb_tpu.server.cluster import Cluster

    # key_limbs=8: the idmp system row (\xff\x02/idmp/ + id) must fit
    # the limb capacity or the batch honestly rides legacy
    knobs = dict(TEST_KNOBS, key_limbs=8)
    idmp_L = 8
    c = Cluster(resolver_backend="cpu" if not native_available()
                else "native", **knobs)
    try:
        db = c.database()
        tr = db.create_transaction()
        tr.options.set_idempotency_id(b"flat-idmp-1")
        tr.set(b"idk", b"v1")
        tr.commit()
        v1 = tr.get_committed_version()
        # resubmit the same id: the proxy's dedupe answers v1
        req = CommitRequest(
            None, [], [], [(b"idk", b"idk\x00")],
            idempotency_id=b"flat-idmp-1",
            flat_conflicts=flatpack.encode_conflicts(
                [], [(b"idk", b"idk\x00")], idmp_L),
        )
        got = c.commit_proxy.commit_batch([req])[0]
        assert got == v1
        inner = getattr(c.commit_proxy, "inner", c.commit_proxy)
        if inner.resolvers[0].accepts_flat:
            assert inner.pack_flat_batches > 0
    finally:
        c.close()


def test_wire_columnar_frame_roundtrip():
    from foundationdb_tpu.rpc import wire

    r = _req(9, [(b"a", b"a\x00"), (b"m", b"q")], [(b"b", b"b\x00")],
             idmp=b"tok")
    blob = wire.dumps(r)
    r2 = wire.loads(blob)
    assert r2.flat_conflicts == r.flat_conflicts
    assert r2.idempotency_id == b"tok"
    # lazy reconstruction from the blobs matches the original ranges
    assert sorted(r2.read_conflict_ranges) == sorted(r.read_conflict_ranges)
    assert sorted(r2.write_conflict_ranges) == sorted(r.write_conflict_ranges)
    # a request without flat blobs still takes the legacy 'R' frame
    plain = CommitRequest(3, [], [(b"x", b"y")], [])
    assert wire.loads(wire.dumps(plain)).read_conflict_ranges == [(b"x", b"y")]
