"""Shard-split parity fixtures (the single-dispatch sharded resolve).

The presharded mesh path (resolver/packing.ShardRouter routing each
packed entry to the lane(s) owning its key range, one shard_map
dispatch running ops/conflict.resolve_batch_presharded) must give
BIT-IDENTICAL verdicts to the paths it replaces:

- the dense single-lane resolve (ops/conflict.make_resolve_scan_fn),
  fixture-by-fixture at several lane counts;
- the legacy proxy clip fan-out (server/proxy._resolve clipping
  sub-batches per resolver and AND-ing verdicts), through two full
  clusters on a scripted contended history.

Chunked dispatches (router overflow, k > 1) are the one exception:
cross-slice pairs route through the bucket-granular coarse structures,
which is CONSERVATIVE — extra CONFLICTs allowed, lost conflicts never
(the same direction as the packer's range coalescing). Bit-parity is
asserted only on k == 1 workloads, the steady-state shape.
"""

import random

import jax
import numpy as np
import pytest

from foundationdb_tpu.core.errors import FDBError
from foundationdb_tpu.core.options import Knobs
from foundationdb_tpu.ops import conflict as ck
from foundationdb_tpu.parallel import mesh as pm
from foundationdb_tpu.resolver.packing import (
    BatchPacker,
    ShardRouter,
    fnv_hash_np,
)
from foundationdb_tpu.resolver.skiplist import TxnRequest
from foundationdb_tpu.server.cluster import Cluster

from conftest import TEST_KNOBS
from tests.test_resolver import plain_status

PARAMS = ck.ResolverParams(
    txns=16, point_reads=2, point_writes=2, range_reads=2,
    range_writes=2, key_width=5, hash_bits=14, ring_capacity=128,
    bucket_bits=8,
)


def _key(rng):
    # byte-uniform keys: every lane's key range actually gets traffic
    return int(rng.integers(2 ** 32)).to_bytes(4, "big")


def _rng_pair(rng):
    a = int(rng.integers(2 ** 32 - 4096))
    return (a.to_bytes(4, "big"),
            (a + int(rng.integers(1, 4096))).to_bytes(4, "big"))


def _fixture(kind, rng, n_txns=16):
    """One batch of TxnRequests for a named fixture shape."""
    txns = []
    for _ in range(n_txns):
        pr = pw = rr = rw = []
        if kind in ("point", "mixed"):
            pr = [_key(rng) for _ in range(int(rng.integers(0, 3)))]
            pw = [_key(rng) for _ in range(int(rng.integers(0, 3)))]
        if kind in ("range", "mixed"):
            rr = [_rng_pair(rng) for _ in range(int(rng.integers(0, 3)))]
            rw = [_rng_pair(rng) for _ in range(int(rng.integers(0, 3)))]
        txns.append(TxnRequest(
            read_version=int(rng.integers(1, 40)),
            point_reads=pr, point_writes=pw,
            range_reads=rr, range_writes=rw,
        ))
    if kind == "empty":
        txns = [TxnRequest(read_version=1) for _ in range(n_txns)]
    if kind == "backlog_pad":
        # live txns fill only part of the batch: the packer pads the
        # remaining slots with txn_mask False — those slots must stay
        # inert through the router and the presharded kernel alike
        txns = txns[: max(2, n_txns // 3)]
    return txns


FIXTURES = ("point", "range", "mixed", "empty", "backlog_pad")


@pytest.mark.parametrize("n_lanes", [2, 3, 8])
def test_presharded_kernel_bit_identical_to_dense(n_lanes):
    packer = BatchPacker(PARAMS, use_native=False)
    rng = np.random.default_rng(23)
    batches = []
    for i, kind in enumerate(FIXTURES):
        cv = 100 + 20 * i
        batches.append(
            packer.pack(_fixture(kind, rng), 0, cv, max(0, cv - 90)))
    stacked = ck.ResolveBatch(
        *(np.stack([getattr(b, f) for b in batches])
          for f in ck.ResolveBatch._fields))

    dense = ck.make_resolve_scan_fn(PARAMS, donate=False)
    _, st_ref = dense(ck.init_state(PARAMS), stacked)

    kern = pm.PreshardedResolverKernel(
        PARAMS, mesh=pm.default_mesh(n_lanes), donate=False)
    router = ShardRouter(PARAMS, n_lanes)
    packed, k, lane_counts = router.split(stacked)
    assert k == 1, "fixtures must not chunk (bit-parity is a k==1 claim)"
    _, st = kern._scan_step.call_packed(kern.state, packed)
    assert np.array_equal(np.asarray(st), np.asarray(st_ref))
    # the router actually spread work (not everything on one lane)
    assert np.count_nonzero(lane_counts) > 1


def test_presharded_statuses_stable_across_lane_counts():
    """The verdict must not depend on HOW MANY lanes served the batch
    (the reference's resolver-count-invariance contract)."""
    packer = BatchPacker(PARAMS, use_native=False)
    outs = {}
    for n in (1, 3, 8):
        rng = np.random.default_rng(71)  # same workload per lane count
        kern = pm.PreshardedResolverKernel(
            PARAMS, mesh=pm.default_mesh(n), donate=False)
        router = ShardRouter(PARAMS, n)
        state = kern.state
        got = []
        for i in range(4):
            cv = 50 + 10 * i
            b = packer.pack(_fixture("mixed", rng), 0, cv, 0)
            stacked = ck.ResolveBatch(
                *(np.asarray(getattr(b, f))[None]
                  for f in ck.ResolveBatch._fields))
            packed, k, _ = router.split(stacked, reuse=True)
            assert k == 1
            state, st = kern._scan_step.call_packed(state, packed)
            got.append(np.asarray(st)[0].tolist())
        outs[n] = got
    assert outs[1] == outs[3] == outs[8]


def test_chunked_overflow_is_conservative_only():
    """Forced router overflow (every key identical -> one lane owns
    everything, tiny headroom): the batch rides the scan as k slices.
    Cross-slice pairs go through the coarse structures — extra
    CONFLICTs allowed, but a dense-path conflict may NEVER come back
    COMMITTED (lost conflicts break serializability; extra ones only
    cost a retry)."""
    packer = BatchPacker(PARAMS, use_native=False)
    txns = [TxnRequest(read_version=1,
                       point_reads=[b"same"], point_writes=[b"same"],
                       range_reads=[(b"same", b"same2")],
                       range_writes=[(b"same", b"same2")])
            for _ in range(PARAMS.txns)]
    b0 = packer.pack(txns, 0, 50, 0)
    stacked = ck.ResolveBatch(
        *(np.asarray(getattr(b0, f))[None]
          for f in ck.ResolveBatch._fields))
    dense = ck.make_resolve_scan_fn(PARAMS, donate=False)
    _, st_ref = dense(ck.init_state(PARAMS), stacked)
    st_ref = np.asarray(st_ref)

    kern = pm.PreshardedResolverKernel(
        PARAMS, mesh=pm.default_mesh(8), donate=False)
    router = ShardRouter(PARAMS, 8, headroom=0.5)
    packed, k, _ = router.split(stacked)
    assert k > 1, "fixture must actually overflow into chunking"
    _, st = kern._scan_step.call_packed(kern.state, packed)
    st = plain_status(router.reassemble(st, k))
    st_ref = plain_status(st_ref)
    from foundationdb_tpu.core.status import COMMITTED, CONFLICT

    conservative = (st == st_ref) | (
        (st == CONFLICT) & (st_ref == COMMITTED))
    assert bool(np.all(conservative))


# ── the cell's own shape: default Knobs(), four lanes ──────────────────
# T = 1024, a lane takes 1,792 point and 896 range slots a side. The
# matrix of the presharded step comes from a [T, K] grid rebuilt on the
# device, so the inputs that would show a slot lost on the way are here.

CELL_LANES = 4


def _user(i):
    return b"user%08d" % i


def _one_hot_key():
    """(a) the cell's traffic: nine attempts at one key, one lane."""
    return [TxnRequest(read_version=5, point_reads=[_user(7)],
                       point_writes=[_user(7)]) for _ in range(9)]


def _full_lane():
    """(b) 448 txns × 4 fill all 1,792 point slots a side of the one
    lane every ``user…`` key has. 447 accepted writers put 1,788 slots
    on the last txn's four reads (a count kept in bf16, or in 8 bits,
    is wrong long before that); txn 200 dies by one pair alone, the
    K-th write of txn 100 against its own K-th read."""
    hot = [_user(i) for i in range(4)]
    fresh = iter(range(10, 10_000))
    txns = []
    for t in range(447):
        reads = [_user(next(fresh)) for _ in range(4)]
        writes = list(hot)
        if t == 100:
            writes[3] = b"user-kth"
        if t == 200:
            reads[3] = b"user-kth"
        txns.append(TxnRequest(read_version=5, point_reads=reads,
                               point_writes=writes))
    txns.append(TxnRequest(
        read_version=5, point_reads=hot,
        point_writes=[_user(next(fresh)) for _ in range(4)]))
    return txns


def _k_a_side_and_ranges_in_every_lane():
    """(c) K entries a side of one txn in one lane, ranges with a slot
    in all four lanes, and a kill through each of the four blocks."""
    everywhere = (b"\x00", b"\xff\xff")
    return [
        TxnRequest(read_version=5,
                   point_reads=[_user(i) for i in range(4)],
                   point_writes=[_user(i) for i in range(4, 8)],
                   range_reads=[(_user(100), _user(200)),
                                (_user(300), _user(400))],
                   range_writes=[(_user(500), _user(600)), everywhere]),
        TxnRequest(read_version=5, point_reads=[_user(7)]),   # pw × pr
        TxnRequest(read_version=5,
                   range_reads=[(_user(6), _user(9))]),       # pw × rr
        TxnRequest(read_version=5, point_reads=[b"\x90zz"]),  # rw × pr
        TxnRequest(read_version=5, range_reads=[everywhere]),  # rw × rr
        TxnRequest(read_version=5, point_reads=[b"\xff\xff\x01"],
                   point_writes=[b"\xff\xff\x01"]),           # untouched
    ]


def _padding_beside_a_live_txn_0():
    """(d) every padding slot names txn 0 with the all-zero key (the
    empty key's limbs) and mask False. Txn 1 reads the empty key: it
    dies if padding is taken for writes of the live txn 0."""
    return [TxnRequest(read_version=5, point_reads=[_user(1)],
                       point_writes=[_user(1)],
                       range_writes=[(_user(2), _user(3))]),
            TxnRequest(read_version=5, point_reads=[b""],
                       range_reads=[(b"", b"\x00")])]


CELL_CASES = {
    "one_hot_key": (_one_hot_key, [1]),
    "full_lane": (_full_lane, [446]),
    "k_a_side_ranges_everywhere": (_k_a_side_and_ranges_in_every_lane,
                                   [2]),
    "padding_beside_live_txn_0": (_padding_beside_a_live_txn_0, [2]),
}


@pytest.fixture(scope="module")
def cell_shape():
    from foundationdb_tpu.resolver.resolver import params_from_knobs

    params = params_from_knobs(Knobs())
    router = ShardRouter(params, CELL_LANES)
    assert (params.txns, router.caps["pr"], router.caps["rr"]) == (
        1024, 1792, 896)
    kern = pm.PreshardedResolverKernel(
        params, mesh=pm.default_mesh(CELL_LANES), donate=False)
    dense = ck.make_resolve_scan_fn(params, donate=False)
    return params, router, kern, dense


def _stack(params, txns, window=0):
    b = BatchPacker(params, use_native=False).pack(txns, 0, 10, window)
    return jax.tree.map(lambda a: np.asarray(a)[None], b)


@pytest.mark.parametrize("case", sorted(CELL_CASES))
def test_presharded_step_at_the_cells_shape_matches_dense(cell_shape, case):
    from foundationdb_tpu.core.status import COMMITTED

    params, router, kern, dense = cell_shape
    build, committed = CELL_CASES[case]
    txns = build()
    stacked = _stack(params, txns)
    _, st_ref = dense(ck.init_state(params), stacked)
    packed, k, lane_counts = router.split(stacked)
    assert k == 1
    if case == "full_lane":
        assert lane_counts.tolist() == [0, 2 * 1792, 0, 0]
    st, _, _ = kern._step.call_packed(
        kern.state, packed._replace(buf=packed.buf[0]))
    st, st_ref = np.asarray(st), np.asarray(st_ref)[0]
    assert np.array_equal(st, st_ref)
    # and the verdicts are the ones the case was built to draw
    assert committed == [int(np.sum(st[:len(txns)] == COMMITTED))]
    _, st_scan = kern._scan_step.call_packed(kern.state, packed)
    assert np.array_equal(np.asarray(st_scan)[0], st_ref)


def test_a_txn_with_more_than_k_slots_in_a_lane_loses_no_conflict(
        cell_shape):
    """No routed batch holds more than K slots a side of one txn in one
    lane; a ShardBatch that does still misses nothing. Txn 1's write of
    ``z`` is handed to txn 0 as its fifth: txn 2, which reads ``z``,
    must not commit."""
    from foundationdb_tpu.core.status import COMMITTED, CONFLICT

    params, router, kern, _ = cell_shape
    txns = [TxnRequest(read_version=5,
                       point_writes=[_user(i) for i in range(4)]),
            TxnRequest(read_version=5, point_writes=[b"user-z"]),
            TxnRequest(read_version=5, point_reads=[b"user-z"])]
    packed, k, _ = router.split(_stack(params, txns))
    single = jax.tree.map(lambda a: a[0], ck.unpack_args_host(packed))
    moved = single.pw_txn.copy()
    moved[(single.pw_txn == 1) & single.pw_mask] = 0
    st, _, _ = kern._step(kern.state, single._replace(pw_txn=moved))
    st = plain_status(st)
    assert st[0] == COMMITTED and st[2] == CONFLICT


def _scripted_outcomes(cluster, seed=13, steps=60):
    """A contended scripted history: interleaved writers + an aged
    reader committing every 8 steps. Returns (outcomes, final rows)."""
    rng = random.Random(seed)
    db = cluster.database()
    outcomes = []
    stale = None
    for step in range(steps):
        key = b"sk%03d" % rng.randrange(24)
        if stale is None:
            stale = db.create_transaction()
            stale.get(key)
            stale_key = key
        tr = db.create_transaction()
        if rng.random() < 0.6:
            tr.get(key)
            tr[key] = b"v%d" % step
        else:
            lo = b"sk%03d" % rng.randrange(24)
            list(tr.get_range(lo, lo + b"\xff"))
            tr.clear_range(lo, lo + b"\xff")
        tr.commit()
        if step % 8 == 7:
            stale[stale_key] = b"stale"
            try:
                stale.commit()
                outcomes.append("ok")
            except FDBError as e:
                outcomes.append(e.code)
            stale = None
    rows = db.run(lambda tr: list(tr.get_range(b"sk", b"sl")))
    return outcomes, rows


@pytest.mark.parametrize("legacy_backend", ["cpu", "native"])
def test_mesh_range_matches_legacy_clip_fleet(legacy_backend):
    """The single-dispatch sharded resolve vs the legacy clip fan-out
    (3 separate host resolvers behind the proxy's _resolve loop):
    identical outcomes and identical final state on the same scripted
    history."""
    if legacy_backend == "native":
        native = pytest.importorskip("foundationdb_tpu.native")
        if not native.native_available():
            pytest.skip("g++ toolchain unavailable")
    mesh = Cluster(n_resolvers=3, resolver_backend="tpu", **TEST_KNOBS)
    legacy = Cluster(n_resolvers=3, resolver_backend=legacy_backend,
                     **TEST_KNOBS)
    try:
        assert mesh.resolvers[0].sharding == "range"
        assert len(mesh.resolvers) == 1  # clip loop retired: ONE dispatch
        assert len(legacy.resolvers) == 3  # the host fan-out under test
        assert _scripted_outcomes(mesh) == _scripted_outcomes(legacy)
        # satellite instrument: BOTH paths filled the same lane-balance
        # rollup — the mesh at router split time, the legacy fleet at
        # the proxy's clip loop
        for c in (mesh, legacy):
            agg = c.device_profile_status()["aggregate"]
            assert len(agg["lane_entries"]) == 3
            assert sum(agg["lane_entries"]) > 0
            assert 0.0 <= agg["lane_skew_pct"] <= 100.0
    finally:
        mesh.close()
        legacy.close()


def test_sharded_to_local_fallback_fires_and_counts():
    """Asking for more lanes than the hardware hosts clamps the fleet
    and records the structured sharded_to_local cause — and the clamped
    resolver still resolves correctly."""
    from foundationdb_tpu.resolver.meshresolver import MeshResolver

    knobs = Knobs(batch_txn_capacity=16, hash_table_bits=12,
                  range_ring_capacity=64, coarse_buckets_bits=8,
                  key_limbs=4)
    r = MeshResolver(knobs, n_lanes=64)
    assert r.n_lanes == 8  # the 8-device conftest mesh
    snap = r.profile.snapshot()
    assert snap["fallback_causes"]["sharded_to_local"] == 64 - 8
    txns = [TxnRequest(read_version=1, point_writes=[b"k"]),
            TxnRequest(read_version=1, point_writes=[b"k"])]
    assert r.resolve(txns, 10, 0) == [0, 0]
    stale = [TxnRequest(read_version=5, point_reads=[b"k"],
                        point_writes=[b"k"])]
    assert r.resolve(stale, 20, 0) == [1]


def _sim_run(seed, datadir):
    from foundationdb_tpu.sim.simulation import Simulation

    sim = Simulation(
        seed=seed, buggify=False, crash_p=0.0, n_resolvers=3,
        datadir=datadir, commit_pipeline="manual",
        resolver_backend="tpu", **TEST_KNOBS,
    )
    try:
        assert sim.cluster.resolvers[0].sharding == "range"
        rng = random.Random(seed)
        outcomes = []
        for i in range(30):
            k = b"d%02d" % rng.randrange(8)
            tr = sim.db.create_transaction()
            cur = tr.get(k)
            tr.set(k, str(int(cur or b"0") + 1).encode())
            try:
                tr.commit()
                outcomes.append("ok")
            except FDBError as e:
                outcomes.append(e.code)
        state = tuple(sim.db.get_range(b"d", b"e"))
        return outcomes, state
    finally:
        sim.close()
        from foundationdb_tpu.core import deterministic

        deterministic.unseed()


def test_same_seed_sim_deterministic_with_sharded_resolve(tmp_path):
    """Two same-seed sims with the presharded mesh resolve enabled
    replay byte-identically: the router's split order and the
    single-dispatch kernel draw no entropy (FL001/FL004)."""
    a = _sim_run(77, str(tmp_path / "a"))
    b = _sim_run(77, str(tmp_path / "b"))
    assert a == b
    assert a[1]  # the workload actually wrote state


# ── the argument the router writes in place, against a plain build ───
# ``ShardRouter.split`` writes each live entry straight into the rows
# the four-lane program takes, into a buffer it reuses. The reference
# here places every live entry by a loop, lane by lane in arrival order,
# into a ShardBatch, and packs that with ``pack_args``: the two must
# agree word for word, whatever the router wrote before.

def _lane_of(bounds, row):
    return sum(b <= row for b in bounds)


def _reference_rows(router, stacked):
    """(pack_args of the ShardBatch a loop over the live entries
    builds, its k)."""
    p, n, W = router.params, router.n, router.params.key_width
    B, T = stacked.rv.shape
    bounds = [tuple(b) for b in router.bounds.tolist()]
    sides = (("pr", False), ("pw", False), ("rr", True), ("rw", True))
    k = 1
    while True:
        Ts, slots = -(-T // k), {}
        for side, is_range in sides:
            mask = getattr(stacked, side + "_mask")
            for b, t, i in zip(*np.nonzero(mask)):
                if is_range:
                    begin = tuple(getattr(stacked, side + "_b")[b, t, i])
                    end = tuple(getattr(stacked, side + "_e")[b, t, i])
                    lo = _lane_of(bounds, begin)
                    hi = max(lo, sum(x < end for x in bounds))
                    lanes = range(lo, hi + 1)
                else:
                    key = tuple(getattr(stacked, side + "_key")[b, t, i])
                    lanes = [_lane_of(bounds, key)]
                for j in lanes:
                    slots.setdefault((side, b * k + t // Ts, j), []).append(
                        (b, t, i))
        if all(len(v) <= router.caps[s] for (s, _, _), v in slots.items()):
            break
        k *= 2
    rows = B * k
    zero_hash = fnv_hash_np(np.zeros((1, W), np.uint32))[0]
    f = {}
    for side, is_range in sides:
        nq = n * router.caps[side]
        f[side + "_txn"] = np.zeros((rows, nq), np.int32)
        f[side + "_mask"] = np.zeros((rows, nq), np.bool_)
        if is_range:
            for part in ("b", "e"):
                f[side + "_" + part] = np.zeros((rows, nq, W), np.uint32)
            for part in ("lo", "hi"):
                f[side + "_" + part] = np.zeros((rows, nq), np.int32)
        else:
            f[side + "_hash"] = np.full((rows, nq), zero_hash, np.uint32)
            f[side + "_key"] = np.zeros((rows, nq, W), np.uint32)
            f[side + "_bucket"] = np.zeros((rows, nq), np.int32)
    for (side, row, j), entries in slots.items():
        for s, (b, t, i) in enumerate(entries):
            col = j * router.caps[side] + s
            f[side + "_txn"][row, col] = t % Ts
            f[side + "_mask"][row, col] = True
            for name, arr in f.items():
                if name.startswith(side) and name not in (
                        side + "_txn", side + "_mask"):
                    arr[row, col] = getattr(stacked, name)[b, t, i]
    f["rv"] = np.zeros((rows, T), np.uint32)
    f["txn_mask"] = np.zeros((rows, T), np.bool_)
    f["cv"] = np.zeros(rows, np.uint32)
    f["new_window_start"] = np.zeros(rows, np.uint32)
    for row in range(rows):
        b, c = divmod(row, k)
        txns = slice(c * Ts, min(T, (c + 1) * Ts))
        width = txns.stop - txns.start
        f["rv"][row, :width] = stacked.rv[b, txns]
        f["txn_mask"][row, :width] = stacked.txn_mask[b, txns]
        f["cv"][row] = stacked.cv[b]
        if c == k - 1:  # the window moves with a batch's last slice
            f["new_window_start"][row] = stacked.new_window_start[b]
    sb = ck.ShardBatch(**f)
    return ck.pack_args(sb, ck.arg_layout(sb, n)), k


def _ycsb_points():
    rng = np.random.default_rng(3)
    return [TxnRequest(read_version=5, point_reads=[_user(i)],
                       point_writes=[_user(i)])
            for i in rng.integers(100_000, size=17)]


def _ranges_over_one_two_and_four_lanes():
    # the first limb's uniform split: bounds at 0x40…, 0x80…, 0xc0…
    return [TxnRequest(read_version=5, range_reads=[(_user(1), _user(2))],
                       point_writes=[_user(3)]),
            TxnRequest(read_version=6, range_reads=[(b"\x3f", b"\x41")],
                       range_writes=[(b"\x00", b"\xff\xff")]),
            TxnRequest(read_version=7, point_reads=[b"\xc0"],
                       range_writes=[(b"\x7f\xff", b"\xc0\x01")]),
            TxnRequest(read_version=8, range_reads=[(b"\x00", b"\xff")],
                       point_reads=[b"\x10", b"\xf0"])]


def _one_side_empty():
    """Reads alone: both write sides hold nothing."""
    return [TxnRequest(read_version=5, point_reads=[_user(i), b"\x90%d" % i],
                       range_reads=[(_user(i), _user(i + 9))])
            for i in range(12)]


def _lane_overflow(reads):
    """Every txn reads ``reads`` keys of the one ``user`` lane: 1024 ×
    ``reads`` entries against its 1,792 slots."""
    return [TxnRequest(read_version=5,
                       point_reads=[_user(4 * t + r) for r in range(reads)],
                       point_writes=[_user(9_000_000 + t)])
            for t in range(1024)]


def _rebound_bounds(params):
    codec = BatchPacker(params, use_native=False).codec
    return codec.encode_lower_batch(
        [_user(25_000), _user(50_000), _user(75_000)])


def _spread_over_rebound_lanes():
    rng = np.random.default_rng(9)
    return [TxnRequest(read_version=5, point_reads=[_user(a)],
                       point_writes=[_user(b)],
                       range_reads=[(_user(a), _user(a + 30_000))])
            for a, b in rng.integers(100_000, size=(40, 2))]


ROUTED_CASES = {
    "ycsb_points": (_ycsb_points, False, 1),
    "ranges_one_two_four_lanes": (_ranges_over_one_two_and_four_lanes,
                                  False, 1),
    "one_side_empty": (_one_side_empty, False, 1),
    "slices_k2": (lambda: _lane_overflow(2), False, 2),
    "slices_k4": (lambda: _lane_overflow(4), False, 4),
    "after_a_rebound": (_spread_over_rebound_lanes, True, 1),
}


def _cell_router(params, rebound):
    return ShardRouter(params, CELL_LANES, bounds=(
        _rebound_bounds(params) if rebound else None))


@pytest.mark.parametrize("case", sorted(ROUTED_CASES))
def test_the_routed_argument_is_pack_args_of_a_plain_build(cell_shape,
                                                           case):
    params = cell_shape[0]
    build, rebound, want_k = ROUTED_CASES[case]
    router = _cell_router(params, rebound)
    stacked = _stack(params, build(), window=4)
    assert stacked.new_window_start.tolist() == [4]
    want, k_ref = _reference_rows(router, stacked)
    packed, k, lane_counts = router.split(stacked, reuse=True)
    assert (k, k_ref) == (want_k, want_k)
    assert packed.layout == ck.arg_layout(
        ck.unpack_args_host(packed), CELL_LANES)
    assert packed.buf.shape == want.shape == (k, CELL_LANES, want.shape[-1])
    assert np.array_equal(packed.buf, want)
    assert not router.reused  # a router's first rows are fresh
    if rebound:
        assert np.count_nonzero(lane_counts) == CELL_LANES
    # the host-side unpack gives back what pack_args took
    assert np.array_equal(
        ck.pack_args(ck.unpack_args_host(packed), packed.layout), want)


@pytest.mark.parametrize("first,second", [
    ("ranges_one_two_four_lanes", "ycsb_points"),
    ("ycsb_points", "one_side_empty"),
    ("one_side_empty", "ranges_one_two_four_lanes"),
])
def test_reused_rows_keep_nothing_of_the_dispatch_before(cell_shape, first,
                                                         second):
    """The second split writes the first's buffer again: it is
    bit-identical to a fresh router's build, and then the first's
    again."""
    params = cell_shape[0]
    router = _cell_router(params, False)
    a, b = (_stack(params, ROUTED_CASES[c][0](), window=4)
            for c in (first, second))
    got_a = router.split(a, reuse=True)[0].buf.copy()
    got_b = router.split(b, reuse=True)[0]
    assert router.reused
    fresh_b = _cell_router(params, False).split(b)[0]
    assert np.array_equal(got_b.buf, fresh_b.buf)
    assert np.array_equal(got_b.buf, _reference_rows(router, b)[0])
    again = router.split(a, reuse=True)[0]
    assert again.buf is got_b.buf and np.array_equal(again.buf, got_a)
    # a split that does not reuse leaves the kept rows alone
    other = router.split(b)[0]
    assert not router.reused and other.buf is not again.buf
    assert np.array_equal(again.buf, got_a)
