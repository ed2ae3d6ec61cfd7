"""Refusals a batch causes itself: the steps' ``CONFLICT_BATCH`` held to
a plain sequential resolver.

The reference below takes a batch's transactions in order and refuses
one for the first of three causes: its read version is below the window
(TOO_OLD), a write of an earlier batch newer than its read version meets
what it reads (the history), or an earlier *accepted* transaction of
the same batch writes what it reads (the batch itself). Only the last is
``CONFLICT_BATCH`` on the device. Every program that resolves a batch is
driven on seeded batches of a few hot keys: the one-lane point-only step
and the one-lane step with range lanes (``resolve_batch``), and the
presharded step on one lane and on four (``resolve_batch_presharded``).
Their statuses, with both device-only codes folded to CONFLICT, are the
reference's, and ``CONFLICT_BATCH`` stands exactly where the reference
blames the batch. A chain shows the code is read at the fixpoint and
not after the first sweep. Last, ``Resolver`` answers the code as
CONFLICT, counts it once, and a client is told ``not_committed``.
"""

import functools

import jax
import numpy as np
import pytest

from foundationdb_tpu.core.commit import CommitRequest
from foundationdb_tpu.core.errors import FDBError
from foundationdb_tpu.core.mutations import Mutation, Op
from foundationdb_tpu.core.options import Knobs
from foundationdb_tpu.core.status import (
    COMMITTED, CONFLICT, CONFLICT_BATCH, CONFLICT_COARSE, TOO_OLD)
from foundationdb_tpu.ops import conflict as ck
from foundationdb_tpu.parallel import mesh as pm
from foundationdb_tpu.resolver.packing import BatchPacker, ShardRouter
from foundationdb_tpu.resolver.resolver import Resolver, fast_params_of
from foundationdb_tpu.resolver.skiplist import CpuConflictSet, TxnRequest
from foundationdb_tpu.server.cluster import Cluster

from conftest import TEST_KNOBS
from tests.test_resolver import plain_status

PARAMS = ck.ResolverParams(
    txns=64, point_reads=2, point_writes=2, range_reads=2,
    range_writes=2, key_width=5, hash_bits=14, ring_capacity=128,
    bucket_bits=8,
)
HOT = 16  # keys, drawn Zipf-like: the first takes about a fifth
# first bytes spread over the keyspace, so four lanes all hold some
KEYS = [bytes([(i * 53) % 251 + 1]) + b"/hot%03d" % i for i in range(HOT)]
WEIGHTS = 1.0 / np.arange(1, HOT + 1) ** 0.99
WEIGHTS /= WEIGHTS.sum()
VARIANTS = ("one_lane_fast", "one_lane_full", "presharded_1",
            "presharded_4")


# ── the plain reference ─────────────────────────────────────────────
def _meets(ranges, writes):
    return any(rb < we and wb < re for rb, re in ranges for wb, we in writes)


def reference(batches):
    """(status, cause) of every transaction of every batch, resolved one
    at a time in arrival order: cause is "too_old", "history", "batch"
    or None (committed)."""
    history, window, out = [], 0, []
    for txns, cv, ws in batches:
        mine, got = [], []
        for t in txns:
            reads = list(t.read_ranges())
            if t.read_version < window:
                got.append((TOO_OLD, "too_old"))
            elif _meets(reads, [(b, e) for b, e, v in history
                                if v > t.read_version]):
                got.append((CONFLICT, "history"))
            elif _meets(reads, mine):
                got.append((CONFLICT, "batch"))
            else:
                got.append((COMMITTED, None))
                mine.extend(t.write_ranges())
        history.extend((b, e, cv) for b, e in mine)
        window = max(window, ws)
        out.append(got)
    return out


def random_batches(seed, n=6):
    """``n`` batches of 40–64 transactions over the hot keys: half read
    and write one key (YCSB's update), a third read one or two keys, the
    rest write one key blind; read versions from the last few commit
    versions, so that history and the batch both refuse, and the window
    moves so that some read versions fall below it."""
    rng = np.random.default_rng(seed)
    batches = []
    for b in range(n):
        cv = 100 * (b + 1)
        txns = []
        for _ in range(int(rng.integers(40, PARAMS.txns + 1))):
            # the last commit version, one or two before it, or one
            # below the window the last batch set (cv − 350)
            rv = max(0, cv - int(rng.choice([100, 100, 100, 150, 250,
                                             400])))
            keys = [KEYS[i] for i in rng.choice(HOT, 2, p=WEIGHTS)]
            kind = rng.random()
            if kind < 0.5:
                txns.append(TxnRequest(read_version=rv,
                                       point_reads=keys[:1],
                                       point_writes=keys[:1]))
            elif kind < 0.85:
                txns.append(TxnRequest(read_version=rv,
                                       point_reads=sorted(set(keys))))
            else:
                txns.append(TxnRequest(read_version=rv,
                                       point_writes=keys[1:]))
        batches.append((txns, cv, max(0, cv - 250)))
    return batches


# ── the programs under test ─────────────────────────────────────────
@functools.lru_cache(maxsize=None)
def program(variant):
    """→ run(batches) → the raw device statuses of every batch, from a
    fresh state."""
    if variant.startswith("one_lane"):
        params = (fast_params_of(PARAMS) if variant == "one_lane_fast"
                  else PARAMS)
        packer = BatchPacker(params)
        step = ck.make_resolve_fn(params, donate=False)

        def run(batches):
            state, out = ck.init_state(params), []
            for txns, cv, ws in batches:
                status, _, state = step(state, packer.pack(txns, 0, cv, ws))
                out.append(np.asarray(status)[:len(txns)])
            return out
        return run
    lanes = int(variant.rsplit("_", 1)[1])
    kern = pm.PreshardedResolverKernel(
        PARAMS, mesh=pm.default_mesh(lanes), donate=False)
    # a lane holds a whole batch: no slice, which would turn the
    # batch's own refusals into history's
    router = ShardRouter(PARAMS, lanes, headroom=lanes)
    packer = BatchPacker(PARAMS, use_native=False)

    def run(batches):
        state, out = kern.state, []
        for txns, cv, ws in batches:
            b = packer.pack(txns, 0, cv, ws)
            packed, k, _ = router.split(
                jax.tree.map(lambda a: np.asarray(a)[None], b))
            assert k == 1
            status, _, state = kern._step.call_packed(
                state, packed._replace(buf=packed.buf[0]))
            out.append(np.asarray(status)[:len(txns)])
        return out
    return run


def check_against_reference(variant, batches):
    want = reference(batches)
    got = program(variant)(batches)
    oracle = CpuConflictSet()
    for (txns, cv, ws), ref, dev in zip(batches, want, got):
        statuses = [s for s, _ in ref]
        assert oracle.resolve(txns, cv, ws) == statuses  # the reference
        assert plain_status(dev).tolist() == statuses
        assert (dev == CONFLICT_BATCH).tolist() \
            == [cause == "batch" for _, cause in ref]
        assert not (dev == CONFLICT_COARSE).any()
    return [cause for ref in want for _, cause in ref]


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("variant", VARIANTS)
def test_every_step_blames_the_batch_where_the_reference_does(
        variant, seed):
    causes = check_against_reference(variant, random_batches(seed))
    # the draw refuses for every cause, the batch most of all
    assert {"too_old", "history", "batch", None} <= set(causes)
    assert causes.count("batch") > causes.count("history")


# ── a chain: read at the fixpoint, not after the first sweep ────────
def chain(range_read=False):
    """t1 writes x; t2 reads x, writes y; t3 reads y, writes z; t4 reads
    z. The first sweep kills t2, t3 and t4 (each behind a transaction
    still admissible); the fixpoint gives t3 back, since t2 wrote
    nothing, and so refuses t4 again."""
    x, y, z = KEYS[1], KEYS[2], KEYS[3]
    t3_reads = dict(range_reads=[(y, y + b"\x00\x00")]) if range_read \
        else dict(point_reads=[y])
    return [TxnRequest(read_version=10, point_writes=[x]),
            TxnRequest(read_version=10, point_reads=[x], point_writes=[y]),
            TxnRequest(read_version=10, point_writes=[z], **t3_reads),
            TxnRequest(read_version=10, point_reads=[z])]


@pytest.mark.parametrize("variant", VARIANTS)
def test_a_chain_commits_its_third_link(variant):
    causes = check_against_reference(variant, [(chain(), 20, 0)])
    assert causes == [None, "batch", None, "batch"]


@pytest.mark.parametrize("variant", [v for v in VARIANTS if v != "one_lane_fast"])
def test_a_chain_through_a_range_read(variant):
    (dev,) = program(variant)([(chain(range_read=True), 20, 0)])
    assert dev.tolist() == [COMMITTED, CONFLICT_BATCH, COMMITTED,
                            CONFLICT_BATCH]


# ── out of the resolver, and out of the server ──────────────────────
# the proxy's reordering off, so that a batch keeps its arrival order
KNOBS = Knobs(**{**TEST_KNOBS, "resolver_backend": "tpu",
                 "commit_batch_scheduling": False})


def test_the_resolver_answers_conflict_and_counts_each_once():
    r = Resolver(KNOBS)
    assert r._plain_statuses([COMMITTED, CONFLICT_BATCH, TOO_OLD,
                              CONFLICT_COARSE, CONFLICT_BATCH, CONFLICT]) \
        == [COMMITTED, CONFLICT, TOO_OLD, CONFLICT, CONFLICT, CONFLICT]
    snap = r.profile.snapshot()
    assert (snap["conflicts_in_batch"], snap["conflicts_coarse_only"]) \
        == (2, 1)
    assert r.resolve(chain(), 20, 0) == [COMMITTED, CONFLICT, COMMITTED,
                                         CONFLICT]
    assert r.profile.snapshot()["conflicts_in_batch"] == 4
    # history alone is not the batch's
    late = TxnRequest(read_version=10, point_reads=[KEYS[1]])
    assert r.resolve([late], 30, 0) == [CONFLICT]
    assert r.profile.snapshot()["conflicts_in_batch"] == 4


def test_a_client_is_told_not_committed():
    """Two transactions of one batch, the second reading what the first
    writes: the second is answered 1020, and the cluster's aggregate
    counts one refusal of the batch's own."""
    cl = Cluster(knobs=KNOBS)
    try:
        db = cl.database()
        db.set(b"x", b"0")
        rv = cl.grv_proxy.get_read_version()
        w = CommitRequest(
            read_version=rv, mutations=[Mutation(Op.SET, b"x", b"W")],
            read_conflict_ranges=[],
            write_conflict_ranges=[(b"x", b"x\x00")])
        t = CommitRequest(
            read_version=rv, mutations=[Mutation(Op.SET, b"y", b"T")],
            read_conflict_ranges=[(b"x", b"x\x00")],
            write_conflict_ranges=[(b"y", b"y\x00")])
        out = cl.commit_proxy.commit_batch([w, t])
        assert not isinstance(out[0], FDBError)
        assert isinstance(out[1], FDBError) and out[1].code == 1020
        agg = cl.status()["cluster"]["device"]["aggregate"]
        assert agg["conflicts_in_batch"] == 1
        assert db.get(b"y") is None
    finally:
        cl.close()
