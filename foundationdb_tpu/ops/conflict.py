"""The TPU conflict-detection kernel — FDB's Resolver hot path, redesigned.

Reference semantics (fdbserver/Resolver.actor.cpp + fdbserver/SkipList.cpp,
ConflictSet::detectConflicts): a resolver keeps the last ~5s of committed
write ranges; a transaction commits iff none of its read conflict ranges
intersects a write range committed after the transaction's read version —
including writes of earlier transactions *in the same batch* that were
themselves accepted.

The reference walks a lock-free skip list per conflict range. That design
is pointer-chasing and branchy — exactly what a TPU cannot do. This kernel
replaces it with four data-parallel structures, all fixed-shape device
arrays updated in one fused jit step:

1. **Point-version hash table** ``ht[2^HB]``: max commit-version offset per
   key-hash bucket. Point writes scatter-max into it; point reads gather
   and compare. Exact for point↔point conflicts up to hash collisions,
   which only ever *add* conflicts (a spurious retry — safe, same
   direction FDB's own conservative conflict ranges lean).

2. **Range ring** of the most recent ``KR`` committed range-writes, kept
   as limb-encoded intervals and checked exactly (vectorized interval
   overlap, ops/intervals.py).

3. **Coarse interval summary** ``(range_L, range_R)[C]`` over ``C``
   order-contiguous key buckets (cut by the host, below), absorbing
   range-writes *evicted* from the ring: scatter-max of the version at
   the interval's begin bucket into L and end bucket into R. A query
   range [qlo,qhi] can only overlap
   a stored interval if that interval starts at or before qhi (so its
   version is ≤ prefix-max of L at qhi) *and* ends at or after qlo (≤
   suffix-max of R at qlo); ``min(prefmax_L[qhi], sufmax_R[qlo])`` is
   therefore an upper bound on the newest possibly-overlapping write —
   conservative, never a miss.

4. **Coarse point summary** ``point[C]``: per-bucket max version of all
   point writes, with a per-batch sparse table for O(1) range-max — used
   only by range reads (point reads use the exact hash table).

How the ``C`` buckets are cut is the host's business
(resolver/packing.py ``CoarseBuckets``): the device only ever sees
bucket indices, and needs of them that the map from key to index is
weakly monotone in the whole limb-encoded key and that a write is
recorded and later read under the SAME map. Until the resolver has met a
range the map is the top bits of a key's first limb; from then on it is a
``searchsorted`` against ``C − 1`` sorted boundary rows, quantiles of a
sample of the keys the resolver itself packs (upstream's resolvers are
balanced from such a sample: Resolver.actor.cpp ``iopsSample``), so that
keys sharing a prefix — every key of one application does — still spread
over the buckets.

Intra-batch ordering — the sequential part of the reference's resolver —
becomes a **Jacobi fixpoint on the MXU**: build the strict-lower-
triangular conflict matrix O[t',t] ("t' writes intersect t's reads"),
then iterate  a ← a0 ∧ ¬(a·O)  until unchanged. The greedy sequential
acceptance is the *unique* fixpoint of that map (induction on t: position
0 is exact immediately, position t is exact once 0..t-1 are), and each
iteration is one T×T matvec, so batches with conflict chains of depth d
cost d matmuls instead of T dependent skip-list walks.

Safety argument (why conservative lanes compose): every structure is used
both to *record* accepted writes and to *check* reads, and each lane's
check provably sees every write its record admitted (hash: same bucket;
ring: exact; coarse: bucket monotonicity). Hence the accepted set is
always mutually serializable — false positives only shrink it.

A change of the bucket map (a *rebucket*) would break "same map" for
what the summaries already hold, so the host runs :func:`fold_coarse`
between the last step under the old map and the first under the new:
``point_coarse``, ``range_L`` and ``range_R`` each become their own
maximum in every bucket, and every ring entry's begin / end bucket
becomes 0 / C − 1 (what eviction will scatter). Whatever index a later
read computes, it then meets a version at least as new as any the old
map would have shown it: a fold can add refusals, for the one round of
read versions older than those maxima, and can never lose a write. The
exact lanes (hash table, ring compare, intra-batch matrix) never look at
a bucket. Two places *place* entries by bucket: with
``ring_partition_bits`` an entry sits in the sub-ring of its old begin
bucket and a query looks only in its own end partitions, so there the
fold also empties the ring into the summaries; and the replicated-batch
sharded step (``axis_name``) records a range write on the shard that
``bucket_owned(rw_lo)`` names, but every shard checks every read against
its own ring exactly and the verdicts are OR-reduced, so an entry
recorded under the old map is still found wherever it sits.

The full step also says which refusals only a coarse lane raised
(``CONFLICT_COARSE``: the hash table, the ring and the intra-batch
matrix found nothing): an upper bound on the false conflicts the
summaries cost. The host counts them and answers CONFLICT.

Every step also says which refusals only the batch itself raised
(``CONFLICT_BATCH``: admissible against the history and the window,
``a0``, and not accepted at the fixpoint): a smaller batch might have
committed them, a larger one refuses more. The two sets never meet (a
coarse-only refusal is a history hit, so not in ``a0``); the host counts
both and answers CONFLICT.

Versions are uint32 offsets from a host-held base (core/versions.py);
version 0 means "no write recorded".
"""

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from foundationdb_tpu.ops.intervals import lex_lt, ranges_overlap


class ResolverParams(NamedTuple):
    """Static shape config (hashable; passed as a jit-static arg)."""

    txns: int = 1024  # T
    point_reads: int = 4  # PR per txn
    point_writes: int = 4  # PW per txn
    range_reads: int = 2  # RR per txn
    range_writes: int = 2  # RW per txn
    key_width: int = 9  # W = limbs + 1 (length limb)
    hash_bits: int = 22  # point table size 2^HB
    ring_capacity: int = 4096  # KR
    bucket_bits: int = 14  # C = 2^bucket_bits coarse buckets
    use_pallas: bool = False  # ring lanes via the Pallas VMEM kernel
    # record point writes into the coarse per-bucket summary even when
    # this variant has no range-read lanes to read it: set ONLY on the
    # point-specialized fast-path variant (Resolver), which shares
    # history with a full kernel whose future range reads must see these
    # writes. A config that is point-only by knobs (no full twin exists)
    # keeps the old gate and records nothing nothing can read.
    record_point_coarse: bool = False
    # Bucket-partitioned ring (single-device path): 2^bits sub-rings
    # keyed by the begin-key's top coarse-bucket bits. A range write
    # contained in ONE partition records exactly in its sub-ring;
    # spanning writes fold into the coarse interval summaries
    # (conservative). A query then checks only its two end partitions'
    # sub-rings exactly (plus a per-partition version max for any
    # middle partitions) — ~2/2^bits of the flat ring's pairwise work,
    # which is what bounds range-heavy throughput on-device. 0 = flat
    # ring (the mesh-sharded path always uses the flat ring).
    ring_partition_bits: int = 0


class ResolverState(NamedTuple):
    """Device-resident conflict history (the MVCC window)."""

    window_start: jnp.ndarray  # uint32[] — oldest admissible read version
    ht: jnp.ndarray  # uint32[2^HB] point-write version table
    ring_b: jnp.ndarray  # uint32[KR, W] range-write begins
    ring_e: jnp.ndarray  # uint32[KR, W] range-write ends
    ring_v: jnp.ndarray  # uint32[KR] commit versions
    ring_lo: jnp.ndarray  # int32[KR] begin bucket
    ring_hi: jnp.ndarray  # int32[KR] end bucket
    ring_mask: jnp.ndarray  # bool[KR]
    ring_head: jnp.ndarray  # int32[]
    range_L: jnp.ndarray  # uint32[C] evicted range-writes: v at begin bucket
    range_R: jnp.ndarray  # uint32[C] evicted range-writes: v at end bucket
    point_coarse: jnp.ndarray  # uint32[C] point writes per bucket


class ResolveBatch(NamedTuple):
    """One commit batch, packed to static shapes (invalid slots masked)."""

    rv: jnp.ndarray  # uint32[T] read-version offsets
    txn_mask: jnp.ndarray  # bool[T]
    pr_hash: jnp.ndarray  # uint32[T, PR]
    pr_key: jnp.ndarray  # uint32[T, PR, W] limb-encoded point-read keys
    pr_bucket: jnp.ndarray  # int32[T, PR]
    pr_mask: jnp.ndarray  # bool[T, PR]
    pw_hash: jnp.ndarray  # uint32[T, PW]
    pw_key: jnp.ndarray  # uint32[T, PW, W]
    pw_bucket: jnp.ndarray  # int32[T, PW]
    pw_mask: jnp.ndarray  # bool[T, PW]
    rr_b: jnp.ndarray  # uint32[T, RR, W]
    rr_e: jnp.ndarray  # uint32[T, RR, W]
    rr_lo: jnp.ndarray  # int32[T, RR]
    rr_hi: jnp.ndarray  # int32[T, RR]
    rr_mask: jnp.ndarray  # bool[T, RR]
    rw_b: jnp.ndarray  # uint32[T, RW, W]
    rw_e: jnp.ndarray  # uint32[T, RW, W]
    rw_lo: jnp.ndarray  # int32[T, RW]
    rw_hi: jnp.ndarray  # int32[T, RW]
    rw_mask: jnp.ndarray  # bool[T, RW]
    cv: jnp.ndarray  # uint32[] commit-version offset for this batch
    new_window_start: jnp.ndarray  # uint32[]


class ShardBatch(NamedTuple):
    """One commit batch COMPACTED per key-range lane — the presharded
    single-dispatch layout (resolver/packing.py ShardRouter writes it
    straight into the rows ``pack_args`` makes of it).

    Where ``ResolveBatch`` keeps a dense ``[T, K]`` slot grid per
    conflict side, this layout pools each side into a flat slot array of
    per-lane capacity Q with an explicit owning-txn index: the host
    router sends each entry ONLY to the lane(s) whose key range it
    touches, so a lane's history checks (table gathers, the [Q, KR]
    ring scan) and its history run over its own keys alone. Point
    entries go to exactly ``lane(key)``; range entries get one slot in
    EVERY lane their span overlaps, carrying the FULL unclipped range
    (the overlap checks stay exact; duplicates only re-derive the same
    verdict). So one txn holds at most K slots a side in one lane (K
    the side's per-txn width): the step puts a lane's slots back on a
    dense [T, K] grid for the intra-batch matrix, which costs each lane
    what it costs the one-lane step. ``rv``/``txn_mask``/``cv``/
    ``new_window_start`` stay replicated — the verdict fold needs them
    on every lane.
    """

    rv: jnp.ndarray  # uint32[T] read-version offsets (replicated)
    txn_mask: jnp.ndarray  # bool[T] (replicated)
    pr_hash: jnp.ndarray  # uint32[Qpr]
    pr_key: jnp.ndarray  # uint32[Qpr, W]
    pr_bucket: jnp.ndarray  # int32[Qpr]
    pr_txn: jnp.ndarray  # int32[Qpr] owning txn slot in [0, T)
    pr_mask: jnp.ndarray  # bool[Qpr]
    pw_hash: jnp.ndarray  # uint32[Qpw]
    pw_key: jnp.ndarray  # uint32[Qpw, W]
    pw_bucket: jnp.ndarray  # int32[Qpw]
    pw_txn: jnp.ndarray  # int32[Qpw]
    pw_mask: jnp.ndarray  # bool[Qpw]
    rr_b: jnp.ndarray  # uint32[Qrr, W]
    rr_e: jnp.ndarray  # uint32[Qrr, W]
    rr_lo: jnp.ndarray  # int32[Qrr]
    rr_hi: jnp.ndarray  # int32[Qrr]
    rr_txn: jnp.ndarray  # int32[Qrr]
    rr_mask: jnp.ndarray  # bool[Qrr]
    rw_b: jnp.ndarray  # uint32[Qrw, W]
    rw_e: jnp.ndarray  # uint32[Qrw, W]
    rw_lo: jnp.ndarray  # int32[Qrw]
    rw_hi: jnp.ndarray  # int32[Qrw]
    rw_txn: jnp.ndarray  # int32[Qrw]
    rw_mask: jnp.ndarray  # bool[Qrw]
    cv: jnp.ndarray  # uint32[] commit-version offset (replicated)
    new_window_start: jnp.ndarray  # uint32[] (replicated)


from foundationdb_tpu.core.status import (  # noqa: E402
    COMMITTED, CONFLICT, CONFLICT_BATCH, CONFLICT_COARSE, TOO_OLD)


def has_coarse_lanes(params: ResolverParams):
    """Whether the program ``params`` describes checks a read against a
    coarse summary at all (the point-only variants do not)."""
    return bool(params.range_writes
                or (params.range_reads and params.point_writes))


def init_state(params: ResolverParams) -> ResolverState:
    kr, c, w = params.ring_capacity, 1 << params.bucket_bits, params.key_width
    u32 = jnp.uint32
    # partitioned ring: one append cursor per sub-ring
    head_shape = (
        (1 << params.ring_partition_bits,)
        if params.ring_partition_bits else ()
    )
    return ResolverState(
        window_start=jnp.zeros((), u32),
        ht=jnp.zeros((1 << params.hash_bits,), u32),
        ring_b=jnp.zeros((kr, w), u32),
        ring_e=jnp.zeros((kr, w), u32),
        ring_v=jnp.zeros((kr,), u32),
        ring_lo=jnp.zeros((kr,), jnp.int32),
        ring_hi=jnp.zeros((kr,), jnp.int32),
        ring_mask=jnp.zeros((kr,), bool),
        ring_head=jnp.zeros(head_shape, jnp.int32),
        range_L=jnp.zeros((c,), u32),
        range_R=jnp.zeros((c,), u32),
        point_coarse=jnp.zeros((c,), u32),
    )


def _sparse_table(vals):
    """Sparse-table (doubling) range-max preprocessing over a 1-D array.

    Returns list of arrays: level l gives max over [i, i + 2^l)."""
    levels = [vals]
    n = vals.shape[0]
    span = 1
    while span < n:
        prev = levels[-1]
        shifted = jnp.concatenate([prev[span:], jnp.zeros((span,), prev.dtype)])
        levels.append(jnp.maximum(prev, shifted))
        span *= 2
    return levels


def _range_max(levels, lo, hi):
    """Max over [lo, hi] inclusive (int32 indices, lo <= hi), O(1)/query."""
    length = (hi - lo + 1).astype(jnp.float32)
    j = jnp.floor(jnp.log2(jnp.maximum(length, 1.0))).astype(jnp.int32)
    j = jnp.clip(j, 0, len(levels) - 1)
    stacked = jnp.stack(levels)  # [L, C]
    n = levels[0].shape[0]
    a = stacked[j, jnp.clip(lo, 0, n - 1)]
    b = stacked[j, jnp.clip(hi - (1 << j) + 1, 0, n - 1)]
    return jnp.maximum(a, b)


def _point_in(k, b, e):
    """bool: limb key k in [b, e). Broadcasting over leading dims."""
    return (~lex_lt(k, b)) & lex_lt(k, e)


def _overlap_matrix(T, pw, pr, rw, rr):
    """The intra-batch conflict matrix of dense ``[T, K]`` sides:
    O[t1, t2] = some write of t1 hits some read of t2 (not yet cut to
    t1 < t2 or to live txns). Both device steps build theirs here.

    A side is falsy where the program has none. Reads are ``(hash, key,
    mask)`` / ``(b, e, mask)``; writes ``(hash, key, ok)`` / ``(b, e,
    ok)``, ``ok()`` giving the writes this lane answers for. ``ok`` is
    called in each block it masks, which is how the one-lane step
    always traced its ownership masks: that step has to lower to the
    program it was (tests/test_presharded_structure.py holds the hash
    of its text), and XLA folds the repeats.
    """
    u32 = jnp.uint32
    if pw:
        pw_hash, pw_key, pw_ok = pw
    if pr:
        pr_hash, pr_key, pr_mask = pr
    if rw:
        rw_b, rw_e, rw_ok = rw
    if rr:
        rr_b, rr_e, rr_mask = rr
    O = jnp.zeros((T, T), bool)
    if pw and pr:
        wh = jnp.where(pw_ok(), pw_hash, u32(0xFFFFFFFF))  # [T, PW]
        rh = jnp.where(pr_mask, pr_hash, u32(0xFFFFFFFE))  # [T, PR]
        eq = wh[:, :, None, None] == rh[None, None, :, :]  # [T1, PW, T2, PR]
        O |= jnp.any(eq, axis=(1, 3))
    if pw and rr:
        inr = _point_in(
            pw_key[:, :, None, None, :], rr_b[None, None], rr_e[None, None]
        )  # [T1, PW, T2, RR]
        m = pw_ok()[:, :, None, None] & rr_mask[None, None]
        O |= jnp.any(inr & m, axis=(1, 3))
    if rw and pr:
        inr = _point_in(
            pr_key[None, None],  # [1, 1, T2, PR, W]
            rw_b[:, :, None, None, :],  # [T1, RW, 1, 1, W]
            rw_e[:, :, None, None, :],
        )  # [T1, RW, T2, PR]
        m = rw_ok()[:, :, None, None] & pr_mask[None, None]
        O |= jnp.any(inr & m, axis=(1, 3))
    if rw and rr:
        ov = ranges_overlap(
            rr_b[None, None],  # [1, 1, T2, RR, W]
            rr_e[None, None],
            rw_b[:, :, None, None, :],  # [T1, RW, 1, 1, W]
            rw_e[:, :, None, None, :],
        )
        m = rw_ok()[:, :, None, None] & rr_mask[None, None]
        O |= jnp.any(ov & m, axis=(1, 3))
    return O


def _verdicts(too_old, a0, accepted):
    """A step's statuses before the coarse mark: TOO_OLD, COMMITTED, or
    a refusal, CONFLICT_BATCH where only an earlier accepted transaction
    of this batch stood in the way (``a0``: admissible against the
    history and the window) and CONFLICT where the history did."""
    return jnp.where(too_old, TOO_OLD, jnp.where(
        accepted, COMMITTED, jnp.where(a0, CONFLICT_BATCH, CONFLICT)))


def _mark_coarse_only(status, accepted, Of, coarse_hist, axis_name):
    """``status`` with CONFLICT_COARSE where only a coarse summary stood
    in the way: ``coarse_hist`` (a history hit no exact lane shares, read
    version inside the window) and no accepted earlier transaction of the
    batch writes what this one reads (``Of``: the intra-batch matrix the
    fixpoint ran on, one more row-vector product at its fixpoint)."""
    killed = jnp.dot(
        accepted.astype(jnp.bfloat16), Of, preferred_element_type=jnp.float32
    )
    if axis_name is not None:
        killed = jax.lax.psum(killed, axis_name)
    return jnp.where(coarse_hist & (killed < 0.5), CONFLICT_COARSE, status)


def resolve_batch(
    state: ResolverState,
    batch: ResolveBatch,
    params: ResolverParams,
    axis_name=None,
    n_shards=1,
):
    """One resolver step: statuses for a batch + updated history. Pure/jittable.

    Ref parity: Resolver::resolveBatch + ConflictSet::detectConflicts.

    With ``axis_name`` set (under shard_map over a mesh axis), each device
    is one resolver *shard* — the TPU analog of FDB's key-range-sharded
    resolvers, but finer: the point hash table is hash-sharded, the range
    ring is begin-bucket-sharded, the small coarse summaries are
    replicated (pmax-synced), and the batch is replicated. Per-lane
    invariant: whichever shard records a write is the shard whose check
    can see it, so OR-reducing per-shard verdicts (psum) loses nothing.
    Cross-device traffic per batch: a few [T]-bool reductions + two [C]
    pmax — all ICI-friendly.
    """
    T = params.txns
    u32 = jnp.uint32
    rv = batch.rv  # [T]

    if axis_name is None:
        n_shards, shard_idx = 1, 0

        def por(x):  # OR-reduce across shards
            return x

        def pmax_arr(x):
            return x

    else:
        # axis_name may be a tuple (hybrid host×chip mesh: state shards
        # over every axis; the flattened coordinate is the shard id and
        # collectives reduce over all of them — psum/pmax take tuples
        # natively, the index/size just need the row-major fold)
        names = axis_name if isinstance(axis_name, tuple) else (axis_name,)
        shard_idx = jnp.int32(0)
        mesh_n = 1
        for nm in names:
            sz = jax.lax.axis_size(nm)
            shard_idx = shard_idx * sz + jax.lax.axis_index(nm)
            mesh_n *= sz
        if n_shards != mesh_n:
            raise ValueError(
                f"n_shards={n_shards} does not match mesh axes "
                f"{names!r} total size {mesh_n}: ownership masks would "
                "silently un-own part of the key space"
            )

        def por(x):
            return jax.lax.psum(x.astype(jnp.int32), names) > 0

        def pmax_arr(x):
            return jax.lax.pmax(x, names)

    C = 1 << params.bucket_bits

    def hash_owned(h):  # point-lane ownership: hash mod n
        return (h % u32(n_shards)).astype(jnp.int32) == shard_idx

    def bucket_owned(bucket):  # range-lane ownership: contiguous buckets
        return (bucket * n_shards) // C == shard_idx

    # ───────────────────────── history conflicts ─────────────────────────
    too_old = rv < state.window_start

    hist = jnp.zeros((T,), bool)
    # what the exact lanes alone found: only a program with a coarse
    # check keeps it (the point-only variants lower to the text they had)
    coarse_lanes = has_coarse_lanes(params)
    exact = jnp.zeros((T,), bool)

    # The ring + coarse interval summaries are populated ONLY by range
    # writes: with params.range_writes == 0 they are statically all-zero,
    # and checking them would stream [T, *, KR, W] broadcast intermediates
    # through HBM for nothing (this alone is ~25x on the YCSB-A point
    # workload). Gate every dead lane on the static params.
    if params.range_writes:
        pref_L = jax.lax.associative_scan(jnp.maximum, state.range_L)
        suf_R = jax.lax.associative_scan(jnp.maximum, state.range_R, reverse=True)

    # bucket-partitioned ring (single-device path only — the mesh
    # bucket-shards the ring across devices instead): sub-ring views +
    # the partition shift, shared by the check and record lanes
    PB = params.ring_partition_bits if axis_name is None else 0
    if PB and params.range_writes:
        P = 1 << PB
        KRs = params.ring_capacity // P
        pshift = params.bucket_bits - PB
        rb_p = state.ring_b.reshape(P, KRs, params.key_width)
        re_p = state.ring_e.reshape(P, KRs, params.key_width)
        rv_p = state.ring_v.reshape(P, KRs)
        rm_p = state.ring_mask.reshape(P, KRs)
        # per-partition newest version: the conservative verdict for a
        # query's MIDDLE partitions (its end partitions get exact checks)
        part_max = jnp.max(jnp.where(rm_p, rv_p, u32(0)), axis=1)

    # the Pallas ring kernel runs the single-shard flat-ring path only
    # (each shard_map lane is its own program; the jnp lanes stay
    # canonical there; the partitioned ring has its own gather-based
    # layout) — interpret mode keeps it runnable (and
    # differential-testable) on CPU.
    pallas_ring_on = params.use_pallas and axis_name is None and not PB
    if pallas_ring_on:
        from foundationdb_tpu.ops.pallas_ring import ring_hits

        interp = jax.default_backend() != "tpu"

    # point reads vs point-write hash table (exact lane)
    if params.point_reads:
        own_pr = hash_owned(batch.pr_hash)
        ht_v = state.ht[batch.pr_hash & u32((1 << params.hash_bits) - 1)]  # [T, PR]
        hit = (ht_v > rv[:, None]) & batch.pr_mask & own_pr
        if params.range_writes:
            # point reads vs recent range-writes (exact ring)
            # lane counts come from the arrays: packers may statically
            # zero-width lanes a workload never uses
            PR = batch.pr_key.shape[1]
            if pallas_ring_on and PR:
                flat_k = batch.pr_key.reshape(T * PR, params.key_width)
                rv_q = jnp.broadcast_to(rv[:, None], (T, PR)).reshape(-1)
                ring_hit = ring_hits(
                    flat_k, flat_k, rv_q, state.ring_b, state.ring_e,
                    state.ring_v, state.ring_mask,
                    point_mode=True, interpret=interp,
                ).reshape(T, PR)
            elif PB:
                # a point's partition is its bucket's partition; any
                # single-partition entry containing it lives exactly
                # there (spanning entries are in the coarse summaries)
                pq = jnp.clip(batch.pr_bucket >> pshift, 0, P - 1)
                in_rng = _point_in(
                    batch.pr_key[:, :, None, :], rb_p[pq], re_p[pq]
                )  # [T, PR, KRs]
                newer = (rv_p[pq] > rv[:, None, None]) & rm_p[pq]
                ring_hit = jnp.any(in_rng & newer, axis=2)
            else:
                in_rng = _point_in(
                    batch.pr_key[:, :, None, :], state.ring_b[None, None], state.ring_e[None, None]
                )  # [T, PR, KR]
                newer = (state.ring_v[None, None] > rv[:, None, None]) & state.ring_mask[None, None]
                ring_hit = jnp.any(in_rng & newer, axis=2)
            hit |= ring_hit & batch.pr_mask
        if coarse_lanes:
            exact |= jnp.any(hit, axis=1)
        if params.range_writes:
            # point reads vs evicted range-writes (coarse interval summary)
            coarse = jnp.minimum(pref_L[batch.pr_bucket], suf_R[batch.pr_bucket])
            hit |= (coarse > rv[:, None]) & batch.pr_mask
        hist |= jnp.any(hit, axis=1)

    # range reads vs ring (exact), coarse ranges, and coarse points
    if params.range_reads:
        hit = jnp.zeros((T, params.range_reads), bool)
        if params.range_writes:
            RR = batch.rr_b.shape[1]
            if pallas_ring_on and RR:
                rv_q = jnp.broadcast_to(rv[:, None], (T, RR)).reshape(-1)
                ring_hit = ring_hits(
                    batch.rr_b.reshape(T * RR, params.key_width),
                    batch.rr_e.reshape(T * RR, params.key_width),
                    rv_q, state.ring_b, state.ring_e,
                    state.ring_v, state.ring_mask,
                    point_mode=False, interpret=interp,
                ).reshape(T, RR)
            elif PB:
                # exact checks against the query's TWO end partitions'
                # sub-rings (equal for short scans — the common case),
                # conservative per-partition version max for middles
                pq_lo = jnp.clip(batch.rr_lo >> pshift, 0, P - 1)
                pq_hi = jnp.clip(batch.rr_hi >> pshift, 0, P - 1)

                def _sub_hit(pq):
                    ov = ranges_overlap(
                        batch.rr_b[:, :, None, :],
                        batch.rr_e[:, :, None, :],
                        rb_p[pq], re_p[pq],
                    )  # [T, RR, KRs]
                    newer = (rv_p[pq] > rv[:, None, None]) & rm_p[pq]
                    return jnp.any(ov & newer, axis=2)

                ring_hit = _sub_hit(pq_lo) | _sub_hit(pq_hi)
                pidx = jnp.arange(P)
                mid = (pidx[None, None, :] > pq_lo[:, :, None]) & (
                    pidx[None, None, :] < pq_hi[:, :, None]
                )
                mid_max = jnp.max(
                    jnp.where(mid, part_max[None, None, :], u32(0)), axis=2
                )
                ring_hit |= mid_max > rv[:, None]
            else:
                ov = ranges_overlap(
                    batch.rr_b[:, :, None, :],
                    batch.rr_e[:, :, None, :],
                    state.ring_b[None, None],
                    state.ring_e[None, None],
                )  # [T, RR, KR]
                newer = (state.ring_v[None, None] > rv[:, None, None]) & state.ring_mask[None, None]
                ring_hit = jnp.any(ov & newer, axis=2)
            hit |= ring_hit & batch.rr_mask
            exact |= jnp.any(hit, axis=1)
            coarse_rng = jnp.minimum(pref_L[batch.rr_hi], suf_R[batch.rr_lo])
            hit |= (coarse_rng > rv[:, None]) & batch.rr_mask
        if params.point_writes:
            levels = _sparse_table(state.point_coarse)
            pmax = _range_max(levels, batch.rr_lo, batch.rr_hi)
            hit |= (pmax > rv[:, None]) & batch.rr_mask
        hist |= jnp.any(hit, axis=1)

    hist = por(hist)
    if coarse_lanes:
        exact = por(exact)

    # a0: admissible before intra-batch ordering (history + window + mask)
    a0 = (~too_old) & (~hist) & batch.txn_mask

    # ───────────────── intra-batch conflict matrix ─────────────────
    # O[t1, t2]: an accepted t1 < t2 would abort t2 (t1's writes hit
    # t2's reads). Each shard builds rows only from writes it owns;
    # the Jacobi loop OR-reduces the kill vectors.
    O = _overlap_matrix(
        T,
        params.point_writes and (
            batch.pw_hash, batch.pw_key,
            lambda: batch.pw_mask & hash_owned(batch.pw_hash)),
        params.point_reads and (
            batch.pr_hash, batch.pr_key, batch.pr_mask),
        params.range_writes and (
            batch.rw_b, batch.rw_e,
            lambda: batch.rw_mask & bucket_owned(batch.rw_lo)),
        params.range_reads and (batch.rr_b, batch.rr_e, batch.rr_mask),
    )
    strict_lower = jnp.tril(jnp.ones((T, T), bool), k=-1).T  # [t1 < t2]
    O &= strict_lower & batch.txn_mask[:, None] & batch.txn_mask[None, :]

    # ───────── Jacobi fixpoint for sequential acceptance ─────────
    # The kill vector is psum-reduced per iteration rather than
    # OR-folding the whole [T,T] matrix up front: d small [T]
    # reductions measure cheaper than one [T,T] all-reduce for the
    # shallow conflict chains real batches carry (d is the chain
    # depth, typically 1-3).
    Of = O.astype(jnp.bfloat16)

    def cond(carry):
        _, changed = carry
        return changed

    def body(carry):
        a, _ = carry
        killed_local = jnp.dot(
            a.astype(jnp.bfloat16), Of, preferred_element_type=jnp.float32
        )
        if axis_name is not None:
            killed_local = jax.lax.psum(killed_local, axis_name)
        killed = killed_local > 0.5
        a_new = a0 & ~killed
        return a_new, jnp.any(a_new != a)

    accepted, _ = jax.lax.while_loop(cond, body, (a0, jnp.array(True)))

    status = _verdicts(too_old, a0, accepted)
    if coarse_lanes:
        status = _mark_coarse_only(
            status, accepted, Of, hist & ~exact & ~too_old, axis_name)
    status = jnp.where(batch.txn_mask, status, CONFLICT)

    # ───────────────────────── history update ─────────────────────────────
    cv = batch.cv
    hb_mask = u32((1 << params.hash_bits) - 1)

    ht = state.ht
    point_coarse = state.point_coarse
    if params.point_writes:
        ok = batch.pw_mask & accepted[:, None]  # [T, PW]
        flat_h = (batch.pw_hash & hb_mask).reshape(-1)
        flat_bk = batch.pw_bucket.reshape(-1)
        # hash table: only the owning shard records (its check lane reads it);
        # point_coarse: replicated — every shard applies the identical update.
        ht_ok = (ok & hash_owned(batch.pw_hash)).reshape(-1)
        ht = ht.at[flat_h].max(
            jnp.where(ht_ok, cv, u32(0)), mode="promise_in_bounds"
        )
        if params.range_reads or params.record_point_coarse:
            # read only by range reads, but a point-specialized variant
            # must still RECORD (the full kernel reads it later)
            val = jnp.where(ok.reshape(-1), cv, u32(0))
            point_coarse = point_coarse.at[
                jnp.clip(flat_bk, 0, point_coarse.shape[0] - 1)
            ].max(val)

    ring_b, ring_e, ring_v = state.ring_b, state.ring_e, state.ring_v
    ring_lo, ring_hi, ring_mask = state.ring_lo, state.ring_hi, state.ring_mask
    ring_head = state.ring_head
    range_L, range_R = state.range_L, state.range_R
    if params.range_writes:
        kr = params.ring_capacity
        own_rw = bucket_owned(batch.rw_lo)
        ok = (batch.rw_mask & own_rw & accepted[:, None]).reshape(-1)  # [T*RW]
        flat_lo = batch.rw_lo.reshape(-1)
        flat_hi = batch.rw_hi.reshape(-1)
        if PB:
            # single-partition entries go exactly to their sub-ring;
            # spanning (or a flood overflowing one sub-ring in a single
            # batch) entries fold conservatively into the coarse
            # summaries — the same direction as eviction
            part_lo = jnp.clip(flat_lo >> pshift, 0, P - 1)
            part_hi = jnp.clip(flat_hi >> pshift, 0, P - 1)
            single = part_lo == part_hi
            ok_ring = ok & single
            onehot = ok_ring[:, None] & (
                part_lo[:, None] == jnp.arange(P)[None, :]
            )
            ranks = jnp.cumsum(onehot.astype(jnp.int32), axis=0) - 1
            rank = jnp.sum(jnp.where(onehot, ranks, 0), axis=1)
            overflow = ok_ring & (rank >= KRs)
            ok_ring = ok_ring & (rank < KRs)
            ok_coarse = ok & (~single | overflow)
            counts = jnp.minimum(
                jnp.sum(onehot.astype(jnp.int32), axis=0), KRs
            )
            pos = jnp.where(
                ok_ring,
                part_lo * KRs + (ring_head[part_lo] + rank) % KRs,
                kr,
            )
            new_head = ((ring_head + counts) % KRs).astype(jnp.int32)
            c_val = jnp.where(ok_coarse, cv, u32(0))
            range_L = range_L.at[
                jnp.clip(flat_lo, 0, range_L.shape[0] - 1)
            ].max(c_val)
            range_R = range_R.at[
                jnp.clip(flat_hi, 0, range_R.shape[0] - 1)
            ].max(c_val)
        else:
            ok_ring = ok
            slot_order = jnp.cumsum(ok) - 1  # position among accepted
            pos = jnp.where(ok, (ring_head + slot_order) % kr, kr)
            new_head = ((ring_head + jnp.sum(ok)) % kr).astype(jnp.int32)
        # fold evicted entries into the coarse interval summary first
        will_evict = jnp.zeros((kr,), bool).at[pos].set(True, mode="drop")
        evict = will_evict & ring_mask
        ev_val = jnp.where(evict, ring_v, u32(0))
        range_L = range_L.at[jnp.clip(ring_lo, 0, range_L.shape[0] - 1)].max(ev_val)
        range_R = range_R.at[jnp.clip(ring_hi, 0, range_R.shape[0] - 1)].max(ev_val)
        # append
        flat_b = batch.rw_b.reshape(-1, params.key_width)
        flat_e = batch.rw_e.reshape(-1, params.key_width)
        ring_b = ring_b.at[pos].set(flat_b, mode="drop")
        ring_e = ring_e.at[pos].set(flat_e, mode="drop")
        ring_v = ring_v.at[pos].set(jnp.where(ok_ring, cv, u32(0)), mode="drop")
        ring_lo = ring_lo.at[pos].set(flat_lo, mode="drop")
        ring_hi = ring_hi.at[pos].set(flat_hi, mode="drop")
        ring_mask = ring_mask.at[pos].set(ok_ring, mode="drop")
        ring_head = new_head
        # folds target arbitrary buckets; sync the replicated summaries
        range_L = pmax_arr(range_L)
        range_R = pmax_arr(range_R)

    new_state = ResolverState(
        # monotone: never regress the window (a recovered resolver's fence
        # must survive proxies whose cv-derived window is still behind it)
        window_start=jnp.maximum(state.window_start, batch.new_window_start),
        ht=ht,
        ring_b=ring_b,
        ring_e=ring_e,
        ring_v=ring_v,
        ring_lo=ring_lo,
        ring_hi=ring_hi,
        ring_mask=ring_mask,
        ring_head=ring_head,
        range_L=range_L,
        range_R=range_R,
        point_coarse=point_coarse,
    )
    return status, accepted, new_state


def validate_params(params: ResolverParams):
    """Shape invariants the kernel's safety argument depends on."""
    if params.txns * params.range_writes > params.ring_capacity:
        raise ValueError(
            f"ring_capacity {params.ring_capacity} < txns*range_writes "
            f"{params.txns * params.range_writes}: one batch could wrap the "
            "ring and silently drop committed range-writes from history"
        )
    if params.bucket_bits > 30 or params.hash_bits > 28:
        raise ValueError("bucket_bits/hash_bits unreasonably large")
    pb = params.ring_partition_bits
    if pb:
        if pb > params.bucket_bits:
            raise ValueError(
                "ring_partition_bits exceeds bucket_bits: partitions are "
                "keyed by the top coarse-bucket bits"
            )
        if params.ring_capacity % (1 << pb):
            raise ValueError(
                "ring_capacity must divide evenly into 2^ring_partition_bits "
                "sub-rings"
            )
        if params.use_pallas:
            raise ValueError(
                "ring_partition_bits and use_pallas are mutually "
                "exclusive: the Pallas VMEM kernel implements the FLAT "
                "ring layout (silently ignoring the explicit pallas "
                "request would misattribute benchmarks)"
            )


def _dense_side(T, K, txn, mask, *fields):
    """One compacted lane side ``[Q]`` back on the dense ``[T, K]`` grid
    of the one-lane step → ``((*fields[T, K, ...], live[T, K]), over)``,
    the first None for a side with no slots.

    A live slot's column is its ordinal among the live slots of its txn
    (counted here, whatever order the router emitted them in); padding
    slots (txn 0, mask False) land nowhere. ``over``: some txn holds
    more than K live slots here, which no routed batch does (a point has
    one lane, a range one slot a lane) — the caller keeps it safe.
    """
    Q = txn.shape[0]
    if not Q:
        return None, False
    q = jnp.arange(Q, dtype=jnp.int32)
    earlier = (
        (txn[None, :] == txn[:, None]) & mask[None, :]
        & (q[None, :] < q[:, None])
    )  # [Q, Q']: q' is a live slot of q's txn ahead of q
    ordinal = jnp.sum(earlier, axis=1, dtype=jnp.int32)
    fits = mask & (ordinal < K)
    cell = jnp.where(fits, txn * K + ordinal, T * K)
    slot = (
        jnp.full((T * K,), Q, jnp.int32).at[cell].set(q, mode="drop")
    ).reshape(T, K)
    src = jnp.minimum(slot, Q - 1)
    dense = (*(f[src] for f in fields), slot < Q)
    return dense, jnp.any(mask & ~fits)


def resolve_batch_presharded(
    state: ResolverState,
    sb: ShardBatch,
    params: ResolverParams,
    axis_name=None,
):
    """The compacted-lane resolver step (single-dispatch sharded path).

    Semantics match ``resolve_batch``'s sharded mode, but ownership is
    established HOST-side by the router instead of in-kernel masks: each
    lane sees only the entries whose keys it owns. The history checks
    run over the lane's Q compacted slots (the [Q, KR] ring scan is
    the term that shrinks with the lane count); the intra-batch matrix
    is ``resolve_batch``'s own dense compare (``_overlap_matrix``) over
    the lane's slots put back on a [T, K] grid (``_dense_side``).

    Correctness rests on the routing invariants (ShardBatch docstring):
    any read/write pair that overlaps shares a key point p, and both
    entries are routed to lane(p), so every conflict is checked on at
    least one lane; ``por``/psum folds the per-lane partials. Per-lane
    scalars (``rv``, ``txn_mask``, ``cv``, window) are replicated, so
    ``too_old``/``status``/``accepted`` come out replicated — the proxy
    reads ONE verdict vector.
    """
    T = params.txns
    u32 = jnp.uint32
    rv = sb.rv  # [T]
    Qpr = sb.pr_key.shape[0]
    Qpw = sb.pw_key.shape[0]
    Qrr = sb.rr_b.shape[0]
    Qrw = sb.rw_b.shape[0]

    if axis_name is None:

        def por(x):
            return x

        def pmax_arr(x):
            return x

    else:
        names = axis_name if isinstance(axis_name, tuple) else (axis_name,)

        def por(x):
            return jax.lax.psum(x.astype(jnp.int32), names) > 0

        def pmax_arr(x):
            return jax.lax.pmax(x, names)

    # ───────────────────────── history conflicts ─────────────────────────
    too_old = rv < state.window_start

    # per-txn hit counts accumulate by scatter-ADD (a bool scatter-max is
    # not portably lowered); padding slots point at txn 0 with mask False
    # so they add zero
    hist_i = jnp.zeros((T,), jnp.int32)
    exact_i = jnp.zeros((T,), jnp.int32)  # the exact lanes' hits alone

    if params.range_writes:
        pref_L = jax.lax.associative_scan(jnp.maximum, state.range_L)
        suf_R = jax.lax.associative_scan(jnp.maximum, state.range_R, reverse=True)

    if Qpr:
        rv_q = rv[sb.pr_txn]  # [Qpr]
        hit = (
            state.ht[sb.pr_hash & u32((1 << params.hash_bits) - 1)] > rv_q
        ) & sb.pr_mask
        if params.range_writes:
            in_rng = _point_in(
                sb.pr_key[:, None, :], state.ring_b[None], state.ring_e[None]
            )  # [Qpr, KR]
            newer = (state.ring_v[None] > rv_q[:, None]) & state.ring_mask[None]
            hit |= jnp.any(in_rng & newer, axis=1) & sb.pr_mask
        exact_i = exact_i.at[sb.pr_txn].add(
            hit.astype(jnp.int32), mode="promise_in_bounds"
        )
        if params.range_writes:
            coarse = jnp.minimum(pref_L[sb.pr_bucket], suf_R[sb.pr_bucket])
            hit |= (coarse > rv_q) & sb.pr_mask
        hist_i = hist_i.at[sb.pr_txn].add(
            hit.astype(jnp.int32), mode="promise_in_bounds"
        )

    if Qrr:
        rv_q = rv[sb.rr_txn]  # [Qrr]
        hit = jnp.zeros((Qrr,), bool)
        if params.range_writes:
            ov = ranges_overlap(
                sb.rr_b[:, None, :], sb.rr_e[:, None, :],
                state.ring_b[None], state.ring_e[None],
            )  # [Qrr, KR]
            newer = (state.ring_v[None] > rv_q[:, None]) & state.ring_mask[None]
            hit |= jnp.any(ov & newer, axis=1) & sb.rr_mask
            exact_i = exact_i.at[sb.rr_txn].add(
                hit.astype(jnp.int32), mode="promise_in_bounds"
            )
            coarse_rng = jnp.minimum(pref_L[sb.rr_hi], suf_R[sb.rr_lo])
            hit |= (coarse_rng > rv_q) & sb.rr_mask
        if params.point_writes:
            levels = _sparse_table(state.point_coarse)
            pmax = _range_max(levels, sb.rr_lo, sb.rr_hi)
            hit |= (pmax > rv_q) & sb.rr_mask
        hist_i = hist_i.at[sb.rr_txn].add(
            hit.astype(jnp.int32), mode="promise_in_bounds"
        )

    hist = por(hist_i > 0)
    exact = por(exact_i > 0)

    # ─────────────────────── intra-batch conflict matrix ───────────────────
    # Each compacted side goes back onto a dense [T, K] grid (one
    # scatter of Q slot indices, a gather of its fields) and the matrix
    # is the dense compare of the one-lane step, over this lane's
    # entries only; cross-lane duplicates (a spanning write × spanning
    # read seen on two lanes) re-derive the same bit, and the Jacobi
    # loop's psum folds the lanes.
    pw, pw_over = _dense_side(
        T, params.point_writes, sb.pw_txn, sb.pw_mask, sb.pw_hash, sb.pw_key)
    pr, pr_over = _dense_side(
        T, params.point_reads, sb.pr_txn, sb.pr_mask, sb.pr_hash, sb.pr_key)
    rw, rw_over = _dense_side(
        T, params.range_writes, sb.rw_txn, sb.rw_mask, sb.rw_b, sb.rw_e)
    rr, rr_over = _dense_side(
        T, params.range_reads, sb.rr_txn, sb.rr_mask, sb.rr_b, sb.rr_e)
    O = _overlap_matrix(
        T,
        pw and (*pw[:2], lambda: pw[2]),
        pr,
        rw and (*rw[:2], lambda: rw[2]),
        rr,
    )
    # a txn with more than K slots a side in one lane is not a ShardBatch
    # the router builds, and its surplus is not on the grid: such a batch
    # stays on the safe side, every txn dying behind any earlier one
    O |= pw_over | pr_over | rw_over | rr_over

    strict_lower = jnp.tril(jnp.ones((T, T), bool), k=-1).T  # [t1 < t2]
    O &= strict_lower & sb.txn_mask[:, None] & sb.txn_mask[None, :]

    # Jacobi fixpoint — identical to resolve_batch: the kill vector is
    # psum-reduced per iteration (d small [T] reductions beat one [T,T]
    # all-reduce for the shallow chains real batches carry)
    a0 = (~too_old) & (~hist) & sb.txn_mask
    Of = O.astype(jnp.bfloat16)

    def cond(carry):
        _, changed = carry
        return changed

    def body(carry):
        a, _ = carry
        killed_local = jnp.dot(
            a.astype(jnp.bfloat16), Of, preferred_element_type=jnp.float32
        )
        if axis_name is not None:
            killed_local = jax.lax.psum(killed_local, axis_name)
        killed = killed_local > 0.5
        a_new = a0 & ~killed
        return a_new, jnp.any(a_new != a)

    accepted, _ = jax.lax.while_loop(cond, body, (a0, jnp.array(True)))

    status = _verdicts(too_old, a0, accepted)
    if has_coarse_lanes(params):
        status = _mark_coarse_only(
            status, accepted, Of, hist & ~exact & ~too_old, axis_name)
    status = jnp.where(sb.txn_mask, status, CONFLICT)

    # ───────────────────────── history update ─────────────────────────────
    cv = sb.cv
    ht = state.ht
    point_coarse = state.point_coarse
    if Qpw:
        ok = sb.pw_mask & accepted[sb.pw_txn]  # [Qpw]
        ht = ht.at[sb.pw_hash & u32((1 << params.hash_bits) - 1)].max(
            jnp.where(ok, cv, u32(0)), mode="promise_in_bounds"
        )
        if params.range_reads or params.record_point_coarse:
            # unlike the dense sharded path (where every lane applies the
            # identical replicated update), lanes here record DIFFERENT
            # subsets — the replicated summary needs an explicit pmax
            point_coarse = point_coarse.at[
                jnp.clip(sb.pw_bucket, 0, point_coarse.shape[0] - 1)
            ].max(jnp.where(ok, cv, u32(0)))
            point_coarse = pmax_arr(point_coarse)

    ring_b, ring_e, ring_v = state.ring_b, state.ring_e, state.ring_v
    ring_lo, ring_hi, ring_mask = state.ring_lo, state.ring_hi, state.ring_mask
    ring_head = state.ring_head
    range_L, range_R = state.range_L, state.range_R
    if Qrw:
        kr = ring_v.shape[0]
        ok = sb.rw_mask & accepted[sb.rw_txn]  # [Qrw]
        slot_order = jnp.cumsum(ok) - 1
        # a skewed split can exceed the per-lane ring in one batch (the
        # dense path's T*RW <= KR invariant is per-lane Q-dependent
        # here): overflowing entries fold conservatively into the coarse
        # interval summaries — the same direction as eviction
        ok_ring = ok & (slot_order < kr)
        overflow = ok & (slot_order >= kr)
        pos = jnp.where(ok_ring, (ring_head + slot_order) % kr, kr)
        new_head = (
            (ring_head + jnp.minimum(jnp.sum(ok), kr)) % kr
        ).astype(jnp.int32)
        o_val = jnp.where(overflow, cv, u32(0))
        range_L = range_L.at[
            jnp.clip(sb.rw_lo, 0, range_L.shape[0] - 1)
        ].max(o_val)
        range_R = range_R.at[
            jnp.clip(sb.rw_hi, 0, range_R.shape[0] - 1)
        ].max(o_val)
        # fold evicted entries into the coarse interval summary first
        will_evict = jnp.zeros((kr,), bool).at[pos].set(True, mode="drop")
        evict = will_evict & ring_mask
        ev_val = jnp.where(evict, ring_v, u32(0))
        range_L = range_L.at[jnp.clip(ring_lo, 0, range_L.shape[0] - 1)].max(ev_val)
        range_R = range_R.at[jnp.clip(ring_hi, 0, range_R.shape[0] - 1)].max(ev_val)
        ring_b = ring_b.at[pos].set(sb.rw_b, mode="drop")
        ring_e = ring_e.at[pos].set(sb.rw_e, mode="drop")
        ring_v = ring_v.at[pos].set(jnp.where(ok_ring, cv, u32(0)), mode="drop")
        ring_lo = ring_lo.at[pos].set(sb.rw_lo, mode="drop")
        ring_hi = ring_hi.at[pos].set(sb.rw_hi, mode="drop")
        ring_mask = ring_mask.at[pos].set(ok_ring, mode="drop")
        ring_head = new_head
        # folds target arbitrary buckets; sync the replicated summaries
        range_L = pmax_arr(range_L)
        range_R = pmax_arr(range_R)

    new_state = ResolverState(
        window_start=jnp.maximum(state.window_start, sb.new_window_start),
        ht=ht,
        ring_b=ring_b,
        ring_e=ring_e,
        ring_v=ring_v,
        ring_lo=ring_lo,
        ring_hi=ring_hi,
        ring_mask=ring_mask,
        ring_head=ring_head,
        range_L=range_L,
        range_R=range_R,
        point_coarse=point_coarse,
    )
    return status, accepted, new_state


def validate_presharded_params(params: ResolverParams):
    """Invariants of the compacted-lane path. The dense path's
    T*RW <= KR wrap check does not apply: the kernel detects per-lane
    ring overflow at trace shapes and folds the excess into the coarse
    summaries instead of wrapping."""
    if params.use_pallas:
        raise ValueError(
            "presharded resolve has no Pallas lanes: the VMEM kernel "
            "implements the dense [T, K] layout (silently ignoring the "
            "explicit pallas request would misattribute benchmarks)"
        )
    if params.ring_partition_bits:
        raise ValueError(
            "ring_partition_bits is a single-device layout; the presharded "
            "path shards the ring across lanes instead"
        )
    if params.bucket_bits > 30 or params.hash_bits > 28:
        raise ValueError("bucket_bits/hash_bits unreasonably large")


# ShardBatch fields every lane holds whole (everything else is a lane's
# own compacted slot array, split on its leading axis)
SHARD_REPLICATED = frozenset({"rv", "txn_mask", "cv", "new_window_start"})


class ArgLayout(NamedTuple):
    """What one packed row holds: the batch type, the lanes it is split
    over (0: not split) and, for every field in order, its dtype char
    and the shape one row carries (a lane's share of a split field).
    Hashable: the jitted programs take it as a static argument."""

    cls: type
    lanes: int
    fields: tuple


def arg_layout(batch, lanes=0):
    """The :class:`ArgLayout` of ``batch`` (a ``ResolveBatch`` or a
    ``ShardBatch``, arrays or shapes), read off its fields' shapes;
    axes ahead of ``rv``'s ``[T]`` are batch axes and belong to no row."""
    lead = len(batch.rv.shape) - 1
    fields = []
    for name, a in zip(batch._fields, batch):
        shape = tuple(a.shape)[lead:]
        if lanes and name not in SHARD_REPLICATED:
            shape = (shape[0] // lanes,) + shape[1:]
        fields.append((np.dtype(a.dtype).char, shape))
    return ArgLayout(type(batch), lanes, tuple(fields))


@functools.lru_cache(maxsize=None)
def _arg_spans(layout):
    """((word offset, words, bytes) of every field, words a row): each
    field starts on a word, so a bool field (one byte a slot) is padded
    to a whole one."""
    spans, at = [], 0
    for char, shape in layout.fields:
        nbytes = math.prod(shape) * np.dtype(char).itemsize
        words = -(-nbytes // 4)
        spans.append((at, words, nbytes))
        at += words
    return tuple(spans), at


def arg_spans(layout):
    """(word offset, words, bytes) of every field of one packed row."""
    return _arg_spans(layout)[0]


def arg_words(layout):
    """uint32 words in one packed row."""
    return _arg_spans(layout)[1]


class PackedBatch(NamedTuple):
    """A batch already in the one array its program takes: the rows
    ``pack_args`` would make of it (or a router wrote in place:
    resolver/packing.py ``ShardRouter``) and their layout."""

    buf: np.ndarray
    layout: ArgLayout


def pack_args(batch, layout):
    """``batch`` as ONE host array: ``uint32[*lead, N]``, or
    ``uint32[*lead, lanes, N]`` where row j is lane j's slots and the
    replicated fields are written into every row. A field's bytes lie at
    its static word offset as they lie in memory (int32 as its bits,
    four mask bytes a word); ``unpack_args`` is the inverse, traced.

    One ``bytes.join`` makes the copy, and the result is a fresh
    read-only buffer nothing else writes (a CPU backend may alias host
    memory). A numpy store of more than a few hundred elements gives the
    interpreter lock up, and on the dispatching thread every such store
    is a wait behind the request threads (PERF.md §6, PR 33); ``join``
    over buffers that are not ``bytes`` never does."""
    spans, N = _arg_spans(layout)
    lead = tuple(batch.rv.shape[:-1])
    rows, lanes = math.prod(lead), layout.lanes
    cols = []
    for name, a, (_, words, nbytes) in zip(batch._fields, batch, spans):
        if not nbytes:
            continue
        a = np.ascontiguousarray(a)
        split = lanes and name not in SHARD_REPLICATED
        cols.append((a.reshape((rows, lanes, -1) if split else (rows, -1)),
                     split, bytes(4 * words - nbytes)))
    pieces = []
    for r in range(rows):
        for j in range(lanes or 1):
            for a, split, pad in cols:
                pieces.append(a[r, j] if split else a[r])
                if pad:
                    pieces.append(pad)
    buf = np.frombuffer(b"".join(pieces), np.uint32)
    return buf.reshape(lead + ((lanes,) if lanes else ()) + (N,))


def unpack_args(buf, layout):
    """The batch ``pack_args`` packed, from ``uint32[..., N]`` inside a
    jitted function: static slices, a bitcast for int32, shifts for the
    mask bytes. Leading axes of ``buf`` lead every field."""
    lead = tuple(buf.shape[:-1])
    shifts = jnp.arange(0, 32, 8, dtype=jnp.uint32)
    out = []
    for (char, shape), (at, words, nbytes) in zip(
            layout.fields, _arg_spans(layout)[0]):
        x = buf[..., at:at + words]
        if char == "?":
            x = (x[..., None] >> shifts) & jnp.uint32(0xFF)
            x = x.reshape(lead + (4 * words,))[..., :nbytes] != 0
        elif char == "i":
            x = jax.lax.bitcast_convert_type(x, jnp.int32)
        out.append(x.reshape(lead + shape))
    return layout.cls(*out)


def unpack_args_host(packed):
    """numpy mirror of :func:`unpack_args`: the batch ``pack_args``
    packed into ``packed.buf``, as ``pack_args`` took it — a lane-split
    layout's lane rows joined again on each split field's slot axis, a
    replicated field read from lane 0."""
    buf, layout = packed
    lanes = layout.lanes
    raw = np.ascontiguousarray(buf).view(np.uint8)
    lead = raw.shape[:-2] if lanes else raw.shape[:-1]
    out = []
    for name, (char, shape), (at, _words, nbytes) in zip(
            layout.cls._fields, layout.fields, arg_spans(layout)):
        x = raw[..., 4 * at:4 * at + nbytes].view(np.dtype(char))
        if lanes and name in SHARD_REPLICATED:
            x = x[..., 0, :]
        elif lanes:
            shape = (lanes * shape[0],) + shape[1:]
        out.append(x.reshape(lead + shape).copy())
    return layout.cls(*out)


class PackedProgram:
    """A resolve program ``fn(state, buf, layout)``, jitted with the
    layout static and the state donated, behind the call its callers
    make, ``(state, batch)``: the batch goes to the device as the one
    array ``pack_args`` builds, whatever its fields. The XLA module
    keeps ``fn``'s name: the benchmark finds a program by it."""

    def __init__(self, fn, donate, lanes=0):
        self.jitted = jax.jit(fn, static_argnums=2,
                              donate_argnums=(0,) if donate else ())
        self.lanes = lanes

    def __call__(self, state, batch):
        layout = arg_layout(batch, self.lanes)
        return self.jitted(state, pack_args(batch, layout), layout)

    def call_packed(self, state, packed):
        """The call for a :class:`PackedBatch`: its rows go to the
        device as they are."""
        return self.jitted(state, packed.buf, packed.layout)

    def trace(self, state, batch):
        """``jitted.trace`` for a batch of arrays or of shapes."""
        layout = arg_layout(batch, self.lanes)
        lead = tuple(batch.rv.shape[:-1])
        row = ((self.lanes,) if self.lanes else ()) + (arg_words(layout),)
        return self.jitted.trace(
            state, jax.ShapeDtypeStruct(lead + row, jnp.uint32), layout)

    def lower(self, state, batch):
        return self.trace(state, batch).lower()


def fold_coarse(state: ResolverState, params: ResolverParams):
    """What the coarse lanes hold, made true under ANY bucket map: the
    host runs this between the last step under one map and the first
    under the next (module text, "rebucket"). Each summary becomes its
    own maximum everywhere; a ring entry's begin / end bucket, which only
    its eviction reads, becomes 0 / C − 1. A partitioned ring is emptied
    into the summaries, since its entries sit where their old begin
    bucket put them."""
    u32 = jnp.uint32
    top_L, top_R = jnp.max(state.range_L), jnp.max(state.range_R)
    ring_mask = state.ring_mask
    if params.ring_partition_bits:
        live = jnp.max(jnp.where(ring_mask, state.ring_v, u32(0)))
        top_L, top_R = jnp.maximum(top_L, live), jnp.maximum(top_R, live)
        ring_mask = jnp.zeros_like(ring_mask)
    return state._replace(
        range_L=jnp.full_like(state.range_L, top_L),
        range_R=jnp.full_like(state.range_R, top_R),
        point_coarse=jnp.full_like(
            state.point_coarse, jnp.max(state.point_coarse)),
        ring_lo=jnp.zeros_like(state.ring_lo),
        ring_hi=jnp.full_like(state.ring_hi, state.range_L.shape[0] - 1),
        ring_mask=ring_mask,
    )


def make_fold_fn(params: ResolverParams, like: ResolverState):
    """jit-compiled :func:`fold_coarse`, the history donated, every
    array placed where ``like``'s is (a mesh's state stays sharded as
    its step programs take it, so that no step is built again). One
    program a shape and placement, whoever asks."""
    placed = None
    if len(like.ht.sharding.device_set) > 1:
        # (one device's arrays stay uncommitted, as the step made them:
        # a committed input would be a new program to the step's jit)
        placed = jax.tree.map(lambda a: a.sharding, like)
    return _fold_fn(params, placed)


@functools.lru_cache(maxsize=None)
def _fold_fn(params, out_shardings):
    def fold_coarse_state(state):
        return fold_coarse(state, params)

    placed = {} if out_shardings is None else {"out_shardings": out_shardings}
    return jax.jit(fold_coarse_state, donate_argnums=(0,), **placed)


def _packed_step(params):
    """``resolve_batch`` on one packed row, ``(state, row, layout)``."""
    return lambda state, row, layout: resolve_batch(
        state, unpack_args(row, layout), params)


def make_resolve_fn(params: ResolverParams, donate=True):
    """jit-compiled resolver step with the history buffers donated,
    behind :class:`PackedProgram`: called ``(state, batch)``, it hands
    the device the state and ONE host array, as every mesh program does
    (parallel/mesh.py). The jitted call costs the dispatching thread
    work for every host array it is handed, nobody contending: 22
    fields 1.48 ms, one array 0.62 with its pack, on the chip's idle
    host, and under ``CommitProxy._commit_mu`` that work is the batch
    every commit waits behind (PERF.md §6, PR 40)."""
    validate_params(params)
    fn = _packed_step(params)
    if params.range_reads or params.range_writes:
        # the step with range lanes has a name of its own in a trace
        # (``jit_resolve_full``); the point-only step keeps the lambda's
        # (``jit__lambda``), which the benchmark's older cells read
        fn.__name__ = "resolve_full"
    return PackedProgram(fn, donate)


def scan_of(step_fn):
    """Lift a single-batch resolver step into a multi-batch scan:
    (state, batches[B, ...]) → (state, statuses[B, T]), the history
    threaded sequentially exactly as B successive calls would. Shared by
    the single-device and shard_map paths so the scan semantics cannot
    diverge between them."""

    def scan_step(state, batches):
        def body(s, b):
            status, _accepted, s2 = step_fn(s, b)
            return s2, status

        return jax.lax.scan(body, state, batches)

    return scan_step


def packed_scan_of(step):
    """:func:`scan_of` for ``step(state, row, layout)`` over a stack of
    packed rows: an iteration unpacks its own row."""

    def scan_step(state, rows, layout):
        return scan_of(functools.partial(step, layout=layout))(state, rows)

    return scan_step


def make_resolve_scan_fn(params: ResolverParams, donate=True):
    """jit-compiled *multi-batch* resolver step: ``lax.scan`` threads the
    history through a stack of batches (leading axis B) in one dispatch.

    The scan runs the jnp ring lanes whatever ``use_pallas`` says: the
    Pallas ring kernel belongs to the single-step program
    (``make_resolve_fn``) only. The reason on record is that XLA overlaps
    the fused jnp lanes across scan iterations where it would serialise
    repeated ``pallas_call`` launches, so the ring kernel was expected to
    win inside a scan only where the ring walk dominates the step
    (range-heavy traffic); no cell has measured either (PERF.md §7,
    "ring kernel against jnp lanes").

    Semantics are identical to calling ``resolve_batch`` B times in order
    — the scan carry is the same sequential state dependency — but one
    dispatch covers the stack and amortizes the host→device launch
    cost across B batches. This is the proxy's throughput path;
    single-batch ``make_resolve_fn`` is the latency path.
    Returns (state, statuses[B, T]).
    """
    validate_params(params)
    params = params._replace(use_pallas=False)
    return PackedProgram(packed_scan_of(_packed_step(params)), donate)


def count_retraces(fn, on_retrace, gate=None):
    """HOST-side compile-cache observer: wrap a jitted dispatch callable
    so every NEW argument shape/dtype signature fires ``on_retrace(sig)``
    once — a new signature is exactly what forces XLA to retrace and
    recompile. The check runs around the jit call (never inside the
    traced region — FL004), costs one tree-leaves walk per dispatch, and
    is skipped entirely while ``gate()`` is falsy (the profiler kill
    switch), so the disabled arm of the overhead smoke pays nothing but
    the gate call."""
    seen = set()

    def wrapped(*args):
        if gate is None or gate():
            sig = tuple(
                (tuple(getattr(leaf, "shape", ())),
                 str(getattr(leaf, "dtype", type(leaf).__name__)))
                for leaf in jax.tree.leaves(args)
            )
            if sig not in seen:
                seen.add(sig)
                on_retrace(sig)
        return fn(*args)

    return wrapped


def rebase_state(state: ResolverState, delta):
    """Shift all version offsets down by ``delta`` (saturating at 0).

    Called by the host when offsets approach uint32 range
    (core/versions.py REBASE_THRESHOLD). Safe when delta <= the current
    window start: clamped-to-0 entries had versions no read inside the
    window can still see (such reads are rejected TOO_OLD), so clamping
    only forgets writes that can no longer conflict.
    """
    d = jnp.uint32(delta)

    def shift(v):
        return jnp.where(v > d, v - d, jnp.uint32(0))

    return state._replace(
        window_start=shift(state.window_start),
        ht=shift(state.ht),
        ring_v=shift(state.ring_v),
        range_L=shift(state.range_L),
        range_R=shift(state.range_R),
        point_coarse=shift(state.point_coarse),
    )
