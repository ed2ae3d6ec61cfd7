"""Host-side packing: TxnRequests → fixed-shape ResolveBatch arrays.

The analog of ResolveTransactionBatchRequest serialization (ref:
fdbserver/ResolverInterface.h): the commit proxy packs a batch of
transactions' conflict ranges into device arrays once per batch; all key
comparison work then happens on the TPU.

Host hashing/bucketing MUST match the device (ops/intervals.fnv_hash):
the hash table and coarse buckets are written by the kernel with values
the host computed — keep the two implementations in lockstep (test:
tests/test_resolver.py::test_host_device_hash_parity).
"""

import numpy as np

from foundationdb_tpu.core.keys import KeyCodec
from foundationdb_tpu.ops.conflict import (
    SHARD_REPLICATED,
    ArgLayout,
    PackedBatch,
    ResolveBatch,
    ResolverParams,
    ShardBatch,
    arg_spans,
    arg_words,
)


def fnv_hash_np(limbs):
    """numpy twin of ops.intervals.fnv_hash. limbs: uint32[..., W]."""
    with np.errstate(over="ignore"):
        h = np.full(limbs.shape[:-1], 2166136261, dtype=np.uint32)
        for i in range(limbs.shape[-1]):
            h = (h ^ limbs[..., i]) * np.uint32(16777619)
        h = h ^ (h >> 16)
        h = h * np.uint32(0x7FEB352D)
        h = h ^ (h >> 15)
    return h


def bucket_of(limbs, bucket_bits):
    """The coarse bucket a resolver starts with: the top bits of the
    first limb (monotone in the key, and one bucket for every key that
    shares its first four bytes: :class:`CoarseBuckets` cuts finer)."""
    return (limbs[..., 0] >> np.uint32(32 - bucket_bits)).astype(np.int32)


def _slots(c):
    """counts[n] → (txn index, lane index) per flattened op."""
    t_idx = np.repeat(np.arange(len(c)), c)
    starts = np.cumsum(c) - c
    i_idx = np.arange(len(t_idx)) - np.repeat(starts, c)
    return t_idx, i_idx


def _rows_sortable(rows):
    """uint32[..., W] limb rows → bytes scalars[...] whose sort and
    searchsorted order is exactly the limb-lexicographic key order (the
    host twin of ops/intervals.lex_lt): a row's limbs big-endian, end to
    end, compare as one byte string. (A structured dtype of W fields
    gives the same order forty times slower: numpy compares its fields
    one Python-visible step at a time.)"""
    W = rows.shape[-1]
    be = np.ascontiguousarray(rows.astype(">u4"))
    return be.view("S%d" % (4 * W)).reshape(rows.shape[:-1])


class CoarseBuckets:
    """The bucket function of the coarse lanes (ops/conflict.py:
    ``point_coarse``, ``range_L`` / ``range_R``, a ring entry's begin and
    end bucket): an order-preserving map from a limb-encoded key to
    ``[0, C)``, shared by every packer of one resolver.

    It starts as :func:`bucket_of`, the top bits of the first limb, and
    stays that for as long as the resolver meets no range: a server of
    point transactions never reads a coarse lane, and pays one short
    slice store a dispatch here. Real keys share prefixes by construction (tuple
    layer, subspaces, ``user…``, ``mako…``), so under that map all keys
    of an application have one bucket and a range read conflicts with
    any point write anywhere. :meth:`recut` replaces it by ``C − 1``
    sorted boundary rows and a ``searchsorted``: quantiles of a sample of
    the keys this resolver packed (point writes and range begins,
    duplicates kept, so a hot key gets a narrow bucket), as upstream
    balances its resolvers from the keys they see (Resolver.actor.cpp
    ``iopsSample``). Any sorted boundaries are weakly monotone in the
    whole key, which is all safety needs; the resolver folds what the
    summaries hold whenever the boundaries change
    (``Resolver._maybe_rebucket``, ops/conflict.py ``fold_coarse``).

    The sample is every noted row until its buffer of ``2 · capacity``
    rows is full; then every second row is dropped, so it settles between
    ``capacity`` and twice that, a row's weight halving with every
    ``capacity`` rows noted after it. When to cut again has one constant, ``FACTOR``: a cut made
    from fewer than ``capacity`` rows is made again once the rows noted
    have grown by it; a cut made from a full sample only when, every
    ``capacity`` rows, the fullest bucket holds ``FACTOR`` times the
    share a fresh cut would give it (both measured on the half of the
    sample neither cut was made from).
    """

    FACTOR = 2

    def __init__(self, params: ResolverParams, capacity=None):
        self.bits = params.bucket_bits
        self.C = 1 << params.bucket_bits
        # four sampled keys a bucket: a bucket's share is then known to
        # within half of itself, and 2 · capacity rows of W limbs are a
        # few megabytes
        self.capacity = int(capacity or 4 * self.C)
        self._width = params.key_width
        self._bounds = None  # sortable[C − 1], or None: the first limb's bits
        self._sample = None  # uint32[2 · capacity, W], made at the first row
        self._held = 0  # rows of it in use
        self.seen = 0  # rows ever noted
        self._cut_seen = 0  # … when the bounds were last cut
        self._checked = 0  # … when a full-sample cut was last held up

    @property
    def cut(self):
        """Whether boundaries have been cut from a sample."""
        return self._bounds is not None

    def of(self, rows):
        """int32[...] bucket of each limb row uint32[..., W]."""
        if self._bounds is None:
            return bucket_of(rows, self.bits)
        return np.searchsorted(
            self._bounds, _rows_sortable(rows), side="right"
        ).astype(np.int32)

    def of_entries(self, blob, rows):
        """:meth:`of` for the rows of a flat entry blob
        (core/flatpack.py), searched in the blob's own bytes: an entry,
        key padded ‖ ``>I(len)``, is its row in :func:`_rows_sortable`'s
        form already."""
        if self._bounds is None:
            return bucket_of(rows, self.bits)
        if not blob:
            return np.zeros(0, np.int32)
        return np.searchsorted(
            self._bounds, np.frombuffer(blob, self._bounds.dtype),
            side="right").astype(np.int32)

    def note_rows(self, rows):
        """Take limb rows uint32[N, W] into the sample. On the thread
        that dispatches this is one short slice store, which keeps the
        interpreter lock (a call that gives it up costs a herd's turn:
        PERF.md §6, PR 29), and once every ``capacity`` rows a strided
        copy of the buffer."""
        if self._sample is None:
            self._sample = np.empty(
                (2 * self.capacity, rows.shape[-1]), np.uint32)
        buf = self._sample
        self.seen += len(rows)
        while self._held + len(rows) > len(buf):
            kept = buf[1:self._held:2]
            buf[:len(kept)] = kept
            self._held, rows = len(kept), rows[1::2]
        buf[self._held:self._held + len(rows)] = rows
        self._held += len(rows)

    def sample(self):
        """The sampled rows uint32[held, W], oldest first (a view of the
        buffer: the older a row, the more thinned out its neighbours).
        What the lane bounds are cut from too (:class:`LaneBounds`)."""
        if self._sample is None:
            return np.empty((0, self._width), np.uint32)
        return self._sample[:self._held]

    def recut_due(self):
        """Whether :meth:`recut` has something to do (cheap)."""
        if self._cut_seen < self.capacity:
            return self.seen >= max(1, self.FACTOR * self._cut_seen)
        return self.seen - self._checked >= self.capacity

    def recut(self):
        """Cut the boundaries again from the sample if the class text's
        rule says so → whether they changed (the caller then folds the
        device's summaries before its next step)."""
        rows = self.sample()
        self._checked = self.seen
        if self._cut_seen >= self.capacity and not self._stale(rows):
            return False
        self._bounds = self._quantiles(_rows_sortable(rows))
        self._cut_seen = self.seen
        return True

    def _quantiles(self, keys):
        keys = np.sort(keys)
        return keys[(np.arange(1, self.C, dtype=np.int64) * len(keys))
                    // self.C]

    def _stale(self, rows):
        fit, held = _rows_sortable(rows[0::2]), _rows_sortable(rows[1::2])

        def fullest(bounds):
            return np.bincount(
                np.searchsorted(bounds, held, side="right")).max()

        return fullest(self._bounds) >= self.FACTOR * fullest(
            self._quantiles(fit))


# the conflict sides a ShardBatch routes, each with whether its entries
# are ranges
_SIDES = (("pr", False), ("pw", False), ("rr", True), ("rw", True))
# a routed field's dtype by its name's last part, and the parts that are
# key rows (W words a slot)
_PART_CHAR = {"hash": "I", "key": "I", "b": "I", "e": "I", "bucket": "i",
              "txn": "i", "lo": "i", "hi": "i", "mask": "?"}
_KEY_PARTS = ("key", "b", "e")


def _bits(stacked, field, live, rep):
    """A one-word ``field`` of the entries at flat indices ``live``,
    one value a routed slot (``rep``), as uint32 bits."""
    return getattr(stacked, field).reshape(-1)[live][rep].view(np.uint32)


class _Staging:
    """One packed argument a router writes in place: the rows, 1-D views
    of their words and bytes, and each store of its last write with the
    value the slots held before it (what the next write puts back)."""

    def __init__(self, buf):
        self.buf = buf
        self.words = buf.reshape(-1)
        self.octets = self.words.view(np.uint8)
        self.bytes = memoryview(self.octets)
        self.dirty = []

    def reset(self):
        for view, idx, pad in self.dirty:
            view[idx] = pad
        self.dirty.clear()

    def put(self, view, idx, vals, pad):
        # noted before the store: a write that raises halfway leaves no
        # slot changed that the next reset misses
        self.dirty.append((view, idx, pad))
        view[idx] = vals


class ShardRouter:
    """Key-range router for the presharded single-dispatch resolve.

    Consumes the stacked numpy ResolveBatch ``pack_flat_group`` already
    built (no blob re-parse, no per-key Python) and writes every live
    entry at its place in the mesh program's one argument: the rows
    ``uint32[rows, n, N]`` that ``ops/conflict.pack_args`` makes of a
    ShardBatch, row j lane j's COMPACTED slots. Point entries go to
    exactly ``lane(key)``; range entries get one slot in every lane
    their span touches, carrying the full unclipped range. All routing
    is vectorized — one flat nonzero a side, one searchsorted against
    the lane bounds, and a stable argsort-rank (the cumsum trick) to
    assign slots within each (batch, lane) group — and each field of
    the routed entries is stored through one flat index into a 1-D view
    of the rows: the lane row, the field's word offset
    (``ops/conflict._arg_spans``), the slot.

    The rows are the router's own, one buffer a shape, written again by
    the next :meth:`split` that asks to ``reuse`` them. That write first
    puts back what the last one stored (mask bytes 0, the zero key's
    hash, every other word 0), so the argument is bit-identical to a
    fresh build, and a dispatch costs its live entries, not the ``n·Q``
    slots a side and the copy of them into one array (1.3 MB at the
    four-lane cells' shape).

    Per-lane capacity ``Q`` per conflict side is sized to
    ``headroom × T·K / n`` (the balanced-split expectation plus slack);
    a batch whose skew overflows a lane retries split into ``k``
    txn-slices (verdict-equivalent: intra-batch kills become
    history-version kills of the same direction, order preserved) —
    ``reassemble`` undoes the slicing on the status matrix. A
    single-txn slice always fits because Q ≥ K per side.

    ``bounds``: uint32[n-1, W] sorted limb-row split points; lane j owns
    [bounds[j-1], bounds[j]). Defaults to the uniform first-limb split —
    the same keyspace carve ``server/proxy._resolver_range`` uses before
    DD moves boundaries.
    """

    MAX_CHUNK_WARN = 16  # beyond this the host slicing dominates

    def __init__(self, params: ResolverParams, n, bounds=None,
                 headroom=1.75):
        self.params = params
        self.n = int(n)
        W = params.key_width
        if bounds is None:
            first = (
                (np.arange(1, self.n, dtype=np.uint64) << np.uint64(32))
                // np.uint64(self.n)
            ).astype(np.uint32)
            bounds = np.zeros((max(self.n - 1, 0), W), np.uint32)
            bounds[:, 0] = first
        self.bounds = np.ascontiguousarray(
            np.asarray(bounds, np.uint32).reshape(self.n - 1, W)
        )
        self._bounds_s = _rows_sortable(self.bounds)
        T = params.txns
        self.caps = {
            "pr": self._cap(T, params.point_reads, headroom),
            "pw": self._cap(T, params.point_writes, headroom),
            "rr": self._cap(T, params.range_reads, headroom),
            "rw": self._cap(T, params.range_writes, headroom),
        }
        self._zero_hash = fnv_hash_np(np.zeros((1, W), np.uint32))[0]
        self._limbs = np.arange(W)
        self._layout, self._at = self._arg_layout(T)
        self._staging = {}  # rows → _Staging
        self.reused = False  # whether the last split wrote reused rows

    def _cap(self, T, K, headroom):
        """Per-lane slot capacity for a side with K entries/txn: the
        full dense width at n=1 (no routing win possible), otherwise
        the balanced-split share with headroom, floored at K (one txn's
        entries always fit → chunking terminates) and 8-rounded."""
        if not K:
            return 0
        full = T * K
        if self.n == 1:
            return full
        q = max(K, int(np.ceil(headroom * full / self.n)))
        q = -(-q // 8) * 8
        return min(q, full)

    def lane_of_points(self, rows):
        """lane index per limb row (uint32[N, W])."""
        return np.searchsorted(
            self._bounds_s, _rows_sortable(rows), side="right"
        ).astype(np.int64)

    def lane_span(self, b_rows, e_rows):
        """(first, last) lane touched by each range [b, e): the last
        lane is the one containing the greatest key < e, i.e. the count
        of bounds strictly below e."""
        lo = np.searchsorted(
            self._bounds_s, _rows_sortable(b_rows), side="right"
        ).astype(np.int64)
        hi = np.searchsorted(
            self._bounds_s, _rows_sortable(e_rows), side="left"
        ).astype(np.int64)
        return lo, np.maximum(hi, lo)  # degenerate ranges stay 1-lane

    def split(self, stacked: ResolveBatch, reuse=False):
        """stacked numpy ResolveBatch [B, T, …] → (the packed argument:
        ``ops/conflict.PackedBatch`` of rows ``uint32[B·k, n, N]``,
        chunk factor k, per-lane entry counts[n] — the lane_skew_pct
        instrument). ``reuse``: write into the rows the last reusing
        split of this shape returned; the caller has read back the
        verdicts of the dispatch that took them, which depend on them,
        so the device holds them no more. Else into fresh rows, for a
        caller that cannot know. :attr:`reused` says which it was."""
        B, T = stacked.rv.shape
        k = 1
        while True:
            routed = self._route(stacked, B, T, k)
            if routed is not None:
                sides, lane_counts = routed
                return (self._write(stacked, sides, B, T, k, reuse), k,
                        lane_counts)
            k *= 2
            if k > T:
                raise ValueError(
                    "shard split cannot place a single-txn slice: "
                    f"caps {self.caps} mis-sized for T={T}"
                )

    def reassemble(self, st, k):
        """Undo txn-slice chunking on a status stack: [B·k, T] → [B, T]
        (sub-batch c carried txns [c·Ts, (c+1)·Ts) in slots [0, Ts))."""
        if k == 1:
            return st
        T = st.shape[-1]
        Ts = -(-T // k)
        B = st.shape[0] // k
        return st.reshape(B, k, T)[:, :, :Ts].reshape(B, k * Ts)[:, :T]

    def _route(self, stacked, B, T, k):
        """Each side's live entries at ``k`` slices → ([(side, is_range,
        flat entry index, entry of each slot, lane row, slot, txn slot,
        key rows)], per-lane entry counts), or None where a lane would
        take more than its ``Q`` slots of a side."""
        n, Ts, rows = self.n, -(-T // k), B * k
        W = self.params.key_width
        lane_counts = np.zeros(n, np.int64)
        sides = []
        for name, is_range in _SIDES:
            Q = self.caps[name]
            if not Q:
                continue
            mask = getattr(stacked, name + "_mask")
            live = np.flatnonzero(mask)
            if not len(live):
                continue
            b_idx, t_idx = np.divmod(live // mask.shape[-1], T)
            if is_range:
                keys = (getattr(stacked, name + "_b").reshape(-1, W)[live],
                        getattr(stacked, name + "_e").reshape(-1, W)[live])
                lo, hi = self.lane_span(*keys)
                span = hi - lo + 1
                rep = np.repeat(np.arange(len(live)), span)
                lane = lo[rep] + np.arange(len(rep)) - np.repeat(
                    np.cumsum(span) - span, span)
            else:
                keys = getattr(stacked, name + "_key").reshape(-1, W)[live]
                lane = self.lane_of_points(keys)
                rep = slice(None)  # one slot an entry
            g = (b_idx * k + t_idx // Ts)[rep] * n + lane
            counts = np.bincount(g, minlength=rows * n)
            if counts.max() > Q:
                return None
            lane_counts += counts.reshape(rows, n).sum(axis=0)
            order = np.argsort(g, kind="stable")
            rank = np.empty(len(g), np.int64)
            rank[order] = np.arange(len(g)) - (
                np.cumsum(counts) - counts)[g[order]]
            sides.append((name, is_range, live, rep, g, rank,
                          (t_idx % Ts)[rep], keys))
        return sides, lane_counts

    def _write(self, stacked, sides, B, T, k, reuse):
        rows, n, W = B * k, self.n, self.params.key_width
        layout, at = self._layout, self._at
        N = arg_words(layout)
        st = self._staging.get(rows) if reuse else None
        self.reused = st is not None
        if st is None:
            st = _Staging(self._fresh(rows, N))
            if reuse:
                self._staging[rows] = st
        else:
            st.reset()
        words, octets, put = st.words, st.octets, st.put
        u32 = np.uint32
        for name, is_range, live, rep, g, rank, txn, keys in sides:
            row = g * N  # the slot's lane row: its first word
            one = row + rank  # + a one-word field's offset
            wide = (row + rank * W)[:, None] + self._limbs  # + a key's
            put(octets, 4 * (row + at[name + "_mask"]) + rank, 1, 0)
            put(words, one + at[name + "_txn"], txn.astype(u32), 0)
            if is_range:
                put(words, wide + at[name + "_b"], keys[0][rep], 0)
                put(words, wide + at[name + "_e"], keys[1][rep], 0)
                plain = ("lo", "hi")
            else:
                put(words, wide + at[name + "_key"], keys[rep], 0)
                plain = ("hash", "bucket")
            for part in plain:
                f = name + "_" + part
                put(words, one + at[f], _bits(stacked, f, live, rep),
                    self._zero_hash if part == "hash" else 0)

        if k == 1:
            rv, txn_mask = stacked.rv, stacked.txn_mask
            cv = np.asarray(stacked.cv, u32).reshape(B)
            nws = np.asarray(stacked.new_window_start, u32).reshape(B)
        else:
            Ts = -(-T // k)
            pad = k * Ts - T
            rv = np.zeros((rows, T), u32)
            txn_mask = np.zeros((rows, T), np.bool_)
            rv[:, :Ts] = np.pad(
                stacked.rv, ((0, 0), (0, pad))
            ).reshape(rows, Ts)
            txn_mask[:, :Ts] = np.pad(
                stacked.txn_mask, ((0, 0), (0, pad))
            ).reshape(rows, Ts)
            cv = np.repeat(np.asarray(stacked.cv, u32).reshape(B), k)
            # the window advance rides ONLY the last slice of each
            # batch: earlier slices of the same batch must be judged
            # under the pre-batch window, exactly as the dense kernel
            # computes too_old before applying new_window_start
            nws = np.zeros(rows, u32)
            nws[k - 1 :: k] = np.asarray(
                stacked.new_window_start, u32
            ).reshape(B)
        # the replicated fields, whole into every lane row: copies of a
        # memoryview keep the interpreter lock, which a numpy store of
        # a few thousand elements gives up (PERF.md §6)
        rv = np.ascontiguousarray(rv, u32)
        txn_mask = np.ascontiguousarray(txn_mask, np.bool_)
        a_rv, a_tm = 4 * at["rv"], 4 * at["txn_mask"]
        for r in range(rows):
            rv_r = memoryview(rv[r]).cast("B")
            tm_r = memoryview(txn_mask[r]).cast("B")
            for o in range(4 * N * r * n, 4 * N * (r + 1) * n, 4 * N):
                st.bytes[o + a_rv:o + a_rv + 4 * T] = rv_r
                st.bytes[o + a_tm:o + a_tm + T] = tm_r
        st.buf[:, :, at["cv"]] = cv[:, None]
        st.buf[:, :, at["new_window_start"]] = nws[:, None]
        return PackedBatch(st.buf, layout)

    def _arg_layout(self, T):
        """(the ArgLayout of this router's ShardBatch at ``T`` txns, the
        word offset of each field in a lane row)."""
        W = self.params.key_width
        fields = []
        for name in ShardBatch._fields:
            if name in SHARD_REPLICATED:
                fields.append(("?" if name == "txn_mask" else "I",
                               (T,) if name in ("rv", "txn_mask") else ()))
                continue
            side, part = name.split("_")
            fields.append((_PART_CHAR[part], (self.caps[side],) + (
                (W,) if part in _KEY_PARTS else ())))
        layout = ArgLayout(ShardBatch, self.n, tuple(fields))
        return layout, {name: span[0] for name, span in zip(
            ShardBatch._fields, arg_spans(layout))}

    def _fresh(self, rows, N):
        """Rows in the state a reset leaves: every slot empty, its hash
        the zero key's (``BatchPacker``'s pad entry)."""
        buf = np.zeros((rows, self.n, N), np.uint32)
        for name in ("pr", "pw"):
            at = self._at[name + "_hash"]
            buf[:, :, at:at + self.caps[name]] = self._zero_hash
        return buf


class LaneBounds:
    """When a mesh's lane bounds are cut again from the keys its
    resolver packed (upstream: masterserver.actor.cpp
    ``resolutionBalancing``, fed by the resolvers' ``iopsSample``).

    The rows are :meth:`CoarseBuckets.sample`'s: point writes and range
    begins in arrival order, duplicates kept. A look takes the newest
    ``CHECK`` rows as what the lanes are asked to carry now and cuts
    fresh bounds at the n-quantiles of the rows before them, so the rows
    a cut is judged on are never rows it was made from. The bounds in
    force are stale when the fresh ones would take a lane's fair share,
    1/n of those newest rows, off the fullest lane. The test is a
    difference and not a ratio because a bound is a whole key row and no
    bound splits a key: under YCSB's Zipfian the hottest key is a third
    to a half of the commit attempts, its lane holds 55-60% of them
    under the best bounds there are, and "twice the share a fresh cut
    would give" is then something one lane holding everything never
    reaches. The first cut, from the first limb's uniform split, is
    held to the same test: a table loaded in key order sends every new
    row past every row before it, one lane has them all under any bounds
    cut from the past, and nothing is cut until rows arrive that a cut
    would spread. A moved bound moves exact history, so every cut costs
    the resolver a fence (``MeshResolver._maybe_rebound``): the rule is
    there to cut seldom.

    The constants, swept on the chip's host (PERF.md §6, PR 37):
    """

    # rows the sample holds before the first look: a quartile of 4,096
    # rows is known to 0.7% of the rows (sqrt(3/16 / 4096))
    FIRST = 4096
    # rows noted between two looks, and the newest rows a look judges
    # by: a lane's share of 512 rows is known to 2%, against a test of
    # 25%; at 600 rows a second (YCSB-A behind four lanes) the cut that
    # follows a load falls a second into the traffic
    CHECK = 512
    # rows of the sample a look sorts, evenly strided: three quantiles
    # of 4,096 rows are as good as of 131,072, and the sort then takes
    # 1.4 ms where the whole sample's takes 53
    FIT = 4096

    def __init__(self, n):
        self.n = int(n)
        self._looked = 0  # CoarseBuckets.seen at the last look

    def due(self, buckets):
        """Whether :meth:`look` has something to do (cheap)."""
        return (self.n > 1 and buckets.seen >= self.FIRST
                and buckets.seen - self._looked >= self.CHECK)

    def fresh(self, rows):
        """Bounds uint32[n − 1, W] at the n-quantiles of limb rows
        ``rows`` (a stride of them), or None under ``FIRST`` rows."""
        if len(rows) < self.FIRST:
            return None
        fit = rows[::max(1, len(rows) // self.FIT)]
        order = np.argsort(_rows_sortable(fit), kind="stable")
        return fit[order[(np.arange(1, self.n) * len(fit)) // self.n]]

    def look(self, buckets, router):
        """→ (a router with new bounds, the newest rows' share a lane
        under ``router``, … under the new one) where ``router``'s bounds
        are stale, else None."""
        self._looked = buckets.seen
        rows = buckets.sample()
        newest = rows[-self.CHECK:]

        def shares(r):
            return np.bincount(r.lane_of_points(newest),
                               minlength=self.n) / len(newest)

        before = shares(router)
        if before.max() < 2.0 / self.n:
            # no cut takes 1/n off a lane that holds under 2/n: the
            # look of a balanced mesh ends here, before the sort (under
            # load a sort costs the dispatching thread a turn at the
            # interpreter lock: 5 ms a look, PERF.md §6, PR 37)
            return None
        bounds = self.fresh(rows[:-self.CHECK])
        if bounds is None:
            return None
        cut = ShardRouter(router.params, self.n, bounds=bounds)
        after = shares(cut)
        if before.max() - after.max() < 1.0 / self.n:
            return None
        return cut, before, after


class BatchPacker:
    """Packs transactions for one resolver (arrival order preserved).

    Two paths, bit-identical outputs (tests/test_packing_native.py):
      - native: one C pass over the txn list (native/packer.cpp) — the
        default when the toolchain is available; >10x the numpy path.
      - numpy: whole-batch frombuffer encoding — the fallback, and the
        only path that handles lane overflow (spill/coalesce), so the
        native path defers to it on overflow (return code 1).
    """

    # staging sets kept alive per stacked shape before a slot is reused:
    # jax may alias (zero-copy) host numpy arrays into device buffers on
    # CPU backends, and the commit pipeline keeps up to
    # commit_pipeline_depth groups in flight — a slot must outlive every
    # dispatch that could still be reading it
    STAGING_RING = 4

    def __init__(self, params: ResolverParams, use_native=True,
                 buckets=None):
        self.params = params
        # the coarse lanes' bucket function: a resolver hands both its
        # packers (full and point-only) the one it rebuckets
        self.buckets = buckets if buckets is not None else CoarseBuckets(
            params)
        self.codec = KeyCodec(num_limbs=params.key_width - 1)
        self._native = None
        self._empty = None  # cached zero-txn pad batch (pack_empty)
        self._flat_rings = {}  # B → list of reusable staging sets
        self._flat_ring_next = {}  # B → next slot index
        # fnv of an all-zero key row: what a pad entry's hash lane holds
        self._zero_hash = fnv_hash_np(
            np.zeros((1, params.key_width), np.uint32))[0]
        # one limb row as ONE element, and arange, cached (_iota_to)
        self._row = np.dtype((np.void, 4 * params.key_width))
        self._iota = np.arange(4 * params.txns)
        self.flat_reuse_hits = 0
        # device-path profiler hook (utils/deviceprofile.py): the
        # owning resolver attaches its DeviceProfile so staging-ring
        # reuse-vs-realloc events land in the cluster.device doc
        self.profile = None
        if use_native and params.key_width - 1 <= 16:
            from foundationdb_tpu.native import load_packer

            self._native = load_packer()

    # ── flat columnar path (core/flatpack.py FlatTxnBatch) ───────────
    def flat_fits(self, flat):
        """Whether pack_flat_group can serve this batch: matching limb
        width and every txn's op counts inside the packed lanes (the
        legacy path's _normalize spill/coalesce has no flat twin — the
        rare overflowing batch decodes and rides legacy)."""
        p = self.params
        return (
            flat.num_limbs == p.key_width - 1
            and len(flat) <= p.txns
            and flat.prc.max(initial=0) <= p.point_reads
            and flat.pwc.max(initial=0) <= p.point_writes
            and flat.rrc.max(initial=0) <= p.range_reads
            and flat.rwc.max(initial=0) <= p.range_writes
        )

    def _iota_to(self, n):
        """``arange(n)`` as a view of one cached array. On the thread
        that dispatches, every call that gives the interpreter lock up
        is a wait behind the request threads, and ``np.arange`` gives it
        up at any size — as a fancy store through several index arrays
        does, which is why the flat pack stores through ONE flat index
        into 1-D views (numpy keeps the lock there below 500 entries)."""
        if n > len(self._iota):
            self._iota = np.arange(2 * n)
        return self._iota[:n]

    def _flat_staging(self, B, n_txns):
        """A staging set from the per-shape reuse ring, about to take
        ``n_txns[b]`` transactions in batch row ``b``: the stacked
        (B, T, …) arrays, every slot in its pad state (zero key,
        ``zero_hash``, bucket 0, false mask), and a 1-D view of each to
        store through (a key array's elements are whole limb rows).
        Arrival order fills txn slots ``0 … n-1``, so a set remembers one
        count a batch row (``dirty``) and a reused set resets only the
        rows its last pack wrote: the work follows the live rows, not
        ``params.txns``."""
        p = self.params
        ring = self._flat_rings.get(B)
        if ring is None:
            ring = self._flat_rings[B] = []
            self._flat_ring_next[B] = 0
        if len(ring) < self.STAGING_RING:
            if self.profile is not None:
                self.profile.record_staging(hit=False)
            T, W = p.txns, p.key_width
            zero_hash = self._zero_hash
            bufs = {
                "rv": np.zeros((B, T), np.uint32),
                "txn_mask": np.zeros((B, T), np.bool_),
                "pr_key": np.zeros((B, T, p.point_reads, W), np.uint32),
                "pr_hash": np.full((B, T, p.point_reads), zero_hash,
                                   np.uint32),
                "pr_bucket": np.zeros((B, T, p.point_reads), np.int32),
                "pr_mask": np.zeros((B, T, p.point_reads), np.bool_),
                "pw_key": np.zeros((B, T, p.point_writes, W), np.uint32),
                "pw_hash": np.full((B, T, p.point_writes), zero_hash,
                                   np.uint32),
                "pw_bucket": np.zeros((B, T, p.point_writes), np.int32),
                "pw_mask": np.zeros((B, T, p.point_writes), np.bool_),
                "rr_b": np.zeros((B, T, p.range_reads, W), np.uint32),
                "rr_e": np.zeros((B, T, p.range_reads, W), np.uint32),
                "rr_lo": np.zeros((B, T, p.range_reads), np.int32),
                "rr_hi": np.zeros((B, T, p.range_reads), np.int32),
                "rr_mask": np.zeros((B, T, p.range_reads), np.bool_),
                "rw_b": np.zeros((B, T, p.range_writes, W), np.uint32),
                "rw_e": np.zeros((B, T, p.range_writes, W), np.uint32),
                "rw_lo": np.zeros((B, T, p.range_writes), np.int32),
                "rw_hi": np.zeros((B, T, p.range_writes), np.int32),
                "rw_mask": np.zeros((B, T, p.range_writes), np.bool_),
                "cv": np.zeros(B, np.uint32),
                "nws": np.zeros(B, np.uint32),
            }
            flat = {
                name: (a.reshape(a.size // W, W).view(self._row)
                       if a.ndim == 4 else a).reshape(-1)
                for name, a in bufs.items()
            }
            # what a reset restores: every non-empty per-txn array (cv /
            # nws are fully overwritten by each pack) with its pad value
            pads = [
                (a, zero_hash if name in ("pr_hash", "pw_hash") else 0)
                for name, a in bufs.items()
                if a.size and name not in ("cv", "nws")
            ]
            dirty = [0] * B
            ring.append((bufs, flat, pads, dirty))
        else:
            i = self._flat_ring_next[B]
            self._flat_ring_next[B] = (i + 1) % len(ring)
            self.flat_reuse_hits += 1
            if self.profile is not None:
                self.profile.record_staging(hit=True)
            bufs, flat, pads, dirty = ring[i]
            for b, n in enumerate(dirty):
                if n:
                    for a, pad in pads:
                        a[b, :n] = pad
        if self.profile is not None:
            self.profile.record_pack_rows(sum(dirty) + sum(n_txns))
        # marked before the scatter: a pack that raises halfway leaves
        # no row dirty beyond its mark
        dirty[:] = n_txns + [0] * (B - len(n_txns))
        return bufs, flat

    def pack_flat_group(self, flats, metas, base_version, B=None):
        """Pack a whole backlog group of FlatTxnBatches into ONE stacked
        ResolveBatch (leading dim ``B``, zero-padded past ``len(flats)``
        like resolve_many's pack_empty pads) — bit-identical to packing
        each batch with :meth:`pack` and ``np.stack``-ing, without a
        single per-transaction Python step: blob bytes become limb rows
        with one frombuffer per lane, slot indices come from cumsums,
        and hashing/bucketing run over those compact live rows before
        one scatter stores them — no pass touches a pad slot.

        ``metas``: [(commit_version, new_window_start)] per flat batch;
        pads inherit the last entry (matching the legacy pad template).
        Callers must have checked :meth:`flat_fits` per batch.
        """
        from foundationdb_tpu.core import flatpack

        p = self.params
        nb = len(flats)
        if B is None:
            B = nb
        u32 = np.uint32
        # group-GLOBAL scatter: one index build + one store per lane for
        # the whole backlog, however many batches it holds (per-batch
        # loops were the next-largest pack cost after the dispatch
        # itself)
        lens = [len(f) for f in flats]
        if nb == 1:
            f = flats[0]
            rv_all = f.rv
            cat = (
                (f.prc, f.pwc, f.rrc, f.rwc),
                (f.pr_blob, f.pw_blob, f.rr_blob, f.rw_blob),
            )
        else:
            rv_all = np.concatenate([f.rv for f in flats])
            cat = (
                tuple(
                    np.concatenate([getattr(f, c) for f in flats])
                    for c in ("prc", "pwc", "rrc", "rwc")
                ),
                tuple(
                    b"".join([getattr(f, c) for f in flats])
                    for c in ("pr_blob", "pw_blob", "rr_blob", "rw_blob")
                ),
            )
        (prc, pwc, rrc, rwc), (pr_blob, pw_blob, rr_blob, rw_blob) = cat
        bufs, flat = self._flat_staging(B, lens)
        # slot[g] = b·T + t: the flat txn slot of global txn row g
        n_txns = np.array(lens, dtype=np.int64)
        slot = self._iota_to(len(rv_all)) + np.repeat(
            self._iota_to(nb) * p.txns - (np.cumsum(n_txns) - n_txns),
            n_txns)

        def entry_slots(counts, lanes):
            """(b·T + t)·lanes + i of every op, from the per-txn op
            counts: ops of a txn take its lanes 0 … count-1 in order."""
            starts = np.cumsum(counts) - counts
            return np.repeat(slot * lanes - starts, counts) + self._iota_to(
                int(counts.sum()))

        if len(rv_all):
            flat["rv"][slot] = np.clip(
                rv_all - base_version, 0, 0xFFFFFFFF
            ).astype(u32)
            flat["txn_mask"][slot] = True
        # the four sides' entries decoded, their buckets found and the
        # points' hashes taken in ONE pass each over the joined blobs: a
        # numpy call costs the dispatching thread its fixed overhead
        # whatever its size, and a pass a side was four decodes, six
        # bucket searches and two hash chains a range pack
        w4 = 4 * p.key_width
        blob = b"".join((pr_blob, pw_blob, rr_blob, rw_blob))
        rows = flatpack.point_limbs(blob, p.key_width - 1)
        buckets = self.buckets.of_entries(blob, rows)
        n_points = (len(pr_blob) + len(pw_blob)) // w4
        hashes = fnv_hash_np(rows[:n_points]) if n_points else None
        first = 0  # the side's first entry among ``rows``
        for side, counts, n, lanes in (
                ("pr", prc, len(pr_blob) // w4, p.point_reads),
                ("pw", pwc, len(pw_blob) // w4, p.point_writes)):
            if n:
                at = entry_slots(counts, lanes)
                keys = rows[first:first + n]
                bucket = buckets[first:first + n]
                flat[side + "_key"][at] = keys.view(self._row).reshape(-1)
                flat[side + "_hash"][at] = hashes[first:first + n]
                flat[side + "_bucket"][at] = bucket
                flat[side + "_mask"][at] = True
                if side == "pw":
                    self._note_writes(keys, bucket)
            first += n
        for side, counts, n, lanes in (
                ("rr", rrc, len(rr_blob) // w4, p.range_reads),
                ("rw", rwc, len(rw_blob) // w4, p.range_writes)):
            if n:  # lower || upper entry pairs
                at = entry_slots(counts, lanes)
                lo = rows[first:first + n:2]
                bucket = buckets[first:first + n]
                flat[side + "_b"][at] = lo.view(self._row).reshape(-1)
                flat[side + "_e"][at] = rows[first + 1:first + n:2].view(
                    self._row).reshape(-1)
                flat[side + "_lo"][at] = bucket[0::2]
                flat[side + "_hi"][at] = bucket[1::2]
                flat[side + "_mask"][at] = True
                self.buckets.note_rows(lo)
            first += n
        for b, (cv, ws) in enumerate(metas):
            bufs["cv"][b] = u32(cv - base_version)
            bufs["nws"][b] = u32(max(0, ws - base_version))
        if nb < B:  # pads share the last batch's version scalars
            bufs["cv"][nb:] = bufs["cv"][nb - 1] if nb else 0
            bufs["nws"][nb:] = bufs["nws"][nb - 1] if nb else 0
        return ResolveBatch(
            rv=bufs["rv"], txn_mask=bufs["txn_mask"],
            pr_hash=bufs["pr_hash"], pr_key=bufs["pr_key"],
            pr_bucket=bufs["pr_bucket"], pr_mask=bufs["pr_mask"],
            pw_hash=bufs["pw_hash"], pw_key=bufs["pw_key"],
            pw_bucket=bufs["pw_bucket"], pw_mask=bufs["pw_mask"],
            rr_b=bufs["rr_b"], rr_e=bufs["rr_e"],
            rr_lo=bufs["rr_lo"], rr_hi=bufs["rr_hi"],
            rr_mask=bufs["rr_mask"],
            rw_b=bufs["rw_b"], rw_e=bufs["rw_e"],
            rw_lo=bufs["rw_lo"], rw_hi=bufs["rw_hi"],
            rw_mask=bufs["rw_mask"],
            cv=bufs["cv"], new_window_start=bufs["nws"],
        )

    def _note_writes(self, rows, bucket):
        """A pack's live point-write rows into the bucket sample and,
        once boundaries are cut, into the profile: how many there were
        and how many fell in the pack's fullest bucket (before a cut a
        server of one application reads 100%, and is not asked)."""
        self.buckets.note_rows(rows)
        if self.buckets.cut and self.profile is not None and len(rows):
            self.profile.count(
                bucket_entries_routed=len(bucket),
                bucket_entries_fullest=int(np.bincount(bucket).max()))

    def _legacy_buckets(self, batch, n):
        """``pack``'s bucket arrays under the boundaries in force, for a
        batch of ``n`` transactions (the native pass writes the first
        limb's bits: once boundaries are cut the live entries' buckets
        are found again from their key rows, in ONE search, and stored
        in place; a pad slot keeps bucket 0), and the batch's live point
        writes and range begins into the sample. Nothing here walks the
        pad or makes an array of its size: arrival order fills the
        transaction slots ``0 … n-1``, and on the thread that dispatches
        a large numpy call is a turn at the interpreter lock given away
        (PERF.md §6, PR 29)."""
        cut = self.buckets.cut
        W = self.params.key_width
        parts = []  # (side, which bound, the bucket array, live slots, rows)
        for side, fields in (
                ("pr", (("pr_key", "pr_bucket"),)),
                ("pw", (("pw_key", "pw_bucket"),)),
                ("rr", (("rr_b", "rr_lo"), ("rr_e", "rr_hi"))),
                ("rw", (("rw_b", "rw_lo"), ("rw_e", "rw_hi")))):
            if side == "pr" and not cut:
                continue  # point reads are not sampled
            mask = getattr(batch, side + "_mask")
            live = np.flatnonzero(mask[:n]) if mask.size else ()
            if not len(live):
                continue
            for k, (keys, name) in enumerate(fields):
                if k and not cut:
                    break  # a range's end is only bucketed
                # (whole rows through ONE index into a 1-D view: half
                # the time of a 2-D gather, and it keeps the lock)
                rows = getattr(batch, keys).reshape(-1, W).view(
                    self._row).reshape(-1)[live].view(np.uint32).reshape(
                    -1, W)
                parts.append((side, k, getattr(batch, name), live, rows))
        if cut and parts:
            found = self.buckets.of(np.concatenate([p[-1] for p in parts]))
        at = 0
        for side, k, out, live, rows in parts:
            bucket = None
            if cut:
                bucket = found[at:at + len(rows)]
                at += len(rows)
                out.reshape(-1)[live] = bucket
            if side == "pw":
                self._note_writes(rows, bucket)
            elif side != "pr" and k == 0:
                self.buckets.note_rows(rows)
        return batch

    def pack_flat(self, flat, base_version, commit_version,
                  new_window_start):
        """Single-batch flat pack: one group slot, leading dim dropped —
        shape-compatible with :meth:`pack`'s output (the sync
        commit_batch path)."""
        stacked = self.pack_flat_group(
            [flat], [(commit_version, new_window_start)], base_version,
            B=1,
        )
        return ResolveBatch(*(a[0] for a in stacked))

    def pack_empty(self, base_version, commit_version, new_window_start):
        """A zero-txn pad batch (resolve_many's fixed-width padding).
        The zero arrays are immutable and version-independent, so ONE
        cached template serves every dispatch — only the cv/window
        scalars are swapped. Re-packing pads each backlog dispatch was
        measurable in the commit pipeline's pack stage."""
        if self._empty is None:
            self._empty = self.pack([], 0, 0, 0)
        return self._empty._replace(
            cv=np.uint32(commit_version - base_version),
            new_window_start=np.uint32(
                max(0, new_window_start - base_version)
            ),
        )

    def _normalize(self, txn):
        """Fold a txn whose op lists exceed the packed lanes: overflow
        point ops spill into the range lanes (a point op is a tiny
        range), and range overflow coalesces into a single covering
        range (conservative — can only add false conflicts)."""
        p = self.params
        preads = txn.point_reads
        pwrites = txn.point_writes
        rreads = txn.range_reads
        rwrites = txn.range_writes
        if len(preads) > p.point_reads:
            rreads = list(rreads) + [
                (k, k + b"\x00") for k in preads[p.point_reads :]
            ]
            preads = preads[: p.point_reads]
        if len(pwrites) > p.point_writes:
            rwrites = list(rwrites) + [
                (k, k + b"\x00") for k in pwrites[p.point_writes :]
            ]
            pwrites = pwrites[: p.point_writes]
        if len(rreads) > p.range_reads:
            if p.range_reads == 0:
                raise ValueError(
                    "txn has range/overflow reads but params.range_reads=0"
                )
            tail = rreads[p.range_reads - 1 :]
            rreads = list(rreads[: p.range_reads - 1]) + [
                (min(b for b, _ in tail), max(e for _, e in tail))
            ]
        if len(rwrites) > p.range_writes:
            if p.range_writes == 0:
                raise ValueError(
                    "txn has range/overflow writes but params.range_writes=0"
                )
            tail = rwrites[p.range_writes - 1 :]
            rwrites = list(rwrites[: p.range_writes - 1]) + [
                (min(b for b, _ in tail), max(e for _, e in tail))
            ]
        from foundationdb_tpu.resolver.skiplist import TxnRequest

        return TxnRequest(
            read_version=txn.read_version,
            point_reads=preads,
            point_writes=pwrites,
            range_reads=rreads,
            range_writes=rwrites,
        )

    def _pack_native(self, txns, base_version, commit_version,
                     new_window_start):
        """One C pass (native/packer.cpp pack_into) into freshly
        allocated arrays; None on lane overflow (numpy path normalizes).
        """
        p = self.params
        T, W = p.txns, p.key_width
        u32, i32 = np.uint32, np.int32
        zero_hash = u32(fnv_hash_np(np.zeros((1, W), u32))[0])
        rv = np.zeros(T, u32)
        txn_mask = np.zeros(T, bool)
        pr_key = np.zeros((T, p.point_reads, W), u32)
        pr_hash = np.full((T, p.point_reads), zero_hash, u32)
        pr_bucket = np.zeros((T, p.point_reads), i32)
        pr_mask = np.zeros((T, p.point_reads), bool)
        pw_key = np.zeros((T, p.point_writes, W), u32)
        pw_hash = np.full((T, p.point_writes), zero_hash, u32)
        pw_bucket = np.zeros((T, p.point_writes), i32)
        pw_mask = np.zeros((T, p.point_writes), bool)
        rr_b = np.zeros((T, p.range_reads, W), u32)
        rr_e = np.zeros((T, p.range_reads, W), u32)
        rr_lo = np.zeros((T, p.range_reads), i32)
        rr_hi = np.zeros((T, p.range_reads), i32)
        rr_mask = np.zeros((T, p.range_reads), bool)
        rw_b = np.zeros((T, p.range_writes, W), u32)
        rw_e = np.zeros((T, p.range_writes, W), u32)
        rw_lo = np.zeros((T, p.range_writes), i32)
        rw_hi = np.zeros((T, p.range_writes), i32)
        rw_mask = np.zeros((T, p.range_writes), bool)
        rc = self._native.pack_into(
            txns, base_version,
            (p.point_reads, p.point_writes, p.range_reads, p.range_writes),
            p.key_width - 1, p.bucket_bits,
            (rv, txn_mask,
             pr_key, pr_hash, pr_bucket, pr_mask,
             pw_key, pw_hash, pw_bucket, pw_mask,
             rr_b, rr_e, rr_lo, rr_hi, rr_mask,
             rw_b, rw_e, rw_lo, rw_hi, rw_mask),
        )
        if rc:
            return None
        return ResolveBatch(
            rv=rv, txn_mask=txn_mask,
            pr_hash=pr_hash, pr_key=pr_key, pr_bucket=pr_bucket,
            pr_mask=pr_mask,
            pw_hash=pw_hash, pw_key=pw_key, pw_bucket=pw_bucket,
            pw_mask=pw_mask,
            rr_b=rr_b, rr_e=rr_e, rr_lo=rr_lo, rr_hi=rr_hi, rr_mask=rr_mask,
            rw_b=rw_b, rw_e=rw_e, rw_lo=rw_lo, rw_hi=rw_hi, rw_mask=rw_mask,
            cv=np.uint32(commit_version - base_version),
            new_window_start=np.uint32(
                max(0, new_window_start - base_version)
            ),
        )

    def pack(self, txns, base_version, commit_version, new_window_start):
        """txns: list[TxnRequest] (resolver/skiplist.py), len <= params.txns.

        Versions are absolute; stored as uint32 offsets from base_version.
        Oversize per-txn conflict-range lists spill into the range lanes
        (a point op is just a tiny range), mirroring how the reference
        treats all conflict ranges as ranges.

        Vectorized: the per-txn walk only gathers (slot, key) pairs into
        flat lists; all limb encoding happens as four whole-batch
        frombuffer passes (KeyCodec.encode_*_batch) and one fancy-index
        scatter per lane. ~30x the per-key scalar-encode path — this is
        the proxy's host-side cost per batch, so it bounds sustainable
        e2e throughput.
        """
        p = self.params
        if len(txns) > p.txns:
            raise ValueError(f"batch of {len(txns)} exceeds capacity {p.txns}")
        if self._native is not None and isinstance(txns, list):
            try:
                batch = self._pack_native(txns, base_version, commit_version,
                                          new_window_start)
            except TypeError:
                batch = None  # e.g. bytearray keys; numpy path takes them
            if batch is not None:
                return self._legacy_buckets(batch, len(txns))
        T, W = p.txns, p.key_width
        u32 = np.uint32

        rv = np.zeros(T, u32)
        txn_mask = np.zeros(T, bool)
        pr_key = np.zeros((T, p.point_reads, W), u32)
        pr_mask = np.zeros((T, p.point_reads), bool)
        pw_key = np.zeros((T, p.point_writes, W), u32)
        pw_mask = np.zeros((T, p.point_writes), bool)
        rr_b = np.zeros((T, p.range_reads, W), u32)
        rr_e = np.zeros((T, p.range_reads, W), u32)
        rr_mask = np.zeros((T, p.range_reads), bool)
        rw_b = np.zeros((T, p.range_writes, W), u32)
        rw_e = np.zeros((T, p.range_writes, W), u32)
        rw_mask = np.zeros((T, p.range_writes), bool)

        n = len(txns)
        txn_mask[:n] = True
        if n:
            rv_abs = np.fromiter(
                (t.read_version for t in txns), dtype=np.int64, count=n
            )
            rv[:n] = np.clip(rv_abs - base_version, 0, 0xFFFFFFFF).astype(u32)

        # Per-txn op counts drive everything: overflow detection (rare —
        # only offending batches pay for normalization) and the flat
        # (txn, lane) slot indices, generated with repeat/cumsum instead
        # of Python loops.
        def counts():
            return (
                np.fromiter((len(x.point_reads) for x in txns), np.int64, count=n),
                np.fromiter((len(x.point_writes) for x in txns), np.int64, count=n),
                np.fromiter((len(x.range_reads) for x in txns), np.int64, count=n),
                np.fromiter((len(x.range_writes) for x in txns), np.int64, count=n),
            )

        prc, pwc, rrc, rwc = counts()
        if (
            prc.max(initial=0) > p.point_reads
            or pwc.max(initial=0) > p.point_writes
            or rrc.max(initial=0) > p.range_reads
            or rwc.max(initial=0) > p.range_writes
        ):
            txns = [self._normalize(t) for t in txns]
            prc, pwc, rrc, rwc = counts()

        pr_t, pr_i = _slots(prc)
        pw_t, pw_i = _slots(pwc)
        rr_t, rr_i = _slots(rrc)
        rw_t, rw_i = _slots(rwc)
        # single-pass key gathers; C-speed zip(*) unzips the range pairs
        pr_k = [k for x in txns for k in x.point_reads]
        pw_k = [k for x in txns for k in x.point_writes]
        rr_p = [r for x in txns for r in x.range_reads]
        rw_p = [r for x in txns for r in x.range_writes]
        rr_kb, rr_ke = (list(z) for z in zip(*rr_p)) if rr_p else ([], [])
        rw_kb, rw_ke = (list(z) for z in zip(*rw_p)) if rw_p else ([], [])

        # encode + scatter, one batched pass per lane
        if pr_k:
            pr_key[pr_t, pr_i] = self.codec.encode_lower_batch(pr_k)
            pr_mask[pr_t, pr_i] = True
        if pw_k:
            pw_key[pw_t, pw_i] = self.codec.encode_lower_batch(pw_k)
            pw_mask[pw_t, pw_i] = True
        if rr_kb:
            lo, hi = self.codec.encode_bounds_batch(rr_kb, rr_ke)
            rr_b[rr_t, rr_i] = lo
            rr_e[rr_t, rr_i] = hi
            rr_mask[rr_t, rr_i] = True
        if rw_kb:
            lo, hi = self.codec.encode_bounds_batch(rw_kb, rw_ke)
            rw_b[rw_t, rw_i] = lo
            rw_e[rw_t, rw_i] = hi
            rw_mask[rw_t, rw_i] = True

        return self._legacy_buckets(ResolveBatch(
            rv=rv,
            txn_mask=txn_mask,
            pr_hash=fnv_hash_np(pr_key),
            pr_key=pr_key,
            pr_bucket=bucket_of(pr_key, p.bucket_bits),
            pr_mask=pr_mask,
            pw_hash=fnv_hash_np(pw_key),
            pw_key=pw_key,
            pw_bucket=bucket_of(pw_key, p.bucket_bits),
            pw_mask=pw_mask,
            rr_b=rr_b,
            rr_e=rr_e,
            rr_lo=bucket_of(rr_b, p.bucket_bits),
            rr_hi=bucket_of(rr_e, p.bucket_bits),
            rr_mask=rr_mask,
            rw_b=rw_b,
            rw_e=rw_e,
            rw_lo=bucket_of(rw_b, p.bucket_bits),
            rw_hi=bucket_of(rw_e, p.bucket_bits),
            rw_mask=rw_mask,
            cv=np.uint32(commit_version - base_version),
            new_window_start=np.uint32(max(0, new_window_start - base_version)),
        ), n)
