"""Storage server: MVCC versioned reads over an ordered key space.

Ref parity: fdbserver/storageserver.actor.cpp — serves reads at a
client's read version within the 5s MVCC window, applies committed
mutations in version order, resolves key selectors, supports watches.
Mirrors the reference's two-tier design: a versioned in-memory overlay
(PTree in the reference) holding the MVCC window, above a pluggable
single-version persistent engine (server/kvstore.py) that stores the
state as of the *durable version*. ``flush()`` advances the durable
version by folding overlay versions into the engine, exactly like the
reference's updateStorage loop making versions durable then popping the
tlog.
"""

import threading

from collections import deque

try:
    from sortedcontainers import SortedDict
except ImportError:  # container without the dep: the in-repo shim
    from foundationdb_tpu.utils.sorteddict import SortedDict

from foundationdb_tpu.core.errors import FDBError, err
from foundationdb_tpu.core.keys import KeySelector, key_successor
from foundationdb_tpu.core.mutations import ATOMIC_OPS, Op, apply_atomic
from foundationdb_tpu.server.kvstore import KeyValueStoreMemory
from foundationdb_tpu.utils import heatmap as heatmap_mod
from foundationdb_tpu.utils import lockdep
from foundationdb_tpu.utils import metrics as metrics_mod
from foundationdb_tpu.utils import span as span_mod

_MISS = object()  # overlay has no entry at-or-below the read version


class Watch:
    """Fires when the watched key's value diverges from the seen value.

    Ref: watchValue in storageserver.actor.cpp."""

    def __init__(self, key, seen_value):
        self.key = key
        self.seen_value = seen_value
        self.fired = False
        self._callbacks = []

    def on_fire(self, cb):
        if self.fired:
            cb()
        else:
            self._callbacks.append(cb)

    def _fire(self):
        if not self.fired:
            self.fired = True
            for cb in self._callbacks:
                cb()



class RangeReadInterface:
    """Key-selector resolution and range reads over any provider of
    ``_iter_live(begin, end, version, reverse)`` + ``_check_version``.

    Shared by StorageServer (one storage's merged overlay/engine view)
    and StorageRouter (the partitioned tier stitched across shards) so
    selector semantics cannot diverge between them.
    """

    _WALK_END = b"\xff\xff"  # past every user + system key

    def _live_keys(self, begin, end, version, reverse=False):
        for k, _ in self._iter_live(begin, end, version, reverse=reverse):
            yield k

    def read_range(self, begin, end, version, limit=None):
        """Plain (key, value) list over [begin, end) at ``version`` —
        the shard-copy read used by data distribution (ref: fetchKeys'
        getRange stream), bypassing key-selector resolution."""
        self._check_version(version)
        out = []
        for kv in self._iter_live(begin, end, version):
            out.append(kv)
            if limit is not None and len(out) >= limit:
                break
        return out

    def resolve_selector(self, sel: KeySelector, version):
        """Resolve a key selector to a concrete key (ref: storageserver
        findKey): start at the last live key < (or <=) sel.key, then move
        ``offset`` live keys right. Clamps to b'' / \\xff sentinel."""
        import itertools

        self._check_version(version)
        offset = sel.offset
        upper = sel.key + b"\x00" if sel.or_equal else sel.key
        # lazily walk left from the reference key, taking only what the
        # offset needs (the reference does the same bounded walk in findKey)
        need = 1 if offset > 0 else (-offset + 1)
        prev = list(
            itertools.islice(self._live_keys(b"", upper, version, reverse=True), need)
        )
        if offset > 0:
            start = prev[0] + b"\x00" if prev else b""
            following = self._live_keys(start, self._WALK_END, version)
            k = next(itertools.islice(following, offset - 1, None), None)
            return k if k is not None else b"\xff"
        else:
            # offset 0 => last-less-than(-or-equal); negative walks left
            idx = -offset
            if idx < len(prev):
                return prev[idx]
            return b""

    def get_range(self, begin_sel, end_sel, version, limit=0, reverse=False):
        """Half-open range read by key selectors. Returns list[(k, v)]."""
        self._check_version(version)
        begin = begin_sel if isinstance(begin_sel, bytes) else self.resolve_selector(begin_sel, version)
        end = end_sel if isinstance(end_sel, bytes) else self.resolve_selector(end_sel, version)
        if begin > end:
            return []
        out = []
        for kv in self._iter_live(begin, end, version, reverse=reverse):
            out.append(kv)
            if limit and len(out) >= limit:
                break
        return out


class StorageServer(RangeReadInterface):
    def __init__(self, window_versions=5_000_000, engine=None):
        # overlay: key -> list[(version, value_or_None)] ascending, all
        # versions > durable_version; None = tombstone
        self._overlay = SortedDict()
        self._dirty = deque()  # (version, key) in apply order, for flush
        # Guards overlay/engine mutation vs reads: in thread-mode batching
        # the batcher thread applies/flushes while client threads read.
        # SortedDict iteration is not safe under concurrent mutation, so
        # readers hold the same lock (RLock: flush iterates internally).
        # Single-threaded deployments pay one uncontended acquire per op.
        self._mu = lockdep.rlock("StorageServer._mu")
        # the same mutex, counted (blocked acquisitions and their wait:
        # cluster.locks.storage_mu_read / _apply), entered at its
        # outermost hot acquisitions: a served read's first, and apply's
        self._mu_read = lockdep.counted(self._mu, "storage_mu_read")
        self._mu_apply = lockdep.counted(self._mu, "storage_mu_apply")
        # the thread serving a read_batch, while it holds the mutex for
        # its batch: its point reads take (and count) it no second time
        self._batch_thread = None
        self.alive = True  # failure detection flips this (sim kill)
        # placement tag (ref: storage locality in DatabaseConfiguration
        # region blocks): the cluster stamps its primary-region id when
        # regions are configured, and recruitment carries it to
        # replacements. None = regions not configured.
        self.region = None
        self.engine = engine if engine is not None else KeyValueStoreMemory()
        # Versioned engines (the Redwood role, kvstore.KeyValueStoreVersioned)
        # store per-key version chains, so the MVCC window extends into the
        # durable tier: flush() writes every overlay version down instead of
        # folding, and reads below durable_version stay serveable.
        self.versioned_engine = bool(getattr(self.engine, "versioned", False))
        self.durable_version = self.engine.stored_version()
        if self.versioned_engine:
            self.oldest_version = self.engine.oldest_retained
        else:
            self.oldest_version = self.durable_version
        self.version = self.durable_version  # latest applied
        self.window_versions = window_versions
        self._watches = {}  # key -> list[Watch]
        # apply/flush-latency bands + volume counters (ref: the storage
        # server's StorageMetrics fed into status json). Recruitment
        # hands the replacement this registry so counters never rewind.
        self.metrics = metrics_mod.MetricsRegistry("storage")
        self._m_apply = self.metrics.latency("storage_apply")
        self._m_mutations = self.metrics.counter("mutations_applied")
        self._m_reads = self.metrics.counter("point_reads")
        self._m_range_reads = self.metrics.counter("range_reads")
        # multiplexed read batches (txn/futures.py ReadBatcher →
        # rpc read_batch endpoint): serve latency band, reads-per-RPC
        # histogram, and the coalesce-rate counters (status json's
        # cluster.metrics.rollups)
        self._m_read_batch = self.metrics.latency("read_batch")
        self._m_read_batch_keys = self.metrics.latency("read_batch_keys")
        self._m_read_batches = self.metrics.counter("read_batches")
        self._m_batched_reads = self.metrics.counter("batched_reads")
        # read/write key sampling (ref: StorageMetrics byte-sampling):
        # cluster-owned heatmaps attached via attach_heatmaps; None =
        # sampling off. Countdown sampling — one integer decrement per
        # access, a "key-sample"-stream draw only when a sample fires —
        # keeps the hot-path cost inside the heatmap_smoke 2% budget.
        self._read_heat = None
        self._write_heat = None
        self._sample_every = 8
        self._sample_w = 8.0
        self._srng = None
        self._read_cd = 1  # first access sampled: heat appears promptly
        self._write_cd = 1

    @classmethod
    def recover(cls, engine, log_records, window_versions=5_000_000):
        """Rebuild from a persistent engine + tlog records past its
        durable version (ref: storage server recovery peeking the tlog)."""
        ss = cls(window_versions=window_versions, engine=engine)
        for version, mutations in log_records:
            if version > ss.durable_version:
                ss.apply(version, mutations)
        return ss

    # ───────────────────────────── writes ──────────────────────────────
    def apply(self, version, mutations):
        """Apply one commit's mutations at ``version`` (monotone order).

        The SET case is inlined (no _append call): it is the bulk of
        every write-heavy batch and this loop runs on the batcher
        thread for the WHOLE cluster — its per-mutation cost is a
        direct throughput tax on the commit pipeline."""
        if version <= self.version:
            raise ValueError(f"apply out of order: {version} <= {self.version}")
        # one stage feeds the storage_apply band, the profiler
        # annotation and, for a traced batch (the proxy's ambient
        # batch-span context), a storage.apply hop span
        with span_mod.stage("storage.apply", version=version,
                            mutations=len(mutations)) as asp:
            with self._mu_apply:
                overlay_get = self._overlay.get
                overlay = self._overlay
                dirty_append = self._dirty.append
                watches = self._watches
                for m in mutations:
                    op = m.op
                    if op is Op.SET:
                        key = m.key
                        chain = overlay_get(key)
                        if chain is None:
                            overlay[key] = chain = []
                        chain.append((version, m.param))
                        dirty_append((version, key))
                        if watches:
                            self._fire_watches(key, m.param)
                    elif op is Op.CLEAR_RANGE:
                        self._apply_clear_range(m.key, m.param, version)
                    elif op is Op.CLEAR:
                        self._append(m.key, version, None)
                    elif op in ATOMIC_OPS:
                        old = self._lookup(m.key, version)
                        self._append(m.key, version, apply_atomic(m.op, old, m.param))
                    else:
                        raise ValueError(f"unresolved mutation {m.op} reached storage")
                self.version = version
        self._m_apply.record(asp.seconds)
        self._m_mutations.inc(len(mutations))
        if self._write_heat is not None and mutations:
            # write sampling stays OUT of the inlined SET loop: one
            # countdown decrement per apply call, a sampled key drawn
            # from the batch only when the countdown fires (and the kill
            # switch checked only then — per fire, not per apply)
            self._write_cd -= len(mutations)
            if self._write_cd <= 0:
                self._write_cd = self._srng.randrange(
                    1, 2 * self._sample_every + 1)
                if heatmap_mod.enabled():
                    m = mutations[self._srng.randrange(len(mutations))]
                    if m.key < b"\xff":  # user keyspace only (see reads)
                        self._write_heat.charge(m.key, self._sample_w)

    def _apply_clear_range(self, begin, end, version):
        # tombstone every key the clear shadows: overlay keys in range plus
        # engine (durable) keys in range not yet overlaid
        keys = set(self._overlay.irange(begin, end, inclusive=(True, False)))
        keys.update(k for k, _ in self.engine.get_range(begin, end))
        for k in keys:
            self._append(k, version, None)

    def _append(self, key, version, value):
        chain = self._overlay.get(key)
        if chain is None:
            chain = []
            self._overlay[key] = chain
        chain.append((version, value))
        self._dirty.append((version, key))
        if self._watches:
            self._fire_watches(key, value)

    def _fire_watches(self, key, value):
        watchers = self._watches.get(key)
        if watchers:
            for w in watchers:
                if value != w.seen_value:
                    w._fire()
            self._watches[key] = [w for w in watchers if not w.fired]

    def flush(self, up_to_version=None):
        """Make versions ≤ ``up_to_version`` durable: fold the newest
        overlay entry at-or-below it into the engine, prune the overlay,
        advance durable_version. Returns the new durable version."""
        if up_to_version is None:
            up_to_version = self.version
        up_to_version = min(up_to_version, self.version)
        if up_to_version <= self.durable_version:
            return self.durable_version
        with self._mu:
            return self._flush_locked(up_to_version)

    def _flush_locked(self, up_to_version):
        # the dirty queue is version-ordered, so flushing touches only keys
        # actually written at-or-below the target (ref: the version-ordered
        # update queue in the storage server's updateStorage loop)
        touched = set()
        while self._dirty and self._dirty[0][0] <= up_to_version:
            touched.add(self._dirty.popleft()[1])
        for key in touched:
            chain = self._overlay.get(key)
            if chain is None:
                continue
            folded = _MISS
            keep = []
            for v, val in chain:
                if v <= up_to_version:
                    if self.versioned_engine:
                        # Redwood-style: every version goes down intact
                        self.engine.set_versioned(key, v, val)
                    folded = val
                else:
                    keep.append((v, val))
            if folded is not _MISS and not self.versioned_engine:
                if folded is None:
                    self.engine.clear_range(key, key_successor(key))
                else:
                    self.engine.set(key, folded)
            if keep:
                self._overlay[key] = keep
            else:
                del self._overlay[key]
        self.engine.commit(up_to_version)
        self.durable_version = up_to_version
        if not self.versioned_engine:
            # reads below the durable version can no longer be served (the
            # engine is single-version); keep the window invariant tight.
            # A versioned engine keeps serving them from its chains, so its
            # read floor moves only with advance_window (+ prune).
            self.oldest_version = max(self.oldest_version, up_to_version)
        return self.durable_version

    def kill(self):
        """Process death: volatile state is gone for callers (reads and
        watches error until the cluster controller recruits a
        replacement). Ref: sim2 killing one storage process."""
        self.alive = False

    # ───────────────────────────── reads ───────────────────────────────
    def _check_version(self, version):
        if not self.alive:
            # retryable: the client re-routes / waits out recruitment
            # (ref: the client's wrong_shard_server / future_version retry
            # loop against a dead storage interface)
            raise err("process_behind")
        if version < self.oldest_version:
            raise err("transaction_too_old")
        if version > self.version:
            raise err("future_version")

    def _lookup(self, key, version):
        """Value of key at version (overlay first, engine beneath)."""
        chain = self._overlay.get(key)
        if chain:
            val = _MISS
            for v, x in chain:
                if v <= version:
                    val = x
                else:
                    break
            if val is not _MISS:
                return val
        if self.versioned_engine:
            return self.engine.get_at(key, version)
        return self.engine.get(key)

    def get(self, key, version):
        self._check_version(version)
        self._m_reads.inc()
        if self._read_heat is not None:
            # countdown inlined: the per-read sampling cost is ONE
            # integer decrement — no function call until a sample fires
            self._read_cd -= 1
            if self._read_cd <= 0:
                self._sample_read(key)
        if self._batch_thread == threading.get_ident():
            # read_batch holds the mutex for its whole batch and counted
            # its one acquisition: every point read still passes here
            return self._lookup(key, version)
        with self._mu_read:
            return self._lookup(key, version)

    def read_batch(self, ops):
        """Vectorized multi-key serve: one LOCK ACQUISITION for the
        whole batch instead of one per key (the Jiffy lesson — batch
        the per-item crossing). ``ops`` is a list of tuples:

        - ``("g", key, rv)`` → value or None
        - ``("r", begin, end, rv, limit, reverse)`` → list[(k, v)]
        - ``("s", selector, rv)`` → resolved key

        Returns one slot per op, FDBError slots included (per-key
        errors are NOT batch-fatal — a too-old key fails alone).
        Delegates to the public per-op methods under the held RLock
        (``get`` sees it held and takes it no second time; the range and
        selector methods re-enter it), so version checks, read counters,
        and countdown
        heat sampling charge EXACTLY as the unbatched path does: one
        decrement per key served, never one per RPC."""
        t0 = metrics_mod.now()
        out = []
        with self._mu_read:
            self._batch_thread = threading.get_ident()
            try:
                for op in ops:
                    try:
                        kind = op[0]
                        if kind == "g":
                            out.append(self.get(op[1], op[2]))
                        elif kind == "r":
                            out.append([
                                (k, v) for k, v in self.get_range(
                                    op[1], op[2], op[3],
                                    limit=op[4], reverse=op[5],
                                )
                            ])
                        elif kind == "s":
                            out.append(self.resolve_selector(op[1], op[2]))
                        else:
                            raise err("client_invalid_operation")
                    except FDBError as e:
                        out.append(e)
            finally:
                self._batch_thread = None  # with the mutex still held
        self._m_read_batch.record(max(0.0, metrics_mod.now() - t0))
        # reads-per-RPC histogram: recorded /1e3 so bands_ms()'s ×1e3
        # yields the RAW batch size (p50_ms field == p50 batch size)
        self._m_read_batch_keys.record(len(ops) / 1e3)
        self._m_read_batches.inc()
        self._m_batched_reads.inc(len(ops))
        return out

    def _overlay_at(self, key, version):
        """Newest overlay value at-or-below ``version`` (or _MISS)."""
        val = _MISS
        for v, x in self._overlay.get(key, ()):
            if v <= version:
                val = x
            else:
                break
        return val

    def _iter_live(self, begin, end, version, reverse=False):
        """Lazy merged (key, value) iteration of engine + overlay at
        ``version`` — overlay wins ties; pulls the engine cursor only as
        far as the caller consumes (limit pushdown).

        Holds the mutation lock for the duration of the iteration: every
        in-package consumer drains (or drops) the generator within one
        call, so the lock's critical section ends when that call returns
        (CPython closes the abandoned generator at function exit)."""
        self._m_range_reads.inc()
        if self._read_heat is not None:
            # a range read charges its begin key: the scan's heat lands
            # on the range's bucket without touching the merge loop
            self._read_cd -= 1
            if self._read_cd <= 0:
                self._sample_read(begin)
        # the router's range reads enter here first; a range or selector
        # op of a direct ``read_batch`` re-enters (counted, never blocked)
        with self._mu_read:
            yield from self._iter_live_locked(begin, end, version, reverse)

    def _iter_live_locked(self, begin, end, version, reverse=False):
        sentinel = object()
        ov = iter(self._overlay.irange(begin, end, inclusive=(True, False), reverse=reverse))
        if self.versioned_engine:
            base = self.engine.iter_range_at(begin, end, version, reverse=reverse)
        else:
            base = self.engine.iter_range(begin, end, reverse=reverse)
        ko = next(ov, sentinel)
        kb = next(base, sentinel)
        while ko is not sentinel or kb is not sentinel:
            if kb is sentinel:
                take_overlay = True
            elif ko is sentinel:
                take_overlay = False
            elif ko == kb[0]:
                # same key in both: overlay decides if it has an entry
                val = self._overlay_at(ko, version)
                if val is _MISS:
                    val = kb[1]
                if val is not None:
                    yield ko, val
                ko = next(ov, sentinel)
                kb = next(base, sentinel)
                continue
            else:
                take_overlay = (ko < kb[0]) != reverse
            if take_overlay:
                val = self._overlay_at(ko, version)
                if val is not _MISS and val is not None:
                    yield ko, val
                ko = next(ov, sentinel)
            else:
                yield kb
                kb = next(base, sentinel)

    def export_shard(self, begin, end):
        """Snapshot a shard WITH its MVCC history: engine base rows at
        the durable version plus every overlay version chain. Data
        distribution hands this to joiners so reads at pre-move read
        versions stay correct (ref: fetchKeys streaming + the mutation
        buffer that brings a joining storage up to date)."""
        with self._mu:
            if self.versioned_engine:
                # the engine holds real history below durable_version —
                # export it intact so the joiner can honor the same floor
                base = {k: c for k, c in self.engine.iter_chains(begin, end)}
            else:
                base = {
                    k: [(self.durable_version, v)]
                    for k, v in self.engine.iter_range(begin, end)
                }
            keys = set(base)
            keys.update(self._overlay.irange(begin, end, inclusive=(True, False)))
            rows = []
            for k in sorted(keys):
                chain = list(base.get(k, ()))
                chain.extend(self._overlay.get(k, ()))
                rows.append((k, chain))
            return (self.oldest_version, self.version, rows)

    def ingest_shard(self, begin, end, export):
        """Install an ``export_shard`` snapshot (ref: fetchKeys applying
        fetched blocks). Physically clears [begin, end) first so stale
        non-owned data and deletes on the source do not survive. The
        read floor rises to the source's: versions below it were not
        exported, and serving them here would silently miss history —
        TOO_OLD (retryable) is the correct answer, exactly as a version
        older than the window gets everywhere else."""
        oldest, version, rows = export
        with self._mu:
            self.version = max(self.version, version)
            self.oldest_version = max(self.oldest_version, oldest)
            if self.versioned_engine:
                # physically evict any stale pre-move history: a clear
                # would tombstone at the durable version, and the later
                # flush of the ingested (lower-version) chain entries
                # would land AFTER it, corrupting the ascending-order
                # invariant chains rely on
                self.engine.erase_range(begin, end)
            else:
                self.engine.clear_range(begin, end)
            for k in list(self._overlay.irange(begin, end, inclusive=(True, False))):
                del self._overlay[k]
            for k, chain in rows:
                self._overlay[k] = list(chain)
                for v, _ in chain:
                    self._dirty.append((v, k))

    # ───────────────────────────── watches ─────────────────────────────
    def fire_watches_in_range(self, begin, end):
        """Spuriously fire every watch on a key in [begin, end) — called
        when a shard relocates away so watchers re-read from the new
        owner instead of hanging on a storage that stopped receiving the
        key's mutations (ref: watches erroring with wrong_shard_server
        on shard moves; ours wakes instead of erroring)."""
        with self._mu:  # vs concurrent watch() registration / _append firing
            for key in list(self._watches):
                if begin <= key and (end is None or key < end):
                    for w in self._watches.pop(key):
                        w._fire()

    def watch(self, key, seen_value):
        if not self.alive:
            raise err("process_behind")
        with self._mu:
            w = Watch(key, seen_value)
            current = self._lookup(key, self.version)
            if current != seen_value:
                w._fire()
            else:
                self._watches.setdefault(key, []).append(w)
            return w

    def advance_window(self, oldest):
        """Advance the MVCC read floor. Folding old overlay versions into
        the engine is NOT done here — the commit proxy's periodic
        durability pump owns flushing (ref: the storage server's
        updateStorage loop being a separate actor from version updates),
        so the pump can observe real durability lag and feed it to the
        ratekeeper instead of hiding it behind a per-batch flush.

        With a versioned engine the floor also garbage-collects: history
        below it is unreachable, so the engine prunes its chains (ref:
        Redwood trimming page versions that left the MVCC window)."""
        if oldest > self.oldest_version:
            self.oldest_version = oldest
            if self.versioned_engine:
                with self._mu:
                    self.engine.prune(min(oldest, self.durable_version))

    def attach_heatmaps(self, read_heat, write_heat, sample_every=8):
        """Wire the cluster-owned read/write heatmaps into this storage
        (and a recruited replacement: the cluster re-attaches the SAME
        objects, so per-shard heat survives recruitment like the
        registry). The sampling stream is the shared deterministic
        "key-sample" stream — same-seed sims replay the exact draws."""
        from foundationdb_tpu.core import deterministic

        self._read_heat = read_heat
        self._write_heat = write_heat
        self._sample_every = max(1, int(sample_every))
        self._sample_w = float(self._sample_every)
        self._srng = deterministic.rng("key-sample")

    def _sample_read(self, key):
        """Fire path — the countdown hit zero (the decrement lives
        inline at the read sites). Randomized stride (mean ≈
        sample_every) instead of a fixed one: periodic access patterns
        cannot alias with the sampler; weight scales by the rate so heat
        estimates TOTAL accesses, matching the ref's byte-sample
        scaling. The kill switch is checked HERE, once per fire, not
        once per access."""
        self._read_cd = self._srng.randrange(
            1, 2 * self._sample_every + 1)
        # system keys (\xff...) stay out of the workload heatmaps: the
        # status/metacluster machinery reads them on every poll, and an
        # observer that heats what it observes would drown user ranges
        if key < b"\xff" and heatmap_mod.enabled():
            self._read_heat.charge(key, self._sample_w)

    def adopt_metrics(self, registry):
        """Recruitment carryover: the replacement continues the dead
        instance's registry, so storage counters never rewind."""
        if registry is self.metrics:
            return
        registry.absorb(self.metrics)
        self.metrics = registry
        self._m_apply = registry.latency("storage_apply")
        self._m_mutations = registry.counter("mutations_applied")
        self._m_reads = registry.counter("point_reads")
        self._m_range_reads = registry.counter("range_reads")
        self._m_read_batch = registry.latency("read_batch")
        self._m_read_batch_keys = registry.latency("read_batch_keys")
        self._m_read_batches = registry.counter("read_batches")
        self._m_batched_reads = registry.counter("batched_reads")

    def status(self):
        """This role's status RPC payload (leaf of the status doc)."""
        self.metrics.gauge("version").set(self.version)
        self.metrics.gauge("durable_version").set(self.durable_version)
        self.metrics.gauge("durability_lag_versions").set(
            max(0, self.version - self.durable_version)
        )
        return {
            "alive": self.alive,
            "region": self.region,
            "metrics": self.metrics.snapshot(),
        }

