"""Client Transaction: snapshot reads, read-your-writes, OCC commit.

Ref parity: fdbclient/NativeAPI.actor.cpp (Transaction) layered with
fdbclient/ReadYourWrites.actor.cpp, exposed in the shape of FDB's Python
binding (bindings/python/fdb/impl.py): tr[key], tr[b:e], tr.get_range,
atomic helpers, snapshot view, watch, on_error retry protocol.
"""

import time

from foundationdb_tpu.core import flatpack
from foundationdb_tpu.core.errors import FDBError, err
from foundationdb_tpu.core.keys import (
    MAX_KEY_SIZE,
    MAX_VALUE_SIZE,
    KeySelector,
    key_successor,
    strinc,
)
from foundationdb_tpu.core.mutations import Mutation, Op
from foundationdb_tpu.core.versions import Versionstamp
from foundationdb_tpu.server.proxy import CommitRequest
from foundationdb_tpu.txn import specialkeys
from foundationdb_tpu.txn.futures import FutureRange, FutureValue
from foundationdb_tpu.txn.rows import WriteMap
from foundationdb_tpu.utils import span as span_mod
from foundationdb_tpu.utils.backoff import Backoff

_INVALID = object()


def _check_key(key, limit=MAX_KEY_SIZE):
    key = bytes(key)
    if len(key) > limit:
        raise err("key_too_large")
    return key


def _check_value(value, limit=MAX_VALUE_SIZE):
    value = bytes(value)
    if len(value) > limit:
        raise err("value_too_large")
    return value


class TransactionOptions:
    def __init__(self, tr):
        self._tr = tr

    def set_read_your_writes_disable(self):
        self._tr._ryw_disabled = True

    def set_snapshot_ryw_disable(self):
        self._tr._snapshot_ryw = False

    def set_next_write_no_write_conflict_range(self):
        self._tr._next_write_no_conflict = True

    def set_report_conflicting_keys(self):
        self._tr._report_conflicting_keys = True

    def set_lock_aware(self):
        """Ref: LOCK_AWARE — commit even while the database is locked."""
        self._tr._lock_aware = True

    def set_tag(self, tag):
        """Attach a transaction tag for per-tag throttling (ref:
        TAG/AUTO_THROTTLE_TAG options + TagThrottler): the ratekeeper
        samples per-tag load and can rate-limit a busy tag (error 1213,
        retryable) without touching other traffic. At most 5 tags of
        ≤16 bytes each (the reference's limits)."""
        if isinstance(tag, bytes):
            # latin-1 is byte-bijective: distinct binary tags stay
            # distinct throttle buckets (utf-8/replace would collide)
            tag = tag.decode("latin-1")
        if len(tag.encode("latin-1", "replace")) > 16:
            raise err("invalid_option_value")
        if tag not in self._tr._tags:
            if len(self._tr._tags) >= 5:
                raise err("invalid_option_value")
            self._tr._tags.append(tag)

    def set_auto_throttle_tag(self, tag):
        """Ref: AUTO_THROTTLE_TAG — same tag semantics as set_tag, but
        the tag is additionally eligible for ratekeeper AUTO throttling
        (here every tag already is: the ratekeeper auto-throttle
        samples all tagged traffic, so this is an alias kept for API
        parity with the reference bindings)."""
        self.set_tag(tag)

    def set_retry_limit(self, n):
        self._tr._retry_limit = int(n)

    def set_max_retry_delay(self, seconds):
        self._tr._max_retry_delay = float(seconds)

    def set_timeout(self, ms):
        self._tr._timeout_s = ms / 1000.0

    def set_read_system_keys(self):
        pass  # system keyspace is readable in-process

    def set_access_system_keys(self):
        pass

    def set_idempotency_id(self, idempotency_id):
        """Ref: IDEMPOTENCY_ID — a client-chosen token (≤255 bytes) the
        proxy records atomically with the commit; a retry after 1021
        resolves to the original outcome instead of double-applying."""
        if not idempotency_id or len(idempotency_id) > 255:
            raise err("invalid_option_value")
        self._tr._idempotency_id = bytes(idempotency_id)

    def set_automatic_idempotency(self):
        """Ref: AUTOMATIC_IDEMPOTENCY — generate a random id at commit
        time (kept across the retry loop) so commit_unknown_result
        becomes exactly-once without the caller inventing tokens."""
        self._tr._auto_idempotency = True

    def set_transaction_repair(self):
        """Enable conflict repair for this transaction regardless of the
        ``txn_repair`` knob (txn/repair.py): on ``not_committed`` with
        conflicting-key info, re-read only the conflicting keys at the
        rejecting commit version and replay (or cache-seed) the retry
        instead of restarting cold."""
        if self._tr._repair is None:
            from foundationdb_tpu.txn.repair import RepairEngine

            self._tr._repair = RepairEngine()

    def set_trace(self):
        """Force this transaction's trace to be SAMPLED regardless of
        ``tracing_sample_rate`` (ref: the DEBUG_TRANSACTION_IDENTIFIER
        / LOG_TRANSACTION option pair; also reachable by writing
        ``\\xff\\xff/tracing/token``). Best set before the first
        operation; a late force still promotes the buffered spans at
        commit."""
        self._tr._trace_forced = True
        if self._tr._span is span_mod.NULL:
            # tracing looked off when the root was (not) created:
            # rebuild sampled on next use — nothing was recorded yet
            self._tr._span = None


class _Snapshot:
    """Snapshot-isolation view: reads add no read conflict ranges."""

    def __init__(self, tr):
        self._tr = tr

    def get(self, key):
        return self._tr.get(key, snapshot=True)

    def __getitem__(self, key):
        if isinstance(key, slice):
            return self._tr.get_range(key.start, key.stop, snapshot=True)
        return self._tr.get(key, snapshot=True)

    def get_range(self, begin, end, **kw):
        kw["snapshot"] = True
        return self._tr.get_range(begin, end, **kw)

    def get_key(self, selector):
        return self._tr.get_key(selector, snapshot=True)

    def get_range_startswith(self, prefix, **kw):
        kw["snapshot"] = True
        return self._tr.get_range_startswith(prefix, **kw)


class Transaction:
    def __init__(self, database):
        self.db = database
        self._reset()

    @property
    def _cluster(self):
        # resolved through the Database each use: after a simulated crash
        # swaps the cluster, in-flight transactions talk to the *new*
        # incarnation and get fenced (too_old) instead of silently
        # committing into a dead object graph
        return self.db._cluster

    def _reset(self):
        # settle any still-outstanding async reads FIRST (before their
        # finalize bookkeeping's targets are replaced below): an
        # abandoned future is cancelled retryably and its span/op-log
        # cleanup runs — reset can never strand a waiter (FL002)
        pending = getattr(self, "_pending_reads", None)
        if pending:
            for fut in pending:
                fut.cancel()
        self._pending_reads = []  # in-flight FutureValue/FutureRange
        knobs = self.db._knobs
        self._knobs = knobs  # cached: ~3 property hops per op otherwise
        self._read_version = None
        self._writes = WriteMap()
        self._mutation_log = []  # [Mutation] in sequence order
        self._read_conflicts = []  # [(begin, end)]
        self._write_conflicts = []
        self._committed_version = None
        self._versionstamp = None
        self._state = "active"  # active | committed | error
        self._ryw_disabled = False
        self._snapshot_ryw = True
        self._next_write_no_conflict = False
        self._report_conflicting_keys = False
        self._lock_aware = False
        self._idempotency_id = None
        self._auto_idempotency = False
        self._tags = []  # transaction tags (per-tag throttling)
        self._retry_limit = None
        self._max_retry_delay = knobs.max_retry_delay_s
        self._timeout_s = None
        # the unified retry-delay policy (utils/backoff.py — flow
        # Backoff parity, jitter off the "backoff-jitter" stream); the
        # OBJECT rides on_error's keep-tuple so growth survives resets
        self._backoff = Backoff(
            initial_s=knobs.initial_backoff_s,
            max_s=knobs.max_retry_delay_s,
            growth=knobs.backoff_growth,
        )
        self._retries = 0
        self._size = 0
        self._special_writes = []  # buffered \xff\xff management writes
        self._conflicting_ranges = None  # from a failed reporting commit
        self._watches_pending = []  # [(key, seen_value, Watch-placeholder)]
        # conflict repair (txn/repair.py): the op-log recorder (None =
        # repair off — every check below is one attribute test), the
        # verified read caches a repaired retry serves from, and the
        # replay/commit bookkeeping flags
        self._repair = None
        if getattr(knobs, "txn_repair", False):
            from foundationdb_tpu.txn.repair import RepairEngine

            self._repair = RepairEngine()
        self._repair_cache = None  # key -> value, proven at _read_version
        self._repair_range_cache = None  # (b,e,limit,rev) -> tuple(rows)
        self._repair_ready = False  # op log replayed: commit, skip the body
        self._repair_assisted = False  # this attempt rode a repair
        # distributed tracing (utils/span.py): the lazy root span (None
        # until the first traced op; NULL when unsampled or off), the
        # in-flight commit span, and the per-txn force-sample flag. The
        # unsampled path keeps NO stamps or objects (the ≤2% budget):
        # abort promotion reconstructs on the error path, slow-commit
        # promotion is the batcher's per-window record.
        self._span = None
        self._commit_span = None
        self._trace_forced = False
        # options/snapshot views are lazy: most transactions never touch
        # them, and two object constructions per txn is real hot-path cost
        self._options = None
        self._snapshot_view = None

    @property
    def options(self):
        o = self._options
        if o is None:
            o = self._options = TransactionOptions(self)
        return o

    @property
    def snapshot(self):
        s = self._snapshot_view
        if s is None:
            s = self._snapshot_view = _Snapshot(self)
        return s

    # ─────────────────────────── tracing ──────────────────────────────
    def _trace_span(self):
        """The lazy root span: NULL when tracing is off or the draw
        missed, an emitting span when the per-txn force (or the draw)
        hits. Created on the first traced operation so untraced
        transactions never touch the sampling stream. Unsampled txns
        under an ENABLED rate arm promotion in _build_commit_request
        with a single clock stamp — no span objects on the 99% path."""
        sp = self._span
        if sp is None:
            sp = self._span = span_mod.transaction_span(
                self._knobs.tracing_sample_rate,
                forced=self._trace_forced,
            )
        return sp

    # ─────────────────────────── versions ─────────────────────────────
    def get_read_version(self):
        if self._read_version is None:
            grv = self._cluster.grv_proxy
            sp = self._trace_span()
            if not sp.sampled:
                # NULL or deferred: per-op child spans only exist for
                # SAMPLED traces — the deferred (promotion) record is
                # root + commit, kept cheap enough for the ≤2% budget
                self._read_version = (
                    grv.get_read_version(tags=tuple(self._tags))
                    if self._tags else grv.get_read_version()
                )
                return self._read_version
            gsp = sp.child("txn.grv")
            # ambient context: an in-process GrvProxy (or the RPC
            # transport's tracing frame) parents its grant span here
            prior = span_mod.set_current(gsp.context())
            try:
                self._read_version = (
                    grv.get_read_version(tags=tuple(self._tags))
                    if self._tags else grv.get_read_version()
                )
            finally:
                span_mod.set_current(prior)
            gsp.finish(version=self._read_version)
        return self._read_version

    def set_read_version(self, version):
        self._read_version = int(version)

    def get_committed_version(self):
        if self._committed_version is None:
            raise err("no_commit_version")
        return self._committed_version

    def get_versionstamp(self):
        """Returns a callable resolving to the txn's 10-byte versionstamp
        after commit (the binding returns a future; call it post-commit)."""
        return lambda: self._require_versionstamp()

    def _require_versionstamp(self):
        if self._versionstamp is None:
            raise err("no_commit_version")
        return self._versionstamp

    # ───────────────────────────── reads ──────────────────────────────
    def _guard(self):
        if self._state in ("committed", "committing"):
            raise err("used_during_commit")
        if self._state == "cancelled":
            raise err("transaction_cancelled")

    @staticmethod
    def _settled(value=None, error=None, cls=FutureValue, finalize=None):
        """An already-resolved future (special-space rows, RYW-complete
        lookups, in-process storage): constructed and settled in one
        place so every return path hands back the same surface."""
        fut = cls(finalize=finalize)
        if error is not None:
            fut.set_exception(error)
        else:
            fut.set(value)
        return fut

    def _read_future(self, key, rv, snapshot, fold_entry=None):
        """One storage point read as a future. A repaired retry serves
        it from the verified cache (txn/repair.py) — resolver-proven
        equal to storage at ``rv`` — and settles immediately. Otherwise
        the read rides the cluster's async path (the connection's
        ReadBatcher — rpc/service.py) when it has one, or resolves
        inline against in-process storage. The finalize callback runs
        once on the consuming ``wait()``: span finish, repair op-log
        record, read-conflict range, RYW fold — the same per-key
        bookkeeping the synchronous path always did."""
        writes = self._writes if fold_entry is not None else None
        cache = self._repair_cache
        if cache is not None and key in cache:
            val = cache[key]
            eng = self._repair
            if eng is not None and not snapshot \
                    and key not in eng.point_reads:
                eng.point_reads[key] = val
            if not snapshot:
                self._add_read_conflict(key, key_successor(key))
            if writes is not None:
                val = writes.fold(fold_entry, val)
            return self._settled(val)
        sp = self._span
        rsp = ctx = None
        if sp is not None and sp.sampled:
            rsp = sp.child("txn.read")
            ctx = rsp.context()

        def finalize(val, error):
            if rsp is not None:
                rsp.finish()
            if error is not None:
                return None
            eng = self._repair
            if eng is not None and not snapshot \
                    and key not in eng.point_reads:
                eng.point_reads[key] = val
            if not snapshot:
                self._add_read_conflict(key, key_successor(key))
            return writes.fold(fold_entry, val) \
                if writes is not None else val

        st = self._cluster.read_storage(key)
        get_async = getattr(st, "get_async", None)
        if get_async is not None:
            fut = get_async(key, rv, finalize=finalize, ctx=ctx)
        else:
            # in-process storage tier: resolve now, defer bookkeeping
            # to the consuming wait() exactly like the batched path
            prior = span_mod.set_current(ctx)
            try:
                val, e = st.get(key, rv), None
            except FDBError as exc:
                val, e = None, exc
            finally:
                span_mod.set_current(prior)
            fut = self._settled(val, error=e, finalize=finalize)
        self._pending_reads.append(fut)
        return fut

    def get_async(self, key, snapshot=False):
        """Future-returning point read (ref: Transaction::get returns
        Future<Optional<Value>>); :meth:`get` is ``.wait()`` over the
        same machinery, so one code path serves both forms."""
        self._guard()
        key = _check_key(key)
        if key.startswith(b"\xff") and specialkeys.contains(key):
            if self._repair is not None:
                # virtual-module rows aren't verifiable at a later
                # version: this op log never auto-replays
                self._repair.unreplayable = True
            try:
                val = specialkeys.get(self, key)
            except FDBError as e:
                return self._settled(error=e)
            return self._settled(val)
        rv = self.get_read_version()
        if not self._ryw_disabled:
            known, needs_base, entry = self._writes.lookup(key)
            if known:
                if not needs_base:
                    return self._settled(self._writes.fold(entry, None))
                return self._read_future(key, rv, snapshot,
                                         fold_entry=entry)
        return self._read_future(key, rv, snapshot)

    def get(self, key, snapshot=False):
        return self.get_async(key, snapshot=snapshot).wait()

    def get_key_async(self, selector, snapshot=False):
        """Future-returning key-selector resolution."""
        self._guard()
        if specialkeys.contains(getattr(selector, "key", None)):
            # selector resolution is not defined over the virtual special
            # space (module rows are materialized, not stored)
            raise err("key_outside_legal_range")
        rv = self.get_read_version()
        if self._repair is not None:
            # selector resolution isn't recorded key-by-key, so it
            # can't be re-verified at the repair version: fall back to
            # the seeded rerun, never the verbatim replay
            self._repair.unreplayable = True

        def finalize(k, error):
            if error is not None:
                return None
            if not snapshot and k not in (b"", b"\xff"):
                self._add_read_conflict(k, key_successor(k))
            return k

        st = self._cluster.read_storage()
        resolve_async = getattr(st, "resolve_selector_async", None)
        if resolve_async is not None:
            fut = resolve_async(selector, rv, finalize=finalize)
        else:
            try:
                k, e = st.resolve_selector(selector, rv), None
            except FDBError as exc:
                k, e = None, exc
            fut = self._settled(k, error=e, finalize=finalize)
        self._pending_reads.append(fut)
        return fut

    def get_key(self, selector, snapshot=False):
        return self.get_key_async(selector, snapshot=snapshot).wait()

    def get_range_async(self, begin, end, limit=0, reverse=False,
                        snapshot=False, streaming_mode=None):
        """Future-returning merged range read: snapshot rows overlaid
        with this txn's writes. begin/end: bytes or KeySelector
        (selectors resolve synchronously at issue — rare, and a
        selector walk cannot ride a key-bounded batch). The RYW
        overlay is captured AT ISSUE TIME, so the result reflects the
        writes present when the read was issued — the reference's
        future semantics."""
        self._guard()
        if specialkeys.contains(begin) or (
            isinstance(begin, KeySelector) and specialkeys.contains(begin.key)
        ):
            # special-space ranges take literal bytes only (the reference
            # rejects selectors against most special-key modules too)
            if not specialkeys.contains(begin) or not isinstance(end, bytes):
                raise err("key_outside_legal_range")
            if self._repair is not None:
                self._repair.unreplayable = True
            try:
                rows = specialkeys.get_range(
                    self, begin, min(end, specialkeys.END),
                    limit=limit, reverse=reverse,
                )
            except FDBError as e:
                return self._settled(error=e, cls=FutureRange)
            return self._settled(rows, cls=FutureRange)
        rv = self.get_read_version()
        st = self._cluster.read_storage()
        if begin is None:
            begin = b""
        if end is None:
            end = b"\xff"
        b = begin if isinstance(begin, bytes) else st.resolve_selector(begin, rv)
        e = end if isinstance(end, bytes) else st.resolve_selector(end, rv)
        if b > e:
            raise err("inverted_range")

        overlaps = not self._ryw_disabled and (
            self._writes.cleared_in(b, e)
            or next(self._writes.overlay_range(b, e), None) is not None
        )
        if overlaps:
            # merge path: fetch the whole base range, overlay at wait()
            # (cleared/overlay snapshots taken NOW — issue-time RYW)
            cleared = list(self._writes.cleared_in(b, e))
            overlay = list(self._writes.overlay_range(b, e))
            req_limit, req_reverse = 0, False
        else:
            # fast path: no uncommitted writes in range — push
            # limit/reverse down to storage instead of materializing
            cleared = overlay = None
            req_limit, req_reverse = limit, reverse
        sig = (b, e, req_limit, req_reverse)
        writes = self._writes

        def postprocess(rows):
            if overlay is None:
                return rows
            d = dict(rows)
            for cb, ce in cleared:
                for k in [k for k in d if cb <= k < ce]:
                    del d[k]
            for k, entry in overlay:
                base = d.get(k) if not entry.independent else None
                v = writes.fold(entry, base)
                if v is None:
                    d.pop(k, None)
                else:
                    d[k] = v
            out = sorted(d.items(), reverse=reverse)
            if limit:
                out = out[:limit]
            return out

        def record_conflict(out):
            if snapshot:
                return
            # conflict range covers what was actually observed: up to
            # the last row where the limit cut the scan short, and the
            # whole of [b, e) where the range ran out first (a row that
            # appears behind the last one would have been returned)
            if limit and len(out) >= limit:
                hi = key_successor(out[-1][0]) if not reverse else e
                lo = b if not reverse else out[-1][0]
                self._add_read_conflict(lo, hi)
            else:
                self._add_read_conflict(b, e)

        rcache = self._repair_range_cache
        if rcache is not None and sig in rcache:
            rows = list(rcache[sig])
            eng = self._repair
            if eng is not None and not snapshot \
                    and sig not in eng.range_reads:
                eng.range_reads[sig] = tuple(rows)
            out = postprocess(rows)
            record_conflict(out)
            return self._settled(out, cls=FutureRange)
        sp = self._span
        rsp = ctx = None
        if sp is not None and sp.sampled:
            rsp = sp.child("txn.read_range")
            ctx = rsp.context()

        def finalize(rows, error):
            if rsp is not None:
                rsp.finish()
            if error is not None:
                return None
            eng = self._repair
            if eng is not None and not snapshot \
                    and sig not in eng.range_reads:
                eng.range_reads[sig] = tuple(rows)
            out = postprocess(rows)
            record_conflict(out)
            return out

        range_async = getattr(st, "get_range_async", None)
        if range_async is not None:
            fut = range_async(b, e, rv, limit=req_limit,
                              reverse=req_reverse, finalize=finalize,
                              ctx=ctx)
        else:
            prior = span_mod.set_current(ctx)
            try:
                rows, exc = st.get_range(
                    b, e, rv, limit=req_limit, reverse=req_reverse
                ), None
            except FDBError as x:
                rows, exc = None, x
            finally:
                span_mod.set_current(prior)
            fut = self._settled(rows, error=exc, cls=FutureRange,
                                finalize=finalize)
        self._pending_reads.append(fut)
        return fut

    def get_range(self, begin, end, limit=0, reverse=False, snapshot=False,
                  streaming_mode=None):
        """Merged range read: snapshot rows overlaid with this txn's writes.

        begin/end: bytes or KeySelector. Returns list[(key, value)].
        """
        return self.get_range_async(
            begin, end, limit=limit, reverse=reverse, snapshot=snapshot,
            streaming_mode=streaming_mode,
        ).wait()

    def get_range_startswith_async(self, prefix, **kw):
        prefix = bytes(prefix)
        return self.get_range_async(prefix, strinc(prefix), **kw)

    def get_range_startswith(self, prefix, **kw):
        prefix = bytes(prefix)
        return self.get_range(prefix, strinc(prefix), **kw)

    # ───────────────────────────── writes ─────────────────────────────
    def _add_read_conflict(self, begin, end):
        self._read_conflicts.append((begin, end))

    def _add_write_conflict(self, begin, end):
        if self._next_write_no_conflict:
            self._next_write_no_conflict = False
            return
        self._write_conflicts.append((begin, end))

    def add_read_conflict_range(self, begin, end):
        self._guard()
        self._read_conflicts.append((bytes(begin), bytes(end)))

    def add_read_conflict_key(self, key):
        self.add_read_conflict_range(key, key_successor(key))

    def add_write_conflict_range(self, begin, end):
        self._guard()
        self._write_conflicts.append((bytes(begin), bytes(end)))

    def add_write_conflict_key(self, key):
        self.add_write_conflict_range(key, key_successor(key))

    def _log_mutation(self, m):
        self._mutation_log.append(m)
        self._size += len(m.key) + len(m.param or b"")
        if self._size > self._knobs.transaction_size_limit:
            raise err("transaction_too_large")

    def set(self, key, value):
        # the hottest client call: helpers (_log_mutation,
        # _add_write_conflict, key_successor) are inlined — at tens of
        # thousands of commits/sec their call overhead was measurable
        self._guard()
        # limits come from the knobs (defaults mirror core.keys
        # constants) so key_size_limit / value_size_limit are tunable
        key = _check_key(key, self._knobs.key_size_limit)
        value = _check_value(value, self._knobs.value_size_limit)
        if key.startswith(b"\xff") and specialkeys.contains(key):
            specialkeys.write(self, key, value)
            return
        self._writes.set(key, value)
        self._mutation_log.append(Mutation(Op.SET, key, value))
        self._size += len(key) + len(value)
        if self._size > self._knobs.transaction_size_limit:
            raise err("transaction_too_large")
        if self._next_write_no_conflict:
            self._next_write_no_conflict = False
        else:
            self._write_conflicts.append((key, key + b"\x00"))

    def clear(self, key):
        self._guard()
        key = _check_key(key)
        if specialkeys.contains(key):
            specialkeys.clear(self, key)
            return
        self._writes.clear(key)
        self._log_mutation(Mutation(Op.CLEAR_RANGE, key, key_successor(key)))
        self._add_write_conflict(key, key_successor(key))

    def clear_range(self, begin, end):
        self._guard()
        begin, end = _check_key(begin), _check_key(end)
        if begin > end:
            raise err("inverted_range")
        if specialkeys.contains(begin):
            specialkeys.clear_range(self, begin, end)
            return
        self._writes.clear_range(begin, end)
        self._log_mutation(Mutation(Op.CLEAR_RANGE, begin, end))
        self._add_write_conflict(begin, end)

    def clear_range_startswith(self, prefix):
        prefix = bytes(prefix)
        self.clear_range(prefix, strinc(prefix))

    def _atomic(self, op, key, param):
        self._guard()
        key = _check_key(key)
        if specialkeys.contains(key):
            # management modules take set/clear only; an atomic would
            # smuggle a raw mutation into the virtual keyspace
            raise err("key_outside_legal_range")
        param = bytes(param)
        self._writes.atomic(op, key, param)
        self._log_mutation(Mutation(op, key, param))
        self._add_write_conflict(key, key_successor(key))

    def add(self, key, param):
        self._atomic(Op.ADD, key, param)

    def bit_and(self, key, param):
        self._atomic(Op.BIT_AND, key, param)

    def bit_or(self, key, param):
        self._atomic(Op.BIT_OR, key, param)

    def bit_xor(self, key, param):
        self._atomic(Op.BIT_XOR, key, param)

    def min(self, key, param):
        self._atomic(Op.MIN, key, param)

    def max(self, key, param):
        self._atomic(Op.MAX, key, param)

    def byte_min(self, key, param):
        self._atomic(Op.BYTE_MIN, key, param)

    def byte_max(self, key, param):
        self._atomic(Op.BYTE_MAX, key, param)

    def append_if_fits(self, key, param):
        self._atomic(Op.APPEND_IF_FITS, key, param)

    def compare_and_clear(self, key, param):
        self._atomic(Op.COMPARE_AND_CLEAR, key, param)

    def set_versionstamped_key(self, key, value):
        self._guard()
        self._log_mutation(Mutation(Op.SET_VERSIONSTAMPED_KEY, key, value))
        # write conflict on the placeholder-resolved key is unknowable now;
        # the reference adds it server-side. Conservatively skip (matches
        # the binding: versionstamped keys are unique, conflicts moot).

    def set_versionstamped_value(self, key, value):
        self._guard()
        key = _check_key(key)
        self._log_mutation(Mutation(Op.SET_VERSIONSTAMPED_VALUE, key, value))
        self._add_write_conflict(key, key_successor(key))

    # dict-style sugar (binding parity)
    def __getitem__(self, key):
        if isinstance(key, slice):
            return self.get_range(key.start, key.stop)
        return self.get(key)

    def __setitem__(self, key, value):
        self.set(key, value)

    def __delitem__(self, key):
        if isinstance(key, slice):
            self.clear_range(key.start, key.stop)
        else:
            self.clear(key)

    # ───────────────────── size/split estimation ──────────────────────
    def get_estimated_range_size_bytes(self, begin, end):
        """Ref: fdb_transaction_get_estimated_range_size_bytes (sampled
        storage metrics — an estimate, not an exact byte count)."""
        self._guard()
        if self._repair is not None:
            self._repair.unreplayable = True  # sampled, not re-verifiable
        return self._cluster.estimated_range_size_bytes(
            _check_key(begin), _check_key(end)
        )

    def get_range_split_points(self, begin, end, chunk_size):
        """Ref: fdb_transaction_get_range_split_points — boundary keys
        cutting [begin, end) into ~chunk_size-byte chunks (includes both
        endpoints)."""
        self._guard()
        if self._repair is not None:
            self._repair.unreplayable = True
        return self._cluster.range_split_points(
            _check_key(begin), _check_key(end), int(chunk_size)
        )

    def get_approximate_size(self):
        """Ref: fdb_transaction_get_approximate_size — the commit
        payload this transaction has accumulated so far."""
        self._guard()
        return self._size

    # ─────────────────────────── watches ──────────────────────────────
    def watch(self, key):
        """Register interest in key changes; activates at commit.

        Ref: Transaction::watch — the watch compares against the value as
        of this transaction and fires when it changes afterward."""
        self._guard()
        key = _check_key(key)
        seen = self.get(key, snapshot=True)
        handle = _WatchHandle(key, seen)
        self._watches_pending.append(handle)
        return handle

    # ─────────────────────────── commit ───────────────────────────────
    def _drain_reads(self):
        """Settle every still-outstanding async read before the commit
        request is built: drained reads add their conflict ranges (an
        unwaited ``get_async`` the app ignored still participates in
        OCC, matching the reference where the read future's storage
        reply registered the range regardless of the app consuming
        it). Per-key read errors stay with their futures — an app that
        caught (or ignored) a failed read can still commit what it has."""
        pending, self._pending_reads = self._pending_reads, []
        for fut in pending:
            try:
                fut.wait()
            except FDBError:
                pass

    def _build_commit_request(self):
        self._drain_reads()
        # Lazy read version for READ-FREE transactions: with no read
        # conflict ranges the resolver never compares anything against
        # rv — it only places the txn inside the MVCC window — so the
        # PROXY assigns its current committed version at batch time
        # (read_version=None on the wire). Write-only traffic thus
        # skips the GRV round trip entirely: over a remote transport
        # that round trip was the single largest per-txn cost. A txn
        # that ever read (or pinned a version) keeps its honest rv, and
        # TAGGED txns always pay the GRV — per-tag throttling is
        # enforced at that gate (skipping it would let a throttled tag
        # write unthrottled); the untagged global budget is enforced at
        # the proxy for rv-None requests instead.
        idmp = self._ensure_idempotency_id()
        if (self._read_version is None and not self._read_conflicts
                and not self._tags and idmp is None):
            # id-carrying txns never ride the lazy-rv fast path: the
            # OCC serialization of a 1021 retry against its own
            # original (the idmp-row conflict ranges the proxy declares
            # in _build_txns) needs an honest read version — a
            # proxy-assigned rv on a different fleet member could land
            # at-or-after the original's commit and miss the conflict
            # (ADVICE r5: the read-free retry double-apply race)
            rv = None
        else:
            rv = self.get_read_version()
        rcr = _coalesce(self._read_conflicts)
        wcr = _coalesce(self._write_conflicts)
        # columnar fast path (core/flatpack.py): pre-encode the conflict
        # ranges into limb-entry blobs HERE, on the client, so neither
        # the wire decode nor the proxy's batch build ever re-parses a
        # key. Pure bytes ops — the limb encoding of an in-capacity key
        # is its zero-padded bytes plus a length word. None (a key past
        # limb capacity) simply leaves the request on the legacy path.
        flat = None
        if getattr(self._knobs, "commit_pack_path", "legacy") == "flat":
            flat = flatpack.encode_conflicts(
                rcr, wcr, self._knobs.key_limbs
            )
        # commit span (submit → settle): its context rides the request —
        # the server batch/stage spans parent to it
        sctx = None
        sp = self._trace_span()
        if sp is not span_mod.NULL:
            csp = self._commit_span = sp.child(
                "txn.commit", mutations=len(self._mutation_log))
            sctx = csp.context()
        return CommitRequest(
            read_version=rv,
            mutations=list(self._mutation_log),
            read_conflict_ranges=rcr,
            write_conflict_ranges=wcr,
            # the repair engine needs the conflicting ranges AND the
            # rejecting commit version on every 1020 it might repair
            report_conflicting_keys=(self._report_conflicting_keys
                                     or self._repair is not None),
            lock_aware=self._lock_aware,
            idempotency_id=idmp,
            flat_conflicts=flat,
            span_context=sctx,
            tags=tuple(self._tags),
        )

    def _ensure_idempotency_id(self):
        if self._idempotency_id is None and self._auto_idempotency:
            from foundationdb_tpu.core import deterministic

            # injected entropy: a seeded sim mints the same ids every
            # run, so 1021-retry histories replay byte-identically
            self._idempotency_id = deterministic.token_bytes(
                16, name="idempotency-id"
            )
        return self._idempotency_id

    def _finish_commit(self, result):
        """Mixed data+management transactions are NOT atomic: the data
        commit becomes durable first, then the buffered special-key
        writes apply. ``commit()`` re-checks the lock up front so a
        locked database rejects the whole transaction before any data
        commits; if a lock races in between the two halves, the data
        commit stands (it passed the proxy's lock check) and the fenced
        management writes are dropped with a trace — they are exactly
        the writes the new lock exists to fence, and raising here would
        falsely report a durably-committed transaction as failed."""
        if isinstance(result, FDBError):
            if result.code == 1021 and self._idempotency_id is not None:
                # commit_unknown_result disambiguation (ref:
                # IdempotencyId.actor.cpp): the id row is written
                # atomically WITH the mutations, so its presence at a
                # fresh read version proves the commit applied — resolve
                # to the original outcome instead of surfacing 1021
                recovered = self._lookup_idempotency()
                if recovered is not None:
                    result = recovered
            if isinstance(result, FDBError):
                self._state = "error"
                # conflict reporting: the failed txn's conflicting read
                # ranges become readable at
                # \xff\xff/transaction/conflicting_keys/ until the next
                # reset (ref: SpecialKeySpace ConflictingKeys)
                self._conflicting_ranges = getattr(
                    result, "conflicting_key_ranges", None
                )
                self._trace_commit_done(result)
                raise result
        # the data half is durable regardless of what the management
        # half does below: record it first so the client can always
        # observe what committed (mixed transactions are not atomic)
        if self._repair_assisted:
            # a repaired retry made it durable: the goodput the engine
            # exists for (txn/repair.py; rides the proxy registry)
            from foundationdb_tpu.txn import repair as repair_mod

            repair_mod.note(self._cluster, "repair_commits")
            self._repair_assisted = False
        self._committed_version = result
        self._versionstamp = Versionstamp.from_version(result).tr_version
        self._trace_commit_done(None)
        try:
            specialkeys.commit_special(self)
        except FDBError as e:
            if e.description == "database_locked" and not self._lock_aware:
                from foundationdb_tpu.utils.trace import TraceEvent

                TraceEvent("ManagementWritesFencedByLock",
                           severity=30).detail(
                    committed_version=result).log()
            else:
                # a genuine management failure (a lock-AWARE txn is
                # never fenced by the lock — e.g. locking over another
                # operator's uid raises its own 1038): surface it
                self._state = "error"
                raise
        self._state = "committed"
        self._activate_watches()

    def _trace_commit_done(self, error):
        """Settle the transaction's trace. Sampled: finish the commit
        span and the root. Unsampled-but-enabled: the ABORT promotion
        gate — a commit that failed (or was force-traced too late to
        re-root) reconstructs and emits its record on the error path;
        the happy path keeps nothing (slow-commit promotion is the
        batcher's per-window ``commit.window`` record instead — the
        per-txn clock stamps this once took busted the ≤2% budget)."""
        root = self._span
        if root is None:
            return
        if root is span_mod.NULL:
            if ((error is not None or self._trace_forced)
                    and self._knobs.tracing_sample_rate > 0.0):
                end = span_mod.now()
                span_mod.promote_lite(
                    end, end, commit_begin=end,
                    error_code=None if error is None else error.code,
                    retries=self._retries,
                )
            self._span = None
            return
        csp = self._commit_span
        if csp is not None:
            if error is not None:
                csp.finish(status="error", error_code=error.code)
            else:
                csp.finish(status="committed",
                           version=self._committed_version)
            self._commit_span = None
        root.finish(
            status="error" if error is not None else "committed",
            retries=self._retries,
        )
        self._span = None  # settled: a reused handle restarts its trace

    def _lookup_idempotency(self):
        """Best-effort id-row check at a fresh read version: the commit
        version if the id committed, else None. A cluster mid-recovery
        can fail the check — the 1021 then stands and the retry loop
        resubmits the SAME id, where the proxy's dedupe (the
        authoritative check, serialized with every commit) resolves it."""
        from foundationdb_tpu.core import systemdata

        try:
            rv = self._cluster.grv_proxy.get_read_version(
                priority="immediate"
            )
            key = systemdata.idmp_key(self._idempotency_id)
            row = self._cluster.read_storage(key).get(key, rv)
        except Exception:
            return None
        return None if row is None else systemdata.unpack_version(row)

    def _precheck_special_lock(self):
        """A mixed data+management transaction checks the lock BEFORE the
        data commit: without this, a lock landing between the (durable)
        data commit and the management application would surface as a
        non-retryable database_locked on a transaction whose data already
        committed (see _finish_commit for the remaining race)."""
        if self._special_writes and not self._lock_aware \
                and self._cluster.lock_uid() is not None:
            raise err("database_locked")

    @property
    def repair_ready(self):
        """True when a conflict repair replayed this transaction's op
        log verbatim (txn/repair.py): the retry loop should resubmit —
        ``commit()`` / ``commit_async()`` — WITHOUT re-running the
        body; running it anyway would double-apply the restored
        mutations."""
        return self._repair_ready

    def try_repair(self, error):
        """Attempt conflict repair for a failed commit instead of the
        cold restart (txn/repair.py). True = repaired: the read version
        moved to the rejecting commit version, reads are verified or
        refreshed, no backoff is owed — retry immediately (checking
        :attr:`repair_ready` first). False = restart cold (the caller
        owns reset/backoff). ``on_error`` calls this automatically."""
        if not isinstance(error, FDBError):
            return False
        from foundationdb_tpu.txn import repair as repair_mod

        return repair_mod.attempt(self, error)

    def commit(self):
        self._guard()
        self._repair_ready = False  # consumed: this IS the resubmission
        self._drain_reads()
        if not self._mutation_log and not self._write_conflicts:
            # read-only (or management-only): nothing to resolve
            # (ref: read-only commits skip proxies)
            specialkeys.commit_special(self)
            self._state = "committed"
            self._activate_watches()
            self._trace_commit_done(None)
            return
        self._precheck_special_lock()
        self._finish_commit(
            self._cluster.commit_proxy.commit(self._build_commit_request())
        )

    def commit_async(self):
        """Submit to the batching commit proxy; returns a CommitFuture.

        The cooperative-actor commit path (ref: Transaction::commit is an
        ACTOR returning Future<Void>): the caller yields until
        ``fut.done()``, then calls :meth:`commit_finish` to apply the
        outcome. Requires the cluster's proxy to support ``submit``
        (BatchingCommitProxy); the plain synchronous proxy does not.
        """
        self._guard()
        self._repair_ready = False  # consumed: this IS the resubmission
        self._drain_reads()
        if not self._mutation_log and not self._write_conflicts:
            from foundationdb_tpu.server.batcher import CommitFuture

            # same contract as commit()'s read-only path: management-only
            # transactions still apply their buffered special writes
            specialkeys.commit_special(self)
            self._state = "committed"
            self._activate_watches()
            self._trace_commit_done(None)
            fut = CommitFuture()
            fut.set(None)
            return fut
        self._precheck_special_lock()
        req = self._build_commit_request()
        # in-flight: further ops (or a second commit) must fail
        # used_during_commit, not silently re-submit the mutation log
        # (ref: used_during_commit in NativeAPI while the commit actor runs)
        self._state = "committing"
        return self._cluster.commit_proxy.submit(req)

    def commit_finish(self, fut):
        """Apply a resolved commit_async future (raises FDBError on
        conflict, exactly like commit())."""
        if self._state == "committed":  # read-only fast path already done
            return
        self._finish_commit(fut.result(timeout=0))

    def _activate_watches(self):
        for h in self._watches_pending:
            h._bind(self._cluster.read_storage(h.key).watch(h.key, h.seen_value))
        self._watches_pending = []

    def on_error(self, error):
        """The retry protocol (ref: Transaction::onError): backoff and
        reset for retryable errors, re-raise otherwise."""
        if not isinstance(error, FDBError) or not error.is_retryable:
            raise error
        self._retries += 1
        if self._retry_limit is not None and self._retries > self._retry_limit:
            raise error
        if self.try_repair(error):
            # repaired (txn/repair.py): read version moved to the
            # rejecting commit version, reads verified or refreshed —
            # no backoff owed, retry immediately (repair_ready decides
            # whether the body re-runs)
            return
        # set_max_retry_delay may have moved the cap after _reset built
        # the policy: the option always wins (reference binding parity)
        self._backoff.max_s = self._max_retry_delay
        self._backoff.sleep()
        # timeout/retry_limit/max_retry_delay persist across resets, like
        # the reference binding (fdb_transaction_reset keeps those
        # options); the idempotency id persists too — the SAME id must
        # ride every retry of this logical transaction or the proxy's
        # dedupe has nothing to match (ref: IdempotencyId surviving
        # onError)
        keep = (self._retries, self._backoff, self._retry_limit,
                self._max_retry_delay, self._timeout_s,
                self._idempotency_id, self._auto_idempotency,
                self._trace_forced, self._tags)
        self._reset()
        (self._retries, self._backoff, self._retry_limit,
         self._max_retry_delay, self._timeout_s,
         self._idempotency_id, self._auto_idempotency,
         self._trace_forced, self._tags) = keep

    def reset(self):
        self._reset()

    def cancel(self):
        """Ref: fdb_transaction_cancel — all further use raises 1025
        until reset()."""
        self._state = "cancelled"
        # outstanding async reads settle with 1025 NOW (FL002): a
        # waiter blocked on a cancelled txn's read must not hang
        pending, self._pending_reads = self._pending_reads, []
        for fut in pending:
            fut.cancel()


class _WatchHandle:
    """Client-side watch future (ref: Watch in NativeAPI)."""

    def __init__(self, key, seen_value):
        self.key = key
        self.seen_value = seen_value
        self._watch = None

    def _bind(self, storage_watch):
        self._watch = storage_watch

    @property
    def active(self):
        return self._watch is not None

    def is_set(self):
        return self._watch is not None and self._watch.fired

    def wait(self, timeout=None, poll=0.001):
        """Block until fired (in-process: commits fire synchronously;
        remote: a blocking server-side wait instead of poll RPCs)."""
        if self._watch is None:
            raise err("operation_failed")
        waiter = getattr(self._watch, "wait_remote", None)
        if waiter is not None:
            if waiter(timeout):
                return True
            raise err("timed_out")
        start = time.monotonic()
        # jittered growing poll (utils/backoff.py): a long-parked watch
        # costs ~50 wakeups/s at first, decaying to ~50/s-worst-case
        # 20ms polls — not a 1ms busy spin for its whole life
        poller = Backoff(initial_s=poll, max_s=0.02, growth=1.5)
        while not self._watch.fired:
            if timeout is not None and time.monotonic() - start > timeout:
                raise err("timed_out")
            poller.sleep()
        return True


def _coalesce(ranges):
    """Sort + merge overlapping conflict ranges (smaller resolver
    payload). 0/1-range transactions — the bulk of point traffic —
    skip the sort entirely."""
    if len(ranges) <= 1:
        return list(ranges)
    rs = sorted(ranges)
    out = [list(rs[0])]
    for b, e in rs[1:]:
        if b <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([b, e])
    return [(b, e) for b, e in out]
