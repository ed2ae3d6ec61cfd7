"""Cross-client commit batching — the commit proxy's real job.

Ref parity: fdbserver/CommitProxyServer.actor.cpp commitBatcher (~L300):
client commits accumulate into a batch bounded by an interval and a size
cap; the whole batch shares one commit version and one resolver dispatch.
The TPU resolver inverts the reference's cost model — big batches are
*cheaper* per txn — so keeping batches full is the whole performance
story: a 1-txn batch pads the kernel's T-lane to 0.1% occupancy.

Two drive modes:

- **thread** (live deployments): a daemon batcher thread
  collects submissions for up to ``interval_s`` (or until ``max_batch``),
  then drives the inner proxy. In-process clients block on a
  CommitFuture; a served ``commit`` (rpc/service.py) blocks no thread:
  it hands the future a completion and the settle answers it. With
  ``knobs.commit_pipeline_depth > 1`` the drain loop is a bounded
  TWO-STAGE pipeline: the batcher thread runs stage A+B of each backlog
  group (version grant + host packing + gate-ordered lazy resolve
  dispatch, proxy.commit_batches_begin) and a second apply worker runs
  stage C (status sync + tlog push + storage apply,
  proxy.commit_batches_finish) strictly in grant order — so group N+1
  packs on the host and resolves on the device while group N applies.
  Depth 1 reproduces the old serial loop exactly. Client threads read
  storage under each StorageServer's mutation lock (storage.py
  ``_mu``), which the apply/flush path also takes — point and range
  reads are consistent even while the pipeline mutates the overlay.

- **manual** (deterministic simulation): no thread, no wall clock.
  Actors submit and yield on the future; the sim scheduler calls
  ``pump(step)`` which flushes when the batch is full or ``flush_after``
  scheduling steps have passed since the first pending submission.
  A synchronous ``commit()`` flushes immediately — riding every pending
  async submission along in the same batch.
"""

import threading
import time
from collections import deque

from foundationdb_tpu.core.errors import FDBError
from foundationdb_tpu.utils import lockdep
from foundationdb_tpu.utils import metrics as metrics_mod
from foundationdb_tpu.utils import span as span_mod
from foundationdb_tpu.utils.trace import SEV_ERROR, StageStats, TraceEvent


_UNSET = object()

# set() racing set() (the watchdog's 1021 against a wedged drive's late
# result) has one winner, and the winner alone runs the completion: one
# lock for every future, held for a compare and two stores
_settle_mu = lockdep.lock("batcher._settle_mu")


class CommitFuture:
    """Resolves to a commit version (int) or an FDBError.

    Futures from one BatchingCommitProxy share its completion condition
    instead of carrying a private threading.Event each: a whole batch
    resolves together, so one notify_all per batch wakes every waiter —
    the per-commit Event (allocation + lock dance on both set and wait)
    was measurable e2e overhead at tens of thousands of commits/sec.
    A standalone future (no proxy) must be ``set`` before ``result`` is
    awaited — the pattern of every standalone construction site
    (read-only fast paths, fault wrappers resolve immediately).

    A holder that must not block (the served ``commit``: its thread owns
    a connection) takes the result through ``add_done_callback`` and
    calls ``poll`` now and then in place of ``result``."""

    __slots__ = ("_result", "_proxy", "born", "_on_done")

    def __init__(self, proxy=None):
        self._result = _UNSET
        self._proxy = proxy
        self.born = None  # injected-clock stamp set at submit (spans)
        self._on_done = None

    def done(self):
        return self._result is not _UNSET

    def set(self, result):
        """Settle; returns what the completion returned (below), None
        where there is none or the future was settled already."""
        # first settlement wins: once a waiter may have observed a
        # verdict (e.g. the stranded-batch watchdog's 1021, already
        # acted on by a retry), a late real result must not replace it
        # — an acked-then-changed verdict is how double-applies happen
        with _settle_mu:
            if self._result is not _UNSET:
                return None
            self._result = result
            fn, self._on_done = self._on_done, None
        return None if fn is None else fn(result)

    def add_done_callback(self, fn):
        """The future's one completion: ``fn(result)`` runs exactly
        once, on the thread whose ``set`` wins — here and now if that
        has happened — outside every lock of this module, and must not
        raise. It may hand back a callable (a flush of what it queued):
        whoever settled the future calls it once it has settled all it
        had to, so completions of one batch can share a send."""
        with _settle_mu:
            if self._result is _UNSET:
                if self._on_done is not None:
                    raise RuntimeError("commit future has a completion")
                self._on_done = fn
                return None
            result = self._result
        return fn(result)

    def poll(self):
        """The proxy's stranded-batch watchdog, for a holder that does
        not wait in ``result`` (which runs it between wait chunks)."""
        if self._proxy is not None:
            self._proxy._check_stranded()

    def result(self, timeout=None):
        """Block until resolved (thread mode); returns version or FDBError.

        Waits in bounded chunks, invoking the proxy's stranded-batch
        watchdog between them: a batch wedged inside the inner proxy
        past the commit deadline settles as commit_unknown_result on
        the WAITING thread — a hung pipeline costs a deadline, never a
        hung client (FL002 settle-and-retry)."""
        if self._result is not _UNSET:
            return self._result
        if self._proxy is None:
            raise TimeoutError("standalone commit future never resolved")
        cond = self._proxy._done_cond
        deadline = None if timeout is None else time.monotonic() + timeout
        while True:
            chunk = 0.25
            if deadline is not None:
                remaining = deadline - time.monotonic()
                if remaining <= 0 and not self.done():
                    raise TimeoutError("commit future not resolved")
                chunk = min(chunk, max(0.0, remaining))
            with cond:
                cond.wait_for(self.done, chunk)
            if self.done():
                return self._result
            self._proxy._check_stranded()


class BatchingCommitProxy:
    """Accumulates CommitRequests into shared-version batches."""

    WATCHDOG_GRACE_S = 1.0

    def __init__(self, inner, max_batch=None, interval_s=None,
                 flush_after=4, mode="thread"):
        self.inner = inner
        knobs = inner.knobs
        self.max_batch = max_batch or min(
            knobs.batch_txn_capacity, 1024
        )
        self.interval_s = (
            interval_s if interval_s is not None
            else knobs.commit_batch_interval_s
        )
        self.flush_after = flush_after  # manual mode: sim steps before flush
        self.mode = mode
        self._lock = lockdep.lock("BatchingCommitProxy._lock")
        self._pending = []  # [(request, future)]
        self._first_pending_step = None
        self._wake = lockdep.condition("BatchingCommitProxy._lock", self._lock)
        self._done_cond = lockdep.condition("BatchingCommitProxy._done_cond")  # batch-completion waiters
        self._closed = False
        # stranded-batch watchdog bound: a batch inside the inner proxy
        # longer than this settles 1021 from the waiting client thread.
        # Two commit deadlines of slack — the inner proxy may itself be
        # a deadline-bounded RPC that retries once — plus grace.
        self.watchdog_s = (
            2 * getattr(knobs, "rpc_deadline_commit_s", 15.0)
            + self.WATCHDOG_GRACE_S
        )
        self._running = None  # batch currently driving the inner proxy
        self._running_since = 0.0
        self.stranded_settled = 0
        self.batches_committed = 0
        self.txns_batched = 0
        self.max_batch_seen = 0
        # flowlint: shared(last-writer-wins debug breadcrumb; readers only poll it)
        self.last_batch_error = None
        # flowlint: shared(AIMD heuristic target; GIL-atomic int, staleness is benign)
        self._backlog_target = self.MAX_BACKLOG
        self._thread = None
        # ── bounded commit pipeline (thread mode only) ──
        # Up to ``commit_pipeline_depth`` backlog groups in flight:
        # this thread runs stage A+B (version grant + host packing +
        # lazy resolve dispatch) for group N+1 while the apply worker
        # runs stage C (status sync + tlog push + storage apply) for
        # group N. Depth 1 — and manual/sim mode always — is the
        # strictly serial drain loop, byte-for-byte today's behavior.
        depth = getattr(knobs, "commit_pipeline_depth", 1)
        self.pipeline_depth = max(1, int(depth)) if mode == "thread" else 1
        # share the inner proxy's registry (one "commit_proxy" document
        # carrying both the proxy's error-class counters and the
        # batcher's spans); remote/inner-less wrappers get their own
        self.metrics = getattr(inner, "metrics", None) \
            or metrics_mod.MetricsRegistry("commit_proxy")
        if hasattr(inner, "spans_owned_externally"):
            # claim the commit_e2e span: this wrapper sees the full
            # submit→settle window (queue wait included), so the inner
            # proxy must not double-record the narrower batch span
            inner.spans_owned_externally = True
        # the per-batch end-to-end commit span (submit → settle): the
        # latency-band number the <2ms-added-p99 target is gated on
        self._m_e2e = self.metrics.latency("commit_e2e")
        self._m_settled_batches = self.metrics.counter("batches_settled")
        # how long a drained window's OLDEST submit waited for the
        # batcher thread (the queue in front of the proxy): one record
        # per window, from the stamp commit_e2e already takes
        self._m_wait = self.metrics.latency("batcher_wait")
        self.stages = StageStats(registry=self.metrics)
        self._inflight = deque()  # [(chunks, _PipelinedGroup)] FIFO
        self._inflight_cv = lockdep.condition("BatchingCommitProxy._inflight_cv")
        self._apply_thread = None
        if mode == "thread" and self.pipeline_depth > 1 \
                and hasattr(inner, "commit_batches_begin"):
            self._apply_thread = threading.Thread(
                target=self._apply_loop, name="commit-apply", daemon=True
            )
            self._apply_thread.start()
        if mode == "thread":
            self._thread = threading.Thread(
                target=self._batcher_loop, name="commit-batcher", daemon=True
            )
            self._thread.start()

    # ────────────────────────── client surface ──────────────────────────
    def submit(self, request):
        """Enqueue a commit; returns a CommitFuture."""
        fut = CommitFuture(self)
        with self._lock:
            if self._closed:
                raise RuntimeError("batching proxy is closed")
            if not self._pending:
                # stamp the FIRST submit of each batch window only: it
                # is the oldest — the span _record_span publishes — and
                # one clock call per window keeps per-txn metric cost
                # out of the commit hot path
                fut.born = metrics_mod.now()
            self._pending.append((request, fut))
            self._wake.notify()
        return fut

    def commit(self, request):
        """Synchronous commit (the Transaction.commit path).

        Thread mode: submit and block — the batcher thread forms the
        batch, so concurrent committers share a version. Manual mode:
        submit and flush now, batching up every pending async commit.
        """
        fut = self.submit(request)
        if self.mode == "thread":
            return fut.result()
        self.flush()
        return fut.result(timeout=0)

    # ─────────────────────────── batch driving ──────────────────────────
    def flush(self):
        """Drain everything pending into one inner commit_batch, then
        wait for any in-flight pipelined groups to settle — a returned
        flush means every submitted commit has resolved."""
        with self._lock:
            pending, self._pending = self._pending, []
            self._first_pending_step = None
        if pending:
            self._run_batch(pending)
        self.drain_pipeline()

    def pump(self, step):
        """Manual-mode heartbeat from the sim scheduler: flush when full
        or when ``flush_after`` steps have passed since the first pending
        submission (the deterministic analog of the batch interval)."""
        with self._lock:
            n = len(self._pending)
            if n and self._first_pending_step is None:
                self._first_pending_step = step
            due = n >= self.max_batch or (
                n and step - self._first_pending_step >= self.flush_after
            )
        if due:
            self.flush()

    # cap on batches per commit_batches call. The resolver chunks the
    # backlog into BACKLOG_B-wide scans internally, so this only bounds
    # how much queue drains per settle round (keeping client latency and
    # host-side packing memory bounded), not the dispatch width.
    MAX_BACKLOG = 64

    # Conflict-adaptive backlog depth: every txn in one settle round
    # resolves against read versions from before the round, so OCC
    # conflict probability grows with depth × contention. On contended
    # workloads (TPC-C hot rows) a 64-deep backlog turns throughput into
    # retries; on YCSB-shaped traffic depth is pure win. AIMD on the
    # observed conflict rate — the same signal the reference's
    # ratekeeper damps overload with (ref: Ratekeeper.actor.cpp).
    BACKLOG_SHRINK_AT = 0.35  # conflict rate that halves the depth
    BACKLOG_GROW_AT = 0.15  # conflict rate that lets depth double

    def _adapt_backlog(self, txns, conflicts):
        if txns == 0:
            return
        rate = conflicts / txns
        if rate > self.BACKLOG_SHRINK_AT:
            self._backlog_target = max(1, self._backlog_target // 2)
        elif rate < self.BACKLOG_GROW_AT:
            self._backlog_target = min(
                self.MAX_BACKLOG, self._backlog_target * 2
            )

    def _check_stranded(self):
        """Stranded-batch watchdog (invoked by waiting clients between
        wait chunks, and for the commits nobody waits on by whoever
        holds their futures: ``CommitFuture.poll``): a batch
        that has been driving the inner proxy
        past ``watchdog_s`` settles every future in it with 1021 — the
        commits MAY have happened; the retry loop's idempotency ids own
        the disambiguation. The wedged drive keeps running; its eventual
        ``set`` calls lose to the watchdog's (first settlement wins)."""
        with self._lock:
            run = self._running
            if run is None \
                    or time.monotonic() - self._running_since \
                    < self.watchdog_s:
                return
            self._running = None  # claimed: exactly one waiter settles
            self.stranded_settled += len(run)
        TraceEvent("CommitBatchStranded", severity=30).detail(
            txns=len(run), bound_s=self.watchdog_s).log()
        unknown = FDBError.from_name("commit_unknown_result")
        self._set_all((fut, unknown) for _, fut in run)

    def _run_batch(self, pending):
        with self._lock:
            self._running = pending
            self._running_since = time.monotonic()
        try:
            self._run_batch_inner(pending)
        finally:
            with self._lock:
                if self._running is pending:
                    self._running = None

    def _run_batch_inner(self, pending):
        chunks = [
            pending[i : i + self.max_batch]
            for i in range(0, len(pending), self.max_batch)
        ]
        while chunks:
            depth = self._backlog_target
            group, chunks = chunks[:depth], chunks[depth:]
            if len(group) > 1 and hasattr(self.inner, "commit_batches"):
                # a backlog: one resolver dispatch covers every chunk
                # (ref: the proxy pipelining resolution across batches)
                reqs = [[r for r, _ in c] for c in group]
                if self._apply_thread is not None:
                    try:
                        eligible = self.inner.pipeline_eligible(reqs)
                    except Exception as e:
                        TraceEvent("CommitBatchError",
                                   severity=SEV_ERROR).detail(
                            phase="eligibility",
                            etype=type(e).__name__,
                            error=str(e)[:200]).log()
                        self._fail_chunks(group, e)
                        continue
                    if eligible:
                        # the pipelined route: stages A+B now, stage C
                        # on the apply worker while the NEXT group
                        # packs here
                        try:
                            self._pipeline_submit(group, reqs)
                        except Exception as e:
                            # begin died outside its own guards (e.g. a
                            # dedupe/storage TOCTOU): same contract as a
                            # failed commit_batches — futures resolve
                            TraceEvent("CommitBatchError",
                                       severity=SEV_ERROR).detail(
                                phase="pipeline_begin",
                                etype=type(e).__name__,
                                error=str(e)[:200]).log()
                            self._fail_chunks(group, e)
                        continue
                # serial fallback (lock/dedupe-hit/overload/fleet of
                # resolvers): in-flight pipelined groups must settle
                # first or this group's versions would overtake theirs
                # at the log
                self.drain_pipeline()
                try:
                    results_list = self.inner.commit_batches(reqs)
                except Exception as e:
                    TraceEvent("CommitBatchError",
                               severity=SEV_ERROR).detail(
                        phase="backlog",
                        etype=type(e).__name__,
                        error=str(e)[:200]).log()
                    self._fail_chunks(group, e)
                    continue
                txns = conflicts = 0
                for chunk, results in zip(group, results_list):
                    self._settle(chunk, results)
                    txns += len(results)
                    conflicts += sum(
                        1 for r in results
                        if isinstance(r, FDBError) and r.code == 1020
                    )
                self._adapt_backlog(txns, conflicts)
                continue
            self.drain_pipeline()
            for chunk in group:
                try:
                    results = self.inner.commit_batch([r for r, _ in chunk])
                except Exception as e:  # resolve/apply blew up: fail it
                    # Never propagate: every future must resolve (an
                    # escaped exception would kill the batcher thread and
                    # leave later chunks' clients blocked forever) and
                    # the remaining chunks still deserve their shot. The
                    # pipeline may or may not have made the chunk durable
                    # — exactly what commit_unknown_result (1021) means.
                    TraceEvent("CommitBatchError",
                               severity=SEV_ERROR).detail(
                        phase="batch",
                        etype=type(e).__name__,
                        error=str(e)[:200]).log()
                    self._fail_chunks([chunk], e)
                    continue
                self._settle(chunk, results)
                self._adapt_backlog(
                    len(results),
                    sum(1 for r in results
                        if isinstance(r, FDBError) and r.code == 1020),
                )

    # ─────────────────────── pipeline executor ──────────────────────
    def _dispatch_wall(self):
        """The resolvers' cumulative device-dispatch wall time (the
        scan call inside resolve_many) — subtracted from the stage-A+B
        timer so pack and dispatch report as separate stages."""
        return sum(
            getattr(r, "dispatch_wall_s", 0.0)
            for r in getattr(self.inner, "resolvers", ())
        )

    def _pipeline_submit(self, group_chunks, reqs):
        """Run stages A+B for one backlog group and hand it to the
        apply worker; blocks while ``pipeline_depth`` groups are already
        in flight (bounding version-grant runahead and host memory)."""
        with self._inflight_cv:
            while len(self._inflight) >= self.pipeline_depth \
                    and self._apply_thread.is_alive():
                self._inflight_cv.wait(timeout=1.0)
        t0s = span_mod.now()  # stage-span stamp (cheap; ctx known after)
        d0 = self._dispatch_wall()
        t0 = time.perf_counter()
        pgroup = self.inner.commit_batches_begin(reqs)
        pack_s = time.perf_counter() - t0
        # the group's trace context was scanned ONCE inside begin
        gctx = getattr(pgroup, "trace_ctx", None)
        # hand the group to the apply worker BEFORE any other fallible
        # call (FL002): once queued, stage C settles its futures even if
        # this thread dies; the stage timers record after the handoff
        with self._inflight_cv:
            self._inflight.append((group_chunks, pgroup))
            self._inflight_cv.notify_all()
        # dispatch (stage B's scan call) accumulated on this same
        # thread inside begin: report it as its own stage so
        # the pack stage measures HOST PACKING (grant + batch build +
        # staging scatter), the stage the flat path exists to cut
        dispatch_s = max(0.0, self._dispatch_wall() - d0)
        self.stages.add("pack", max(0.0, pack_s - dispatch_s))
        self.stages.add("dispatch", dispatch_s)
        if gctx is not None:
            # per-stage spans mirroring the StageStats split: the pack
            # span is the host-packing share of begin(), the dispatch
            # span the device scan call carved off its tail
            t1s = span_mod.now()
            cut = max(t0s, t1s - dispatch_s)
            span_mod.emit_span("stage.pack", gctx, begin=t0s, end=cut)
            span_mod.emit_span("stage.dispatch", gctx, begin=cut,
                               end=t1s)

    def drain_pipeline(self):
        """Block until every in-flight group has settled (ordering
        barrier before serial fallbacks, flush, and close)."""
        if self._apply_thread is None:
            return
        with self._inflight_cv:
            while self._inflight and self._apply_thread.is_alive():
                self._inflight_cv.wait(timeout=1.0)

    def _apply_loop(self):
        while True:
            with self._inflight_cv:
                while not self._inflight and not self._closed:
                    self._inflight_cv.wait()
                if not self._inflight and self._closed:
                    return
                group_chunks, pgroup = self._inflight[0]
            try:
                self._finish_group(group_chunks, pgroup)
            except BaseException as e:  # pragma: no cover — last resort
                # _finish_group resolves futures itself; this guard only
                # keeps the worker alive (a dead worker would hang both
                # drain_pipeline and every waiting client). Futures are
                # re-set defensively — set() on a settled future is a
                # no-op-safe overwrite the waiters never observe twice.
                TraceEvent("CommitApplyWorkerError",
                           severity=SEV_ERROR).detail(
                    etype=type(e).__name__, error=str(e)[:200]).log()
                self.last_batch_error = e
                try:
                    self._fail_chunks(group_chunks, e)
                except Exception as e2:
                    TraceEvent("CommitSettleError",
                               severity=SEV_ERROR).detail(
                        etype=type(e2).__name__,
                        error=str(e2)[:200]).log()
            finally:
                with self._inflight_cv:
                    self._inflight.popleft()
                    self._inflight_cv.notify_all()

    def _finish_group(self, group_chunks, pgroup):
        """Stage C for one group: finish at the proxy, settle futures
        in order, feed the AIMD backlog and the stage timers."""
        gctx = getattr(pgroup, "trace_ctx", None)
        t0s = span_mod.now() if gctx is not None else 0.0
        try:
            results_list = self.inner.commit_batches_finish(pgroup)
        except Exception as e:
            self._fail_chunks(group_chunks, e)
            return
        if pgroup.error is not None:
            # the group failed inside the proxy (results are honest
            # 1020/1021s); record the root cause like the serial path
            self.last_batch_error = pgroup.error
        self.stages.add("resolve", pgroup.resolve_s)
        self.stages.add("apply", pgroup.apply_s)
        if gctx is not None:
            # stage-C spans mirroring the timers finish() recorded:
            # resolve (the host sync stall) from the front of the call,
            # apply (log push + storage apply) carved off its tail
            t1s = span_mod.now()
            span_mod.emit_span(
                "stage.resolve", gctx, begin=t0s,
                end=min(t1s, t0s + pgroup.resolve_s))
            span_mod.emit_span(
                "stage.apply", gctx,
                begin=max(t0s, t1s - pgroup.apply_s), end=t1s)
        txns = conflicts = 0
        for chunk, results in zip(group_chunks, results_list):
            self._settle(chunk, results)
            txns += len(results)
            conflicts += sum(
                1 for r in results
                if isinstance(r, FDBError) and r.code == 1020
            )
        self._adapt_backlog(txns, conflicts)

    def _set_all(self, settled):
        """Settle every (future, result) pair, wake the threads that
        wait in ``result`` ONCE for the whole batch, then run each
        flush the completions handed back once: a batch's served
        commits are answered together, behind the last ``set``."""
        flushes = set()
        with span_mod.annotation("batcher.settle"):
            for fut, res in settled:
                flush = fut.set(res)
                if flush is not None:
                    flushes.add(flush)
            with self._done_cond:
                self._done_cond.notify_all()
            for flush in flushes:
                flush()

    def _settle(self, chunk, results):
        self._record_span(chunk)
        with self._done_cond:
            # stat counters live under _done_cond: _settle runs on the
            # batcher thread, the apply worker, AND caller threads
            # (manual/sim pipelines), so the bare += was a lost-update
            self.batches_committed += 1
            self.txns_batched += len(chunk)
            self.max_batch_seen = max(self.max_batch_seen, len(chunk))
        self._set_all(zip((fut for _, fut in chunk), results))

    def _record_span(self, chunk):
        """One commit_e2e band record per settled batch window: the
        span from the window's OLDEST submit (the stamped head future —
        submit order is preserved into the chunks) to now. Every txn in
        the window replies together, so this is the honest worst case;
        per batch, not per txn, because tens of thousands of record()
        calls per second would themselves be commit-path overhead.

        The SAME stamps drive slow-commit promotion (utils/span.py): a
        window outliving ``tracing_slow_commit_ms`` while tracing is
        enabled emits a ``commit.window`` span — per-window, like the
        band itself, so unsampled transactions pay nothing extra."""
        if not metrics_mod.enabled():
            return
        born = chunk[0][1].born if chunk else None
        if born is not None:
            end = metrics_mod.now()
            dur = max(0.0, end - born)
            self._m_e2e.record(dur)
            knobs = getattr(self.inner, "knobs", None)
            if (knobs is not None
                    and getattr(knobs, "tracing_sample_rate", 0.0) > 0.0
                    and dur * 1e3 >= knobs.tracing_slow_commit_ms):
                span_mod.slow_window_span(born, end, txns=len(chunk))
        self._m_settled_batches.inc()

    def _fail_chunks(self, chunks, e):
        self.last_batch_error = e
        error = e if isinstance(e, FDBError) else \
            FDBError.from_name("commit_unknown_result")
        for chunk in chunks:
            self._record_span(chunk)  # a failure reply is still a reply
        self._set_all((fut, error) for chunk in chunks for _, fut in chunk)

    def _batcher_loop(self):
        while True:
            # acquire via the Condition (it wraps self._lock — the same
            # mutex): waiting on the object we hold keeps the
            # release-while-parked relationship explicit (FL003)
            with self._wake:
                while not self._pending and not self._closed:
                    self._wake.wait()
                if self._closed and not self._pending:
                    return
            # batch window: let concurrent committers pile in
            if self.interval_s:
                with span_mod.stage("batcher.window"):
                    time.sleep(self.interval_s)
            with self._lock:
                pending, self._pending = self._pending, []
                self._first_pending_step = None
            if pending:
                born = pending[0][1].born
                if born is not None and metrics_mod.enabled():
                    self._m_wait.record(max(0.0, metrics_mod.now() - born))
                try:
                    self._run_batch(pending)
                except BaseException as e:  # pragma: no cover — last resort
                    # _run_batch resolves futures itself; this guard only
                    # keeps the batcher alive if future.set's internals fail
                    TraceEvent("CommitBatcherError",
                               severity=SEV_ERROR).detail(
                        etype=type(e).__name__, error=str(e)[:200]).log()
                    self.last_batch_error = e

    def fail_pending(self, error):
        """Resolve every queued commit with ``error`` — a cluster crash
        took the proxy down before the batch formed; clients see
        commit_unknown_result and retry against the new incarnation."""
        with self._lock:
            pending, self._pending = self._pending, []
            self._first_pending_step = None
        self._set_all((fut, error) for _, fut in pending)

    def close(self):
        with self._lock:
            self._closed = True
            self._wake.notify_all()
        if self._thread is not None:
            self._thread.join(timeout=30)
            if self._thread.is_alive():
                # still mid-batch (e.g. first-dispatch JIT compile): the
                # batcher owns the pipeline; flushing from this thread
                # would interleave two commit_batch runs on shared state
                return
        self.flush()
        if self._apply_thread is not None:
            # flush drained the pipe; the closed flag lets the worker
            # exit its wait loop
            with self._inflight_cv:
                self._inflight_cv.notify_all()
            self._apply_thread.join(timeout=30)
        if hasattr(self.inner, "close"):
            self.inner.close()  # release the sub-resolve pool

    # pass everything else (commit_count, pump_durability, …) through
    def __getattr__(self, name):
        return getattr(self.inner, name)
