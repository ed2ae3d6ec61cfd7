"""GRV proxy: hands out read versions, gated by the ratekeeper.

Ref parity: fdbserver/GrvProxyServer.actor.cpp — a read version is the
latest committed version (so reads observe all prior commits: external
consistency), batched across clients; the ratekeeper can delay or reject
under saturation.

``BatchingGrvProxy`` is the reference's transaction-start batching loop:
concurrent clients' GRV requests accumulate for a batch window and are
granted from ONE committed-version read; under throttling a request is
DELAYED in the queue until the token bucket refills (the reference's
GRV queue), not bounced — only a request older than ``max_wait_s`` is
rejected (retryable), bounding client latency.
"""

import threading

import time

from foundationdb_tpu.core.errors import err
from foundationdb_tpu.utils import lockdep
from foundationdb_tpu.utils.backoff import Backoff
from foundationdb_tpu.utils import metrics as metrics_mod
from foundationdb_tpu.utils import span as span_mod


class GrvProxy:
    def __init__(self, sequencer, ratekeeper=None, metrics=None):
        self.sequencer = sequencer
        self.ratekeeper = ratekeeper
        self.grv_count = 0
        # persistent across recovery incarnations (the cluster hands the
        # same registry to the replacement): started-txn counters and
        # the grant-latency bands must never rewind
        self.metrics = metrics if metrics is not None \
            else metrics_mod.MetricsRegistry("grv_proxy")
        self._m_grants = self.metrics.counter("grv_grants")
        self._m_throttled = self.metrics.counter("grv_throttled")
        self._m_tag_throttled = self.metrics.counter("grv_tag_throttled")
        self._m_tag_started = {}  # tag -> counter handle (lazy)

    def _note_tag_started(self, tags):
        """Per-tag started counters (workload attribution): the tag
        rollup's denominator. Lives in the role registry so recovery
        absorption carries it like every other counter."""
        for t in tags:
            c = self._m_tag_started.get(t)
            if c is None:
                c = self._m_tag_started[t] = self.metrics.counter(
                    "tag_started_" + t)
            c.inc()

    def get_read_version(self, priority="default", tags=()):
        if not getattr(self.sequencer, "alive", True):
            # version authority dead: stall GRVs retryably until the
            # failure monitor recruits a new generation (ref: GRVs
            # blocking through a master recovery)
            raise err("process_behind")
        # the grant as a stage: profiler annotation, and for a traced
        # request (in-process ambient context or the wire's tracing
        # frame) a server-side hop span
        with span_mod.stage("grv.grant", priority=priority) as gsp:
            if self.ratekeeper is not None:
                ok, reason = self.ratekeeper.admit_with_reason(priority,
                                                               tags)
                if not ok:
                    # tag-throttled (1213) vs cluster-saturated (1037):
                    # both retryable, but the client (and its operator)
                    # should know WHICH gate closed (ref:
                    # GrvProxyTagThrottler)
                    if reason == "tag":
                        self._m_tag_throttled.inc()
                        raise err("tag_throttled")
                    self._m_throttled.inc()
                    raise err("process_behind")
            self.grv_count += 1
            self._m_grants.inc()
            if tags:
                self._note_tag_started(tags)
            v = self.sequencer.committed_version
            gsp.attr(version=v)
        return v

    def status(self):
        """This role's status RPC payload (leaf of the status doc)."""
        return {
            "alive": getattr(self.sequencer, "alive", True),
            "metrics": self.metrics.snapshot(),
        }


class BatchingGrvProxy:
    """Cross-client GRV batching with delay-based admission (thread
    deployments; the deterministic simulation keeps the synchronous
    proxy, whose rejects its workloads already ride out)."""

    def __init__(self, inner, interval_s=0.0005, max_wait_s=2.0,
                 start_thread=True):
        # start_thread=False: deterministic harnesses drive
        # _grant_round themselves (no thread, no wall clock)
        self.inner = inner
        self.interval_s = interval_s
        self.max_wait_s = max_wait_s
        self._lock = lockdep.lock("BatchingGrvProxy._lock")
        self._wake = lockdep.condition("BatchingGrvProxy._lock", self._lock)
        # the same mutex, counted (cluster.locks.grv_lock), entered by
        # every request's grant_now and by the grant loop's drain
        self._lock_counted = lockdep.counted(self._lock, "grv_lock")
        # two queues so a starved batch-priority request cannot head-of-
        # line-block default traffic (ref: per-priority GRV queues)
        self._queues = {"default": [], "batch": []}
        self._closed = False
        self._pending = 0  # queued + drained-but-unresolved requests
        self.batches_granted = 0
        self.delayed_count = 0  # requests that waited ≥1 extra window
        self.max_round = 0  # largest single-round grant (batch size seen)
        # grant-latency bands (ref: GrvProxyServer's GRV latency sample):
        # queued requests record their wait at grant; the uncontended
        # fast path is counted (its wait is ~0 by construction) so the
        # bands measure the queue, not a flood of zeros
        self._m_wait = inner.metrics.latency("grv_grant")
        self._m_fast = inner.metrics.counter("grv_fast_grants")
        self._m_queue_depth = inner.metrics.gauge("grv_queue_depth")
        self._thread = None
        if start_thread:
            self._thread = threading.Thread(
                target=self._grant_loop, name="grv-batcher", daemon=True
            )
            self._thread.start()

    def __getattr__(self, name):  # grv_count, sequencer, ... pass through
        return getattr(self.inner, name)

    def get_read_version(self, priority="default", tags=()):
        v = self.grant_now(priority, tags)
        return self.wait_for_grant(priority) if v is None else v

    def grant_now(self, priority="default", tags=()):
        """All of a GRV short of waiting: the checks, the tags'
        attribution and the uncontended grant. → the version, or None
        where the request has to queue: the caller then owes one
        ``wait_for_grant``, which may park (the RPC server runs this
        half on a connection's own thread and that one on its pool)."""
        if not getattr(self.inner.sequencer, "alive", True):
            # dead version authority: stall retryably (1037) — the fast
            # path and grant loop read committed_version directly, so
            # the liveness check must happen here too
            raise err("process_behind")
        if priority == "immediate":
            with self._lock_counted:  # consistency with the grant loop
                return self.inner.get_read_version(priority)  # bypass
        rk = self.inner.ratekeeper
        if rk is not None and tags and not rk.tag_gate(tags):
            # tag gates close immediately (1213, retryable) rather than
            # queueing: a throttled tag's requests must not occupy the
            # shared FIFO ahead of well-behaved traffic (ref: the
            # per-tag queues in GrvProxyTagThrottler); the global
            # budget is charged by the grant loop as usual
            raise err("tag_throttled")
        if tags:
            # the batcher's fast path and grant loop are tag-blind (one
            # committed-version read for the whole round): attribute the
            # start HERE, where the tags are still in hand
            self.inner._note_tag_started(tags)
        with self._lock_counted:
            if (
                self._closed
                or self._pending != 0  # drained-but-unresolved too
                or not (rk is None or rk.admit(priority))
            ):
                return None
            # uncontended fast path: no request is ahead of us in ANY
            # state (queued or mid-round) and the budget has room —
            # grant inline, no thread handoff. Checking _pending rather
            # than the raw queues means a fresh arrival can never steal
            # a refilled token from an older request the grant loop is
            # currently holding.
            self.inner.grv_count += 1
            self.inner._m_grants.inc()
            self._m_fast.inc()
            fast_v = self.inner.sequencer.committed_version
        # the grant as a stage (profiler annotation; a hop span for a
        # traced request, finished OUTSIDE the grant lock — file sinks
        # write): ~0 here, the grant-queue wait the latency bands
        # measure when queued
        with span_mod.stage("grv.grant", priority=priority) as gsp:
            gsp.attr(version=fast_v)
        return fast_v

    def wait_for_grant(self, priority="default"):
        """Queue behind the grant loop and wait for its round: the half
        of a GRV that ``grant_now`` could not give."""
        with span_mod.stage("grv.grant", priority=priority) as gsp:
            fut = self._make_future(priority)
            with self._lock:
                if self._closed:
                    raise err("process_behind")
                self._queues["batch" if priority == "batch"
                             else "default"].append(fut)
                self._pending += 1
                self._wake.notify()
            fut["event"].wait()
            if fut["error"] is not None:
                raise fut["error"]
            gsp.attr(version=fut["value"], queued=1)
            return fut["value"]

    def _grant_loop(self):
        # throttled rounds back off exponentially (cap 20ms) instead of
        # hammering the bucket every half millisecond; a granting round
        # resets to the base batch interval. jitter=0: this is a batch
        # cadence, not a retrying fleet — lockstep is harmless and the
        # unjittered schedule keeps thread-mode timing unchanged.
        throttle = Backoff(initial_s=self.interval_s, max_s=0.02,
                           growth=2.0, jitter=0.0)
        while True:
            # acquire via the Condition (it wraps self._lock, so this IS
            # the same mutex): waiting on the object we hold makes the
            # release-while-parked relationship explicit (FL003)
            with self._wake:
                while not (self._queues["default"] or self._queues["batch"]
                           or self._closed):
                    self._wake.wait()
                if self._closed:
                    pending = self._queues["default"] + self._queues["batch"]
                    self._queues = {"default": [], "batch": []}
                    self._pending = 0
                    for fut in pending:
                        fut["error"] = err("process_behind")
                        fut["event"].set()
                    return
            with self._lock:
                n_waiting = len(self._queues["default"]) + len(
                    self._queues["batch"]
                )
            # adaptive batch window (ref: GRV batch interval min/max): a
            # lone request waits briefly for companions; under continuous
            # load the previous round's processing time IS the window —
            # sleeping on top of it would only tax per-client latency
            sleep_s = throttle.current
            if n_waiting < 2 or sleep_s > self.interval_s:
                time.sleep(sleep_s)
            if self._grant_round():
                throttle.reset()
            else:
                throttle.delay()

    @staticmethod
    def _make_future(priority, born=None):
        """The queued-request record _grant_round consumes (one
        construction point, shared with deterministic test drivers)."""
        return {"event": threading.Event(), "value": None, "error": None,
                "born": time.monotonic() if born is None else born,
                "waited": False, "priority": priority}

    def _grant_round(self, now=None):
        """ONE grant round: drain the queues, grant strict-FIFO per
        priority until the first denial, age out over-waited requests,
        requeue the rest. Extracted from the loop so the deterministic
        simulation (and tests) can drive rounds without the thread or
        wall clock (``now`` overrides the aging clock). Returns whether
        anything was granted."""
        with self._lock_counted:
            work = {p: list(self._queues[p]) for p in ("default", "batch")}
            self._queues = {"default": [], "batch": []}
        rk = self.inner.ratekeeper
        if not getattr(self.inner.sequencer, "alive", True):
            # the sequencer died with requests queued: fail them
            # retryably rather than granting a dead authority's
            # frozen version
            with self._lock:
                n = 0
                for qkey in ("default", "batch"):
                    for fut in work[qkey]:
                        fut["error"] = err("process_behind")
                        fut["event"].set()
                        n += 1
                self._pending -= n
            return False
        version = None  # ONE committed-version read per grant round
        granted_any = False
        round_granted = 0
        resolved = 0  # granted + aged-out: leave the _pending count
        for qkey in ("default", "batch"):
            queue = work[qkey]
            # strict FIFO: grant from the head until the first denial
            # (ONE admit call per denial — a denied head means the
            # whole queue behind it waits, so no per-future hammering
            # of the token bucket and no younger request overtaking)
            n_granted = 0
            t_grant = time.monotonic() if now is None else now
            for fut in queue:
                if rk is not None and not rk.admit(fut["priority"]):
                    break
                if version is None:
                    version = self.inner.sequencer.committed_version
                    self.batches_granted += 1
                fut["value"] = version
                self._m_wait.record(max(0.0, t_grant - fut["born"]))
                fut["event"].set()
                n_granted += 1
                granted_any = True
            round_granted += n_granted
            resolved += n_granted
            rest = queue[n_granted:]
            if not rest:
                continue
            t = time.monotonic() if now is None else now
            keep = []
            for fut in rest:
                if t - fut["born"] > self.max_wait_s:
                    fut["error"] = err("process_behind")
                    fut["event"].set()
                    resolved += 1
                else:
                    if not fut["waited"]:
                        fut["waited"] = True
                        self.delayed_count += 1
                    keep.append(fut)
            if keep:
                with self._lock:  # requeue AT FRONT: FIFO preserved
                    self._queues[qkey] = keep + self._queues[qkey]
        with self._lock:
            self.inner.grv_count += round_granted
            self._pending -= resolved
            self.max_round = max(self.max_round, round_granted)
            depth = self._pending
        self.inner._m_grants.inc(round_granted)
        self._m_queue_depth.set(depth)
        return granted_any

    def close(self):
        with self._lock:
            self._closed = True
            self._wake.notify_all()
        if self._thread is not None:
            self._thread.join(timeout=10)
