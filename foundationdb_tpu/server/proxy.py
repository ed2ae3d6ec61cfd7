"""Commit proxy: batches client commits through resolution to the log.

Ref parity: fdbserver/CommitProxyServer.actor.cpp commitBatch() — the
pipeline is getVersion → resolve → tlog push → reply. Client commits
accumulate into a batch; the whole batch shares one commit version. The
TPU resolver makes large batches *cheaper* per txn, so the proxy's job is
to keep batches full (the opposite pressure from the reference, whose
resolver cost grows with batch size).
"""

import threading

from foundationdb_tpu.core.commit import CommitRequest  # noqa: F401  (re-export)
from foundationdb_tpu.core.errors import FDBError, err
from foundationdb_tpu.core.mutations import (
    Mutation, Op, substitute_versionstamp,
)
from foundationdb_tpu.core.status import COMMITTED, CONFLICT, TOO_OLD
from foundationdb_tpu.resolver.resolver import ResolverDown
from foundationdb_tpu.resolver.skiplist import TxnRequest
from foundationdb_tpu.server.sequencer import SequencerDown
from foundationdb_tpu.server.tlog import TLogDown
from foundationdb_tpu.utils import deviceprofile
from foundationdb_tpu.utils import heatmap as heatmap_mod
from foundationdb_tpu.utils import lockdep
from foundationdb_tpu.utils import metrics as metrics_mod
from foundationdb_tpu.utils import span as span_mod
from foundationdb_tpu.utils.trace import StageStats


class GateTimeout(Exception):
    """A gate turn no one will take (a peer proxy died between its
    grant and its advance): the fleet is wedged and only a txn-system
    recovery — which rebuilds the gates — can unwedge it. Callers map
    this to a retryable 1021 and mark the proxy dead so the failure
    monitor runs that recovery; it must never escape to a client."""


class VersionGate:
    """Version-ordered turnstile for a commit-proxy FLEET (ref: the
    sequencer's prevVersion chaining + the resolvers/tlogs processing
    batches in version order). A batch granted (prev, v) may only pass
    once every earlier grant has passed: ``enter(prev)`` blocks until
    the gate's frontier reaches ``prev``; ``advance(v)`` moves it. Two
    gates order the two stateful pipeline stages independently (resolve
    history; log+storage apply), so proxy B packs and routes while
    proxy A resolves — the fleet pipelines, the state stays serial."""

    def __init__(self, start, timeout=60.0):
        self._v = start
        self.timeout = timeout
        self._cond = lockdep.condition("VersionGate._cond")

    def enter(self, prev, timeout=None):
        with self._cond:
            if not self._cond.wait_for(
                lambda: self._v >= prev,
                self.timeout if timeout is None else timeout,
            ):
                raise GateTimeout(
                    f"version gate stuck at {self._v}, waiting for {prev}"
                )

    def advance(self, v):
        with self._cond:
            if v > self._v:
                self._v = v
            self._cond.notify_all()


class _PipelinedGroup:
    """One backlog group mid-pipeline: versions granted, txns packed,
    resolve dispatched lazily (stage A+B done). ``commit_batches_finish``
    completes stage C. A group that failed in begin carries its
    precomputed ``results_list`` plus whether its grant's gate turns are
    still owed; ``resolve_s``/``apply_s`` are stage-C timings the
    batcher folds into its StageStats."""

    __slots__ = ("request_batches", "metas", "handle", "first_prev",
                 "last_cv", "granted", "results_list", "error",
                 "resolve_s", "apply_s", "trace_ctx", "plans")

    def __init__(self, request_batches):
        self.request_batches = request_batches
        self.metas = None
        # per-batch SchedulePlans (abort-aware scheduling): finish maps
        # position-ordered results back to request order through these
        self.plans = None
        self.handle = None
        self.first_prev = self.last_cv = None
        self.granted = False
        self.results_list = None
        self.error = None
        self.resolve_s = 0.0
        self.apply_s = 0.0
        # the group's first sampled SpanContext, scanned ONCE in begin
        # (the batcher's stage spans reuse it — re-scanning the whole
        # group per stage was a measured hot-path cost)
        self.trace_ctx = None


class CommitProxy:
    def __init__(self, sequencer, resolvers, tlog, storages, knobs,
                 ratekeeper=None, dd=None, change_feeds=None,
                 resolve_gate=None, log_gate=None, metrics=None,
                 heatmap=None, regions=None, fanout_profile=None):
        self.alive = True
        # lane-balance instrument for the legacy host fan-out (clip
        # loop below): per-sub-batch entry counts feed the same
        # lane_skew_pct rollup the mesh router fills at split time.
        # The cluster hands its resolver-0 DeviceProfile so the counts
        # land in the standard device doc even for host (cpu/native)
        # resolver fleets, which carry no profile of their own.
        self._fanout_profile = fanout_profile
        # multi-region replication (server/region.py RegionReplicator):
        # in sync satellite mode the finalize tail pushes each batch to
        # the remote region BEFORE acknowledging it. The cluster swaps
        # this attribute when regions are (de)configured — read fresh
        # per batch, never cached.
        self.regions = regions
        # per-role metrics (ref: Stats.h CounterCollection on the commit
        # proxy). The cluster hands recovery incarnations the SAME
        # registry, so counters survive recruitment without rewinding;
        # abort counters are keyed by error class (_note_abort).
        self.metrics = metrics if metrics is not None \
            else metrics_mod.MetricsRegistry("commit_proxy")
        self._m_committed = self.metrics.counter("txn_committed")
        self._m_batches = self.metrics.counter("commit_batches")
        self._abort_counters = {}
        # workload attribution (utils/heatmap.py): the cluster-owned
        # conflict heatmap this incarnation charges at its abort-
        # fabrication site (None = sampling off), plus lazy per-tag
        # outcome counters in the role registry (tag_committed_x, ...)
        # so recovery absorption carries them like any other counter
        self.conflict_heat = heatmap
        self._tag_counters = {}
        # commit_e2e spans: recorded HERE for bare (sync) deployments;
        # a batching wrapper claims ownership at construction and
        # records the wider submit→settle span instead (queue included)
        self.spans_owned_externally = False
        self._m_e2e = self.metrics.latency("commit_e2e")
        # the serial commit_batch route's stages (utils/span.stage):
        # commit.batch ⊃ build, resolve, assemble, log_push,
        # storage_apply, report — sequential, so they sum to the batch;
        # bands stage_commit_* in this registry
        self.stages = StageStats(registry=self.metrics)
        # fleet ordering (None when this proxy is the whole fleet)
        self.resolve_gate = resolve_gate
        self.log_gate = log_gate
        self.sequencer = sequencer
        self.resolvers = resolvers  # list; key-range sharded when >1
        self.tlog = tlog
        self.storages = storages
        self.knobs = knobs
        self.ratekeeper = ratekeeper
        self.dd = dd  # data distribution byte accounting
        self.change_feeds = change_feeds  # ChangeFeedRegistry | None
        self.commit_count = 0
        self.conflict_count = 0
        # commit pack-path observability: how many request batches
        # packed columnar vs legacy (tests/test_packing_flat.py holds
        # the knob matrix to these)
        self.pack_flat_batches = 0
        self.pack_legacy_batches = 0
        # abort-aware batch scheduling (server/scheduler.py, knob
        # commit_batch_scheduling): plain totals that count even with
        # the metrics kill switch off; the registry counters feed
        # status rollups
        self.sched_batches = 0
        self.sched_reordered_total = 0
        self.sched_deferred_total = 0
        self._m_sched_reordered = self.metrics.counter("sched_reordered")
        self._m_sched_deferred = self.metrics.counter("sched_deferred")
        # Concurrent client threads may drive the synchronous proxy
        # directly (no batching wrapper): the pipeline mutates shared
        # state (donated resolver buffers, tlog order, storage overlay),
        # so commits serialize here. Reentrant: the lock path re-enters
        # commit_batch for lock-aware sub-batches. Uncontended cost is
        # noise; deterministic sims are single-threaded so ordering is
        # unchanged. (Ref: the proxy's commit path is one actor.)
        self._commit_mu = lockdep.rlock("CommitProxy._commit_mu")
        # the same mutex, counted (cluster.locks.commit_mu), entered
        # where a batch, a backlog group or its second half takes it
        self._commit_mu_counted = lockdep.counted(self._commit_mu,
                                                  "commit_mu")
        self._batches_since_pump = 0
        self.pump_interval = 64  # batches between flush + ratekeeper rounds
        self.resolver_bounds = None  # n-1 split keys; None = static split
        self._pool = None  # lazy thread pool for concurrent sub-resolves
        self.update_resolver_ranges(fence=False)

    def _note_abort(self, name, n=1):
        """Per-error-class abort accounting (ref: the reference's
        per-reason txn counters in status json): one counter per error
        name — conflicts, too-old, unknown-result, admission rejects —
        so contention is attributable, not one lump."""
        if n <= 0 or not metrics_mod.enabled():
            return
        c = self._abort_counters.get(name)
        if c is None:
            c = self._abort_counters[name] = self.metrics.counter(
                f"abort_{name}"
            )
        c.inc(n)

    def _note_tags(self, outcome, tags):
        """Per-tag outcome accounting (ref: the per-tag counters
        TagThrottle reads): every tagged commit/abort/conflict lands in
        a ``tag_{outcome}_{tag}`` counter."""
        if not tags or not metrics_mod.enabled():
            return
        for t in tags:
            key = (outcome, t)
            c = self._tag_counters.get(key)
            if c is None:
                c = self._tag_counters[key] = self.metrics.counter(
                    f"tag_{outcome}_{t}"
                )
            c.inc()

    def _charge_conflict(self, req):
        """Charge the conflict heatmap for one rejected transaction at
        its fabrication site. On the flat path the charged bucket keys
        are the client's raw limb ENTRIES sliced straight out of the
        request blobs — order-isomorphic to keys, zero decode (the same
        trick as server/scheduler.py); legacy requests pay one cheap
        entry encode per key, abort path only. The abort's unit weight
        is split across its charged read entries so total heat counts
        ABORTS (the attribution tests' denominator), not read width."""
        hm = self.conflict_heat
        if hm is None or not heatmap_mod.enabled():
            return
        from foundationdb_tpu.core import flatpack

        entries = []
        f = req.flat_conflicts
        if f is not None:
            w = flatpack.entry_width(f.num_limbs)
            blob = f.read_point_blob
            for o in range(0, min(len(blob), 8 * w), w):
                entries.append(blob[o: o + w])
            rblob = f.read_range_blob  # pairs: charge each range BEGIN
            for o in range(0, min(len(rblob), 16 * w), 2 * w):
                entries.append(rblob[o: o + w])
            if not entries:  # read-free: charge the write set instead
                blob = f.write_point_blob
                for o in range(0, min(len(blob), 8 * w), w):
                    entries.append(blob[o: o + w])
        else:
            limbs = self.knobs.key_limbs
            ranges = req.read_conflict_ranges or req.write_conflict_ranges
            for begin, _end in ranges[:8]:
                e = flatpack.encode_entry(begin, limbs)
                if e is not None:  # over-capacity keys stay unsampled
                    entries.append(e)
        if entries:
            wgt = 1.0 / len(entries)
            for e in entries:
                hm.charge(e, wgt)

    def _note_result_errors(self, results):
        """Tally FDBError entries of a finished result list by class."""
        if not metrics_mod.enabled():
            return
        for r in results:
            if isinstance(r, FDBError):
                self._note_abort(r.description)

    def status(self):
        """This role's status RPC payload: liveness + metrics snapshot
        (the per-process leaf of the aggregated status document)."""
        return {"alive": self.alive, "metrics": self.metrics.snapshot()}

    def update_resolver_ranges(self, fence=True):
        """Derive each resolver's key range from the LIVE DD shard map,
        weighting by sampled shard bytes so resolver load tracks actual
        write traffic (ref: the keyResolvers map the proxies maintain
        from keyServers). Falls back to a static first-byte split until
        the map has enough shards to cut n balanced ranges. The cluster
        calls this after every DD rebalance round and at recovery.

        Moving a boundary makes conflict history recorded under the OLD
        split unreachable (a key's writes live in the resolver that used
        to own it), so a bounds change REBUILDS the resolvers fenced at
        the current committed version — in-flight transactions get
        TOO_OLD and retry with fresh reads, exactly like the reference,
        where resolver ranges only change through a fencing recovery.
        ``fence=False`` is for construction, when no history exists yet.
        """
        n = len(self.resolvers)
        if n == 1:
            return
        smap = self.dd.map if self.dd is not None else None
        if smap is None or len(smap) < n:
            new_bounds = None  # static split
        else:
            weights = [s + 1 for s in smap.sizes]  # +1: empty shards count
            total = sum(weights)
            bounds, acc = [], 0
            for i in range(len(smap) - 1):
                acc += weights[i]
                if acc >= (len(bounds) + 1) * total / n and len(bounds) < n - 1:
                    bounds.append(smap.boundaries[i + 1])
            new_bounds = bounds if len(bounds) == n - 1 else None
        if new_bounds != self.resolver_bounds and fence:
            cv = self.sequencer.committed_version
            for i in range(n):
                self.resolvers[i] = self.resolvers[i].respawn(cv)
        self.resolver_bounds = new_bounds

    def commit(self, request):
        """Single-transaction batch (the synchronous client path)."""
        return self.commit_batch([request])[0]

    def commit_batch(self, requests):
        """Resolve and commit a batch; returns per-request (version|FDBError).

        All requests share one commit version, like the reference's
        commitBatch. Mutations of accepted txns are pushed to the tlog in
        batch order and applied to storage before replying, so a
        subsequent GRV observes them (external consistency).
        """
        if not requests:
            return []
        if not self.alive or not self.sequencer.alive:
            # the proxy (or the version authority behind it) is dead:
            # honest 1021 — a request may have been in flight when the
            # process died; clients retry and the failure monitor
            # recruits a new transaction-system generation (ref: proxy
            # death surfacing as broken connections → 1021)
            self._note_abort("commit_unknown_result", len(requests))
            return [
                FDBError.from_name("commit_unknown_result")
                for _ in requests
            ]
        t0 = None if self.spans_owned_externally \
            or not metrics_mod.enabled() else metrics_mod.now()
        # ambient trace context for the batch's stage spans and the
        # hops under them: the first sampled member's commit span is
        # the parent (over the wire the context arrived inside the
        # CommitRequest, so the handler thread has no ambient one to
        # inherit)
        rctx = span_mod.first_request_context(requests)
        prior_ctx = span_mod.set_current(rctx) if rctx is not None \
            else None
        try:
            with self._commit_mu_counted:
                with span_mod.stage("commit.batch", self.stages,
                                    txns=len(requests)):
                    return self._commit_batch_locked(requests)
        except GateTimeout:
            return self._gate_wedged(len(requests))
        finally:
            if rctx is not None:
                span_mod.set_current(prior_ctx)
            if t0 is not None:
                self._note_e2e(t0, len(requests))

    def _gate_wedged(self, n):
        """A gate turn went unclaimed (peer died between grant and
        advance): this generation of the fleet cannot make progress.
        Mark this proxy dead so the failure monitor's next round runs a
        txn-system recovery (fresh gates), and answer honest 1021s —
        the batch's fate is unknown until the new generation fences."""
        self.kill()
        self._note_abort("commit_unknown_result", n)
        return [
            FDBError.from_name("commit_unknown_result") for _ in range(n)
        ]

    def _partition_rejects(self, requests, reject_fn):
        """Per-request admission gate: ``reject_fn(request)`` returns an
        error name (rejected) or None (passing); passing requests
        commit as a sub-batch. Returns merged results, or None when
        nothing was rejected (caller continues with the full batch)."""
        results = [None] * len(requests)
        passing = []
        for i, r in enumerate(requests):
            bad = reject_fn(r)
            if bad is None:
                passing.append((i, r))
            else:
                self._note_abort(bad)
                results[i] = FDBError.from_name(bad)
        if len(passing) == len(requests):
            return None
        if passing:
            try:
                # sub-batches re-enter past the dedupe: their requests
                # already passed it this very call
                sub = self._commit_batch_admitted([r for _, r in passing])
            except GateTimeout:
                # only the sub-batch's fate is unknown: the definitive
                # rejections already in ``results`` must stand (a known
                # not-committed must never degrade to maybe-committed)
                sub = self._gate_wedged(len(passing))
            for (i, _), res in zip(passing, sub):
                results[i] = res
        return results

    @staticmethod
    def _tenant_mode_violation(mode, mutations):
        """Structural tenant-mode check by KEY RANGE: tenant data lives
        in [\xfd, \xfe), plain user data in [, \xfd) ∪ [\xfe, \xff),
        system (mode-exempt) in [\xff, ...). CLEAR_RANGE is judged by
        its whole [key, param) span — a range straddling the boundary
        violates whichever space the mode forbids."""
        for m in mutations:
            if m.key >= b"\xff":
                continue
            if m.op == Op.CLEAR_RANGE:
                b, e = m.key, min(m.param, b"\xff")
                touches_tenant = b < b"\xfe" and e > b"\xfd"
                touches_plain = b < b"\xfd" or e > b"\xfe"
            else:
                touches_tenant = m.key.startswith(b"\xfd")
                touches_plain = not touches_tenant
            if mode == "required" and touches_plain:
                return "tenant_name_required"
            if mode == "disabled" and touches_tenant:
                return "tenants_disabled"
        return None

    def _idmp_lookup(self, idempotency_id):
        """The committed version recorded for ``idempotency_id``, or
        None. Read from any live storage's system keyspace (replicated
        everywhere) at its latest version — every earlier commit through
        this serialized pipeline is visible there."""
        from foundationdb_tpu.core import systemdata

        key = systemdata.idmp_key(idempotency_id)
        for s in self.storages:
            if s.alive:
                row = s.get(key, s.version)
                return None if row is None else \
                    systemdata.unpack_version(row)
        return None

    def _pin_idmp_rv(self, requests):
        """Assign the lazy read version of read-free id-CARRYING
        requests BEFORE their dedupe lookup runs. The lookup and the
        OCC read conflict on the idmp row (_idmp_point) together cover
        every interleaving with a concurrently-committing original only
        if rv is fixed first: an original visible before the pin is
        caught by the lookup (apply precedes report_committed, so the
        row is readable at rv); one landing after has cv > rv and the
        retry's idmp read range conflicts. Pinning here means these
        requests skip the constrained-budget admission gate's lazy-rv
        charge — acceptable: id-carrying blind writes are rare and the
        alternative is a double-apply window."""
        for reqs in requests:
            for r in reqs:
                if (r.read_version is None
                        and getattr(r, "idempotency_id", None)):
                    r.read_version = self.sequencer.committed_version

    def _dedupe_idempotent(self, requests):
        """Proxy-side exactly-once (ref: IdempotencyId — ours is checked
        AT the proxy, which closes the client-check's resubmit race:
        commits serialize through this pipeline, so by the time a retry
        runs, its original either applied — id row visible — or never
        will; the OCC conflict ranges _idmp_point declares extend the
        guarantee across fleet members and pipeline groups). Returns
        merged results, or None when nothing matched."""
        self._pin_idmp_rv([requests])
        results = [None] * len(requests)
        passing = []
        for i, r in enumerate(requests):
            v = (self._idmp_lookup(r.idempotency_id)
                 if getattr(r, "idempotency_id", None) else None)
            if v is None:
                passing.append((i, r))
            else:
                self.metrics.counter("idmp_dedupe_hits").inc()
                results[i] = v  # the ORIGINAL commit's version: success
        if len(passing) == len(requests):
            return None
        if passing:
            sub = self._commit_batch_admitted([r for _, r in passing])
            for (i, _), res in zip(passing, sub):
                results[i] = res
        return results

    def _commit_batch_locked(self, requests):
        if any(getattr(r, "idempotency_id", None) for r in requests):
            out = self._dedupe_idempotent(requests)
            if out is not None:
                return out
        return self._commit_batch_admitted(requests)

    def _commit_batch_admitted(self, requests):
        """The batch pipeline past the idempotency dedupe (every entry
        route runs the dedupe exactly once before landing here)."""
        rk = self.ratekeeper
        if rk is not None and rk.target_tps < rk.UNLIMITED_TPS:
            # rv-None requests skipped the GRV (read-free fast path);
            # under a CONSTRAINED budget they pay admission here
            # instead — same token bucket, same retryable 1037. The
            # gate assigns the rv on admission, so the sub-batch
            # re-entry through _partition_rejects cannot double-charge.
            rv_now = self.sequencer.committed_version

            def gate(r):
                if r.read_version is not None:
                    return None
                if rk.admit():
                    r.read_version = rv_now
                    return None
                return "process_behind"

            out = self._partition_rejects(requests, gate)
            if out is not None:
                return out
        lock_uid = getattr(self, "lock_uid", None)
        if lock_uid is not None:
            # database locked (ref: lockDatabase / error 1038): only
            # lock-aware transactions pass
            out = self._partition_rejects(
                requests,
                lambda r: None if getattr(r, "lock_aware", False)
                else "database_locked",
            )
            if out is not None:
                return out
        # tenant-mode enforcement (ref: TenantMode in
        # DatabaseConfiguration) — see _tenant_mode_violation
        mode = getattr(self, "tenant_mode", "optional")
        if mode != "optional":
            out = self._partition_rejects(
                requests,
                lambda r: self._tenant_mode_violation(mode, r.mutations),
            )
            if out is not None:
                return out
        with span_mod.stage("commit.build", self.stages):
            try:
                prev, cv = self.sequencer.next_commit_versions(1)[0]
            except SequencerDown:
                # the kill raced past the entry check (TOCTOU): same
                # honest 1021 — a raw exception here would strand
                # batcher futures
                self._note_abort("commit_unknown_result", len(requests))
                return [
                    FDBError.from_name("commit_unknown_result")
                    for _ in requests
                ]
            window = max(
                0, cv - self.knobs.max_read_transaction_life_versions)
            # past every admission gate: reorder for fewer self-inflicted
            # aborts (results are mapped back to request order at return)
            requests, plan = self._maybe_schedule(requests)
            try:
                txns = self._build_txns(requests)
            except BaseException:
                # the grant happened but neither gate was consumed: skip
                # both turns or every successor waits on a turn no one
                # will take (advisor r4: a wedged gate never self-heals)
                self._skip_turns_quiet(prev, cv)
                raise
        # commit_batch made the first sampled member's context ambient:
        # the resolver's scan span parents to the resolve stage's
        traced = span_mod.current() is not None
        try:
            with span_mod.stage("commit.resolve", self.stages):
                statuses = self._resolve_ordered(txns, cv, window, prev)
        except ResolverDown:
            # resolution never ran: definitively not committed (1020,
            # retryable without 1021 disambiguation); the failure monitor
            # recruits a fenced replacement resolver. The granted version
            # still consumes its log turn or the fleet would deadlock —
            # quietly, so a wedged gate cannot replace this KNOWN
            # outcome with blanket 1021s.
            self._skip_turns_quiet(prev, cv)
            self._note_abort("not_committed", len(requests))
            return [FDBError.from_name("not_committed") for _ in requests]
        except GateTimeout:
            raise
        except BaseException:
            # _resolve blew up mid-flight: the resolve gate's finally
            # already advanced (its quiet skip is a no-op), but the
            # log-gate turn is still owed
            self._skip_turns_quiet(prev, cv)
            raise
        results = self._finalize_batch(requests, txns, statuses, cv,
                                       window, prev,
                                       traced=traced, plan=plan)
        return plan.restore(results) if plan is not None else results

    def _resolve_ordered(self, txns, cv, window, prev):
        """Resolution in global version order: conflict history is
        stateful, so the fleet's batches enter it exactly in grant
        order (ref: Resolver.actor.cpp queuing requests by sequence)."""
        if self.resolve_gate is None:
            return self._resolve(txns, cv, window)
        self.resolve_gate.enter(prev)
        try:
            return self._resolve(txns, cv, window)
        finally:
            # advance even on failure: the version is consumed either way
            self.resolve_gate.advance(cv)

    def _skip_turns_quiet(self, prev, cv):
        """Consume a failed batch's turns at BOTH gates without doing
        its work: successors must never wait on a turn no one will
        take. Each skip still waits for order (advancing early would
        let a LATER version pass before an EARLIER one logged), but
        QUIETLY — called from failure handlers, a wedged gate must not
        replace the outcome being propagated (a definitive 1020, or a
        root-cause exception that would otherwise be retried as a
        silent 1021 forever) nor abort before the second gate's skip.
        The gate damage heals the same way either way — this proxy
        marks itself dead and the failure monitor's txn-system recovery
        rebuilds fresh gates. Once one gate proves wedged the rest get
        a zero wait: the dead peer never advanced either gate, and a
        second full timeout only delays the root cause (and the
        recovery's quiesce) for nothing."""
        wedged = False
        for gate in (self.resolve_gate, self.log_gate):
            if gate is None:
                continue
            try:
                gate.enter(prev, timeout=0.0 if wedged else None)
                gate.advance(cv)
            except GateTimeout:
                wedged = True
                self.kill()

    def commit_batches(self, request_batches):
        """Commit a BACKLOG of batches: each gets its own commit version,
        resolution for all of them rides one resolver dispatch
        (Resolver.resolve_many's scanned path), then each batch finalizes
        in order. Semantically identical to sequential commit_batch calls
        — this is the throughput path when commits outrun the link to
        the chip (ref: the proxy pipelining resolution across batches)."""
        if (len(self.resolvers) != 1 or not self.alive
                or not self.sequencer.alive):
            # per-batch route: commit_batch records its own spans
            return [self.commit_batch(reqs) for reqs in request_batches]
        t0 = None if self.spans_owned_externally \
            or not metrics_mod.enabled() else metrics_mod.now()
        try:
            return self._commit_batches_outer(request_batches)
        finally:
            if t0 is not None:
                # one span per backlog group: its batches reply together
                self._note_e2e(
                    t0, sum(len(r) for r in request_batches))

    def _note_e2e(self, t0, n_txns):
        """Record the commit_e2e band AND, when tracing is enabled and
        the window outlived ``tracing_slow_commit_ms``, the per-window
        slow-commit promotion span — both from the same stamps (the
        sync-deployment twin of the batcher's _record_span)."""
        end = metrics_mod.now()
        dur = max(0.0, end - t0)
        self._m_e2e.record(dur)
        if (self.knobs.tracing_sample_rate > 0.0
                and dur * 1e3 >= self.knobs.tracing_slow_commit_ms):
            span_mod.slow_window_span(t0, end, txns=n_txns)

    def _commit_batches_outer(self, request_batches):
        try:
            with self._commit_mu_counted:
                if getattr(self, "lock_uid", None) is not None:
                    # checked UNDER the mutex: a lock landing while this
                    # backlog queued must fence it exactly as commit_batch
                    # would (the per-batch path re-checks per batch).
                    # Results accumulate per batch: a wedge part-way
                    # through must not turn KNOWN outcomes (durable
                    # commits, definitive rejections) into 1021s —
                    # only the unprocessed remainder is unknown.
                    out = []
                    try:
                        for reqs in request_batches:
                            out.append(self._commit_batch_locked(reqs))
                    except GateTimeout:
                        for reqs in request_batches[len(out):]:
                            out.append(self._gate_wedged(len(reqs)))
                    return out
                return self._commit_batches_locked(request_batches)
        except GateTimeout:
            return [
                self._gate_wedged(len(reqs)) for reqs in request_batches
            ]

    def _commit_batches_locked(self, request_batches):
        # the pipelined backlog must dedupe too — 1021 retries are MOST
        # likely to arrive on exactly this throughput path. The scan
        # costs one in-memory storage get per id-CARRYING request
        # (id-free traffic pays nothing); a matched id — rare, only a
        # real 1021 retry — drops the backlog to the per-batch route,
        # whose dedupe answers the duplicate its original version.
        # Degrading the whole backlog on a match trades throughput for
        # simplicity exactly once per retry, not steady-state.
        rk = self.ratekeeper
        self._pin_idmp_rv(request_batches)
        if any(getattr(r, "idempotency_id", None)
               and self._idmp_lookup(r.idempotency_id) is not None
               for reqs in request_batches for r in reqs) or (
            # a constrained budget gates rv-None requests at admission
            # (the per-batch path runs that gate); overload throughput
            # is moot, so losing the backlog pipelining there is fine
            rk is not None and rk.target_tps < rk.UNLIMITED_TPS
            and any(r.read_version is None
                    for reqs in request_batches for r in reqs)
        ):
            out = []
            try:
                for reqs in request_batches:
                    out.append(self._commit_batch_locked(reqs))
            except GateTimeout:
                # known per-batch outcomes stand; only the remainder is
                # unknown (same contract as the locked-backlog branch)
                for reqs in request_batches[len(out):]:
                    out.append(self._gate_wedged(len(reqs)))
            return out
        try:
            # the whole backlog's versions in ONE chained grant: no other
            # proxy's batch can land inside this run, so the backlog is
            # contiguous in the global order and one gate span covers it
            pairs = self.sequencer.next_commit_versions(len(request_batches))
        except SequencerDown:
            self._note_abort("commit_unknown_result",
                             sum(len(r) for r in request_batches))
            return [
                [FDBError.from_name("commit_unknown_result") for _ in reqs]
                for reqs in request_batches
            ]
        first_prev, last_cv = pairs[0][0], pairs[-1][1]
        try:
            metas = []
            plans = []
            for reqs, (prev, cv) in zip(request_batches, pairs):
                window = max(
                    0, cv - self.knobs.max_read_transaction_life_versions
                )
                reqs, plan = self._maybe_schedule(reqs)
                plans.append(plan)
                metas.append((reqs, self._build_txns(reqs), cv, window))
        except BaseException:
            # grant made, gates untouched: consume the whole span's
            # turns or the rest of the fleet wedges behind it
            self._skip_turns_quiet(first_prev, last_cv)
            raise
        gctx = span_mod.first_request_context(
            r for reqs in request_batches for r in reqs
        )
        if self.resolve_gate is not None:
            self.resolve_gate.enter(first_prev)
        try:
            prior_ctx = span_mod.set_current(gctx) \
                if gctx is not None else None
            try:
                statuses_list = self.resolvers[0].resolve_many(
                    [(txns, cv, window) for _, txns, cv, window in metas]
                )
            finally:
                if gctx is not None:
                    span_mod.set_current(prior_ctx)
        except ResolverDown:
            self._skip_turns_quiet(first_prev, last_cv)
            self._note_abort("not_committed",
                             sum(len(r) for r in request_batches))
            return [
                [FDBError.from_name("not_committed") for _ in reqs]
                for reqs in request_batches
            ]
        except BaseException:
            # resolve_many itself never touches a gate, so anything here
            # is a resolver-internal root cause: skip the owed log turn
            # quietly and let IT propagate
            self._skip_turns_quiet(first_prev, last_cv)
            raise
        finally:
            if self.resolve_gate is not None:
                self.resolve_gate.advance(last_cv)
        if self.log_gate is not None:
            self.log_gate.enter(first_prev)
        try:
            out = []
            for (reqs, txns, cv, window), statuses, plan in zip(
                    metas, statuses_list, plans):
                res = self._finalize_batch(reqs, txns, statuses, cv,
                                           window, prev=None,
                                           traced=gctx is not None,
                                           plan=plan)
                out.append(plan.restore(res) if plan is not None else res)
            return out
        finally:
            if self.log_gate is not None:
                self.log_gate.advance(last_cv)

    @staticmethod
    def _idmp_point(r):
        """The idmp system row an id-carrying request writes (and must
        read-conflict on), or None. Declaring both conflict ranges on
        that row makes OCC serialize a retry against its own original
        even when the two land on DIFFERENT fleet members (or different
        pipeline groups) concurrently: whichever resolves second sees
        the other's write over its read and gets 1020, retries, and the
        dedupe then answers the original's version (ADVICE r5: a
        read-free id-carrying retry could double-apply)."""
        iid = getattr(r, "idempotency_id", None)
        if not iid:
            return None
        from foundationdb_tpu.core import systemdata

        return systemdata.idmp_key(iid)

    # ── pipelined backlog (server/batcher.py's bounded pipeline) ─────
    # The serial _commit_batches_locked split into stages so the batcher
    # can keep commit_pipeline_depth groups in flight: stage A+B
    # (commit_batches_begin — version grant, host packing, gate-ordered
    # LAZY resolve dispatch) run on the batcher thread while stage C
    # (commit_batches_finish — status sync, tlog push, storage apply)
    # runs on the apply thread for the PREVIOUS group. Ordering
    # invariants are exactly the fleet's: the resolve gate serializes
    # dispatch in grant order (history is stateful), the log gate
    # serializes the apply tail; intra-proxy the batcher's FIFO apply
    # queue provides the same order when no fleet gates exist.

    def pipeline_eligible(self, request_batches):
        """Cheap stage-A admission check: the pipelined path serves the
        common case only. Anything needing per-request partitioning or
        per-batch serialization (database lock, tenant enforcement, a
        constrained ratekeeper charging lazy-rv requests, a dedupe HIT,
        multi-resolver host fan-out, dead roles) routes back to the
        serial commit_batches, which already handles it."""
        rk = self.ratekeeper
        if (len(self.resolvers) != 1 or not self.alive
                or not self.sequencer.alive
                or getattr(self, "lock_uid", None) is not None
                or getattr(self, "tenant_mode", "optional") != "optional"):
            return False
        if (rk is not None and rk.target_tps < rk.UNLIMITED_TPS
                and any(r.read_version is None
                        for reqs in request_batches for r in reqs)):
            return False
        self._pin_idmp_rv(request_batches)
        return not any(
            getattr(r, "idempotency_id", None)
            and self._idmp_lookup(r.idempotency_id) is not None
            for reqs in request_batches for r in reqs
        )

    def commit_batches_begin(self, request_batches):
        """Stages A+B of the pipelined backlog: chained version grant,
        host packing, and the gate-ordered lazy resolve dispatch.
        Always returns a _PipelinedGroup — failures are captured in the
        group (results precomputed, owed gate turns recorded) so the
        caller settles them through commit_batches_finish IN ORDER with
        the rest of the pipeline. Caller contract: begin runs on one
        thread in grant order; finish runs FIFO on one thread."""
        group = _PipelinedGroup(request_batches)
        n_total = sum(len(reqs) for reqs in request_batches)

        def err_1021():
            self._note_abort("commit_unknown_result", n_total)
            return [
                [FDBError.from_name("commit_unknown_result") for _ in reqs]
                for reqs in request_batches
            ]
        try:
            pairs = self.sequencer.next_commit_versions(len(request_batches))
        except SequencerDown:
            group.results_list = err_1021()
            return group
        group.first_prev, group.last_cv = pairs[0][0], pairs[-1][1]
        group.granted = True
        try:
            metas = []
            plans = []
            for reqs, (prev, cv) in zip(request_batches, pairs):
                window = max(
                    0, cv - self.knobs.max_read_transaction_life_versions
                )
                reqs, plan = self._maybe_schedule(reqs)
                plans.append(plan)
                metas.append((reqs, self._build_txns(reqs), cv, window))
            group.plans = plans
        except BaseException as e:
            group.error = e
            group.results_list = err_1021()
            return group
        gctx = group.trace_ctx = span_mod.first_request_context(
            r for reqs in request_batches for r in reqs
        )
        try:
            if self.resolve_gate is not None:
                self.resolve_gate.enter(group.first_prev)
            try:
                prior_ctx = span_mod.set_current(gctx) \
                    if gctx is not None else None
                try:
                    group.handle = self.resolvers[0].resolve_many(
                        [(txns, cv, window)
                         for _, txns, cv, window in metas],
                        lazy=True,
                    )
                finally:
                    if gctx is not None:
                        span_mod.set_current(prior_ctx)
            finally:
                if self.resolve_gate is not None:
                    self.resolve_gate.advance(group.last_cv)
        except GateTimeout:
            # wedged fleet: kill + blanket 1021s; no turn consumption —
            # only a txn-system recovery (fresh gates) unwedges
            group.granted = False
            group.results_list = [
                self._gate_wedged(len(reqs)) for reqs in request_batches
            ]
            return group
        except ResolverDown:
            # definitively not committed; the log turn is still owed
            self._note_abort("not_committed", n_total)
            group.results_list = [
                [FDBError.from_name("not_committed") for _ in reqs]
                for reqs in request_batches
            ]
            return group
        except BaseException as e:
            group.error = e
            group.results_list = err_1021()
            return group
        group.metas = metas
        return group

    def commit_batches_finish(self, group):
        """Stage C of the pipelined backlog: materialize the resolve
        statuses (the one host↔device sync), then the gate-ordered tail
        — tlog push, storage apply, feeds, reporting. Also the
        settlement point for groups that failed in begin: their owed
        gate turns are consumed HERE, in pipeline order, so successors
        never wait on a turn no one will take."""
        import time as _time

        if group.results_list is not None:
            if group.granted:
                self._skip_turns_quiet(group.first_prev, group.last_cv)
            return group.results_list
        t0 = _time.perf_counter()
        try:
            statuses_list = group.handle.wait()
        except BaseException as e:
            # the dispatched kernel faulted at materialization: the
            # device history for these versions is suspect, but both
            # turns must still be consumed (the resolve gate's advance
            # already ran; the skip's enter/advance there are no-ops)
            self._skip_turns_quiet(group.first_prev, group.last_cv)
            group.error = e
            self._note_abort(
                "commit_unknown_result",
                sum(len(reqs) for reqs in group.request_batches),
            )
            return [
                [FDBError.from_name("commit_unknown_result") for _ in reqs]
                for reqs in group.request_batches
            ]
        group.resolve_s = _time.perf_counter() - t0
        t1 = _time.perf_counter()
        with self._commit_mu_counted:
            if not self.alive or not self.sequencer.alive:
                # killed mid-pipeline (txn-system recovery quiesce):
                # nothing may reach the log after the frontier read —
                # consume the owed turns and answer honest 1021s
                self._skip_turns_quiet(group.first_prev, group.last_cv)
                self._note_abort(
                    "commit_unknown_result",
                    sum(len(reqs) for reqs in group.request_batches),
                )
                return [
                    [FDBError.from_name("commit_unknown_result")
                     for _ in reqs]
                    for reqs in group.request_batches
                ]
            try:
                if self.log_gate is not None:
                    self.log_gate.enter(group.first_prev)
            except GateTimeout:
                return [
                    self._gate_wedged(len(reqs))
                    for reqs in group.request_batches
                ]
            try:
                out = []
                for (reqs, txns, cv, window), statuses, plan in zip(
                        group.metas, statuses_list,
                        group.plans or [None] * len(group.metas)):
                    res = self._finalize_batch(
                        reqs, txns, statuses, cv, window, prev=None,
                        traced=group.trace_ctx is not None, plan=plan)
                    out.append(
                        plan.restore(res) if plan is not None else res)
                return out
            finally:
                if self.log_gate is not None:
                    self.log_gate.advance(group.last_cv)
                group.apply_s = _time.perf_counter() - t1

    def _try_build_flat(self, requests):
        """The columnar batch build (core/flatpack.py): when the knob,
        the resolver, and every request agree, concatenate the clients'
        pre-encoded limb blobs into one FlatTxnBatch — no TxnRequest
        objects, no per-range split, no per-key re-parse. None routes
        the batch to the legacy build (mixed/legacy requests, cpu or
        sharded resolvers, over-capacity idempotency keys); both builds
        pack bit-identically (tests/test_packing_flat.py)."""
        res = self.resolvers
        if (getattr(self.knobs, "commit_pack_path", "legacy") != "flat"
                or len(res) != 1
                or not getattr(res[0], "accepts_flat", False)):
            return None
        from foundationdb_tpu.core import flatpack

        return flatpack.build_flat_batch(
            requests, self.knobs.key_limbs, self._idmp_point
        )

    def _maybe_schedule(self, requests):
        """Abort-aware intra-batch scheduling (server/scheduler.py):
        reorder the batch host-side — over the clients' already-encoded
        flat limb blobs, before any packing — so reads resolve before
        the intra-batch writes they overlap. Returns the (possibly
        reordered) request list plus the plan whose ``restore`` maps
        position-ordered results back to request order; (requests,
        None) when the knob is off or the pass declined."""
        if (not getattr(self.knobs, "commit_batch_scheduling", False)
                or len(requests) < 2):
            return requests, None
        from foundationdb_tpu.server import scheduler

        plan = scheduler.schedule(requests)
        if plan is None or plan.identity:
            return requests, None
        self.sched_batches += 1
        self.sched_reordered_total += plan.reordered
        self.sched_deferred_total += plan.deferred
        self._m_sched_reordered.inc(plan.reordered)
        self._m_sched_deferred.inc(plan.deferred)
        return [requests[i] for i in plan.order], plan

    def _build_txns(self, requests):
        rv_assigned = None
        n_lazy = 0
        for r in requests:
            if r.read_version is None:
                # read-free txn (no read conflict ranges): the client
                # skipped its GRV and the proxy assigns the window
                # position — the resolver never compares anything
                # against a read-free txn's rv (see Transaction.
                # _build_commit_request)
                if rv_assigned is None:
                    rv_assigned = self.sequencer.committed_version
                r.read_version = rv_assigned
                n_lazy += 1
        if n_lazy and self.ratekeeper is not None:
            # they bypassed the GRV's admission sampling: feed the
            # busy-tag base or tagged share reads inflated
            self.ratekeeper.note_untagged_admissions(n_lazy)
        flat = self._try_build_flat(requests)
        if flat is not None:
            self.pack_flat_batches += 1
            return flat
        self.pack_legacy_batches += 1
        if not all(getattr(r_, "wants_point_split", True)
                   for r_ in self.resolvers):
            # host backends: a point IS its tiny range — hand the
            # client's ranges through untouched (both byte strings
            # already exist; the split bought nothing but CPU)
            out = []
            for r in requests:
                ik = self._idmp_point(r)
                extra = [(ik, ik + b"\x00")] if ik is not None else []
                out.append(TxnRequest(
                    read_version=r.read_version,
                    point_reads=(), point_writes=(),
                    range_reads=list(r.read_conflict_ranges) + extra
                    if extra else r.read_conflict_ranges,
                    range_writes=list(r.write_conflict_ranges) + extra
                    if extra else r.write_conflict_ranges,
                ))
            return out
        split = _split_ranges
        out = []
        for r in requests:
            pr, rr = split(r.read_conflict_ranges)
            pw, rw = split(r.write_conflict_ranges)
            ik = self._idmp_point(r)
            if ik is not None:
                pr = pr + [ik]
                pw = pw + [ik]
            out.append(TxnRequest(
                read_version=r.read_version,
                point_reads=pr, point_writes=pw,
                range_reads=rr, range_writes=rw,
            ))
        return out

    def _finalize_batch(self, requests, txns, statuses, cv, window,
                        prev=None, traced=True, plan=None):
        """Everything after resolution: result assembly, DD accounting,
        tlog push (1021 on quorum loss), storage apply, change feeds,
        version reporting, admission + durability pumping. ``prev``
        orders this batch behind the fleet's earlier grants at the log
        gate (None = the caller already holds the order); assembly and
        routing run OUTSIDE the ordered section so a fleet overlaps
        them with another proxy's push."""
        # the batch-level span: parented to the FIRST sampled member's
        # commit span, linking every sampled member (ref: the commit
        # batch span in CommitProxyServer carrying txn tokens); made
        # ambient around the ordered tail so the tlog.push and
        # storage.apply hop spans nest under it. ``traced`` False means
        # the caller already KNOWS no member carries a context — the
        # per-request scan is skipped (a measured per-batch cost).
        bsp = span_mod.batch_span(requests) if traced else span_mod.NULL
        try:
            with span_mod.stage("commit.assemble", self.stages):
                results = []
                batch_mutations = []
                batch_conflicts = 0
                from foundationdb_tpu.core import systemdata

                for i, (req, st) in enumerate(zip(requests, statuses)):
                    if st == COMMITTED:
                        muts = [
                            substitute_versionstamp(m, cv, batch_order=0, txn_order=i)
                            if m.op in (Op.SET_VERSIONSTAMPED_KEY, Op.SET_VERSIONSTAMPED_VALUE)
                            else m
                            for m in req.mutations
                        ]
                        batch_mutations.extend(muts)
                        if getattr(req, "idempotency_id", None):
                            # the id row commits ATOMICALLY with the txn's
                            # mutations — its presence at any later read
                            # version proves this commit applied (ref:
                            # idempotencyIdKeys written in the same batch)
                            batch_mutations.append(Mutation(
                                Op.SET,
                                systemdata.idmp_key(req.idempotency_id),
                                systemdata.pack_version(cv),
                            ))
                        results.append(cv)
                        self._note_tags("committed", getattr(req, "tags", ()))
                    elif st == TOO_OLD:
                        results.append(FDBError.from_name("transaction_too_old"))
                        batch_conflicts += 1
                        self._note_tags("too_old", getattr(req, "tags", ()))
                    else:
                        self._note_tags("conflicted", getattr(req, "tags", ()))
                        self._charge_conflict(req)
                        e = FDBError.from_name("not_committed")
                        if req.report_conflicting_keys:
                            e.conflicting_key_ranges = self._conflicting_ranges(
                                txns[i]
                            )
                            # the version whose writes rejected this txn:
                            # the client repair engine re-reads ONLY the
                            # conflicting keys at exactly this version —
                            # its non-conflicting reads are resolver-proven
                            # unchanged through it (txn/repair.py)
                            e.conflict_version = cv
                        results.append(e)
                        batch_conflicts += 1

                # expired-id GC rides an ordinary batch (same durability /
                # replication / DR path as the rows themselves): every
                # pump_interval batches, clear ids older than RETENTION —
                # a deliberate multiple of the MVCC window, because a 1021
                # retry carries a FRESH read version and can arrive long
                # after the original's window closed (ref: the idempotency
                # id cleaner retaining ids by AGE, far past the window).
                # Runs on the next batch AFTER the pump, capped per round.
                if self._batches_since_pump == 0 and self.commit_count:
                    horizon = max(0, cv - self.IDMP_RETENTION_WINDOWS *
                                  self.knobs.max_read_transaction_life_versions)
                    batch_mutations.extend(self._idmp_expired(horizon))

                # Route BEFORE the push so the log stores the per-tag split
                # (ref: applyMetadataToCommittedTransactions tagging mutations
                # with storage tags, TLogServer's per-tag streams): storage
                # workers then peek only their own stream. Full replication
                # skips tags — every tag's stream IS the full batch.
                routed = self._route(batch_mutations)
                tags = None
                if (self.dd is not None
                        and self.dd.replication < len(self.storages)):
                    tags = dict(enumerate(routed))
        except BaseException:
            # assembly blew up before the ordered section: the version's
            # log turn must still be consumed or successors hang (quiet:
            # the root cause must propagate even if the gate is wedged)
            if prev is not None:
                self._skip_turns_quiet(prev, cv)
            raise
        if prev is not None and self.log_gate is not None:
            self.log_gate.enter(prev)
        try:
            if bsp is span_mod.NULL:
                return self._finalize_ordered(
                    requests, results, batch_mutations, batch_conflicts,
                    routed, tags, cv, window,
                )
            prior_ctx = span_mod.set_current(bsp.context())
            try:
                return self._finalize_ordered(
                    requests, results, batch_mutations, batch_conflicts,
                    routed, tags, cv, window,
                )
            finally:
                span_mod.set_current(prior_ctx)
                if plan is not None:
                    bsp.finish(version=cv, conflicts=batch_conflicts,
                               sched_reordered=plan.reordered,
                               sched_deferred=plan.deferred)
                else:
                    bsp.finish(version=cv, conflicts=batch_conflicts)
        finally:
            if prev is not None and self.log_gate is not None:
                self.log_gate.advance(cv)

    def _finalize_ordered(self, requests, results, batch_mutations,
                          batch_conflicts, routed, tags, cv, window):
        """The version-ordered tail of the pipeline: counters, DD load
        samples, the tlog push, storage apply, feeds, and reporting —
        everything that mutates shared cluster state."""
        self.conflict_count += batch_conflicts
        n_ok = sum(1 for r in results if not isinstance(r, FDBError))
        self.commit_count += n_ok
        self._m_batches.inc()
        self._note_result_errors(results)

        if self.dd is not None:
            for m in batch_mutations:
                if m.key >= b"\xff":
                    continue  # system rows are not user load samples
                if m.op == Op.CLEAR_RANGE:
                    self.dd.note_clear_range(m.key, m.param)
                else:
                    self.dd.note_write(
                        m.key, len(m.key) + len(m.param or b"")
                    )

        # push even empty batches so storage's version advances with cv
        try:
            with span_mod.stage("commit.log_push", self.stages):
                self.tlog.push(cv, batch_mutations, tags=tags)
        except TLogDown:
            # no durability quorum: the would-be-committed txns are in
            # limbo → honest 1021, nothing applied to storage (ref:
            # proxies dying with an unacked tlog push). Definitive
            # resolver rejections (not_committed / too_old) stand —
            # those clients may retry without 1021 disambiguation.
            self.commit_count -= n_ok
            self._note_abort("commit_unknown_result", n_ok)
            return [
                r if isinstance(r, FDBError)
                else FDBError.from_name("commit_unknown_result")
                for r in results
            ]
        self._m_committed.inc(n_ok)  # monotone: counted only once durable
        # sync satellite mode: the batch reaches the remote region's
        # log before any client sees the ack, so a primary-region
        # disaster after this point loses nothing (ref: satellite TLogs
        # in the commit path). sync_push degrades to a counted miss —
        # never a stall — when the WAN is partitioned or the satellite
        # is down; async mode skips this entirely (the streamer drains
        # on its own cadence and the lag is the measured exposure).
        if (self.regions is not None
                and self.regions.config.satellite_mode == "sync"):
            self.regions.sync_push(cv, batch_mutations)
        with span_mod.stage("commit.storage_apply", self.stages):
            for sid, muts in enumerate(routed):
                if not self.storages[sid].alive:
                    # a detected-dead storage misses the batch; recruitment
                    # replaces it wholesale (re-ingest from live teammates),
                    # so skipping cannot strand a partial state
                    continue
                try:
                    self.storages[sid].apply(cv, muts)
                    self.storages[sid].advance_window(window)
                except Exception:  # NOT BaseException: interrupts must escape
                    # the batch IS committed — the log is durable — so an
                    # apply failure must not fail the commit (a 1021 here
                    # would lie: a retry would pass the idempotency dedupe,
                    # whose lookup reads applied state, and double-commit
                    # into the log). The failed storage's state is suspect
                    # (possibly half-applied): declare it dead so
                    # recruitment replays the log from its durable version,
                    # restoring log↔storage agreement (ref: storage apply
                    # being async from the commit point in the reference).
                    from foundationdb_tpu.utils.trace import TraceEvent

                    TraceEvent("StorageApplyFailed", severity=40).detail(
                        storage=sid, version=cv).log()
                    self.storages[sid].kill()
        with span_mod.stage("commit.report", self.stages):
            if self.change_feeds is not None and batch_mutations:
                # after the log has the batch (durable order) and before the
                # version is readable — consumers reading up to a GRV they
                # observed always see the feed entries for it
                self.change_feeds.note_commit(cv, batch_mutations)
            self.sequencer.report_committed(cv)
            if self.ratekeeper is not None:
                self.ratekeeper.observe_commit(len(requests), batch_conflicts)
            self._batches_since_pump += 1
            if self._batches_since_pump >= self.pump_interval:
                self._batches_since_pump = 0
                self._pump_durability(window)
        return results

    def _conflicting_ranges(self, txn):
        """Which of a rejected txn's read ranges conflicted (ref: the
        conflictingKeys reply field of ResolveTransactionBatchReply).
        Exact for host conflict sets; the TPU backend keeps no
        per-range verdicts on device, so it reports every read range —
        conservative, same direction as its false-positive contract."""
        ranges = []
        exact = True
        for r in self.resolvers:
            cset = getattr(r, "cset", None)
            if cset is None or not hasattr(cset, "conflicting_ranges"):
                exact = False
                break
            ranges.extend(cset.conflicting_ranges(txn))
        if exact:
            return sorted(set(ranges))
        return sorted(set(txn.read_ranges()))

    # id rows outlive the MVCC window by this factor (~50s at the
    # default 5s window): the slack a delayed retry has to arrive and
    # still dedupe instead of double-applying
    IDMP_RETENTION_WINDOWS = 10

    def _idmp_expired(self, horizon, cap=1000):
        """CLEAR mutations for idempotency-id rows whose commit version
        fell below the retention horizon (scanned from a live storage's
        system keyspace; empty scan when no idempotent traffic)."""
        from foundationdb_tpu.core import systemdata

        live = next((s for s in self.storages if s.alive), None)
        if live is None:
            return []
        out = []
        for k, v in live.read_range(systemdata.IDMP_PREFIX,
                                    systemdata.IDMP_END, live.version):
            if systemdata.unpack_version(v) < horizon:
                out.append(Mutation(Op.CLEAR, k, None))
                if len(out) >= cap:
                    break
        return out

    def _pump_durability(self, window):
        """Periodic updateStorage analog: fold versions that left the MVCC
        window into the persistent engines, then feed the ratekeeper the
        durability lag (how far the slowest storage is behind the
        flushable frontier — the reference's storage-queue signal).
        The lag is measured BEFORE flushing: it is the backlog this pump
        found, which is what admission control must react to (after a
        synchronous flush it would always read zero)."""
        live = [s for s in self.storages if s.alive]
        if not live:
            return
        lag = max(0, window - min(s.durable_version for s in live))
        for s in live:
            # a versioned (Redwood-role) engine keeps sub-durable reads
            # serveable, so durability can run all the way to the latest
            # version; single-version engines stop at the window floor or
            # reads below the fold would silently lose history
            s.flush(None if s.versioned_engine else window)
        # pop floor includes DEAD storages' frozen durable versions: their
        # recruitment replays the tlog from there, so those records must
        # survive until the replacement catches up (the log grows for at
        # most the detection window)
        self.tlog.pop(min(s.durable_version for s in self.storages))
        if self.ratekeeper is not None:
            self.ratekeeper.update(storage_lag_versions=lag)

    def _route(self, mutations):
        """Bucket mutations by owning storage in one pass (ref:
        applyMetadataToCommittedTransactions tagging mutations with
        storage tags via keyServers). Full replication (every storage on
        every team) short-circuits to the identity. Clear-ranges go to
        every storage whose shards overlap — applying the full range to
        a partial owner is safe, it only clears keys actually held."""
        n = len(self.storages)
        if self.dd is None or self.dd.replication >= n:
            return [mutations] * n
        smap = self.dd.map
        per = [[] for _ in range(n)]
        for m in mutations:
            if m.key >= b"\xff":
                # system keyspace replicates everywhere: recovery must be
                # able to read the shard map from any surviving storage
                # (ref: the system keyspace's wider replication)
                owners = range(n)
            elif m.op == Op.CLEAR_RANGE:
                owners = set()
                for i in smap.shards_overlapping(m.key, m.param):
                    owners.update(smap.teams[i])
            else:
                owners = smap.team_for(m.key)
            for sid in owners:
                per[sid].append(m)
        return per

    def _resolve(self, txns, cv, window):
        if len(self.resolvers) == 1:
            return self.resolvers[0].resolve(txns, cv, window)
        # Key-range sharded resolvers (ref: applyMetadataToCommittedTransactions
        # fan-out): each resolver sees only conflict ranges overlapping its
        # shard; a txn commits iff EVERY resolver accepts it. Because a txn's
        # fate must be agreed, each resolver is also told the full batch
        # structure (masked to its shard) and the proxy ANDs the verdicts.
        # Sub-batches dispatch concurrently: each resolver's work (packing
        # + kernel dispatch, or the GIL-releasing native conflict set) is
        # independent; verdicts join in resolver order, so the result is
        # schedule-independent (deterministic under the sim).
        n = len(self.resolvers)
        shard_batches = []
        for ri in range(n):
            lo, hi = self._resolver_range(ri, n)
            shard_batches.append([
                TxnRequest(
                    read_version=t.read_version,
                    point_reads=_clip_points(t.point_reads, lo, hi),
                    point_writes=_clip_points(t.point_writes, lo, hi),
                    range_reads=_clip(t.range_reads, lo, hi),
                    range_writes=_clip(t.range_writes, lo, hi),
                )
                for t in txns
            ])
        # lane balance on the host fan-out, same instrument the mesh
        # router fills at split time: surviving conflict entries per
        # clipped sub-batch -> lane_skew_pct. The tpu multi-lane backend
        # never reaches here (Cluster builds ONE MeshResolver; its
        # single-dispatch router retires this clip loop), so this covers
        # the cpu/native fleets for before/after skew comparison.
        if deviceprofile.enabled():
            prof = self._fanout_profile or next(
                (r.profile for r in self.resolvers
                 if getattr(r, "profile", None) is not None), None)
            if prof is not None:
                prof.record_lane_counts([
                    sum(len(t.point_reads) + len(t.point_writes)
                        + len(t.range_reads) + len(t.range_writes)
                        for t in batch)
                    for batch in shard_batches
                ])
        if self._pool is None:
            from concurrent.futures import ThreadPoolExecutor

            self._pool = ThreadPoolExecutor(
                max_workers=n, thread_name_prefix="sub-resolve"
            )
        futs = [
            self._pool.submit(res.resolve, batch, cv, window)
            for res, batch in zip(self.resolvers, shard_batches)
        ]
        verdicts = [f.result() for f in futs]
        out = []
        for i in range(len(txns)):
            vs = [v[i] for v in verdicts]
            if any(v == TOO_OLD for v in vs):
                out.append(TOO_OLD)
            elif all(v == COMMITTED for v in vs):
                out.append(COMMITTED)
            else:
                out.append(CONFLICT)
        return out

    def kill(self):
        """Process death: every commit answers 1021 until the failure
        monitor recruits a new transaction-system generation."""
        self.alive = False

    def close(self):
        """Release the sub-resolve thread pool (simulation rebuilds the
        cluster on every injected crash — stranded pools add up)."""
        if self._pool is not None:
            self._pool.shutdown(wait=False)
            self._pool = None

    def _resolver_range(self, i, n):
        """Resolver i's key range: DD-derived bounds when available,
        else an even first-byte split. The last range's upper bound is
        None = +infinity so no key — including the \\xff system
        keyspace — escapes conflict checking."""
        b = self.resolver_bounds
        if b is not None:
            lo = b[i - 1] if i else b""
            hi = b[i] if i < len(b) else None
            return lo, hi
        lo = bytes([256 * i // n]) if i else b""
        hi = bytes([256 * (i + 1) // n]) if i + 1 < n else None
        return lo, hi


def _split_ranges(ranges):
    """One pass splitting conflict ranges into (points, true_ranges).
    Single-key ranges [k, k+\\x00) go to the resolver's point lanes —
    O(1) hash-table checks on device instead of the range lanes' ring
    scans. The reference makes the same point/range distinction inside
    detectConflicts (SkipList point queries vs range walks); semantics
    are identical either way (a point op IS the tiny range), this is
    purely the fast path. The point test allocates nothing — comparing
    against ``b + b"\\x00"`` built a bytes object per range and was the
    single hottest line of the commit pipeline."""
    points, true_ranges = [], []
    for b, e in ranges:
        if len(e) == len(b) + 1 and e[-1] == 0 and e.startswith(b):
            points.append(b)
        else:
            true_ranges.append((b, e))
    return points, true_ranges


def _clip_points(keys, lo, hi):
    return [k for k in keys if k >= lo and (hi is None or k < hi)]


def _clip(ranges, lo, hi):
    out = []
    for b, e in ranges:
        cb = max(b, lo)
        ce = e if hi is None else min(e, hi)
        if cb < ce:
            out.append((cb, ce))
    return out
