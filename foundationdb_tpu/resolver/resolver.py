"""The Resolver role — batched MVCC conflict detection behind a backend knob.

Ref parity: fdbserver/Resolver.actor.cpp (resolveBatch). The commit proxy
hands a batch of transactions in arrival order; the resolver returns
per-txn statuses and remembers accepted writes for the MVCC window.

``resolver_backend="tpu"`` packs the batch to device arrays and runs
ops/conflict.py's jitted kernel (history buffers live on device and are
donated across steps — no host↔device copies of state, only the batch in
and T statuses out). ``"cpu"`` runs the exact host ConflictSet
(resolver/skiplist.py; later a C++ twin via native/).
"""

import time

import jax
import numpy as np

from foundationdb_tpu.core.flatpack import FlatTxnBatch
from foundationdb_tpu.core.options import DEFAULT_KNOBS
from foundationdb_tpu.ops import conflict as ck
from foundationdb_tpu.resolver.packing import BatchPacker, CoarseBuckets
from foundationdb_tpu.resolver.skiplist import CpuConflictSet
from foundationdb_tpu.utils import deviceprofile
from foundationdb_tpu.utils import metrics as metrics_mod
from foundationdb_tpu.utils import span as span_mod

COMMITTED, CONFLICT, TOO_OLD = ck.COMMITTED, ck.CONFLICT, ck.TOO_OLD
CONFLICT_COARSE = ck.CONFLICT_COARSE

# resolve_many's fixed scan width: backlog dispatches pad to a multiple
# of this (server/batcher.py MAX_BACKLOG matches) so every backlog size
# shares one XLA compilation per variant; larger backlogs chunk into
# BACKLOG_B-sized scans rather than falling back to per-batch round
# trips (the overload case is exactly when batching matters most)
BACKLOG_B = 8


def _is_pallas_fallback_error(e):
    """Whether ``e`` is a Pallas kernel failing to build (Mosaic refusing
    the lowering) or to run (an XLA runtime fault) on this backend — the
    errors the fallback handler is designed for. Anything else (packer
    bugs, shape errors from our own code) must propagate, NOT silently
    wipe the device conflict history."""
    if isinstance(e, (jax.errors.JaxRuntimeError, NotImplementedError)):
        return True
    # Mosaic's own error types live in private modules; naming them at
    # module import would drag Pallas into every process that imports
    # the resolver. By the time a kernel has failed, Pallas is loaded.
    from jax._src.pallas.mosaic.error_handling import MosaicError
    from jax._src.pallas.mosaic.lowering import LoweringException

    return isinstance(e, (MosaicError, LoweringException))


class ResolverDown(Exception):
    """This resolver process is dead; the proxy fails the batch
    not_committed and the cluster controller recruits a replacement."""


class ResolveHandle:
    """Deferred-sync result of a ``resolve_many`` dispatch.

    JAX dispatch is asynchronous: the scanned backlog kernel is enqueued
    on the device the moment ``resolve_many`` returns, but the statuses
    only need to exist on the host when the proxy's apply stage consumes
    them. Holding the un-materialized device arrays here lets the commit
    pipeline overlap device compute with the PREVIOUS group's tlog push
    and storage apply; ``wait()`` performs the one host sync
    (``np.asarray``) and unpacks per-batch status lists. Host backends
    (and fallback paths) resolve eagerly at dispatch — their handle just
    hands the finished result back."""

    __slots__ = ("_materialize", "_result")

    def __init__(self, materialize=None, result=None):
        self._materialize = materialize
        self._result = result

    def wait(self):
        if self._materialize is not None:
            self._result = self._materialize()
            self._materialize = None
        return self._result


def params_from_knobs(knobs, use_pallas=False):
    """The one knobs→ResolverParams mapping (Resolver and MeshResolver
    must size their kernels identically or verdicts drift)."""
    return ck.ResolverParams(
        txns=knobs.batch_txn_capacity,
        point_reads=knobs.point_reads_per_txn,
        point_writes=knobs.point_writes_per_txn,
        range_reads=knobs.range_reads_per_txn,
        range_writes=knobs.range_writes_per_txn,
        key_width=knobs.key_limbs + 1,
        hash_bits=knobs.hash_table_bits,
        ring_capacity=knobs.range_ring_capacity,
        bucket_bits=knobs.coarse_buckets_bits,
        ring_partition_bits=knobs.ring_partition_bits,
        use_pallas=use_pallas,
    )


def fast_params_of(params):
    """The point-specialized variant's params: range lanes statically
    off, point writes still recorded into the coarse summary the full
    kernel's future range reads consult. None when the config has no
    range lanes to specialize away. The Pallas ring route is stripped:
    the point-only jnp step is a handful of gathers, and keeping the
    fallback machinery scoped to the FULL variant keeps its safety
    argument simple."""
    if not (params.range_reads or params.range_writes):
        return None
    return params._replace(
        range_reads=0, range_writes=0, use_pallas=False,
        record_point_coarse=True,
    )


class Resolver:
    def __init__(self, knobs=DEFAULT_KNOBS, base_version=0):
        self.knobs = knobs
        self.backend = knobs.resolver_backend
        self.base_version = base_version
        self.alive = True
        self._init_metrics()
        # wall seconds spent inside resolve_many's device dispatch (the
        # scan call; for host backends, the eager resolve) — the batcher
        # subtracts this from its stage-A+B timer so stage_pack_ms
        # measures HOST PACKING and stage_dispatch_ms the dispatch
        self.dispatch_wall_s = 0.0
        # device-path profiler (utils/deviceprofile.py): per-dispatch
        # pad/bucket/fallback accounting. The cluster hands every
        # resolver its cluster-owned DeviceProfile via adopt_profile
        # (the PR-4 registry pattern) so history survives respawn.
        self.profile = deviceprofile.DeviceProfile("resolver")
        # The device kernel has dedicated point LANES, and the native
        # conflict set packs a split-out point key once with its end
        # span aliasing the same blob bytes — both want the proxy's
        # point/range split. The pure-python cpu backend treats a point
        # as the tiny range it is, so the proxy skips the split there
        # (it was the hottest line of the host commit pipeline).
        self.wants_point_split = self.backend in ("tpu", "native")
        # the flat columnar commit path (core/flatpack.py): the device
        # packer consumes limb blobs directly, and the native set reads
        # raw key bytes out of the same blobs; the pure-python cpu
        # backend sticks to byte-pair ranges
        self.accepts_flat = self.backend in ("tpu", "native")
        if self.backend == "tpu":
            pallas = getattr(knobs, "pallas_ring", "auto")
            use_pallas = pallas == "on" or (
                pallas == "auto" and jax.default_backend() == "tpu"
            )
            if getattr(knobs, "ring_partition_bits", 0) and pallas == "auto":
                # the Pallas kernel implements the FLAT ring; a
                # partitioned ring under "auto" downgrades to the jnp
                # lanes (an explicit "on" is rejected by validate_params)
                use_pallas = False
            self.params = params_from_knobs(knobs, use_pallas=use_pallas)
            self._init_buckets()
            self.packer = BatchPacker(self.params, buckets=self.buckets)
            self.state = ck.init_state(self.params)
            self._resolve = ck.make_resolve_fn(self.params)
            # Static specialization (the XLA idiom for workload shapes):
            # a second compiled variant with the range lanes statically
            # OFF serves batches that carry only point ops while no range
            # write has ever entered history — YCSB-shaped traffic never
            # pays the ring/coarse broadcast lanes. Both variants share
            # ResolverState (the fast one records the hash table AND the
            # coarse point summary, so a later range read through the
            # full kernel sees every point write it must conflict with).
            self._fast = None
            self._fast_params = fast_params_of(self.params)
            self._range_history = False
            if self._fast_params is not None:
                self._fast = (
                    BatchPacker(self._fast_params, buckets=self.buckets),
                    ck.make_resolve_fn(self._fast_params),
                )
            # scan fns for backlog dispatch (resolve_many), cached per
            # (variant, padded batch count) — each (fast, B) pair is one
            # XLA compilation
            self._scan_fns = {}
            # pad-width buckets: a backlog dispatch pads to the smallest
            # bucket that fits. Pad batches are pure wasted kernel
            # compute, so on an interpreter-hosted (cpu) device — where
            # a scan compile is cheap — small backlogs pay a fraction of
            # the fixed B=8 dispatch cost; the TPU's compiler takes
            # seconds per scan, so one bucket only there.
            self._scan_pad_buckets = (
                (2, 4, BACKLOG_B)
                if jax.default_backend() == "cpu" else (BACKLOG_B,)
            )
        elif self.backend == "cpu":
            self.cset = CpuConflictSet()
            self.cset.window_start = base_version
        elif self.backend == "native":
            from foundationdb_tpu.native import NativeConflictSet

            self.cset = NativeConflictSet()
            if base_version:
                # windows only move forward; an empty resolve installs it
                self.cset.resolve([], 0, base_version)
        else:
            raise ValueError(f"unknown resolver_backend {self.backend!r}")
        self.adopt_profile(self.profile)  # attach the packer hooks

    def _init_buckets(self):
        """The coarse lanes' bucket function (resolver/packing.py
        ``CoarseBuckets``), one for every packer of this resolver, and
        what :meth:`_maybe_rebucket` needs: whether a range has been met
        (until then no coarse lane is read and the first limb's bits
        stay), and the jitted fold, built at the first rebucket."""
        self.buckets = CoarseBuckets(self.params)
        self._ranges_seen = False
        self._fold = None

    def _maybe_rebucket(self):
        """Cut the coarse buckets again where the sample says so, and
        fold what the device's summaries hold (ops/conflict.py
        ``fold_coarse``) before the next step packs under the new
        boundaries. Called between steps, on the dispatching thread; a
        resolver that has met no range returns at once."""
        if not (self._ranges_seen and self.buckets.recut_due()):
            return
        with span_mod.stage("resolver.rebucket", self.profile):
            if self.buckets.recut():
                if self._fold is None:
                    self._fold = ck.make_fold_fn(self.params, self.state)
                self.state = self._fold(self.state)
                self.profile.count(rebuckets=1)

    def _plain_statuses(self, statuses):
        """A dispatch's verdicts as the proxy takes them: the device's
        fourth code (a refusal only a coarse summary raised) is counted
        and answered as the CONFLICT it is."""
        n = statuses.count(CONFLICT_COARSE)
        if n:
            self.profile.count(conflicts_coarse_only=n)
            statuses = [CONFLICT if s == CONFLICT_COARSE else s
                        for s in statuses]
        return statuses

    def adopt_profile(self, profile):
        """Adopt a cluster-owned :class:`DeviceProfile` (the registry
        carryover pattern): fold whatever this instance already recorded
        into it, then point every capture site — including the packers'
        staging-ring hooks — at the shared object, so device-path
        history survives respawn / recovery / configure."""
        if profile is not getattr(self, "profile", None):
            mine = getattr(self, "profile", None)
            if mine is not None:
                profile.absorb(mine)
            self.profile = profile
        for p in (getattr(self, "packer", None),
                  self._fast[0] if getattr(self, "_fast", None) else None):
            if p is not None:
                p.profile = self.profile
        return self.profile

    def _init_metrics(self, registry=None):
        """Build (or adopt) the role registry + hot-path handles.
        Recruitment hands the replacement the dead instance's registry
        so resolver counters survive respawns without rewinding."""
        if registry is not None and registry is not getattr(
                self, "metrics", None):
            registry.absorb(self.metrics)
        self.metrics = registry if registry is not None \
            else metrics_mod.MetricsRegistry("resolver")
        self._m_batches = self.metrics.counter("resolve_batches")
        self._m_txns = self.metrics.counter("resolve_txns")
        self._m_backlogs = self.metrics.counter("backlog_dispatches")
        self._m_backlog_depth = self.metrics.gauge("backlog_depth")
        self._m_flat_fallbacks = self.metrics.counter("flat_fallbacks")
        self._m_pallas_fallbacks = self.metrics.counter("pallas_fallbacks")
        self._m_respawns = self.metrics.counter("respawns")

    def status(self):
        """This role's status RPC payload (leaf of the status doc)."""
        self.metrics.gauge("lanes").set(getattr(self, "n_lanes", 1))
        return {
            "alive": self.alive,
            "backend": self.backend,
            "lanes": getattr(self, "n_lanes", 1),
            "metrics": self.metrics.snapshot(),
        }

    def kill(self):
        """Process death: in-memory conflict history is gone; the
        replacement must fence pre-death read versions (ref: resolver
        failure forcing a recovery in the reference)."""
        self.alive = False

    def respawn(self, base_version):
        """A replacement of this resolver's own kind, fenced at
        ``base_version`` (the failure monitor's recruitment hook —
        subclasses recruit their own shape)."""
        new = type(self)(self.knobs, base_version=base_version)
        new._init_metrics(self.metrics)
        new.adopt_profile(self.profile)
        new._m_respawns.inc()
        return new

    def _make_scan_fn(self, use_fast):
        """Compile the multi-batch scan for resolve_many (subclasses
        swap in their mesh-sharded twin)."""
        params = self._fast_params if use_fast else self.params
        return ck.make_resolve_scan_fn(params)

    def _pad_bucket(self, nb):
        """Smallest scan pad width that fits ``nb`` batches."""
        for b in self._scan_pad_buckets:
            if nb <= b:
                return b
        return self._scan_pad_buckets[-1]

    def resolve(self, txns, commit_version, new_window_start):
        """txns: list[TxnRequest] (or a FlatTxnBatch — the columnar
        commit path) in arrival order → list of statuses."""
        if not self.alive:
            raise ResolverDown()
        self._m_batches.inc()
        self._m_txns.inc(len(txns))
        # HOST-side scan span for a sampled batch (the proxy's ambient
        # trace context); the stages under it — resolver.pack, enqueue,
        # readback — time the work. Never inside a traced/jitted
        # region — FL004 keeps kernel code pure.
        with span_mod.from_context("resolver.scan", span_mod.current(),
                                   txns=len(txns)):
            return self._resolve_traced(txns, commit_version,
                                        new_window_start)

    def _resolve_traced(self, txns, commit_version, new_window_start):
        if isinstance(txns, FlatTxnBatch):
            return self._resolve_flat(txns, commit_version,
                                      new_window_start)
        if self.backend in ("cpu", "native"):
            with span_mod.stage("resolver.dispatch") as dsp:
                out = self.cset.resolve(txns, commit_version,
                                        new_window_start)
            if deviceprofile.enabled():
                # host sets pack nothing: slots == live, zero pad waste
                self.profile.record_dispatch(
                    bucket=1, live_batches=1, live_txns=len(txns),
                    txn_slots=len(txns), wall_s=dsp.seconds)
            return out
        self._maybe_rebase(commit_version)
        self._maybe_rebound(commit_version)
        statuses, live = self._split_too_old(txns)
        use_fast = self._pick_fast(t for _, t in live)
        self._maybe_rebucket()
        packer, resolve_fn = self._fast if use_fast else (
            self.packer, self._resolve
        )
        for c in range(0, max(len(live), 1), self.params.txns):
            chunk = live[c : c + self.params.txns]
            packed = span_mod.stage("resolver.pack", self.profile,
                                    cpu=self.profile.cpu_turn())
            with packed:
                batch = packer.pack(
                    [t for _, t in chunk], self.base_version,
                    commit_version, new_window_start
                )
            out, step_s = self._step_kernel(resolve_fn, batch, len(chunk),
                                            commit_version, packed)
            if deviceprofile.enabled():
                # each chunk is one device step padded to a full
                # params.txns batch — the single-batch route's pad waste
                pp = self._fast_params if use_fast else self.params
                self.profile.record_dispatch(
                    bucket=1, live_batches=1, live_txns=len(chunk),
                    txn_slots=pp.txns,
                    entries_live={
                        "pr": sum(len(t.point_reads) for _, t in chunk),
                        "pw": sum(len(t.point_writes) for _, t in chunk),
                        "rr": sum(len(t.range_reads) for _, t in chunk),
                        "rw": sum(len(t.range_writes) for _, t in chunk)},
                    entry_slots={"pr": pp.txns * pp.point_reads,
                                 "pw": pp.txns * pp.point_writes,
                                 "rr": pp.txns * pp.range_reads,
                                 "rw": pp.txns * pp.range_writes},
                    transfer_bytes=sum(
                        int(x.nbytes) for x in jax.tree.leaves(batch)),
                    wall_s=step_s)
            if out is None:  # pallas fallback engaged: fenced restart
                for j in range(len(statuses)):
                    if statuses[j] is None:
                        statuses[j] = TOO_OLD
                return statuses
            self.profile.record_kernel_route(self._kernel_route(use_fast))
            for (i, _), s in zip(chunk, out):
                statuses[i] = s
        return statuses

    def _split_too_old(self, txns):
        """→ (statuses with TOO_OLD where the host can say so, [(index,
        txn)] of the rest). base_version only ever advances to a past
        window start or to a fence, so a read version below it is too
        old by construction: reject on host rather than letting the
        uint32 offset clamp to 0. Dropping these txns from the batch is
        safe: they commit nothing."""
        statuses = [None] * len(txns)
        live = []
        for i, t in enumerate(txns):
            if t.read_version < self.base_version:
                statuses[i] = TOO_OLD
            else:
                live.append((i, t))
        self._note_too_old(len(txns) - len(live))
        return statuses, live

    def _maybe_rebound(self, commit_version):
        """Lane bounds are the mesh's (MeshResolver): one lane has none."""

    def _note_too_old(self, n):
        """``n`` transactions refused by the host's rule: the mesh counts
        those a re-bound's fence cost."""

    def _step_kernel(self, resolve_fn, batch, n, commit_version, packed):
        """One threaded kernel step → (statuses[:n], its wall seconds:
        the dispatch wall, enqueue + readback), or (None, seconds) when
        the Pallas fallback engaged (the resolver restarted fenced and
        the caller must answer TOO_OLD). ``packed`` is the batch's
        closed ``resolver.pack`` stage."""
        # enqueue: the jitted call returning (H2D + launch) and the
        # offers of the interpreter behind it; readback: the device
        # wait + D2H of the verdicts. Each starts its CPU
        # split from the closing reading of the stage before it: a read
        # of the thread's CPU clock is a slow system call on the chip's
        # host, and nothing but these stages runs between them (and
        # none does where the pack took none: DeviceProfile.cpu_turn)
        enq = span_mod.stage("resolver.enqueue", self.profile,
                             cpu=packed.cpu and packed)
        rdb = span_mod.stage("resolver.readback", self.profile,
                             cpu=enq.cpu and enq)
        try:
            with enq:
                status, _accepted, self.state = resolve_fn(self.state,
                                                           batch)
                self._offer_interpreter(n)
            self.profile.count(h2d_args=self._h2d_args(batch))
            # materialize INSIDE the try: dispatch is async, so a
            # kernel that compiles but faults at runtime only raises
            # here — outside, the fallback would never engage and
            # self.state would hold poisoned arrays
            with rdb:
                status = np.asarray(status)
            return (self._plain_statuses(status[:n].tolist()),
                    enq.seconds + rdb.seconds)
        except Exception as e:
            if (not self.params.use_pallas
                    or resolve_fn is not self._resolve
                    or not _is_pallas_fallback_error(e)):
                raise  # pallas only runs in the full variant; non-JAX
                # errors (packer bugs …) must not wipe device history
            self._engage_pallas_fallback(commit_version)
            return None, enq.seconds + rdb.seconds

    def _engage_pallas_fallback(self, commit_version):
        """The Pallas ring kernel failed to build/run on this backend:
        fall back to the jnp path for the life of the resolver rather
        than failing every commit. The device history may be
        donated/poisoned by the failed dispatch, so restart fenced
        exactly like a recruited resolver — the in-flight batch (and
        any read version from before the fence) retries TOO_OLD with
        fresh reads."""
        from foundationdb_tpu.utils.trace import TraceEvent

        TraceEvent("PallasRingFallback", severity=30).detail(
            fenced_at=commit_version).log()
        self._m_pallas_fallbacks.inc()
        self.profile.record_fallback("pallas_to_jit")
        self.params = self.params._replace(use_pallas=False)
        self._resolve = ck.make_resolve_fn(self.params)
        self.state = ck.init_state(self.params)
        self.base_version = commit_version

    def _kernel_route(self, use_fast, scan=False):
        """Which per-batch step body actually serves this dispatch —
        the device profiler's kernel-route taxonomy. The fast variant
        (fast_params_of) and the multi-batch scan (make_resolve_scan_fn)
        both strip use_pallas."""
        if not use_fast and not scan and self.params.use_pallas:
            return "pallas_ring"
        return "jit"

    def _resolve_flat(self, flat, commit_version, new_window_start):
        """Resolve one columnar batch. The native set reads raw key
        bytes straight out of the blobs; the tpu path packs limb rows
        into the staging ring. Anything the flat lane can't serve —
        width mismatch, lane overflow, a too-old read version that the
        host must pre-filter — decodes to TxnRequests and rides the
        legacy path (rare by construction)."""
        if self.backend in ("native", "cpu"):
            with span_mod.stage("resolver.dispatch") as dsp:
                if self.backend == "native":
                    out = self.cset.resolve_flat(flat, commit_version,
                                                 new_window_start)
                else:
                    out = self.cset.resolve(
                        flat.to_txn_requests(), commit_version,
                        new_window_start)
            if deviceprofile.enabled():
                self.profile.record_dispatch(
                    bucket=1, live_batches=1, live_txns=len(flat),
                    txn_slots=len(flat), wall_s=dsp.seconds)
            return out
        self._maybe_rebase(commit_version)
        self._maybe_rebound(commit_version)
        cause = self._flat_fallback_cause(flat)
        if cause is not None:
            self._m_flat_fallbacks.inc()
            self.profile.record_fallback(cause)
            return self.resolve(flat.to_txn_requests(), commit_version,
                                new_window_start)
        use_fast = self._pick_fast_flat([flat])
        self._maybe_rebucket()
        packer, resolve_fn = self._fast if use_fast else (
            self.packer, self._resolve
        )
        packed = span_mod.stage("resolver.pack", self.profile,
                                cpu=self.profile.cpu_turn())
        with packed:
            batch = packer.pack_flat(flat, self.base_version,
                                     commit_version, new_window_start)
        out, step_s = self._step_kernel(resolve_fn, batch, len(flat),
                                        commit_version, packed)
        if deviceprofile.enabled():
            pp = self._fast_params if use_fast else self.params
            self.profile.record_dispatch(
                bucket=1, live_batches=1, live_txns=len(flat),
                txn_slots=pp.txns,
                entries_live={"pr": int(flat.prc.sum()),
                              "pw": int(flat.pwc.sum()),
                              "rr": int(flat.rrc.sum()),
                              "rw": int(flat.rwc.sum())},
                entry_slots={"pr": pp.txns * pp.point_reads,
                             "pw": pp.txns * pp.point_writes,
                             "rr": pp.txns * pp.range_reads,
                             "rw": pp.txns * pp.range_writes},
                transfer_bytes=sum(
                    int(x.nbytes) for x in jax.tree.leaves(batch)),
                wall_s=step_s)
        if out is None:
            return [TOO_OLD] * len(flat)
        self.profile.record_kernel_route(self._kernel_route(use_fast))
        return out

    def _flat_fallback_cause(self, flat):
        """Why this flat batch cannot ride the columnar lane — the
        structured fallback_cause taxonomy behind the bare
        flat_fallbacks counter. None when it can: the predicate is
        exactly ``flat_fits and rv fresh`` (the legacy-route guard)."""
        if len(flat) and int(flat.rv.min()) < self.base_version:
            return "too_old_rv"
        if self.packer.flat_fits(flat):
            return None
        p = self.params
        if (len(flat) > p.txns
                or flat.prc.max(initial=0) > p.point_reads
                or flat.pwc.max(initial=0) > p.point_writes
                or flat.rrc.max(initial=0) > p.range_reads
                or flat.rwc.max(initial=0) > p.range_writes):
            return "over_capacity"
        return "flat_to_legacy"  # limb-width mismatch

    def _offer_interpreter(self, n):
        """Give the interpreter lock up and take it again, once for each
        of the ``n`` transactions of the batch just launched, while the
        device runs the step. Each of them was answered at the last
        settle and has sent its next request since (a reply provokes a
        request), and those requests are what stands in line. Until
        PR 40 the jitted call gave the lock up once for each of the 22
        host arrays it was handed, and the request threads were served
        in those gaps; handed one array and offering nothing, the step
        left ycsb_a's reads a p95 of 9.4–10.7 ms for 6.0 and its updates
        a median of 30.8–32.1 ms for 26.5 (PERF.md §6, PR 40). Where
        nobody stands in line an offer is one system call."""
        for _ in range(n):
            time.sleep(0)

    def _h2d_args(self, batch):
        """Host arrays one dispatch hands its jitted program: one, the
        array ``ops/conflict.pack_args`` builds of the batch, on one
        device as on a mesh (``ck.PackedProgram``; PERF.md §6, PR 40)."""
        return 1

    def _profile_lanes(self, statuses):
        """Per-lane dispatch-wall capture hook, called host-side at
        materialize time (never inside a traced fn — FL004). The
        single-device resolver is one implicit lane: nothing to record;
        MeshResolver overrides with the per-shard walls."""

    def _pick_fast(self, txns):
        """Whether the point-specialized variant may serve these txns
        (see __init__) — and the sticky _range_history update when a
        range write (or a point-write spill, which the packer records as
        ring history) appears."""
        point_only = True
        pr_cap = self.params.point_reads
        pw_cap = self.params.point_writes
        for t in txns:
            if t.range_writes or len(t.point_writes) > pw_cap:
                self._range_history = True
                point_only = False
                break
            if t.range_reads or len(t.point_reads) > pr_cap:
                point_only = False  # needs range lanes this batch
        if not point_only:
            self._ranges_seen = True  # the coarse lanes are in use now
        return (self._fast is not None and point_only
                and not self._range_history)

    def _pick_fast_flat(self, flats):
        """_pick_fast's columnar twin — count maxima instead of per-txn
        walks. Callers route lane-overflowing batches to the legacy
        path first, so only range presence matters here."""
        point_only = True
        for f in flats:
            if f.rwc.max(initial=0) > 0:
                self._range_history = True
                point_only = False
                break
            if f.rrc.max(initial=0) > 0:
                point_only = False
        if not point_only:
            self._ranges_seen = True
        return (self._fast is not None and point_only
                and not self._range_history)

    def resolve_many(self, batches, lazy=False):
        """Resolve a BACKLOG of batches in one device dispatch.

        ``batches``: list of (txns, commit_version, new_window_start) in
        commit order. Semantically identical to calling :meth:`resolve`
        per batch (lax.scan threads the history with the same sequential
        dependency) but pays ONE host↔device round trip for the whole
        backlog. The batch count is padded to a small power of two
        (empty batches commit nothing) so distinct backlog sizes share
        compilations.

        ``lazy=True`` returns a :class:`ResolveHandle` instead of the
        status lists: the device work is dispatched (history state is
        threaded at dispatch time, so a later dispatch still sees this
        one's writes) but the host sync is deferred to ``wait()`` — the
        commit pipeline's stage C. Dispatch-time failures (dead
        resolver, packer errors) still raise here; only the
        materialization moves.
        """
        if len(batches) > 1:
            self._m_backlogs.inc()
            self._m_backlog_depth.set(len(batches))
        ssp = span_mod.from_context("resolver.scan", span_mod.current())
        if ssp is not span_mod.NULL:
            # one scan span for the whole backlog dispatch (host-side
            # only — FL004 keeps kernel code pure). Ambient context is
            # cleared so the eager host route's per-batch resolve()
            # calls don't emit nested duplicates.
            ssp.attr(batches=len(batches),
                     txns=sum(len(t) for t, _, _ in batches))
            prior = span_mod.set_current(None)
            try:
                handle = self._dispatch_many(batches)
            finally:
                span_mod.set_current(prior)
                ssp.finish()
            return handle if lazy else handle.wait()
        handle = self._dispatch_many(batches)
        return handle if lazy else handle.wait()

    def _dispatch_many(self, batches):
        import time as _time

        if (self.backend != "tpu" or len(batches) <= 1
                or any(len(t) > self.params.txns for t, _, _ in batches)):
            # host backends / degenerate backlogs resolve eagerly — the
            # handle is already settled. The per-batch resolve() calls
            # own the dispatch accounting (one record per kernel step /
            # host scan), so nothing records here.
            t0 = _time.perf_counter()
            result = [self.resolve(t, cv, ws) for t, cv, ws in batches]
            self.dispatch_wall_s += _time.perf_counter() - t0
            return ResolveHandle(result=result)
        if len(batches) > BACKLOG_B:
            # Oversized backlog — the overload case this path exists for.
            # Chunk into BACKLOG_B-wide scans (each one dispatch) instead
            # of collapsing to per-batch round trips: throughput stays
            # scan-bound, not RTT-bound, no matter how deep the queue.
            handles = [
                self._dispatch_many(batches[i:i + BACKLOG_B])
                for i in range(0, len(batches), BACKLOG_B)
            ]
            return ResolveHandle(materialize=lambda: [
                statuses for h in handles for statuses in h.wait()
            ])
        if not self.alive:
            raise ResolverDown()
        self._maybe_rebase(batches[-1][1])
        # (a fence at the backlog's first commit version: every batch of
        # it still packs at or above base_version)
        self._maybe_rebound(batches[0][1])
        # the scanned paths below bypass resolve(): count their volume
        # here (the eager/host route above counts via resolve itself)
        self._m_batches.inc(len(batches))
        self._m_txns.inc(sum(len(t) for t, _, _ in batches))
        flats_present = any(
            isinstance(t, FlatTxnBatch) for t, _, _ in batches)
        if all(isinstance(t, FlatTxnBatch) for t, _, _ in batches):
            handle = self._dispatch_flat(batches)
            if handle is not None:
                return handle
            self._m_flat_fallbacks.inc()
            self.profile.record_fallback(next(
                (c for c in (self._flat_fallback_cause(t)
                             for t, _, _ in batches) if c),
                "flat_to_legacy"))
        elif flats_present:
            # flat batches interleaved with legacy requests: the whole
            # group must decode (one scan threads one history)
            self.profile.record_fallback("flat_to_legacy")
        # A mixed or flat-ineligible backlog decodes to the legacy path.
        # The decode is DISPATCH work: charge it to dispatch_wall_s so
        # the batcher's stage split doesn't land it in whichever stage
        # timer happens to be open (stage_pack_ms, before this fix).
        t_dec = _time.perf_counter()
        batches = [
            (t.to_txn_requests() if isinstance(t, FlatTxnBatch) else t,
             cv, ws)
            for t, cv, ws in batches
        ]
        if flats_present:
            self.dispatch_wall_s += _time.perf_counter() - t_dec
        per_batch = []
        all_live = []
        for txns, cv, ws in batches:
            statuses, live = self._split_too_old(txns)
            per_batch.append((statuses, live, cv, ws))
            all_live.extend(t for _, t in live)
        use_fast = self._pick_fast(all_live)
        self._maybe_rebucket()
        packer = self._fast[0] if use_fast else self.packer
        packed = [
            packer.pack([t for _, t in live], self.base_version, cv, ws)
            for statuses, live, cv, ws in per_batch
        ]
        # Pad to ONE fixed bucket: the TPU's compiler takes seconds per
        # scan, so every backlog size must share the same compilation
        # (empty padding batches cost ~ms of device time —
        # noise against the round trip this dispatch saves; pads come
        # from the packer's cached template, not a fresh pack). The
        # flat path buckets instead (_dispatch_flat) — variable padded
        # shapes are part of its staging design.
        B = BACKLOG_B
        last_cv, last_ws = batches[-1][1], batches[-1][2]
        if len(packed) < B:
            pad = packer.pack_empty(self.base_version, last_cv, last_ws)
            packed.extend([pad] * (B - len(packed)))
        scan_fn = self._get_scan_fn(use_fast, B)
        stacked = jax.tree.map(lambda *xs: np.stack(xs), *packed)
        cpu_turn = self.profile.cpu_turn()
        prof = deviceprofile.enabled()
        if prof:
            ent = {"pr": 0, "pw": 0, "rr": 0, "rw": 0}
            for t in all_live:
                ent["pr"] += len(t.point_reads)
                ent["pw"] += len(t.point_writes)
                ent["rr"] += len(t.range_reads)
                ent["rw"] += len(t.range_writes)
            pp = self._fast_params if use_fast else self.params
            xfer = sum(int(x.nbytes) for x in jax.tree.leaves(stacked))
            pt0 = deviceprofile.now()
        t0 = _time.perf_counter()
        try:
            self.state, st = scan_fn(self.state, stacked)
        finally:
            self.dispatch_wall_s += _time.perf_counter() - t0
        self.profile.record_kernel_route(
            self._kernel_route(use_fast, scan=True), n=len(per_batch))
        if prof:
            self.profile.count(h2d_args=self._h2d_args(stacked))
            self.profile.record_dispatch(
                bucket=B, live_batches=len(per_batch),
                live_txns=len(all_live), txn_slots=B * pp.txns,
                entries_live=ent,
                entry_slots={"pr": B * pp.txns * pp.point_reads,
                             "pw": B * pp.txns * pp.point_writes,
                             "rr": B * pp.txns * pp.range_reads,
                             "rw": B * pp.txns * pp.range_writes},
                transfer_bytes=xfer,
                wall_s=deviceprofile.now() - pt0)

        def materialize():
            self._profile_lanes(st)
            with span_mod.stage("resolver.readback", self.profile,
                                cpu=cpu_turn):
                arr = np.asarray(st)  # the ONE host sync for the backlog
            out = []
            for b, (statuses, live, cv, ws) in enumerate(per_batch):
                row = self._plain_statuses(arr[b][: len(live)].tolist())
                for (i, _), s in zip(live, row):
                    statuses[i] = s
                out.append(statuses)
            return out

        return ResolveHandle(materialize=materialize)

    def _get_scan_fn(self, use_fast, B):
        """The cached multi-batch scan for (variant, pad width) — a
        cache miss is an XLA compilation, recorded (with any later
        shape-driven retrace through ops/conflict.count_retraces) into
        the device profile's compile-cache accounting."""
        key = (use_fast, B)
        scan_fn = self._scan_fns.get(key)
        if scan_fn is None:
            scan_fn = ck.count_retraces(
                self._make_scan_fn(use_fast),
                lambda _sig, _k=key: self.profile.record_compile(_k),
                gate=deviceprofile.enabled,
            )
            self._scan_fns[key] = scan_fn
        return scan_fn

    def _dispatch_flat(self, batches):
        """The columnar backlog dispatch: the whole group packs into one
        stacked staging set (no per-batch ResolveBatch objects, no
        np.stack copy) and rides the same cached scan. None when any
        batch needs the legacy path (lane overflow, width mismatch, a
        too-old read version the host must pre-filter)."""
        flats = [t for t, _, _ in batches]
        for f in flats:
            if not self.packer.flat_fits(f) or (
                len(f) and int(f.rv.min()) < self.base_version
            ):
                return None
        use_fast = self._pick_fast_flat(flats)
        self._maybe_rebucket()
        packer = self._fast[0] if use_fast else self.packer
        B = self._pad_bucket(len(flats))
        stacked = packer.pack_flat_group(
            flats, [(cv, ws) for _, cv, ws in batches],
            self.base_version, B=B,
        )
        scan_fn = self._get_scan_fn(use_fast, B)
        import time as _time

        cpu_turn = self.profile.cpu_turn()
        prof = deviceprofile.enabled()
        if prof:
            pp = packer.params
            ent = {
                "pr": sum(int(f.prc.sum()) for f in flats),
                "pw": sum(int(f.pwc.sum()) for f in flats),
                "rr": sum(int(f.rrc.sum()) for f in flats),
                "rw": sum(int(f.rwc.sum()) for f in flats),
            }
            xfer = sum(int(x.nbytes) for x in jax.tree.leaves(stacked))
            pt0 = deviceprofile.now()
        t0 = _time.perf_counter()
        try:
            self.state, st = scan_fn(self.state, stacked)
        finally:
            self.dispatch_wall_s += _time.perf_counter() - t0
        self.profile.record_kernel_route(
            self._kernel_route(use_fast, scan=True), n=len(flats))
        if prof:
            self.profile.count(h2d_args=self._h2d_args(stacked))
            self.profile.record_dispatch(
                bucket=B, live_batches=len(flats),
                live_txns=sum(len(f) for f in flats),
                txn_slots=B * pp.txns,
                entries_live=ent,
                entry_slots={"pr": B * pp.txns * pp.point_reads,
                             "pw": B * pp.txns * pp.point_writes,
                             "rr": B * pp.txns * pp.range_reads,
                             "rw": B * pp.txns * pp.range_writes},
                transfer_bytes=xfer,
                wall_s=deviceprofile.now() - pt0)

        def materialize():
            self._profile_lanes(st)
            with span_mod.stage("resolver.readback", self.profile,
                                cpu=cpu_turn):
                arr = np.asarray(st)  # the ONE host sync for the backlog
            return [
                self._plain_statuses(arr[b][: len(f)].tolist())
                for b, f in enumerate(flats)
            ]

        return ResolveHandle(materialize=materialize)

    def _maybe_rebase(self, commit_version):
        """Keep uint32 version offsets in range (core/versions.py).

        Shifts the device state down by the current window start: entries
        clamped to 0 are exactly those no admissible read can conflict
        with anymore."""
        from foundationdb_tpu.core.versions import REBASE_THRESHOLD

        if commit_version - self.base_version < REBASE_THRESHOLD:
            return
        delta = int(jax.device_get(self.state.window_start))
        if delta == 0:
            raise RuntimeError(
                "version offsets exceed rebase threshold but the MVCC window "
                "never advanced; advance new_window_start to allow rebasing"
            )
        self.state = ck.rebase_state(self.state, delta)
        self.base_version += delta

    def window_start(self):
        if self.backend in ("cpu", "native"):
            return self.cset.window_start
        return self.base_version + int(jax.device_get(self.state.window_start))
