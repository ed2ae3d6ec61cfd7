"""In-process cluster: wires sequencer, GRV/commit proxies, resolver(s),
tlog, and storage into a database.

Ref parity: the role wiring that ClusterController + Master recovery
performs (fdbserver/ClusterController.actor.cpp,
masterserver.actor.cpp). There is no separate process model here — the
"simulation deployment" runs every role in-process, exactly how the
reference's simulation (fdbrpc/sim2) hosts a whole cluster in one
process for deterministic testing.
"""

import dataclasses
import itertools
import threading

from foundationdb_tpu.core.errors import FDBError, err
from foundationdb_tpu.core.options import DEFAULT_KNOBS
from foundationdb_tpu.resolver.resolver import Resolver
from foundationdb_tpu.server.coordination import (
    CoordinationQuorum, CoordinatorDown, GenerationConflict,
)
from foundationdb_tpu.server import consistencyscan as consistencyscan_mod
from foundationdb_tpu.server.datadistribution import DataDistributor
from foundationdb_tpu.server.grv import GrvProxy
from foundationdb_tpu.server import health as health_mod
from foundationdb_tpu.server.proxy import CommitProxy
from foundationdb_tpu.server.ratekeeper import Ratekeeper
from foundationdb_tpu.server.router import StorageRouter
from foundationdb_tpu.server.sequencer import Sequencer
from foundationdb_tpu.server.storage import StorageServer
from foundationdb_tpu.server.tlog import TLog, TLogSystem
from foundationdb_tpu.utils import deviceprofile
from foundationdb_tpu.utils import heatmap as heatmap_mod
from foundationdb_tpu.utils import lockdep
from foundationdb_tpu.utils import metrics as metrics_mod
from foundationdb_tpu.utils import timeseries as timeseries_mod
from foundationdb_tpu.utils.trace import TraceEvent


def _lock_state(uid):
    """One consistent snapshot: locked iff a uid exists (including an
    empty one — an empty uid still fences commits and must not read as
    unlocked)."""
    if uid is None:
        return {"locked": False, "lock_uid": None}
    return {"locked": True, "lock_uid": uid.decode("utf-8", "replace")}


class Cluster:
    def __init__(self, knobs=None, n_resolvers=1, n_storage=1, wal_path=None,
                 version_clock="counter", storage_engines=None,
                 coordination=None, n_coordinators=3, coordination_dir=None,
                 replication=None, commit_pipeline="sync",
                 commit_batch_max=None, commit_flush_after=4,
                 target_tps=None, rk_clock=None, n_tlogs=1, fsync=False,
                 n_commit_proxies=1, regions=None,
                 **knob_overrides):
        if knobs is None:
            knobs = (
                dataclasses.replace(DEFAULT_KNOBS, **knob_overrides)
                if knob_overrides
                else DEFAULT_KNOBS
            )
        self.knobs = knobs
        # Per-role metric registries, keyed (role, index), owned by the
        # CLUSTER so they outlive role incarnations: a txn-system
        # recovery hands the replacement proxies the same registries and
        # no counter ever goes backwards (the reference's status
        # counters survive recoveries the same way — they live in the
        # roles' stats collections aggregated by a long-lived process).
        self._metrics_store = {}
        # Workload-attribution heatmaps, same ownership story: keyed
        # (role, index) and handed to every incarnation of the role, so
        # conflict/read/write heat survives txn-system recoveries,
        # storage recruitment, and configure() shrink (absorbed, never
        # rewound) exactly like the metric registries above.
        self._heatmap_store = {}
        # Device-path execution profiles (utils/deviceprofile.py), the
        # third member of the cluster-owned observability store: keyed
        # ("resolver", index) and re-handed to every resolver
        # incarnation via adopt_profile, so dispatch/pad/fallback
        # accounting survives respawn, recovery, and configure shrink.
        self._device_store = {}
        self.ratekeeper = Ratekeeper(
            target_tps=target_tps if target_tps is not None else 1e9,
            clock=rk_clock,
            tag_busy_threshold=knobs.tag_throttle_busyness,
        )
        if storage_engines is None:
            storage_engines = [None] * n_storage
        elif len(storage_engines) != n_storage:
            if n_storage != 1:
                raise ValueError(
                    f"n_storage={n_storage} but {len(storage_engines)} "
                    "storage_engines given"
                )
            n_storage = len(storage_engines)
        self.storages = [
            StorageServer(
                window_versions=knobs.max_read_transaction_life_versions,
                engine=eng,
            )
            for eng in storage_engines
        ]
        if knobs.workload_sampling:
            for i, s in enumerate(self.storages):
                s.attach_heatmaps(
                    self._role_heatmap("storage_read", i),
                    self._role_heatmap("storage_write", i),
                    knobs.storage_sample_every,
                )
        # ── recovery (ref: Master recovery replaying tlogs into storage) ──
        # Replay WAL records newer than each storage's durable version,
        # then restart the version authority above everything recovered.
        # Conflict history is not persisted; instead the resolvers open
        # with their window starting at the recovered version, so any
        # read version from before the crash is rejected TOO_OLD — the
        # same effect as the reference's recovery fencing in-flight txns.
        # replicated logs recover from the union of surviving replica WALs
        # (ref: recovery reading a quorum of the old tlog generation)
        if wal_path and n_tlogs > 1:
            recovered_records = TLogSystem.recover(wal_path, n_tlogs)
        elif wal_path:
            recovered_records = TLog.recover(wal_path)
        else:
            recovered_records = []
        for s in self.storages:
            for version, mutations in recovered_records:
                if version > s.version:
                    s.apply(version, mutations)
        recovered = max((s.version for s in self.storages), default=0)

        # ── coordinated cluster state (ref: master recovery reading then
        # locking the coordinators' generation before recruiting roles) ──
        self.coordination = coordination or CoordinationQuorum.local(
            n_coordinators, coordination_dir
        )
        # Generation lock is a CAS: read g, commit g+1 expecting g — two
        # concurrent recoveries cannot both win the slot (the loser sees
        # GenerationConflict, re-reads, and bids for the next slot).
        self.generation = self._win_generation(recovered)
        TraceEvent("MasterRecovered").detail(
            generation=self.generation, version=recovered).log()

        # fsync=True: every tlog push reaches the platters before the
        # commit acks (ref: TLog's DiskQueue fsync — the reference's
        # durability default; ours is opt-in because sim/test runs pay
        # ~10ms per commit for it)
        if n_tlogs > 1:
            self.tlog = TLogSystem(n_tlogs, wal_path=wal_path, fsync=fsync)
        else:
            self.tlog = TLog(wal_path=wal_path, fsync=fsync)
        self.tlog._first_version = recovered
        self.sequencer = Sequencer(
            version_clock=version_clock, start_version=recovered
        )
        # Multi-resolver TPU deployments run the fleet as ONE mesh
        # program (hash/bucket-sharded history, psum verdicts over ICI)
        # rather than n host-side resolvers — the proxy drives it through
        # the ordinary single-resolver path, backlog dispatch included.
        # cpu/native backends keep the key-range-sharded host fan-out
        # (the reference's process shape). See resolver/meshresolver.py.
        if knobs.resolver_backend == "tpu" and n_resolvers > 1:
            from foundationdb_tpu.resolver.meshresolver import MeshResolver

            self.resolvers = [MeshResolver(
                knobs, base_version=recovered, n_lanes=n_resolvers,
            )]
        else:
            self.resolvers = [
                Resolver(knobs, base_version=recovered)
                for _ in range(n_resolvers)
            ]
        self._attach_device_profiles()
        # Placement: replication defaults to n_storage (every storage a
        # full replica); replication < n_storage partitions the keyspace
        # into shards owned by teams of that size, with the commit proxy
        # routing writes and the StorageRouter stitching reads. The shard
        # map persists in the \xff/keyServers/ system keyspace (ref:
        # fdbclient/SystemData.cpp) — recovery restores the partitioning
        # instead of resetting to full replication. (The WAL still
        # replays everywhere, so non-owners briefly hold shadow copies of
        # recovered data; routing never reads them and relocations clear
        # before installing.)
        from foundationdb_tpu.core import systemdata
        from foundationdb_tpu.server.datadistribution import ShardMap

        restored_map = None
        arg_replication = replication
        if recovered_records:
            s0 = self.storages[0]
            rows = s0.read_range(
                systemdata.KEY_SERVERS_PREFIX, systemdata.KEY_SERVERS_END,
                s0.version,
            )
            decoded = systemdata.decode_shard_map(rows)
            if decoded is not None:
                restored_map = ShardMap.restore(*decoded)
                rep_row = s0.get(systemdata.CONF_REPLICATION, s0.version)
                if rep_row is not None:
                    replication = int(rep_row)
                # A persisted map can name a DIFFERENT storage fleet than
                # this incarnation has (a DR failover recovers the
                # primary's keyServers rows into the satellite's cluster
                # shape): validate team indices; a mismatched map falls
                # back to full replication, like a decode failure.
                fleet = len(self.storages)
                if any(sid >= fleet for team in restored_map.teams
                       for sid in team) or (replication or 0) > fleet:
                    TraceEvent("ShardMapFleetMismatch", severity=30).detail(
                        shards=len(restored_map),
                        map_replication=replication, fleet=fleet).log()
                    restored_map, replication = None, arg_replication
                else:
                    TraceEvent("ShardMapRestored").detail(
                        shards=len(restored_map),
                        replication=replication).log()
        self.replication = replication or n_storage
        self.dd = DataDistributor(
            self.storages, shard_map=restored_map,
            replication=self.replication,
        )
        self._read_rr = itertools.count()  # round-robin read balancing
        self.router = StorageRouter(self.storages, self.dd.map, self._read_rr)
        from foundationdb_tpu.server.changefeed import ChangeFeedRegistry

        self.change_feeds = ChangeFeedRegistry()
        # ── cross-client batching (ref: CommitProxyServer commitBatcher) ──
        # "thread": a daemon batcher collects concurrent commits into
        # shared-version batches (live deployments).
        # "manual": deterministic batching driven by the sim scheduler.
        # "sync": 1-txn batches, the degenerate pipeline.
        self.commit_pipeline = commit_pipeline
        self._commit_batch_max = commit_batch_max
        self._commit_flush_after = commit_flush_after
        self.recruitments = 0  # roles replaced by the failure monitor
        self.n_commit_proxies = n_commit_proxies
        # serializes txn-system recoveries: configure() arrives on an
        # RPC worker thread while the failure monitor ticks on the main
        # thread — two concurrent _recover_txn_system calls would race
        # the generation CAS and tear the frontend swap
        self._recovery_mu = lockdep.lock("Cluster._recovery_mu")
        # ── cluster doctor (server/health.py) ──
        # clock_advance: the simulation's hook — recovery phase marks
        # call it so a simulated recovery consumes simulated time and
        # same-seed runs agree; None in production (real elapsed time)
        self.clock_advance = None
        self.recovery_timeline = health_mod.RecoveryTimeline()
        self.prober = health_mod.LatencyProber(self)
        # ── metrics history + flight recorder (utils/timeseries.py) ──
        # the fourth member of the cluster-owned observability family
        # (registries, heatmaps, device profiles → history rings): the
        # collector samples the stores above each cadence window, so
        # its windows inherit their survive-recovery/absorb-on-shrink
        # semantics and never rewind
        self.history = timeseries_mod.HistoryCollector(self)
        # ── continuous consistency scan (server/consistencyscan.py) ──
        # the fifth cluster-owned subsystem: the background replica
        # auditor's stats ride a cluster-held registry and its cursor
        # persists in \xff/consistencyScan/, so rounds survive both
        # txn-system recoveries and full restarts
        self.scanner = consistencyscan_mod.ConsistencyScanner(self)
        # multi-region replication (server/region.py): None until a
        # region config attaches; the frontend below reads it, so the
        # attribute must exist before _build_txn_frontend
        self.regions = None
        self.commit_proxy, self.grv_proxy = self._build_txn_frontend()
        if recovered_records:
            self._restore_tenant_config()
            # resume the consistency scan where the old incarnation
            # left it (cursor + round count live beside the shard map
            # in the system keyspace) — a restart must not rewind a
            # round that was minutes from completing
            self.scanner.restore_cursor()
        # region config: constructor argument wins; otherwise a
        # recovered \xff/conf/regions row re-attaches replication (the
        # config persists beside the replication factor — `configure
        # regions=...` survives a full restart). Restored attaches
        # re-seed the satellite from the recovered state; only a NEW
        # config writes the system row.
        region_cfg = regions
        if region_cfg is None and recovered_records:
            s0 = self.storages[0]
            row = s0.get(systemdata.CONF_REGIONS, s0.version)
            if row is not None:
                region_cfg = row
        if region_cfg is not None:
            from foundationdb_tpu.server.region import RegionConfig

            self._attach_regions(RegionConfig.parse(region_cfg),
                                 persist=regions is not None)
        # only thread-mode clusters get the background probe loop; sims
        # and sync deployments drive maybe_probe() from their own
        # schedule so determinism is never perturbed
        if commit_pipeline == "thread" and knobs.health_probe_enabled:
            self.prober.start()
        # the history collector follows the prober's driver split: a
        # daemon loop only in thread mode, sim/manual schedules call
        # maybe_collect() themselves
        if commit_pipeline == "thread" and knobs.history_enabled:
            self.history.start()
        # the scanner too: daemon loop XOR sim pump, never both
        if commit_pipeline == "thread" and knobs.consistency_scan_enabled:
            self.scanner.start()

    def _restore_tenant_config(self):
        """Re-apply persisted tenant mode + quotas + lock state after
        recovery (all live in the system keyspace; enforcement is
        proxy/ratekeeper state that died with the old process)."""
        from foundationdb_tpu.core import systemdata
        from foundationdb_tpu.layers.tenant import (
            TENANT_MODE_KEY, TENANT_QUOTA_PREFIX, tenant_tag,
        )

        s0 = self.storages[0]
        lock_row = s0.get(systemdata.DB_LOCKED, s0.version)
        if lock_row is not None:
            self._commit_target().lock_uid = lock_row
        mode_row = s0.get(TENANT_MODE_KEY, s0.version)
        if mode_row is not None:
            self._commit_target().tenant_mode = mode_row.decode()
        for k, v in s0.read_range(
            TENANT_QUOTA_PREFIX, TENANT_QUOTA_PREFIX + b"\xff", s0.version
        ):
            self.ratekeeper.set_tag_quota(
                tenant_tag(k[len(TENANT_QUOTA_PREFIX):]), float(v)
            )

    def _role_registry(self, role, i=0):
        """The persistent (role, index) metrics registry — created on
        first use, reused by every later incarnation of that role."""
        key = (role, i)
        reg = self._metrics_store.get(key)
        if reg is None:
            reg = self._metrics_store[key] = metrics_mod.MetricsRegistry(
                role, index=i
            )
        return reg

    def _role_registries(self, role):
        return [reg for (r, _), reg in sorted(self._metrics_store.items())
                if r == role]

    def _role_heatmap(self, role, i=0, decode=None):
        """The persistent (role, index) heatmap — created on first use,
        reused by every later incarnation of that role (the registry
        accessor's exact twin)."""
        key = (role, i)
        hm = self._heatmap_store.get(key)
        if hm is None:
            hm = self._heatmap_store[key] = heatmap_mod.KeyRangeHeatmap(
                f"{role}:{i}",
                max_buckets=self.knobs.heatmap_max_buckets,
                half_life_s=self.knobs.heatmap_half_life_s,
                decode=decode,
            )
        return hm

    def _role_heatmaps(self, role):
        return [hm for (r, _), hm in sorted(self._heatmap_store.items())
                if r == role]

    def _role_profile(self, i=0):
        """The persistent ("resolver", index) device profile — created
        on first use, reused by every later incarnation of that
        resolver (the registry/heatmap accessors' exact twin)."""
        key = ("resolver", i)
        prof = self._device_store.get(key)
        if prof is None:
            prof = self._device_store[key] = deviceprofile.DeviceProfile(
                "resolver", index=i
            )
        return prof

    def _attach_device_profiles(self):
        """Hand every resolver its cluster-owned DeviceProfile (first
        boot AND txn-system recovery — the resize branch builds brand-
        new instances that would otherwise start blank). A shrinking
        fleet folds the orphaned indices' device history into member 0
        first: dispatch counters never go backwards."""
        n = max(1, len(self.resolvers))
        for (role, i) in list(self._device_store):
            if i >= n:
                self._role_profile(0).absorb(
                    self._device_store.pop((role, i))
                )
        for i, r in enumerate(self.resolvers):
            if hasattr(r, "adopt_profile"):
                r.adopt_profile(self._role_profile(i))

    def _make_commit_proxy(self, resolve_gate=None, log_gate=None, index=0):
        return CommitProxy(
            self.sequencer, self.resolvers, self.tlog, self.storages,
            self.knobs, self.ratekeeper, dd=self.dd,
            change_feeds=self.change_feeds,
            resolve_gate=resolve_gate, log_gate=log_gate,
            regions=getattr(self, "regions", None),
            fanout_profile=self._role_profile(0),
            metrics=self._role_registry("commit_proxy", index),
            heatmap=(
                self._role_heatmap("commit_proxy", index,
                                   decode=heatmap_mod.entry_key)
                if self.knobs.workload_sampling else None
            ),
        )

    def _build_txn_frontend(self):
        """Build the transaction frontend: one commit proxy + GRV proxy
        (the default; sims and single-threaded deployments), or a FLEET
        of ``n_commit_proxies`` of each with sequencer-chained versions
        and ordered pipeline gates (ref: the reference's proxy fleets;
        see server/fleet.py). Used for first boot AND txn-system
        recovery — the two incarnations must never diverge."""
        # a shrinking fleet folds the orphaned indices' metric history
        # into member 0 so cluster totals never go backwards
        n = max(1, self.n_commit_proxies)
        for (role, i) in list(self._metrics_store):
            if role in ("commit_proxy", "grv_proxy") and i >= n:
                self._role_registry(role, 0).absorb(
                    self._metrics_store.pop((role, i))
                )
        for (role, i) in list(self._heatmap_store):
            if role == "commit_proxy" and i >= n:
                # orphaned members' conflict heat folds into member 0:
                # hot-range snapshots never rewind across a shrink
                self._role_heatmap(
                    role, 0, decode=heatmap_mod.entry_key
                ).absorb(self._heatmap_store.pop((role, i)))
        if self.n_commit_proxies <= 1:
            return self._wire_pipeline(self._make_commit_proxy())
        from foundationdb_tpu.server.fleet import GrvFleet, ProxyFleet
        from foundationdb_tpu.server.proxy import VersionGate

        start = self.sequencer.committed_version
        t = self.knobs.gate_timeout_s
        resolve_gate, log_gate = (
            VersionGate(start, timeout=t), VersionGate(start, timeout=t),
        )
        inners, members, grvs = [], [], []
        for i in range(self.n_commit_proxies):
            inner = self._make_commit_proxy(
                resolve_gate=resolve_gate, log_gate=log_gate, index=i
            )
            wrapped, grv = self._wire_pipeline(inner, index=i)
            inners.append(inner)
            members.append(wrapped)
            grvs.append(grv)
        return ProxyFleet(members, inners), GrvFleet(grvs)

    def _inner_proxies(self):
        cp = self.commit_proxy
        if hasattr(cp, "inners"):
            return list(cp.inners)
        return [getattr(cp, "inner", cp)]

    def _wire_pipeline(self, inner, index=0):
        """Wrap a bare CommitProxy + fresh GrvProxy in the configured
        pipeline (one wiring for first boot AND txn-system recovery —
        the two incarnations must never diverge). "thread" batches GRVs
        too (ref: GrvProxyServer's transaction-start batching); the sim
        keeps the synchronous proxy so admission stays deterministic."""
        proxy = inner
        if self.commit_pipeline != "sync":
            from foundationdb_tpu.server.batcher import BatchingCommitProxy

            proxy = BatchingCommitProxy(
                inner, max_batch=self._commit_batch_max,
                flush_after=self._commit_flush_after,
                mode=self.commit_pipeline,
            )
        grv = GrvProxy(self.sequencer, self.ratekeeper,
                       metrics=self._role_registry("grv_proxy", index))
        if self.commit_pipeline == "thread":
            from foundationdb_tpu.server.grv import BatchingGrvProxy

            grv = BatchingGrvProxy(
                grv, interval_s=self.knobs.grv_batch_interval_s,
            )
        return proxy, grv

    def _win_generation(self, recovered):
        """CAS a new recovery generation at the coordinators: read g,
        commit g+1 expecting g — two concurrent recoveries cannot both
        win a slot (the loser re-reads and bids for the next one)."""
        for _ in range(10):
            prior = self.coordination.read_quorum() or {}
            gen = prior.get("generation", 0) + 1
            try:
                self.coordination.write_quorum(
                    {"generation": gen, "recovered_version": recovered},
                    expect_generation=gen - 1,
                )
                return gen
            except GenerationConflict:
                continue
        raise CoordinatorDown("could not win a recovery generation")

    # ── failure detection + recruitment ──────────────────────────────
    # Ref: fdbserver/ClusterController.actor.cpp failureDetectionServer +
    # workerAvailabilityWatch: the controller notices dead role instances
    # and recruits replacements. In-process there is no network heartbeat
    # to miss; "detection" is observing a killed instance's alive flag on
    # the monitor's next round — the same detect-latency shape, minus
    # packet plumbing. The simulation (or an operator loop) pumps
    # ``detect_and_recruit()``.
    def detect_and_recruit(self):
        """One failure-monitor round; returns [(role, index), ...] of
        recruitments performed."""
        events = []
        # whole-primary-region loss comes FIRST: with the sequencer,
        # proxies, storages, and log tier all dead, the ordinary
        # txn-system recovery below cannot even read a log frontier
        # (TLogDown) — the remote region's satellite log is the only
        # surviving durable state, and promotion replaces every primary
        # role in one recovery (ref: ClusterRecovery choosing a remote
        # region when the primary's logs are unrecoverable). A
        # coordination failure mid-failover leaves the roles dead and
        # the NEXT monitor round retries.
        reg = self.regions
        if reg is not None and reg.should_failover(self):
            with self._recovery_mu:
                if reg.should_failover(self):
                    try:
                        self._region_failover()
                    except CoordinatorDown as e:
                        reg.note_failed_attempt(e)
                        return events
                    events.append(("region-failover", 0))
                    self.recruitments += 1
                    TraceEvent("RolesRecruited").detail(
                        events=events).log()
                    return events
        if not self.sequencer.alive or not self._commit_target().alive:
            # a dead sequencer or commit proxy forces a transaction-
            # system recovery: new generation through the coordination
            # CAS, resolvers fenced, fresh sequencer/proxies — WITHOUT
            # touching storage or the logs (ref: ClusterRecovery
            # recruiting a new txn-system generation). Liveness is
            # re-checked under the recovery mutex: a configure() racing
            # on another thread may already have rebuilt the frontend.
            with self._recovery_mu:
                if (not self.sequencer.alive
                        or not self._commit_target().alive):
                    trigger = ("sequencer_failed"
                               if not self.sequencer.alive
                               else "commit_proxy_failed")
                    self._recover_txn_system(trigger=trigger)
                    events.append(("txn-system", 0))
        if isinstance(self.tlog, TLogSystem):
            for i, log in enumerate(self.tlog.logs):
                if not log.alive and self.tlog.revive(i) is not None:
                    events.append(("tlog", i))
        for i, r in enumerate(self.resolvers):
            if not r.alive:
                # fresh resolver with an empty conflict history MUST fence
                # every pre-death read version (it cannot check them), so
                # its window opens at the current committed version —
                # in-flight txns retry with fresh reads (ref: resolver
                # failure forcing a recovery that fences the old epoch).
                # respawn() recruits the instance's own kind (a mesh
                # fleet recruits a mesh fleet).
                self.resolvers[i] = r.respawn(
                    self.sequencer.committed_version
                )
                events.append(("resolver", i))
        for sid, s in enumerate(self.storages):
            if not s.alive:
                self._recruit_storage(sid)
                events.append(("storage", sid))
        if events:
            self.recruitments += len(events)
            TraceEvent("RolesRecruited").detail(events=events).log()
        return events

    def _recover_txn_system(self, new_resolver_lanes=None,
                            trigger="role_failure"):
        """The recovery state machine for dead sequencer/commit-proxy
        roles (ref: fdbserver/ClusterRecovery.actor.cpp): win a new
        generation at the coordinators (CAS), restart the version
        authority above everything the log acked, fence the resolvers
        (their windows open at the recovery version, so pre-death read
        versions retry TOO_OLD), and recruit fresh proxies over the
        SAME storages/logs — data is not torn down or re-ingested.
        ``new_resolver_lanes`` (configure's resize) swaps the resolver
        fleet shape HERE — after the quiesce, never while in-flight
        commits could still resolve against the old history."""
        import contextlib

        # recovery-state timeline (server/health.py): each phase mark
        # closes the phase that just ran; the record lands in the
        # bounded cluster-owned timeline health_status() reports
        rec = self.recovery_timeline.begin(trigger, self.clock_advance)
        old_proxy = self.commit_proxy
        old_inners = self._inner_proxies()
        # Quiesce: mark both roles dead FIRST (future batches answer
        # 1021 at the entry check / SequencerDown guard), then take
        # EVERY old proxy's commit mutex — in-flight batches that
        # already passed the check finish under the OLD generation
        # before we read the log frontier, so every acked commit is
        # covered by ``recovered`` (no acked-but-invisible writes, no
        # overlapping version grants into the shared tlog).
        for p in old_inners:
            p.kill()
        self.sequencer.kill()
        with contextlib.ExitStack() as stack:
            for p in old_inners:
                stack.enter_context(p._commit_mu)
            recovered = max(
                self.tlog.last_version, self.sequencer.committed_version
            )
        rec.phase("fence")
        gen = self.generation = self._win_generation(recovered)
        rec.phase("cas")
        self.sequencer = Sequencer(
            version_clock=self.sequencer.version_clock,
            start_version=recovered,
        )
        # fence conflict history: in-flight txns retry with fresh reads.
        # A resize builds the new shape directly at the recovery version
        # (building earlier would both race in-flight resolution and be
        # discarded by this very fence).
        if new_resolver_lanes is None:
            for i, r in enumerate(self.resolvers):
                self.resolvers[i] = r.respawn(recovered)
        else:
            if self.knobs.resolver_backend == "tpu" \
                    and new_resolver_lanes > 1:
                from foundationdb_tpu.resolver.meshresolver import (
                    MeshResolver,
                )

                # the old fleet's sample, buckets and (at the same
                # lane count) bounds go to the new one: it is fenced
                # anyway, and starts where the old one had got to
                old = self.resolvers[0]
                new = [MeshResolver(
                    self.knobs, base_version=recovered,
                    n_lanes=new_resolver_lanes,
                    heir_of=old if isinstance(old, MeshResolver) else None)]
            else:
                new = [Resolver(self.knobs, base_version=recovered)
                       for _ in range(new_resolver_lanes)]
            # in place: the (old, quiesced) proxies share this list;
            # the new frontend built below re-derives its ranges
            self.resolvers[:] = new
        # every incarnation — respawned or rebuilt — readopts its
        # cluster-owned device profile (shrinks fold orphans first)
        self._attach_device_profiles()
        # the database lock and tenant mode are cluster state, not proxy
        # state: survive the recovery (ref: both living in the system
        # keyspace)
        lock_uid = getattr(old_inners[0], "lock_uid", None)
        tenant_mode = getattr(old_inners[0], "tenant_mode", None)
        old_grv = self.grv_proxy
        self.commit_proxy, self.grv_proxy = self._build_txn_frontend()
        rec.phase("recruit")
        target = self._commit_target()
        if lock_uid is not None:
            target.lock_uid = lock_uid
        if tenant_mode is not None:
            target.tenant_mode = tenant_mode
        target.update_resolver_ranges(fence=False)
        rec.phase("replay")
        if self.commit_pipeline != "sync":
            # queued commits raced the death: resolve them 1021 so
            # their clients retry against the new generation
            old_proxy.fail_pending(err("commit_unknown_result"))
        old_proxy.close()
        if hasattr(old_grv, "close"):
            old_grv.close()
        rec.phase("accept")
        rec.finish(gen, recovered)
        TraceEvent("TxnSystemRecovered").detail(
            generation=gen, version=recovered, trigger=trigger,
            recovery_ms=rec.record["total_ms"]).log()

    def _storage_owns(self, smap, sid, m):
        """Does storage ``sid`` own mutation ``m`` under shard map
        ``smap``? (None = full replication: everyone owns everything;
        the system keyspace replicates everywhere regardless.) Shared
        by storage recruitment and region-failover replay."""
        from foundationdb_tpu.core.mutations import Op

        if smap is None:
            return True
        if m.key >= b"\xff":
            return True  # system keyspace replicates everywhere
        if m.op == Op.CLEAR_RANGE:
            return any(
                sid in smap.teams[i]
                for i in smap.shards_overlapping(m.key, m.param)
            )
        return sid in smap.team_for(m.key)

    def _region_failover(self):
        """Promote the remote region after whole-primary-region loss
        (ref: ClusterRecovery recruiting from a remote region when the
        primary's logs are unrecoverable). The shape is the ordinary
        ``_recover_txn_system`` state machine — same phases, same
        generation CAS, same timeline recorder (trigger
        ``region_failover``) — with two substitutions: the SATELLITE
        log is promoted to be THE log (its frontier, not the dead
        primary tier's, bounds what survives: every acked commit in
        sync satellite mode, acked-minus-measured-lag in async), and
        the storage fleet is rebuilt fresh in the remote region by
        replaying the promoted log from its seed snapshot. Caller holds
        ``_recovery_mu``."""
        import contextlib

        reg = self.regions
        rec = self.recovery_timeline.begin("region_failover",
                                           self.clock_advance)
        old_proxy = self.commit_proxy
        old_inners = self._inner_proxies()
        old_grv = self.grv_proxy
        old_storages = list(self.storages)
        # quiesce (same discipline as _recover_txn_system: dead roles
        # answer 1021 at entry, in-flight batches finish under the old
        # generation before we read the replication frontier)
        for p in old_inners:
            p.kill()
        self.sequencer.kill()
        with contextlib.ExitStack() as stack:
            for p in old_inners:
                stack.enter_context(p._commit_mu)
            frontier = reg.position
        rec.phase("fence")
        # the CAS can raise CoordinatorDown: nothing has been promoted
        # yet, every role is still dead, and the caller counts a failed
        # attempt — the next monitor round retries the whole failover
        gen = self.generation = self._win_generation(frontier)
        rec.phase("cas")
        # the satellite log becomes THE log: full history from the seed
        # snapshot onward, and future commits append to it (after a
        # full process restart the satellite WAL is the durable log)
        self.tlog = reg.promote_log()
        self.sequencer = Sequencer(
            version_clock=self.sequencer.version_clock,
            start_version=frontier,
        )
        # resolvers fence at the frontier exactly like any recovery:
        # pre-disaster read versions retry TOO_OLD
        for i, r in enumerate(self.resolvers):
            self.resolvers[i] = r.respawn(frontier)
        self._attach_device_profiles()
        # fresh storage fleet in the remote region. The primary fleet's
        # engines are LOST with the region (reusing one could carry
        # durable state past the replication frontier); replacements
        # start empty, inherit the cluster-owned metrics/heat so
        # counters never rewind, and swap in place — the dd/router/
        # proxy lists are shared. Fleet shape is unchanged, so the
        # replicated shard map stays valid as-is.
        fresh = []
        for sid, old in enumerate(old_storages):
            new = StorageServer(
                window_versions=(
                    self.knobs.max_read_transaction_life_versions),
            )
            new.region = reg.config.remote
            new.adopt_metrics(old.metrics)
            if self.knobs.workload_sampling:
                new.attach_heatmaps(
                    self._role_heatmap("storage_read", sid),
                    self._role_heatmap("storage_write", sid),
                    self.knobs.storage_sample_every,
                )
            fresh.append(new)
        self.storages[:] = fresh
        for log in (self.tlog.logs if isinstance(self.tlog, TLogSystem)
                    else [self.tlog]):
            log.region = reg.config.remote
        rec.phase("recruit")
        # replay the promoted log from the beginning — record one is
        # the seed snapshot — with the same ownership filter storage
        # recruitment uses, so placement survives the region flip
        smap = self.dd.map if self.replication < len(self.storages) \
            else None
        for sid, new in enumerate(self.storages):
            for version, muts in self.tlog.peek(0):
                if version > new.version:
                    new.apply(
                        version,
                        [m for m in muts
                         if self._storage_owns(smap, sid, m)],
                    )
        self.commit_proxy, self.grv_proxy = self._build_txn_frontend()
        self._commit_target().update_resolver_ranges(fence=False)
        # lock/tenant/quota enforcement re-derives from the replayed
        # system keyspace (the seed + stream carried the rows)
        self._restore_tenant_config()
        rec.phase("replay")
        if self.commit_pipeline != "sync":
            old_proxy.fail_pending(err("commit_unknown_result"))
        old_proxy.close()
        if hasattr(old_grv, "close"):
            old_grv.close()
        for old in old_storages:
            try:
                old.engine.close()
            except Exception as e:
                # a lost region's engine may be gone already, but say so:
                # repeated close failures here would mean leaked redwood
                # files, which the trace is the only way to spot
                TraceEvent("RegionFailoverEngineClose", severity=40).detail(
                    etype=type(e).__name__, error=str(e)[:200]).log()
        # watches parked on dead primary storages wake so clients
        # re-read and re-register against the promoted fleet
        for old in old_storages:
            for key in list(old._watches):
                for w in old._watches.pop(key):
                    w._fire()
        rec.phase("accept")
        rec.finish(gen, frontier)
        reg.note_failover(rec.record["total_ms"])
        TraceEvent("TxnSystemRecovered").detail(
            generation=gen, version=frontier, trigger="region_failover",
            recovery_ms=rec.record["total_ms"]).log()

    def _recruit_storage(self, sid):
        """Replace a dead storage by rebooting onto its durable engine
        and replaying the log from there (ref: a storage process
        rejoining — open the disk store, peek the tlog from the durable
        version). The in-memory MVCC overlay died with the process; the
        tlog covers the gap because the durability pump never pops past a
        dead storage's durable version. The engine object (file handle,
        versioned-ness) carries over, so replacement semantics match its
        peers."""
        old = self.storages[sid]
        new = StorageServer(
            window_versions=self.knobs.max_read_transaction_life_versions,
            engine=old.engine,
        )
        new.adopt_metrics(old.metrics)  # counters survive recruitment
        if self.knobs.workload_sampling:
            # same objects as the dead instance held (cluster-owned):
            # per-shard read/write heat survives recruitment
            new.attach_heatmaps(
                self._role_heatmap("storage_read", sid),
                self._role_heatmap("storage_write", sid),
                self.knobs.storage_sample_every,
            )
        smap = self.dd.map if self.replication < len(self.storages) else None
        for version, muts in self.tlog.peek(new.version):
            new.apply(
                version,
                [m for m in muts if self._storage_owns(smap, sid, m)],
            )
        new.region = getattr(old, "region", None)  # placement tag carries
        self.storages[sid] = new  # lists are shared: router/proxy/dd see it
        # watches parked on the dead instance wake so clients re-read and
        # re-register against the replacement
        for key in list(old._watches):
            for w in old._watches.pop(key):
                w._fire()

    def close(self):
        """Release background machinery (batcher threads, thread pools)
        and durable handles."""
        self.scanner.stop()
        self.prober.stop()
        self.history.stop()
        if self.regions is not None:
            self.regions.close()
        if hasattr(self.grv_proxy, "close"):
            self.grv_proxy.close()
        if hasattr(self.commit_proxy, "close"):
            self.commit_proxy.close()
        for s in self.storages:
            s.engine.close()
        self.tlog.close()

    # v1: single storage team holding the whole keyspace; reads go to [0].
    @property
    def storage(self):
        return self.storages[0]

    def read_storage(self, key=b""):
        """The read-side storage surface: the router resolves each read's
        key (or range) to its shard's team and load-balances across the
        replicas (ref: NativeAPI getKeyLocation + LoadBalance)."""
        return self.router

    # monotone shard-map epoch: bumped on every rebalance so tag-scoped
    # storage workers learn of ownership moves from peek replies instead
    # of polling the map (rpc/storageworker.py)
    shard_epoch = 0

    def rebalance(self):
        """One data-distribution round (splits/merges/moves), then
        persist the new map in the system keyspace and re-derive the
        resolver key ranges from it."""
        moves = self.dd.rebalance()
        self.shard_epoch += 1
        self.persist_shard_map()
        self.commit_proxy.update_resolver_ranges()
        return moves

    def exclude_storage(self, sid):
        """Begin draining a storage (ref: fdbcli exclude → the excluded-
        servers system key → DD relocating its shards). Reads stop
        routing new work there once its last shard moves; poll
        ``storage_drained`` to learn when removal is safe."""
        self.dd.excluded.add(sid)
        return self.rebalance()

    def include_storage(self, sid):
        """Cancel an exclusion (ref: fdbcli include)."""
        self.dd.excluded.discard(sid)

    def list_excluded(self):
        return sorted(self.dd.excluded)

    def connection_string(self):
        """What \\xff\\xff/connection_string reports for an in-process
        cluster (a remote client reports its cluster-file body)."""
        return "local"

    def storage_drained(self, sid):
        return self.dd.storage_owns_nothing(sid)

    def storage_owned_ranges(self, sid):
        """The key ranges storage ``sid``'s tag covers (merged, plus the
        everywhere-replicated system keyspace) — what a tag-scoped
        storage worker bootstraps and serves (ref: the keyServers
        ranges a storage's tag subscribes it to)."""
        end_cap = b"\xff\xff"
        if self.replication >= len(self.storages):
            return [(b"", end_cap)]
        smap = self.dd.map
        owned = []
        for i in range(len(smap)):
            if sid in smap.teams[i]:
                b, e = smap.shard_range(i)
                owned.append((b, e if e is not None else b"\xff"))
        owned.sort()
        merged = []
        for b, e in owned:
            if merged and b <= merged[-1][1]:
                merged[-1][1] = max(merged[-1][1], e)
            else:
                merged.append([b, e])
        merged.append([b"\xff", end_cap])  # system keyspace: everywhere
        return [tuple(r) for r in merged]

    def estimated_range_size_bytes(self, begin, end):
        """Ref: fdb_transaction_get_estimated_range_size_bytes — the
        DD's sampled per-shard byte counts, prorated for the partially
        covered boundary shards (same sampling-based estimate the
        reference serves from storage metrics)."""
        smap = self.dd.map
        total = 0
        for i in smap.shards_overlapping(begin, end):
            sb, se = smap.shard_range(i)
            size = smap.sizes[i]
            if size == 0:
                continue
            if sb >= begin and (se is not None and se <= end):
                total += size  # fully covered
            else:
                # boundary shard: prorate by covered key count in ONE
                # streamed pass (bounded: DD splits shards at
                # max_shard_bytes). Replica choice rides the router's
                # load-balanced pick, raising retryable when the whole
                # team is down like every other read path.
                owner = self.router._pick(smap.teams[i])
                lo = max(begin, sb)
                hi = se if se is not None else b"\xff\xff"
                hi = min(end, hi)
                shard_end = se if se is not None else b"\xff\xff"
                n_all = n_cov = 0
                for k, _ in owner._iter_live(sb, shard_end, owner.version):
                    n_all += 1
                    if lo <= k < hi:
                        n_cov += 1
                total += size * n_cov // max(n_all, 1)
        return total

    def range_split_points(self, begin, end, chunk_size):
        """Ref: fdb_transaction_get_range_split_points — keys splitting
        [begin, end) into chunks of roughly chunk_size bytes, derived
        from an owning storage's actual rows. Returns boundary keys
        including begin and end."""
        if chunk_size <= 0:
            raise err("invalid_option_value")
        if begin > end:
            raise err("inverted_range")
        version = self.sequencer.committed_version
        points = [begin]
        acc = 0
        # stream shard by shard (router-picked live replica each) —
        # never materialize the whole range's rows server-side
        smap = self.dd.map
        for i in smap.shards_overlapping(begin, end):
            sb, se = smap.shard_range(i)
            lo = max(begin, sb)
            hi = min(end, se) if se is not None else end
            owner = self.router._pick(smap.teams[i])
            for k, v in owner._iter_live(lo, hi, min(version, owner.version)):
                acc += len(k) + len(v or b"")
                if acc >= chunk_size and k != points[-1]:
                    points.append(k)  # strictly increasing boundaries
                    acc = 0
        points.append(end)
        return points

    def _commit_target(self):
        """The proxy that actually runs commit_batch (unwrap the
        batching pipeline wrapper) — lock state lives there."""
        return getattr(self.commit_proxy, "inner", self.commit_proxy)

    def resolver_lanes(self):
        return sum(getattr(r, "n_lanes", 1) for r in self.resolvers)

    def configure(self, commit_proxies=None, resolvers=None,
                  regions=None):
        """Live reconfiguration (ref: fdbcli `configure proxies=N
        resolvers=N regions=<json>` → ManagementAPI changeConfig
        forcing a recovery): resizing the commit-proxy fleet, the
        resolver fleet, or the region configuration rides the ordinary
        txn-system recovery — a new generation with the new shape over
        the same storage and logs; in-flight clients ride it out on
        retryable errors. New resolvers open FENCED at the committed
        version (their empty conflict history cannot check older read
        versions), exactly like recovery's respawn. ``regions`` takes a
        RegionConfig / dict / JSON string (validated BEFORE the fencing
        recovery — a typo must not bounce the txn system), or
        ``"off"``/``{}`` to detach replication; the satellite attaches
        AFTER the recovery, against the fresh frontend, and the config
        persists in the \\xff/conf/regions system row."""
        from foundationdb_tpu.server.region import RegionConfig

        for v in (commit_proxies, resolvers):
            if v is not None and int(v) < 1:
                raise err("invalid_option_value")
        region_off = regions in ("off", b"off", "", {})
        new_region_cfg = None
        if regions is not None and not region_off:
            new_region_cfg = RegionConfig.parse(regions)
        with self._recovery_mu:
            changed = False
            lanes = None
            region_change = False
            if (commit_proxies is not None
                    and int(commit_proxies) != self.n_commit_proxies):
                self.n_commit_proxies = int(commit_proxies)
                changed = True
            if resolvers is not None:
                # compare against what was REQUESTED, not what the
                # hardware achieved: the mesh clamps lanes to the
                # device count, and a management loop re-applying its
                # desired config must not force a fencing recovery on
                # every pass
                current = getattr(self, "_requested_resolver_lanes",
                                  None) or self.resolver_lanes()
                if int(resolvers) != current:
                    lanes = int(resolvers)
                    self._requested_resolver_lanes = lanes
                    changed = True
            if regions is not None:
                # same no-op discipline as the resolver compare: a
                # management loop re-applying its desired region config
                # must not re-seed the satellite every pass
                if region_off:
                    region_change = self.regions is not None
                else:
                    region_change = (
                        self.regions is None
                        or self.regions.config != new_region_cfg
                    )
                changed = changed or region_change
            if changed:
                self._recover_txn_system(new_resolver_lanes=lanes,
                                         trigger="configure")
            if region_change:
                if new_region_cfg is None:
                    self._detach_regions()
                else:
                    self._attach_regions(new_region_cfg, persist=True)
        shape = {"commit_proxies": self.n_commit_proxies,
                 "resolver_lanes": self.resolver_lanes()}
        # only a region-touching configure reports the region shape, so
        # proxy/resolver resizes keep their seed-era return contract
        if regions is not None:
            shape["regions"] = (self.regions.config.to_json()
                                if self.regions is not None else None)
        return shape

    def _attach_regions(self, config, persist=True):
        """Install the RegionReplicator for ``config``: satellite log
        at ``<wal_path>.satellite`` (in-memory when the cluster is),
        region tags stamped on the primary's tlog replicas and
        storages, the live proxies handed the replicator for sync-mode
        commit gating, and — in thread pipelines — the continuous
        streamer started. ``persist`` writes the \\xff/conf/regions
        system row (False on restart-restore: the row is already
        durable)."""
        from foundationdb_tpu.server.region import RegionReplicator

        if self.regions is not None:
            self.regions.drop()
            self.regions.close()
        wal = getattr(self.tlog, "wal_path", None)
        self.regions = RegionReplicator(
            self, config,
            wal_path=f"{wal}.satellite" if wal else None,
        )
        # region-tagged placement: every primary role carries the
        # primary region id (the replicator stamped its satellite
        # replicas with the remote id); recruitment carries the tags to
        # replacements
        for s in self.storages:
            s.region = config.primary
        for log in (self.tlog.logs if isinstance(self.tlog, TLogSystem)
                    else [self.tlog]):
            log.region = config.primary
        for p in self._inner_proxies():
            p.regions = self.regions
        if persist:
            self._persist_region_config()
        if self.commit_pipeline == "thread":
            self.regions.start()
        return self.regions

    def _detach_regions(self):
        """``configure regions=off``: release the primary-log pin, stop
        the streamer, close the satellite, clear the placement tags,
        and clear the persisted system row."""
        reg, self.regions = self.regions, None
        if reg is not None:
            reg.drop()
            reg.close()
        for s in self.storages:
            s.region = None
        for log in (self.tlog.logs if isinstance(self.tlog, TLogSystem)
                    else [self.tlog]):
            log.region = None
        for p in self._inner_proxies():
            p.regions = None
        self._persist_region_config()

    def _persist_region_config(self):
        """Write (or clear) the \\xff/conf/regions row through the
        normal commit pipeline — tlog-durable, restored by WAL recovery
        like the shard map, and streamed to the satellite so a promoted
        region knows its own region config. Best-effort like
        persist_shard_map."""
        from foundationdb_tpu.core import systemdata
        from foundationdb_tpu.core.mutations import Mutation, Op
        from foundationdb_tpu.server.proxy import CommitRequest

        if self.regions is not None:
            muts = [Mutation(
                Op.SET, systemdata.CONF_REGIONS,
                self.regions.config.to_json().encode(),
            )]
        else:
            muts = [Mutation(Op.CLEAR, systemdata.CONF_REGIONS)]
        req = CommitRequest(
            read_version=self.sequencer.committed_version,
            mutations=muts, read_conflict_ranges=[],
            write_conflict_ranges=[],
        )
        result = self.commit_proxy.commit(req)
        return not isinstance(result, Exception)

    def lock_database(self, uid=b"lock"):
        """Ref: ManagementAPI lockDatabase — commits from transactions
        without the lock_aware option fail 1038 until unlocked. The uid
        persists as the \\xff/dbLocked system row (ref:
        databaseLockedKey) so the lock survives WAL recovery and rides
        the DR seed/stream; enforcement stays at the proxy."""
        from foundationdb_tpu.core import systemdata

        uid = bytes(uid)

        def txn(tr):
            tr.options.set_lock_aware()
            # ref: lockDatabase reads databaseLockedKey first — locking
            # over ANOTHER operator's lock throws 1038 instead of
            # silently replacing it (same-uid lock is an idempotent
            # no-op); the read's conflict range serializes racing lockers
            held = tr.get(systemdata.DB_LOCKED)
            if held is not None and held != uid:
                raise err("database_locked")
            if held is None:
                tr.set(systemdata.DB_LOCKED, uid)

        self.database().run(txn)
        self._commit_target().lock_uid = uid

    def unlock_database(self):
        from foundationdb_tpu.core import systemdata

        def txn(tr):
            tr.options.set_lock_aware()
            tr.clear(systemdata.DB_LOCKED)

        self.database().run(txn)
        self._commit_target().lock_uid = None

    def lock_uid(self):
        return getattr(self._commit_target(), "lock_uid", None)

    def set_tenant_mode(self, mode):
        """Live enforcement switch (TenantManagement persists the system
        row; this flips the proxy's structural check)."""
        self._commit_target().tenant_mode = mode

    def tenant_mode(self):
        return getattr(self._commit_target(), "tenant_mode", "optional")

    def set_tag_quota(self, tag, tps):
        """Operator per-tag rate limit (tenant quotas ride this)."""
        self.ratekeeper.set_tag_quota(tag, tps)

    # ── distributed tracing config (utils/span.py) ──
    TRACING_DEFAULT_RATE = 0.01  # `tracing on` without an explicit rate

    def tracing_config(self):
        k = self.knobs
        return {"enabled": k.tracing_sample_rate > 0,
                "sample_rate": k.tracing_sample_rate,
                "slow_commit_ms": k.tracing_slow_commit_ms}

    def set_tracing(self, sample_rate=None, enabled=None):
        """Live tracing reconfiguration (fdbcli `tracing`, the
        \\xff\\xff/tracing/ special keys): swaps the cluster's knobs for
        a copy with the new sample rate — the shared DEFAULT_KNOBS
        object is never mutated, and new transactions (which resolve
        knobs per reset through the Database) pick it up immediately."""
        k = self.knobs
        if enabled is not None:
            if enabled:
                sample_rate = (k.tracing_sample_rate
                               if k.tracing_sample_rate > 0
                               else self.TRACING_DEFAULT_RATE)
            else:
                sample_rate = 0.0
        if sample_rate is None:
            return self.tracing_config()
        rate = float(sample_rate)
        if not 0.0 <= rate <= 1.0:
            raise err("invalid_option_value")
        self.knobs = dataclasses.replace(k, tracing_sample_rate=rate)
        # live proxies hold their construction-time knobs reference
        # (slow-window promotion reads the rate there): hand them the
        # new object. Sim fault wrappers shadow this harmlessly — sims
        # configure tracing at construction.
        for p in self._inner_proxies():
            p.knobs = self.knobs
        TraceEvent("TracingConfigured").detail(sample_rate=rate).log()
        return self.tracing_config()

    def consistency_check(self, max_keys_per_shard=None):
        """Replica agreement audit (ref: the ConsistencyCheck workload /
        fdbcli consistencycheck). Returns error strings; [] = clean."""
        from foundationdb_tpu.server.consistency import consistency_check

        return consistency_check(self, max_keys_per_shard)

    def persist_shard_map(self):
        """Write the live shard map to \\xff/keyServers/ through the
        normal commit pipeline — tlog-durable, recovered like user data
        (ref: keyServers commits in SystemData.cpp). Best-effort: a
        failed system commit (fault injection, log quorum loss) leaves
        the previous persisted map; the next round retries."""
        from foundationdb_tpu.core import systemdata
        from foundationdb_tpu.core.mutations import Mutation, Op
        from foundationdb_tpu.server.proxy import CommitRequest

        muts = [Mutation(Op.CLEAR_RANGE, systemdata.KEY_SERVERS_PREFIX,
                         systemdata.KEY_SERVERS_END)]
        muts += [
            Mutation(Op.SET, k, v)
            for k, v in systemdata.encode_shard_map(self.dd.map)
        ]
        muts.append(Mutation(
            Op.SET, systemdata.CONF_REPLICATION,
            str(self.replication).encode(),
        ))
        req = CommitRequest(
            read_version=self.sequencer.committed_version,
            mutations=muts, read_conflict_ranges=[],
            write_conflict_ranges=[],
        )
        result = self.commit_proxy.commit(req)
        return not isinstance(result, Exception)

    def database(self):
        from foundationdb_tpu.txn.database import Database

        return Database(self)

    def _metacluster_status(self):
        """This cluster's metacluster membership (ref: the metacluster
        section of status json): management/data role + name from the
        registration row, or cluster_type "standalone"."""
        import json as _json

        from foundationdb_tpu.layers.metacluster import REGISTRATION_KEY

        s0 = next((s for s in self.storages if s.alive), None)
        if s0 is None:
            # membership is UNREADABLE, not absent — claiming
            # "standalone" with every storage dead would lie to an
            # operator about a registered cluster
            return {"cluster_type": "unknown"}
        try:
            row = s0.get(REGISTRATION_KEY, s0.version)
        except FDBError:
            # a kill raced past the alive check: status() reports
            # chaos as data, it never raises
            return {"cluster_type": "unknown"}
        if row is None:
            return {"cluster_type": "standalone"}
        meta = _json.loads(row)
        return {"cluster_type": f"metacluster_{meta['role']}",
                "name": meta.get("name")}

    def _sum_counter(self, role, name):
        return sum(
            reg.counter(name).value for reg in self._role_registries(role)
        )

    def metrics_status(self):
        """The aggregated metrics section of the status document (ref:
        Status.actor.cpp folding every role's stats into one json):
        cluster-level latency rollups — merged across the role fleets —
        plus hottest-stage attribution for the commit pipeline."""
        commit_regs = self._role_registries("commit_proxy")
        grv_regs = self._role_registries("grv_proxy")
        commit = metrics_mod.merged_bands_ms(
            [r.get_latency("commit_e2e") for r in commit_regs]
        )
        grv = metrics_mod.merged_bands_ms(
            [r.get_latency("grv_grant") for r in grv_regs]
        )
        logs = self.tlog.logs if isinstance(self.tlog, TLogSystem) \
            else [self.tlog]
        push = metrics_mod.merged_bands_ms(
            [l.metrics.get_latency("tlog_push") for l in logs]
        )
        apply_ = metrics_mod.merged_bands_ms(
            [s.metrics.get_latency("storage_apply") for s in self.storages]
        )
        # multiplexed read serving (txn/futures.py ReadBatcher →
        # StorageServer.read_batch): serve-latency bands plus the
        # reads-per-RPC histogram — read_batch_keys records len(ops)/1e3
        # so its ms-scaled bands read back as raw batch sizes
        rbatch = metrics_mod.merged_bands_ms(
            [s.metrics.get_latency("read_batch") for s in self.storages]
        )
        rkeys = metrics_mod.merged_bands_ms(
            [s.metrics.get_latency("read_batch_keys") for s in self.storages]
        )
        read_batches = sum(
            s.metrics.counter("read_batches").value for s in self.storages)
        batched_reads = sum(
            s.metrics.counter("batched_reads").value for s in self.storages)
        # hottest-stage attribution: the commit-pipeline stage with the
        # most TOTAL wall time across the fleet is the critical path an
        # operator should look at first
        # (the backlog route's four, and the serial route's six —
        # commit_batch, their parent, would always win and is left out)
        stage_totals = {}
        for reg in commit_regs:
            for stage in ("pack", "dispatch", "resolve", "apply",
                          "commit_build", "commit_resolve",
                          "commit_assemble", "commit_log_push",
                          "commit_storage_apply", "commit_report"):
                s = reg.get_latency(f"stage_{stage}")
                if s is not None and s.count:
                    stage_totals[stage] = (
                        stage_totals.get(stage, 0.0) + s.total_seconds()
                    )
        hottest = max(stage_totals, key=stage_totals.get) \
            if stage_totals else None
        return {
            "rollups": {
                "commit_latency_p50_ms": commit["p50_ms"],
                "commit_latency_p99_ms": commit["p99_ms"],
                "commit_latency_max_ms": commit["max_ms"],
                "commit_spans": commit["count"],
                "grv_latency_p99_ms": grv["p99_ms"],
                "tlog_push_p99_ms": push["p99_ms"],
                "storage_apply_p99_ms": apply_["p99_ms"],
                # batched-read observability: serve latency, batch-size
                # percentiles (reads-per-RPC), and the coalesce rate
                # (mean reads each batch RPC carried)
                "read_batch_p99_ms": rbatch["p99_ms"],
                "read_batch_size_p50": round(rkeys["p50_ms"], 1),
                "read_batch_size_p99": round(rkeys["p99_ms"], 1),
                "read_batches": read_batches,
                "batched_reads": batched_reads,
                "read_batch_coalesce_rate": round(
                    batched_reads / max(read_batches, 1), 2),
                "hottest_stage": hottest,
                "hottest_stage_totals_s": {
                    k: round(v, 6) for k, v in stage_totals.items()
                },
                # conflict repair + abort-aware scheduling outcomes
                # (txn/repair.py, server/scheduler.py): counted on the
                # commit-proxy registries — client repairs land on the
                # registry of the proxy the client talks to, scheduler
                # decisions on the proxy that reordered the batch
                "repair_attempts": self._sum_counter(
                    "commit_proxy", "repair_attempts"),
                "repair_commits": self._sum_counter(
                    "commit_proxy", "repair_commits"),
                "repair_fallbacks": self._sum_counter(
                    "commit_proxy", "repair_fallbacks"),
                "sched_reordered": self._sum_counter(
                    "commit_proxy", "sched_reordered"),
                "sched_deferred": self._sum_counter(
                    "commit_proxy", "sched_deferred"),
            },
            "commit_latency_bands": commit,
            "grv_latency_bands": grv,
        }

    def _tag_rollup(self):
        """Per-tag outcome totals folded across the role fleets (the
        registries hold ``tag_{outcome}_{tag}`` counters), plus the
        ratekeeper's last-window busyness gauge."""
        out = {}
        scans = (
            ("commit_proxy", "tag_committed_", "committed"),
            ("commit_proxy", "tag_conflicted_", "conflicted"),
            ("commit_proxy", "tag_too_old_", "too_old"),
            ("grv_proxy", "tag_started_", "started"),
        )
        snaps = {
            role: [r.snapshot()["counters"] for r in
                   self._role_registries(role)]
            for role in ("commit_proxy", "grv_proxy")
        }
        for role, prefix, field in scans:
            for counters in snaps[role]:
                for name, v in counters.items():
                    if name.startswith(prefix):
                        row = out.setdefault(name[len(prefix):], {})
                        row[field] = row.get(field, 0) + v
        for tag, busy in self.ratekeeper.tag_busyness.items():
            out.setdefault(tag, {})["busyness"] = busy
        # live admission limits (AIMD + standalone busyness throttle +
        # operator quotas): what GRV is actually enforcing per tag
        for tag, tps in self.ratekeeper.throttled_tags().items():
            out.setdefault(tag, {})["limit_tps"] = round(tps, 2)
        return {t: out[t] for t in sorted(out)}

    def hot_ranges_status(self, top=None):
        """The workload-attribution document (``metrics hot`` RPC /
        \\xff\\xff/metrics/hot_ranges / cluster.workload): fleet-merged
        conflict/read/write hot ranges — each a bounded decayed
        key-range histogram — plus the per-tag rollup. ``top`` keeps
        only the N hottest ranges per dimension."""
        k = self.knobs
        dims = {
            "conflict": heatmap_mod.merged(
                self._role_heatmaps("commit_proxy"), name="conflict",
                max_buckets=k.heatmap_max_buckets,
                half_life_s=k.heatmap_half_life_s,
                decode=heatmap_mod.entry_key,
            ),
            "read": heatmap_mod.merged(
                self._role_heatmaps("storage_read"), name="read",
                max_buckets=k.heatmap_max_buckets,
                half_life_s=k.heatmap_half_life_s,
            ),
            "write": heatmap_mod.merged(
                self._role_heatmaps("storage_write"), name="write",
                max_buckets=k.heatmap_max_buckets,
                half_life_s=k.heatmap_half_life_s,
            ),
        }
        return {
            "sampling": bool(k.workload_sampling) and heatmap_mod.enabled(),
            "hot_ranges": {
                name: hm.snapshot(top=top) for name, hm in dims.items()
            },
            "totals": {
                name: {"heat": round(hm.total_heat(), 4),
                       "charges": hm.charges}
                for name, hm in dims.items()
            },
            "tags": self._tag_rollup(),
        }

    def device_profile_status(self):
        """The device-path execution profile document (``device_profile``
        RPC / \\xff\\xff/metrics/device / cluster.device): per-resolver
        dispatch accounting — pad/bucket occupancy, compile-cache
        events, staging reuse, transfer bytes, per-lane walls — plus a
        cluster aggregate, all from the cluster-owned store so the doc
        survives recoveries and configure(); the devices the live
        resolvers' history sits on; and, in a process started through
        an entry point, its XLA build counts."""
        profs = [p for (_, _), p in sorted(self._device_store.items())]
        log = deviceprofile.compile_log()
        return {
            "enabled": deviceprofile.enabled(),
            **deviceprofile.placement(self.resolvers),
            "compile": log.snapshot() if log is not None else None,
            "resolvers": [p.snapshot() for p in profs],
            "aggregate": deviceprofile.merged_snapshot(profs),
        }

    def lock_wait_status(self):
        """``cluster.locks``: the counted acquisitions
        (``utils/lockdep.counted``) of the three mutexes a served request
        or a batch waits at, each summed over its role's live instances
        here, when status is built: ``acquisitions``, ``blocked`` (those
        that had to wait) and ``wait_us`` (for how long, in all).
        Integers, like ``cluster.rpc``. A recruited instance starts at 0."""
        roles = {
            "storage_mu_read": (self.storages, "_mu_read"),
            "storage_mu_apply": (self.storages, "_mu_apply"),
            "commit_mu": (self._inner_proxies(), "_commit_mu_counted"),
            "grv_lock": ([self.grv_proxy], "_lock_counted"),
        }
        return {
            name: lockdep.sum_counted(
                st for st in (getattr(o, attr, None) for o in owners)
                if st is not None)
            for name, (owners, attr) in roles.items()}

    def health_status(self):
        """The ``cluster.health`` document (``health`` RPC /
        \\xff\\xff/status/health / fdbcli doctor / tools/doctor.py):
        doctor verdict + reasons + FDB-style messages, probe latency
        bands, the recovery timeline, and the lag/saturation rollups —
        a pure read (no probe fires here)."""
        return health_mod.build_health(self)

    def history_status(self):
        """The metrics-history document (``history`` RPC /
        \\xff\\xff/metrics/history / fdbcli history / cluster.history):
        bounded per-metric rings of fixed-cadence windows — counter
        rates, gauge trajectories, latency-band p99 trajectories, heat
        totals, and the verdict timeline — plus the flight recorder's
        summary. A pure read: no window is cut here."""
        return self.history.status()

    def flight_status(self):
        """The flight-recorder document (``flight`` RPC /
        \\xff\\xff/status/flight / tools/flight.py): the black box's
        dump summary plus the newest retained artifact (None until a
        verdict transition, recovery, or probe-SLO breach has fired)."""
        return {**self.history.recorder.summary(),
                "artifact": self.history.recorder.latest()}

    def consistency_scan_status(self):
        """The continuous consistency-scan document
        (``consistency_scan`` RPC / \\xff\\xff/status/consistency_scan
        / fdbcli scan status): round, progress, bytes/keys scanned,
        and confirmed inconsistencies — a pure read (no batch runs
        here)."""
        return self.scanner.status()

    def set_consistency_scan(self, on):
        """Flip the scanner's module kill switch (fdbcli scan on|off /
        the set_consistency_scan RPC). The scan document stays readable
        either way; returns it so callers see the new state."""
        consistencyscan_mod.set_enabled(bool(on))
        return self.consistency_scan_status()

    def _trace_status(self):
        """The trace/span pipeline's own health: per-type suppression
        (satellite of flow/Trace.cpp event suppression) and the tracing
        config + span gauges (utils/span.py)."""
        from foundationdb_tpu.utils import span as span_mod
        from foundationdb_tpu.utils.trace import global_trace_log

        log = global_trace_log()
        return {
            "suppressed_events": log.suppressed_events,
            "suppressed_by_type": dict(log.suppressed_by_type),
            "tracing": self.tracing_config(),
            "spans_sampled": span_mod.spans_sampled(),
            "spans_emitted": span_mod.spans_emitted(),
        }

    def status(self):
        """Cluster status summary (ref: fdbcli status json, Status.actor.cpp
        — processes/roles breakdown, qos, data, recovery state)."""
        rk = self.ratekeeper
        live_storages = sum(1 for s in self.storages if s.alive)
        tlog_info = {"count": 1, "live": 1, "quorum": 1, "replicated": False}
        if isinstance(self.tlog, TLogSystem):
            tlog_info = {
                "count": self.tlog.n,
                "live": self.tlog.live_count,
                "quorum": self.tlog.quorum,
                "replicated": True,
            }
        degraded = (
            live_storages < len(self.storages)
            or tlog_info["live"] < tlog_info["count"]
            or any(not r.alive for r in self.resolvers)
        )
        hot = self.hot_ranges_status()
        return {
            "cluster": {
                "generation": self.generation,
                "coordinators": len(self.coordination.coordinators),
                "data": {
                    "shards": len(self.dd.map),
                    "team_bytes": self.dd.team_bytes(),
                    "replication_factor": self.replication,
                    "moving_data": False,
                },
                "database_available": live_storages > 0,
                "database_lock_state": _lock_state(self.lock_uid()),
                # multi-region replication (server/region.py): config +
                # live replication state, always present so operators
                # and tools never branch on a missing key
                "regions": (self.regions.status()
                            if self.regions is not None
                            else {"configured": False}),
                "metacluster": self._metacluster_status(),
                "change_feeds": len(self.change_feeds),
                "degraded": degraded,
                "recruitments": self.recruitments,
                "qos": {
                    "transactions_per_second_limit": rk.target_tps,
                    "batch_transactions_per_second_limit": (
                        rk.target_tps * rk.batch_priority_fraction
                    ),
                    "throttled_count": rk.throttled_count,
                    "throttled_tags": rk.throttled_tags(),
                    "tag_throttled_count": rk.tag_throttled_count,
                },
                "workload": {
                    # counters come from the cluster-held registries, so
                    # they SURVIVE txn-system recoveries (the live
                    # proxies' own attrs reset with each incarnation)
                    "transactions": {
                        "committed": {"counter": self._sum_counter(
                            "commit_proxy", "txn_committed")},
                        "conflicted": {"counter": self._sum_counter(
                            "commit_proxy", "abort_not_committed")
                            + self._sum_counter(
                                "commit_proxy", "abort_transaction_too_old")},
                        "started": {"counter": self._sum_counter(
                            "grv_proxy", "grv_grants")},
                    },
                    # workload attribution: WHICH keys/tags the traffic
                    # above actually hit (utils/heatmap.py)
                    "hot_ranges": hot["hot_ranges"],
                    "hot_range_totals": hot["totals"],
                    "tags": hot["tags"],
                },
                "metrics": self.metrics_status(),
                # cluster doctor (server/health.py): verdict + reasons +
                # messages + probe bands + recovery timeline + lag
                # rollups — what fdbcli doctor and tools/doctor.py read
                "health": self.health_status(),
                # device-path execution profile (utils/deviceprofile.py):
                # the resolver dispatch layer's pad/bucket/fallback
                # accounting, cluster-owned like metrics/heatmaps above
                "device": self.device_profile_status(),
                # blocked acquisitions and wait of storage's mutex, the
                # commit mutex and the GRV lock (utils/lockdep.counted)
                "locks": self.lock_wait_status(),
                # metrics history (utils/timeseries.py): the retention
                # layer's full doc — bounded per-metric windows, the
                # verdict timeline, and the flight-recorder summary —
                # so status-file consumers (tools/doctor.py --trend)
                # see trajectories without a second RPC
                "history": self.history_status(),
                # continuous consistency scan (consistencyscan.py):
                # the background auditor's round/progress/verdict —
                # the machine-checkable "is the data still consistent"
                # instrument the sim swarm and doctor read
                "consistency_scan": self.consistency_scan_status(),
                # observability plumbing health: process-wide (cumulative
                # across incarnations, so kept OUT of the deterministic
                # per-cluster metrics section) — the trace sink's
                # suppression counters and the span pipeline's gauges
                "trace": self._trace_status(),
                "latest_version": self.sequencer.committed_version,
                "oldest_readable_version": self.storage.oldest_version,
                "commit_pipeline": self.commit_pipeline,
                "processes": {
                    "sequencer": {"alive": self.sequencer.alive},
                    "commit_proxy": {"alive": self._commit_target().alive,
                                     "count": self.n_commit_proxies,
                                     "members": [
                                         p.status()
                                         for p in self._inner_proxies()
                                     ]},
                    "grv_proxies": [
                        {"id": reg.index, "metrics": reg.snapshot()}
                        for reg in self._role_registries("grv_proxy")
                    ],
                    "resolvers": [
                        {"id": i, "alive": r.alive,
                         "backend": self.knobs.resolver_backend,
                         "lanes": getattr(r, "n_lanes", 1),
                         # "range" = single-dispatch presharded mesh,
                         # "hash" = replicated-batch mesh, "local" =
                         # single-lane / host resolvers
                         "sharding": getattr(r, "sharding", "local"),
                         # a range-sharded mesh's n − 1 lane bounds
                         "lane_bounds": r.lane_bounds()
                         if hasattr(r, "lane_bounds") else [],
                         "metrics": r.metrics.snapshot()}
                        for i, r in enumerate(self.resolvers)
                    ],
                    "storage_servers": [
                        {
                            "id": i,
                            "alive": s.alive,
                            "durable_version": s.durable_version,
                            "oldest_version": s.oldest_version,
                            "versioned_engine": s.versioned_engine,
                            "metrics": s.status()["metrics"],
                        }
                        for i, s in enumerate(self.storages)
                    ],
                    "logs": {
                        **tlog_info,
                        "replicas": (
                            self.tlog.status()
                            if isinstance(self.tlog, TLogSystem)
                            else [self.tlog.status()]
                        ),
                    },
                    "ratekeeper": self.ratekeeper.status(),
                },
                "resolvers": sum(
                    getattr(r, "n_lanes", 1) for r in self.resolvers
                ),
                "resolver_backend": self.knobs.resolver_backend,
                "storage_servers": len(self.storages),
            }
        }
