"""Knobs — tunable constants, mirroring flow/Knobs.h / fdbclient/Knobs.h.

The headline knob is ``resolver_backend``: ``"tpu"`` routes conflict
detection through the JAX kernel (ops/conflict.py); ``"cpu"`` uses the
SkipList-style host ConflictSet (resolver/skiplist.py), matching the
reference's default path.
"""

import dataclasses


@dataclasses.dataclass
class Knobs:
    # --- resolver ---
    resolver_backend: str = "tpu"  # "tpu" | "cpu" (python) | "native" (C++)
    batch_txn_capacity: int = 1024  # T: txns per resolver batch (static shape)
    point_reads_per_txn: int = 4  # PR
    point_writes_per_txn: int = 4  # PW
    range_reads_per_txn: int = 2  # RR
    range_writes_per_txn: int = 2  # RW
    hash_table_bits: int = 22  # point-write version table: 2^bits entries
    range_ring_capacity: int = 4096  # recent range-write ring (exact lane)
    coarse_buckets_bits: int = 14  # 2^bits contiguous key buckets (coarse lane)
    ring_partition_bits: int = 0  # 2^bits bucket-partitioned sub-rings
    # (0 = flat ring; >0 cuts range-check work ~2/2^bits on one device)
    key_limbs: int = 8  # 4*L bytes of exact key prefix on device
    # ring lanes via the Pallas VMEM kernel (ops/pallas_ring.py):
    # "auto" = on TPU backends, "on" = everywhere (interpreter off-TPU,
    # for differential tests), "off" = always the jnp lanes
    pallas_ring: str = "auto"
    # mesh lane ownership (resolver/meshresolver.py, multi-lane tpu
    # fleets only): "range" routes each packed entry host-side to the
    # lane(s) owning its key range (resolver/packing.ShardRouter) and
    # runs the compacted single-dispatch kernel: a lane's ring scan and
    # history shrink ~1/n (what four lanes cost beside one: PERF.md).
    # "hash" replicates the batch and carves ownership in-kernel (hash-sharded
    # point table, bucket-sharded ring): no host routing pass, no work
    # reduction.
    resolver_sharding: str = "range"
    # commit-path host packing (core/flatpack.py): "flat" = the client
    # pre-encodes conflict ranges into columnar limb blobs and the
    # proxy/packer consume them without per-txn Python ("legacy" keeps
    # the TxnRequest object path). Flat engages per batch only when
    # every request carries matching-width blobs and the resolver
    # accepts them (tpu/native, single resolver); everything else
    # falls back to legacy with identical packed arrays
    # (tests/test_packing_flat.py).
    commit_pack_path: str = "flat"

    # --- conflict repair & abort-aware batch scheduling ---
    # proxy-side intra-batch scheduling (server/scheduler.py): reorder a
    # commit batch host-side — over the clients' already-encoded flat
    # limb blobs, before packing — so reads resolve before the writes
    # they overlap and the resolver sees fewer self-inflicted aborts.
    # Default ON: the same-seed sim differential (tests/test_repair.py)
    # proved byte-identical final state against the arrival-order
    # baseline on both storage engines, so the reorder is free
    # correctness-wise and strictly reduces in-batch aborts.
    commit_batch_scheduling: bool = True
    # client-side transaction repair (txn/repair.py): on not_committed
    # with conflicting-key info, re-read ONLY the conflicting keys at
    # the failed batch's commit version and either replay the recorded
    # op log (read-set digest match — a spurious conflict) or fall back
    # to the retry loop seeded with the verified read cache. Default ON
    # under the same differential as commit_batch_scheduling: repaired
    # retries reach the identical final state the restart loop does,
    # with fewer storage round trips per conflict.
    txn_repair: bool = True
    # consecutive repair rounds before a conflicted transaction falls
    # back to the full cold restart (fresh GRV + backoff sleep) — the
    # livelock bound on the no-backoff repair retry
    txn_repair_max_rounds: int = 4

    # --- versions / MVCC ---
    # (the version rate itself is core.versions.VERSIONS_PER_SECOND —
    # a protocol constant, not a tunable)
    max_read_transaction_life_versions: int = 5_000_000

    # --- transaction limits (ref: fdbclient/Knobs.h CLIENT_KNOBS) ---
    key_size_limit: int = 10_000
    value_size_limit: int = 100_000
    transaction_size_limit: int = 10_000_000

    # --- retry loop (ref: CLIENT_KNOBS backoff) ---
    max_retry_delay_s: float = 1.0
    initial_backoff_s: float = 0.01
    backoff_growth: float = 2.0

    # --- proxy batching ---
    commit_batch_interval_s: float = 0.0005
    grv_batch_interval_s: float = 0.0005
    # bounded commit-pipeline depth (server/batcher.py): how many backlog
    # groups may be in flight at once — group N+1 packs on the host and
    # dispatches its resolve while group N's tlog push + storage apply
    # runs. 1 = the strictly serial loop (exactly the pre-pipeline
    # behavior); manual/sim mode always runs depth 1 for determinism.
    commit_pipeline_depth: int = 2
    # fleet VersionGate stall bound: a turn unclaimed this long means a
    # peer proxy died between grant and advance → 1021 + txn-system
    # recovery (tests shrink it; see server/proxy.py GateTimeout)
    gate_timeout_s: float = 60.0

    # --- read batching (txn/futures.py) ---
    # client-side multiplexed read batching: outstanding async reads on
    # one connection coalesce into single read_batch RPCs (ref:
    # NativeAPI serving every read through futures). max_keys bounds
    # one flush; window_ms is an optional linger after the first wake
    # (0 = flush whatever is queued immediately — the measured-best
    # default: async issue order already coalesces a client window).
    # Manual/sim pipelines always flush immediately for determinism.
    read_batch_max_keys: int = 128
    read_batch_window_ms: float = 0.0
    # CPython thread-switch interval for server processes
    # (tools/fdbserver.py): a waiting read-RPC thread is scheduled only
    # every switch interval, so under commit load the default 5ms adds
    # whole slices to every synchronous read RTT (measured ~25% of the
    # loaded read cost at 0.5ms vs 5ms).
    server_switch_interval_s: float = 0.0005

    # --- distributed tracing (utils/span.py) ---
    # fraction of transactions that carry a sampled trace (0 = tracing
    # off; `fdbcli tracing on` / \xff\xff/tracing/enabled turns it to
    # the 0.01 default-when-enabled). Sampling draws ride the seeded
    # "span-sample" deterministic stream.
    tracing_sample_rate: float = 0.0
    # error/slow-commit promotion: an UNSAMPLED (but tracing-enabled)
    # transaction whose commit aborts or outlives this bound emits its
    # client-side buffered spans anyway
    tracing_slow_commit_ms: float = 200.0

    # --- workload attribution (utils/heatmap.py) ---
    # default-ON key sampling: conflict heat charged at the proxy's
    # abort-fabrication site, read/write heat sampled storage-side.
    workload_sampling: bool = True
    # bounded histogram state: adjacent-range coalescing keeps each
    # heatmap at most this many buckets no matter how long the run
    heatmap_max_buckets: int = 64
    # exponential decay half-life (injected-clock seconds): old heat
    # fades so the snapshot reflects the CURRENT hot set
    heatmap_half_life_s: float = 30.0
    # storage-side read/write key sampling rate: one sampled key per
    # this many accesses on average (ref: StorageMetrics byte-sampling;
    # draws ride the "key-sample" deterministic stream). Charge weight
    # scales by the stride, so heat stays an unbiased estimate of total
    # accesses; 16 keeps the sampler inside the 2% overhead budget.
    storage_sample_every: int = 16

    # --- cluster doctor (server/health.py, tools/doctor.py) ---
    # latency prober: real GRV/read/commit probe transactions against
    # the live cluster (ref: Status.actor.cpp latencyProbe). Cadence
    # rides the injected clock + the "latency-probe" deterministic
    # stream; thread-mode clusters drive it from a daemon loop, sims
    # call maybe_probe() from their own schedule.
    health_probe_enabled: bool = True
    health_probe_interval_s: float = 1.0
    # doctor SLO thresholds (tools/doctor.py alerts + the storage_lag
    # degraded reason in the health verdict): probe p99 bounds, max
    # acceptable recovery duration, max storage durability lag
    doctor_probe_p99_ms: float = 1000.0
    doctor_recovery_ms: float = 30_000.0
    doctor_lag_versions: int = 5_000_000

    # --- metrics history + flight recorder (utils/timeseries.py) ---
    # cluster-owned retention layer (ref: flow/TDMetric.actor.h
    # continuous metric logging): one fixed-cadence window per interval
    # samples every role registry, the heatmaps, the device profiles,
    # the ratekeeper gauges, and the health verdict into bounded
    # per-metric rings. Cadence rides the injected clock + the
    # "history-cadence" deterministic stream (the FL001 seam, same as
    # the latency prober); thread-mode clusters drive it from a daemon
    # loop, sims call maybe_collect() from their own schedule.
    history_enabled: bool = True
    history_cadence_s: float = 1.0
    history_windows: int = 64  # per-metric ring depth
    history_heat_top: int = 8  # hot-range rows retained per dim/window
    # flight recorder (the black box): verdict transitions, recovery
    # triggers, and probe-SLO breaches dump a bounded artifact — last
    # flight_windows windows + the trace-ring tail + the recovery
    # timeline + activated SimBuggifySites — into an in-memory ring
    # (the \xff\xff/status/flight special key) and, when flight_dir is
    # set, as sorted-key flight-<seq>.json files (byte-identical under
    # a sim seed — the chaos post-mortem contract)
    flight_windows: int = 16
    flight_trace_tail: int = 64
    flight_max_dumps: int = 8
    flight_dir: str = ""
    # trend-aware doctor alerts (tools/doctor.py --trend + the
    # probe_trend degraded reason): a probe p99 strictly rising across
    # this many consecutive windows by at least this total percentage
    # alerts BEFORE the instant doctor_probe_p99_ms threshold breaches
    doctor_trend_windows: int = 3
    doctor_trend_min_rise_pct: float = 5.0

    # --- continuous consistency scan (server/consistencyscan.py) ---
    # cluster-owned background replica auditor (ref: fdbserver/
    # ConsistencyScan.actor.cpp): walks the shard map in bounded
    # key-batches at pinned read versions, compares every live replica
    # in the owning team, and re-reads once against the live map before
    # declaring corruption. Cadence rides the injected clock + the
    # "consistency-scan" deterministic stream (the FL001 seam, same as
    # the latency prober); thread-mode clusters drive it from a daemon
    # loop, sims call maybe_scan() from their own schedule.
    consistency_scan_enabled: bool = True
    consistency_scan_interval_s: float = 0.25
    consistency_scan_batch_keys: int = 256
    # sustained read budget: the next batch is deferred until the bytes
    # the last one read have drained at this rate (0 = unpaced)
    scan_rate_bytes_per_s: float = 2_000_000.0
    # doctor --scan SLO: a completed round older than this — or any
    # confirmed inconsistency — exits 1 (tools/doctor.py)
    doctor_scan_max_round_age_s: float = 600.0

    # --- multi-region replication (server/region.py) ---
    # continuous satellite streamer cadence: the RegionReplicator drains
    # the primary log toward the satellite at most once per interval
    # (jittered off the "region-stream" deterministic stream — the same
    # FL001 seam as the latency prober). Thread-mode clusters drive it
    # from a daemon loop; sims call maybe_stream() from their schedule.
    region_stream_interval_s: float = 0.05
    # doctor SLO thresholds for the regions section of cluster.health:
    # replication lag (versions) before the region_lag degraded reason
    # fires, and the longest acceptable region failover duration
    doctor_region_lag_versions: int = 2_000_000
    doctor_region_failover_ms: float = 60_000.0

    # --- per-tag auto-throttling (server/ratekeeper.py) ---
    # admission share above which a tag auto-throttles EVEN WITHOUT
    # global pressure (ref: TagThrottler's standalone busy-tag policy;
    # the under-pressure AIMD path is always on). 1.0 disables the
    # standalone path — a share can never exceed 1.0 — matching the
    # reference's default of auto-throttling being opt-in.
    tag_throttle_busyness: float = 1.0

    # --- RPC deadlines & failure monitor (rpc/transport.py,
    #     rpc/failuremon.py, rpc/service.py) ---
    # per-class RPC deadlines: every remote call carries one, enforced
    # by the client reader thread's deadline sweep (ref: per-request
    # timeouts via flow's timeoutError). An expired commit-class call
    # surfaces as commit_unknown_result (1021 — the txn MAY have
    # committed); read/GRV/admin expiries are plainly retryable (1037).
    rpc_deadline_read_s: float = 5.0
    rpc_deadline_grv_s: float = 5.0
    rpc_deadline_commit_s: float = 15.0
    rpc_deadline_admin_s: float = 30.0
    # per-endpoint health memory (ref: fdbrpc/FailureMonitor.actor.cpp):
    # deadline/ECONNRESET marks the endpoint failed; the read router
    # skips failed replicas; recovery is probed half-open with
    # exponential spacing. Off = every caller rediscovers a dead worker
    # by timing out against it (the pre-monitor behavior).
    failure_monitor: bool = True
    # keepalive ping cadence on idle client links (jittered off the
    # "ping-cadence" deterministic stream); 0 disables the pinger
    rpc_ping_interval_s: float = 2.0
    # chaos transport arming (rpc/chaos.py): a non-empty seed wraps
    # every NEW client socket in the seeded fault injector — tests
    # only; "" keeps chaos entirely un-imported (the default path)
    rpc_chaos_seed: str = ""

    # --- simulation ---
    # process-global BUGGIFY default (sim/buggify.py): `buggify` arms
    # the module-level BUGGIFY singleton at import (Simulation always
    # builds its own seeded instance regardless); `buggify_prob` is the
    # default per-evaluation fire probability for sites that do not
    # pass an explicit fire_p.
    buggify: bool = False
    buggify_prob: float = 0.05


DEFAULT_KNOBS = Knobs()
