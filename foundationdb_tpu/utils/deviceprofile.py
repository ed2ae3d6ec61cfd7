"""Device-path execution profiler: per-dispatch accounting for the
resolver's jit/pallas path.

Ref parity: flow/Profiler.actor.cpp (the sampling profiler whose doc
rides status json) + the device-side counters Status.actor.cpp folds
into ``cluster.*``. The resolver's device path is the one layer the
metrics/heatmap/trace stack never reached: this module records, per
dispatch, the bucket size chosen vs the txns actually live (pad
waste), live-vs-padded entry counts per conflict side (pr/pw/rr/rw),
jit retraces per shape signature, staging-ring reuse vs realloc,
host→device transfer bytes, per-lane dispatch wall + verdict-reduce
wall for the mesh fleet (lane-utilization skew — ROADMAP item 4's
direct measurement), and a structured ``fallback_cause`` taxonomy
(pallas_to_jit, flat_to_legacy, sharded_to_local, over_capacity,
too_old_rv) replacing the bare fallback counters.

FL004: every capture site is HOST-side — around the jit call, never
inside a traced function. The flowlint fixtures in
tests/test_flowlint.py pin that a profiler hook inside a jit-reachable
fn trips the lint.

Determinism: durations use ``core.deterministic.now()`` (the metrics.py
clock contract) — under the sim's step clock a span inside one step is
exactly 0.0, so two same-seed sims emit byte-identical profiler docs;
in production the clock is the real wall clock. Everything else is
integer counters.

Overhead: the module-level ``set_enabled(False)`` kill switch turns
every ``record_*`` into an early return. What the profile costs when on
is the benchmark's to measure (the untraced run against the parent
commit, the traced pair in PERF.md), not a CPU gate's.

The resolver's host stages reach the profile through
``utils/span.stage(name, stats=profile)``: :meth:`DeviceProfile.add`
maps ``resolver.pack`` / ``resolver.enqueue`` / ``resolver.readback``
/ ``resolver.route`` / ``resolver.rebucket`` / ``resolver.rebound`` to
``pack_wall_ms`` / ``enqueue_wall_ms`` / ``verdict_reduce_wall_ms`` /
``route_wall_ms`` / ``rebucket_wall_ms`` / ``rebound_wall_ms``. The
dispatching thread's four (pack, enqueue, readback, route) are opened
with ``cpu=True`` and bring their CPU seconds too: snapshot() splits
each wall sum into ``<stage>_cpu_ms`` and ``<stage>_offcpu_ms``
(:data:`STAGE_CPU`).
"""

import os
import threading

from foundationdb_tpu.core import deterministic
from foundationdb_tpu.utils import lockdep

_enabled = True

# the closed taxonomy: snapshot() emits every cause (zeros included) so
# the doc's shape is stable for a reader that diffs two documents
FALLBACK_CAUSES = (
    "pallas_to_jit",   # pallas ring kernel unavailable/failed -> jit
    "flat_to_legacy",  # flat batch mixed with legacy / width mismatch
    "sharded_to_local",  # mesh lanes clamped below the requested fleet
    "over_capacity",   # flat batch exceeds a lane cap -> decode+repack
    "too_old_rv",      # read version below the resolver's fenced base
)

SIDES = ("pr", "pw", "rr", "rw")


# utils/span.stage names → the wall each accumulates into
STAGE_WALLS = {
    "resolver.pack": "pack_wall_s",
    "resolver.enqueue": "enqueue_wall_s",
    "resolver.readback": "verdict_reduce_wall_s",
    "resolver.route": "route_wall_s",
    "resolver.rebucket": "rebucket_wall_s",
    "resolver.rebound": "rebound_wall_s",
}

# the stages opened with ``cpu=True`` → the sum of the dispatching
# thread's CPU seconds inside them, as its clock gave them. snapshot()
# prints each beside the rest of the stage's wall, ``<stage>_offcpu_ms``
# = max(0, wall − CPU) of the SUMS: the thread's wait for the
# interpreter (these stages do not sleep) and, in the readback, for the
# device. Not a stage at a time: the chip's host steps a thread's CPU
# clock by 10 ms, so one stage reads 0 or 10 ms (utils/span.stage)
STAGE_CPU = {
    "resolver.pack": "pack_cpu_s",
    "resolver.enqueue": "enqueue_cpu_s",
    "resolver.readback": "readback_cpu_s",
    "resolver.route": "route_cpu_s",
}
CPU_SUMS = tuple(STAGE_CPU.values())
# one dispatch in CPU_EVERY takes those readings (:meth:`DeviceProfile.
# cpu_turn`) and each counts CPU_EVERY times: a read of the thread's CPU
# clock holds the interpreter for about 30 µs under a cell's load on the
# chip's host, four to six of them a dispatch, and read on every
# dispatch they cost a cell ≈ 0.2% of its rate for every 10 dispatches
# a second it makes (PERF.md §6, PR 39: mako, 225 a second, −4.1%)
CPU_EVERY = 2

# the plain counters (:meth:`DeviceProfile.count`), each summed over
# dispatches. The mesh router's, fed by ``MeshResolver._split_counted``:
# the dispatches routed and their slices (k: 1 unless a lane
# overflowed), the entries routed and, of them, those of the dispatch's
# fullest lane. ``h2d_args``, counted by the resolver beside each
# jitted call: the host arrays the dispatch handed its program (the
# state is on the device already) — one array a dispatch, on one device
# as on a mesh (``ops/conflict.pack_args``). The coarse
# buckets' (resolver/packing.py ``CoarseBuckets``): ``rebuckets``, the
# times ``Resolver._maybe_rebucket`` cut new boundaries and folded the
# device's summaries; ``conflicts_coarse_only``, transactions refused
# where only a coarse summary stood in the way (the device's fourth
# status code: an upper bound on the false conflicts the summaries
# cost); ``bucket_entries_routed`` / ``bucket_entries_fullest``, a
# pack's live point writes and those of them in its fullest bucket,
# counted once boundaries are cut. The lane bounds' (resolver/packing.py
# ``LaneBounds``): ``rebounds``, the times ``MeshResolver._maybe_rebound``
# cut new lane bounds and fenced; ``rebound_fenced_txns``, transactions
# the host then answered TOO_OLD because they read before that fence;
# ``range_entries_routed`` / ``range_lane_dups``, the range entries a
# dispatch brought the router and the lane slots it gave them beyond
# one each (a range has a slot in every lane its span touches)
PLAIN_COUNTERS = ("route_dispatches", "route_slices", "lane_entries_routed",
                  "lane_entries_fullest", "h2d_args", "rebuckets",
                  "conflicts_coarse_only", "bucket_entries_routed",
                  "bucket_entries_fullest", "rebounds",
                  "rebound_fenced_txns", "range_entries_routed",
                  "range_lane_dups")
# the stage walls that ride beside them through absorb and snapshot,
# and with them the four stages' CPU sums
PLAIN_WALLS = ("route_wall_s", "rebucket_wall_s",
               "rebound_wall_s") + CPU_SUMS


def set_enabled(on):
    """Process-wide kill switch."""
    global _enabled
    _enabled = bool(on)


def enabled():
    return _enabled


def now():
    """The injected clock every profiler duration uses (sim: the step
    clock; production: the wall clock)."""
    return deterministic.now()


class DeviceProfile:
    """Per-resolver device-path profile. The cluster owns one per
    resolver index (like the PR-4 registries) and re-hands it across
    respawn/recovery/configure so history never rewinds; ``absorb``
    bypasses the kill switch because carried history is not new
    overhead."""

    def __init__(self, name, index=0):
        self.name = name
        self.index = index
        self._lock = lockdep.lock("DeviceProfile._lock")
        # dispatch accounting
        self.dispatches = 0
        self.batches_live = 0
        self.batch_slots = 0
        self.txns_live = 0
        self.txn_slots = 0
        # txn rows the flat pack reset plus txn rows it wrote: follows
        # the live rows, not txn_slots (resolver/packing.py)
        self.pack_rows_touched = 0
        self.bucket_histogram = {}  # str(B) -> dispatches at bucket B
        # per-side entry occupancy: live vs padded slots
        self.entries_live = {s: 0 for s in SIDES}
        self.entry_slots = {s: 0 for s in SIDES}
        # compile-cache events: new shape signatures seen by the
        # dispatch callable (ops/conflict.count_retraces)
        self.recompiles = 0
        self.compile_keys = {}  # str(key) -> count
        # staging ring (resolver/packing.py _flat_staging)
        self.staging_reuse_hits = 0
        self.staging_reuse_misses = 0
        # host->device transfer estimate (sum of packed array nbytes)
        self.transfer_bytes = 0
        # walls (deterministic clock; 0.0 under the sim step clock)
        # dispatch = all of one kernel step (enqueue + readback); pack =
        # host packing before it; enqueue = the jitted call returning
        # (H2D + launch); verdict_reduce = the status readback (device
        # wait + D2H) — on the mesh route, the cross-lane reduce
        self.dispatch_wall_s = 0.0
        self.pack_wall_s = 0.0
        self.enqueue_wall_s = 0.0
        self.verdict_reduce_wall_s = 0.0
        # mesh lanes: accumulated per-lane dispatch wall (hash-sharded
        # mode / legacy host fan-out) OR per-lane routed-entry counts
        # (range-sharded mode — known at split time, before the device
        # runs). One profile only ever fills one of the two: skew over
        # mixed units would be meaningless.
        self.lane_walls_s = []
        self.lane_entries = []
        self.lane_dispatches = 0
        self.route_wall_s = 0.0  # stage resolver.route: the router's split
        # stage resolver.rebucket: sample → boundaries → fold
        self.rebucket_wall_s = 0.0
        # stage resolver.rebound: sample → lane bounds → fresh lane state
        self.rebound_wall_s = 0.0
        for c in CPU_SUMS:
            setattr(self, c, 0.0)
        self._cpu_turns = 0
        # whether the dispatch in hand takes CPU readings (cpu_turn)
        self.cpu_sampled = False
        for c in PLAIN_COUNTERS:
            setattr(self, c, 0)
        # fallback-cause taxonomy
        self.fallback_causes = {c: 0 for c in FALLBACK_CAUSES}
        # kernel-route dispatch records: which per-batch step body
        # actually executed ("pallas_ring" | "jit"), counted per live
        # batch served (the params flag alone is the REQUEST; a
        # pallas_to_jit fallback flips the route) — status json's
        # cluster.device.aggregate.kernel_routes, held by chip_smoke.py
        self.kernel_routes = {}

    # ── capture sites (all host-side, all gated) ──

    def record_dispatch(self, bucket, live_batches, live_txns, txn_slots,
                        entries_live=None, entry_slots=None,
                        transfer_bytes=0, wall_s=0.0):
        if not _enabled:
            return
        with self._lock:
            self.dispatches += 1
            self.batches_live += int(live_batches)
            self.batch_slots += int(bucket)
            self.txns_live += int(live_txns)
            self.txn_slots += int(txn_slots)
            b = str(int(bucket))
            self.bucket_histogram[b] = self.bucket_histogram.get(b, 0) + 1
            if entries_live:
                for s in SIDES:
                    self.entries_live[s] += int(entries_live.get(s, 0))
            if entry_slots:
                for s in SIDES:
                    self.entry_slots[s] += int(entry_slots.get(s, 0))
            self.transfer_bytes += int(transfer_bytes)
            self.dispatch_wall_s += float(wall_s)

    def record_compile(self, key):
        if not _enabled:
            return
        with self._lock:
            self.recompiles += 1
            k = str(key)
            self.compile_keys[k] = self.compile_keys.get(k, 0) + 1

    def record_fallback(self, cause, n=1):
        if not _enabled:
            return
        with self._lock:
            self.fallback_causes[cause] = (
                self.fallback_causes.get(cause, 0) + int(n))

    def record_kernel_route(self, route, n=1):
        """One successful dispatch served by ``route`` (n = live
        batches it carried). Recorded at the call sites' success edge
        only — a dispatch that engaged the Pallas fallback records its
        cause, not a route."""
        if not _enabled:
            return
        with self._lock:
            self.kernel_routes[route] = (
                self.kernel_routes.get(route, 0) + int(n))

    def record_staging(self, hit):
        if not _enabled:
            return
        with self._lock:
            if hit:
                self.staging_reuse_hits += 1
            else:
                self.staging_reuse_misses += 1

    def record_pack_rows(self, rows):
        if not _enabled:
            return
        with self._lock:
            self.pack_rows_touched += int(rows)

    def record_lanes(self, walls_s):
        """Per-lane dispatch walls for ONE mesh dispatch (index = lane,
        stable device order) — accumulated so skew reflects the run."""
        if not _enabled:
            return
        with self._lock:
            if len(self.lane_walls_s) < len(walls_s):
                self.lane_walls_s.extend(
                    0.0 for _ in range(len(walls_s) - len(self.lane_walls_s)))
            for i, w in enumerate(walls_s):
                self.lane_walls_s[i] += float(w)
            self.lane_dispatches += 1

    def record_lane_counts(self, counts):
        """Per-lane routed-entry counts for ONE dispatch (range-sharded
        mesh: the ShardRouter split, or the legacy proxy fan-out's
        clipped sub-batches). Same lane_skew_pct rollup as the wall
        instrument — balance in entries instead of seconds."""
        if not _enabled:
            return
        with self._lock:
            if len(self.lane_entries) < len(counts):
                self.lane_entries.extend(
                    0 for _ in range(len(counts) - len(self.lane_entries)))
            for i, c in enumerate(counts):
                self.lane_entries[i] += int(c)
            self.lane_dispatches += 1

    def count(self, **counters):
        """Add to :data:`PLAIN_COUNTERS`."""
        if not _enabled:
            return
        with self._lock:
            for c, n in counters.items():
                setattr(self, c, getattr(self, c) + int(n))

    def cpu_turn(self):
        """Called by the dispatching thread once a dispatch, before its
        pack → whether this one's stages read the thread's CPU clock
        (``stage(..., cpu=True)``): one dispatch in :data:`CPU_EVERY`,
        none with the profile switched off. Kept in ``cpu_sampled`` for
        the stages opened deeper in the same dispatch (the mesh's
        route)."""
        self._cpu_turns += 1
        self.cpu_sampled = _enabled and self._cpu_turns % CPU_EVERY == 0
        return self.cpu_sampled

    def add(self, stage, seconds, cpu_s=None):
        """The ``stats`` sink of ``utils/span.stage``: a resolver host
        stage's seconds into its wall (:data:`STAGE_WALLS`) and, from a
        stage opened with ``cpu=True``, its CPU seconds into the sum
        beside it (:data:`STAGE_CPU`), :data:`CPU_EVERY` times: it
        stands for the dispatches that took no reading."""
        if not _enabled:
            return
        wall = STAGE_WALLS[stage]
        with self._lock:
            setattr(self, wall, getattr(self, wall) + float(seconds))
            if cpu_s is not None:
                cpu = STAGE_CPU[stage]
                setattr(self, cpu,
                        getattr(self, cpu) + CPU_EVERY * float(cpu_s))

    def _cpu_split_ms(self):
        """``<stage>_cpu_ms`` and ``<stage>_offcpu_ms`` of the four
        stages: each wall sum as its CPU sum and the rest (never below
        0). Called with the lock held."""
        out = {}
        for stage, cpu in STAGE_CPU.items():
            on = getattr(self, cpu)
            off = max(0.0, getattr(self, STAGE_WALLS[stage]) - on)
            name = cpu[:-len("_cpu_s")]  # pack_cpu_s -> pack
            out[name + "_cpu_ms"] = round(on * 1e3, 3)
            out[name + "_offcpu_ms"] = round(off * 1e3, 3)
        return out

    # ── carryover + rollup ──

    def absorb(self, other):
        """Fold a prior incarnation's totals in (respawn / recovery /
        configure shrink). Bypasses the kill switch: carried history is
        not new overhead."""
        with other._lock:
            o = {
                "dispatches": other.dispatches,
                "batches_live": other.batches_live,
                "batch_slots": other.batch_slots,
                "txns_live": other.txns_live,
                "txn_slots": other.txn_slots,
                "pack_rows_touched": other.pack_rows_touched,
                "bucket_histogram": dict(other.bucket_histogram),
                "entries_live": dict(other.entries_live),
                "entry_slots": dict(other.entry_slots),
                "recompiles": other.recompiles,
                "compile_keys": dict(other.compile_keys),
                "staging_reuse_hits": other.staging_reuse_hits,
                "staging_reuse_misses": other.staging_reuse_misses,
                "transfer_bytes": other.transfer_bytes,
                "dispatch_wall_s": other.dispatch_wall_s,
                "pack_wall_s": other.pack_wall_s,
                "enqueue_wall_s": other.enqueue_wall_s,
                "verdict_reduce_wall_s": other.verdict_reduce_wall_s,
                "lane_walls_s": list(other.lane_walls_s),
                "lane_entries": list(other.lane_entries),
                "lane_dispatches": other.lane_dispatches,
                "fallback_causes": dict(other.fallback_causes),
                "kernel_routes": dict(other.kernel_routes),
                **{c: getattr(other, c)
                   for c in PLAIN_WALLS + PLAIN_COUNTERS},
            }
        with self._lock:
            self.dispatches += o["dispatches"]
            self.batches_live += o["batches_live"]
            self.batch_slots += o["batch_slots"]
            self.txns_live += o["txns_live"]
            self.txn_slots += o["txn_slots"]
            self.pack_rows_touched += o["pack_rows_touched"]
            for k, v in o["bucket_histogram"].items():
                self.bucket_histogram[k] = (
                    self.bucket_histogram.get(k, 0) + v)
            for s in SIDES:
                self.entries_live[s] += o["entries_live"].get(s, 0)
                self.entry_slots[s] += o["entry_slots"].get(s, 0)
            self.recompiles += o["recompiles"]
            for k, v in o["compile_keys"].items():
                self.compile_keys[k] = self.compile_keys.get(k, 0) + v
            self.staging_reuse_hits += o["staging_reuse_hits"]
            self.staging_reuse_misses += o["staging_reuse_misses"]
            self.transfer_bytes += o["transfer_bytes"]
            self.dispatch_wall_s += o["dispatch_wall_s"]
            self.pack_wall_s += o["pack_wall_s"]
            self.enqueue_wall_s += o["enqueue_wall_s"]
            self.verdict_reduce_wall_s += o["verdict_reduce_wall_s"]
            if len(self.lane_walls_s) < len(o["lane_walls_s"]):
                self.lane_walls_s.extend(
                    0.0 for _ in range(len(o["lane_walls_s"])
                                       - len(self.lane_walls_s)))
            for i, w in enumerate(o["lane_walls_s"]):
                self.lane_walls_s[i] += w
            if len(self.lane_entries) < len(o["lane_entries"]):
                self.lane_entries.extend(
                    0 for _ in range(len(o["lane_entries"])
                                     - len(self.lane_entries)))
            for i, c in enumerate(o["lane_entries"]):
                self.lane_entries[i] += c
            self.lane_dispatches += o["lane_dispatches"]
            for c in PLAIN_WALLS + PLAIN_COUNTERS:
                setattr(self, c, getattr(self, c) + o[c])
            for c, v in o["fallback_causes"].items():
                self.fallback_causes[c] = (
                    self.fallback_causes.get(c, 0) + v)
            for r, v in o["kernel_routes"].items():
                self.kernel_routes[r] = self.kernel_routes.get(r, 0) + v

    def snapshot(self):
        """JSON-ready doc (sorted, stably rounded). ``pad_waste_pct``
        is the slot share PADDING burned: 1 - live/slots over every
        dispatch; ``lane_skew_pct`` is (max-min)/max over the
        accumulated per-lane loads — walls when the wall instrument
        filled, routed-entry counts otherwise — 0 when balanced or
        single-lane."""
        with self._lock:
            lanes = list(self.lane_walls_s)
            entries = list(self.lane_entries)
            skew_src = [float(x) for x in (lanes or entries)]
            txn_slots = self.txn_slots
            txns_live = self.txns_live
            hits, misses = (self.staging_reuse_hits,
                            self.staging_reuse_misses)
            pad_waste = (
                round((1.0 - txns_live / txn_slots) * 100, 2)
                if txn_slots else 0.0)
            lane_max = max(skew_src) if skew_src else 0.0
            lane_skew = (
                round((lane_max - min(skew_src)) / lane_max * 100, 2)
                if lane_max > 0 else 0.0)
            return {
                "name": self.name,
                "id": self.index,
                "dispatches": self.dispatches,
                "batches_live": self.batches_live,
                "batch_slots": self.batch_slots,
                "txns_live": txns_live,
                "txn_slots": txn_slots,
                "pack_rows_touched": self.pack_rows_touched,
                "pad_waste_pct": pad_waste,
                "bucket_histogram": dict(sorted(
                    self.bucket_histogram.items(),
                    key=lambda kv: int(kv[0]))),
                "entries_live": dict(self.entries_live),
                "entry_slots": dict(self.entry_slots),
                "recompiles": self.recompiles,
                "compile_keys": dict(sorted(self.compile_keys.items())),
                "staging_reuse_hits": hits,
                "staging_reuse_misses": misses,
                "staging_reuse_rate": round(
                    hits / max(hits + misses, 1), 3),
                "transfer_bytes": self.transfer_bytes,
                "dispatch_wall_ms": round(self.dispatch_wall_s * 1e3, 3),
                "pack_wall_ms": round(self.pack_wall_s * 1e3, 3),
                "enqueue_wall_ms": round(self.enqueue_wall_s * 1e3, 3),
                "verdict_reduce_wall_ms": round(
                    self.verdict_reduce_wall_s * 1e3, 3),
                "lanes": max(len(lanes), len(entries)),
                "lane_dispatches": self.lane_dispatches,
                "lane_walls_ms": [round(w * 1e3, 3) for w in lanes],
                "lane_entries": entries,
                "lane_skew_pct": lane_skew,
                "route_wall_ms": round(self.route_wall_s * 1e3, 3),
                "rebucket_wall_ms": round(self.rebucket_wall_s * 1e3, 3),
                "rebound_wall_ms": round(self.rebound_wall_s * 1e3, 3),
                **self._cpu_split_ms(),
                **{c: getattr(self, c) for c in PLAIN_COUNTERS},
                "fallback_causes": dict(sorted(
                    self.fallback_causes.items())),
                "kernel_routes": dict(sorted(
                    self.kernel_routes.items())),
            }


def placement(resolvers):
    """Where the resolvers' history actually lives: platform, device
    kind and distinct-device count read off the devices holding each
    device-backed resolver's state arrays (not off ``jax.devices()`` —
    a mesh clamped to one device and a host backend both answer
    differently from what the process could see). All None/0 when no
    resolver keeps state on a device."""
    devs = set()
    for r in resolvers:
        state = getattr(r, "state", None)
        if state is not None:
            # the sharding outlives donation; .devices() raises on an
            # array a concurrent step has just consumed
            devs |= state.ht.sharding.device_set
    first = min(devs, key=lambda d: d.id, default=None)
    return {
        "platform": first.platform if first else None,
        "device_kind": first.device_kind if first else None,
        "device_count": len(devs),
    }


class CompileLog:
    """Process-wide XLA build accounting off ``jax.monitoring``: every
    program JAX builds (``backend_compiles``, persistent-cache hits
    included), the seconds that took, and how many came out of the
    persistent cache. A steady window that builds anything has met a
    shape its warm-up missed."""

    def __init__(self):
        self._lock = lockdep.lock("CompileLog._lock")
        self.backend_compiles = 0
        self.backend_compile_s = 0.0
        self.cache_hits = 0

    def _on_duration(self, event, duration_secs, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                self.backend_compiles += 1
                self.backend_compile_s += duration_secs

    def _on_event(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            with self._lock:
                self.cache_hits += 1

    def snapshot(self):
        with self._lock:
            return {
                "backend_compiles": self.backend_compiles,
                "backend_compile_s": round(self.backend_compile_s, 3),
                "cache_hits": self.cache_hits,
            }


_compile_log = None


def compile_log():
    """This process's :class:`CompileLog`, or None until an entry point
    has called :func:`enter_process` (a library import installs no
    listener, and same-seed sim docs stay byte-identical)."""
    return _compile_log


def enter_process():
    """Called once by each process entry point (chip_smoke.py's
    children, tools/fdbserver.py) before JAX builds anything:
    place the persistent compile cache, start counting builds, and hand
    ``utils/span.stage`` the profiler's annotation, so that a
    ``jax.profiler`` trace of this process carries ``fdb.<stage>``
    events on its host planes, on the clock of its device planes.

    The cache goes where ``JAX_COMPILATION_CACHE_DIR`` says; only when
    that is unset does code name a place, ``<checkout>/.jax_cache`` —
    fixed, because the path is part of the cache key."""
    global _compile_log
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        checkout = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(checkout, ".jax_cache"))
    if _compile_log is None:
        _compile_log = CompileLog()
        jax.monitoring.register_event_duration_secs_listener(
            _compile_log._on_duration)
        jax.monitoring.register_event_listener(_compile_log._on_event)
    from foundationdb_tpu.utils import span as span_mod

    span_mod.set_annotator(jax.profiler.TraceAnnotation)


def merged_snapshot(profiles):
    """One aggregate doc over several DeviceProfiles (the cluster-wide
    ``cluster.device.aggregate`` rollup)."""
    acc = DeviceProfile("aggregate")
    for p in profiles:
        if p is not None:
            acc.absorb(p)
    return acc.snapshot()
