"""MeshResolver — the resolver fleet as ONE mesh program, behind the
single-resolver API.

Ref parity: multi-resolver deployments in the reference key-range-shard
conflict detection across resolver processes, with the commit proxy
fanning out sub-batches and AND-ing verdicts over the network
(fdbserver/CommitProxyServer.actor.cpp resolution fan-out,
fdbserver/Resolver.actor.cpp). The TPU-native shape keeps the whole
fleet inside one SPMD program over a `jax.sharding.Mesh`
(parallel/mesh.py ShardedResolverKernel): every device owns a shard of
the conflict history (hash-sharded point table, bucket-sharded range
ring), the batch is replicated, and verdicts combine with psum over ICI
— no host fan-out, no clipped sub-batches, ONE dispatch per batch.

Two lane-ownership schemes, selected by ``knobs.resolver_sharding``:

- ``"range"`` (default): the host routes each already-encoded entry to
  the lane(s) owning its key range (resolver/packing.ShardRouter — a
  vectorized cumsum pass over the packed arrays, no TxnRequest decode)
  and the device runs the COMPACTED per-lane slots
  (ops/conflict.resolve_batch_presharded). A lane's ring scan and
  history shrink ~1/n; what four lanes cost beside one is in PERF.md.
- ``"hash"``: the batch is replicated and each lane carves ownership
  in-kernel (hash-sharded point table, bucket-sharded ring). No host
  routing pass, but per-lane work never shrinks. No resolver
  boundaries to re-derive from the data distribution — the coordination
  problem the reference's keyResolvers map exists to solve disappears —
  at the price of k× replicated FLOPs.

`Cluster(n_resolvers=k, resolver_backend="tpu")` constructs one
MeshResolver over a k-lane mesh (clamped to the devices present; a
single-chip deployment degenerates to one lane). The commit proxy sees
`len(resolvers) == 1` and drives the plain single-resolver path —
including `resolve_many`'s scanned backlog dispatch, which runs the
whole mesh under `lax.scan`.
"""

import jax
import numpy as np

from foundationdb_tpu.core.options import DEFAULT_KNOBS
from foundationdb_tpu.resolver.packing import (
    BatchPacker,
    LaneBounds,
    ShardRouter,
)
from foundationdb_tpu.resolver.resolver import (
    BACKLOG_B,
    Resolver,
    fast_params_of,
    params_from_knobs,
)
from foundationdb_tpu.utils import deviceprofile
from foundationdb_tpu.utils import span as span_mod


class MeshResolver(Resolver):
    """Resolver-interface facade over ShardedResolverKernel.

    Inherits every host-side behavior from Resolver — base-version
    fencing, chunking over-capacity batches, the point-specialized fast
    variant, backlog chunking in resolve_many, uint32 rebase — and swaps
    the compiled steps for their shard_map twins. The device state lives
    here (donated through each step), exactly like the single-device
    resolver.
    """

    def __init__(self, knobs=DEFAULT_KNOBS, base_version=0, n_lanes=None,
                 mesh=None, heir_of=None):
        from foundationdb_tpu.parallel.mesh import (
            PreshardedResolverKernel,
            ShardedResolverKernel,
            default_mesh,
        )

        self.knobs = knobs
        self.backend = "tpu"
        self.base_version = base_version
        self.alive = True
        self._init_metrics()
        self.profile = deviceprofile.DeviceProfile("resolver")
        self.wants_point_split = True
        self.accepts_flat = True  # same packer machinery as Resolver
        self.dispatch_wall_s = 0.0
        if mesh is None:
            n = max(1, min(n_lanes or 1, len(jax.devices())))
            if n_lanes is not None and n < n_lanes:
                from foundationdb_tpu.utils.trace import TraceEvent

                # fewer lanes = proportionally less global conflict-
                # history capacity than the operator sized for (more
                # conservative 1020s under load) — say so loudly
                TraceEvent("ResolverLanesClamped", severity=30).detail(
                    requested=n_lanes, lanes=n,
                    devices=len(jax.devices())).log()
                # the structured taxonomy's sharded_to_local cause: the
                # operator asked for a fleet the hardware can't host
                self.profile.record_fallback("sharded_to_local",
                                             n_lanes - n)
            mesh = default_mesh(n)
        self.mesh = mesh
        self.n_lanes = int(mesh.devices.size)
        # use_pallas stays False: the Pallas ring kernel is single-shard
        # only (each shard_map lane is its own program); the mesh runs
        # the jnp lanes. ring_partition_bits too — the mesh already
        # bucket-shards the ring ACROSS devices; partitioning within a
        # shard would nest two ownership schemes.
        self.params = params_from_knobs(knobs, use_pallas=False)._replace(
            ring_partition_bits=0
        )
        self._init_buckets()
        if heir_of is not None:
            # a replacement is fenced anyway: the sample its predecessor
            # gathered, and the buckets cut from it, cost it nothing
            self.buckets = heir_of.buckets
            self._ranges_seen = heir_of._ranges_seen
        self.packer = BatchPacker(self.params, buckets=self.buckets)
        # "range" (the default) is the single-dispatch compacted path:
        # the host routes each entry to the lane(s) owning its keys
        # (ShardRouter), so per-lane scan/pairwise work shrinks ~1/n.
        # "hash" is the replicated-batch path (in-kernel hash/bucket
        # ownership): no per-lane work reduction, but no host routing
        # pass either — the latency-floor choice for tiny fleets.
        self.sharding = getattr(knobs, "resolver_sharding", "range")
        self._fast = None
        self._fast_params = None
        self._fast_kernel = None
        self._range_history = False
        self._rebound_fence = None  # the base_version a re-bound set
        if self.sharding == "range":
            self._kernel = PreshardedResolverKernel(self.params,
                                                    mesh=self.mesh)
            # the lane bounds start as the first limb's uniform split
            # and are cut from the keys this resolver packs (LaneBounds,
            # _maybe_rebound); a replacement takes its predecessor's, or
            # where the lanes' number changed cuts its own at once
            self._lanes = LaneBounds(self.n_lanes)
            bounds = None
            if getattr(heir_of, "_router", None) is not None:
                bounds = (heir_of._router.bounds
                          if heir_of.n_lanes == self.n_lanes
                          else self._lanes.fresh(self.buckets.sample()))
            self._router = ShardRouter(self.params, self.n_lanes,
                                       bounds=bounds)
            self._resolve = self._route_step
            # no point-specialized twin: the compacted layout already
            # skips dead sides per-entry, and a second compiled variant
            # would double the routing/compile surface for little win
        else:
            self._kernel = ShardedResolverKernel(self.params,
                                                 mesh=self.mesh)
            self._router = self._lanes = None
            self._resolve = self._kernel._step
            # point-specialized fast variant (see Resolver.__init__):
            # same state, range lanes statically off. make_state=False —
            # the twin kernel shares THIS resolver's state arrays.
            self._fast_params = fast_params_of(self.params)
            if self._fast_params is not None:
                self._fast_kernel = ShardedResolverKernel(
                    self._fast_params, mesh=self.mesh, make_state=False
                )
                self._fast = (
                    BatchPacker(self._fast_params, buckets=self.buckets),
                    self._fast_kernel._step,
                )
        self.state = self._kernel.state
        self._kernel.state = None  # ownership moves here (donated per step)
        self._scan_fns = {}
        self._scan_pad_buckets = (
            (2, 4, BACKLOG_B)
            if jax.default_backend() == "cpu" else (BACKLOG_B,)
        )
        self.adopt_profile(self.profile)  # attach the packer hooks

    def _split_counted(self, stacked):
        """Route a stacked numpy ResolveBatch through the ShardRouter,
        recording per-lane ENTRY COUNTS as the lane-balance instrument
        (host-side, FL004-clean). The counts feed the same lane_skew_pct
        rollup the hash path fills with per-lane walls — in range mode
        the split balance IS the utilization story, and it is known
        before the device ever runs."""
        with span_mod.stage("resolver.route", self.profile,
                            cpu=self.profile.cpu_sampled):
            sb, k, lane_counts = self._router.split(stacked)
        if deviceprofile.enabled():
            self.profile.record_lane_counts(lane_counts.tolist())
            # a range has a slot in every lane its span touches: what
            # the lanes were given beyond the entries that came in
            ranges = (np.count_nonzero(stacked.rr_mask)
                      + np.count_nonzero(stacked.rw_mask))
            points = (np.count_nonzero(stacked.pr_mask)
                      + np.count_nonzero(stacked.pw_mask))
            routed = lane_counts.sum()
            self.profile.count(
                route_dispatches=1, route_slices=k,
                lane_entries_routed=routed,
                lane_entries_fullest=lane_counts.max(),
                range_entries_routed=ranges,
                range_lane_dups=routed - points - ranges)
        return sb, k

    def _maybe_rebound(self, commit_version):
        """Cut the lane bounds again where the sample says so
        (resolver/packing.py ``LaneBounds``). A moved bound moves exact
        history, a lane's hash table and ring, which no fold makes safe,
        so a re-bound is a fence, as a respawn is: a new router, fresh
        lane state and empty summaries, ``base_version`` the batch in
        hand's commit version. That batch and every read version from
        before it are answered TOO_OLD by the host's rule and retry with
        a fresh read version; a read at or above the fence needs only
        writes from after it, and all of those were routed under the new
        bounds. (Upstream sends a read to every resolver that owned its
        range inside the MVCC window instead: ROADMAP B-I.10.) Between
        steps, on the dispatching thread."""
        if self._lanes is None or not self._lanes.due(self.buckets):
            return
        with span_mod.stage("resolver.rebound", self.profile):
            found = self._lanes.look(self.buckets, self._router)
            if found is None:
                return
            self._router, before, after = found
            # (the old lanes' history is let go before its successor
            # is made: the devices never hold both)
            self.state = None
            self.state = self._kernel.init_state()
            self.base_version = self._rebound_fence = commit_version
        self.profile.count(rebounds=1)
        from foundationdb_tpu.utils.trace import TraceEvent

        TraceEvent("ResolverLanesRebound").detail(
            fenced_at=commit_version, lane_bounds=self.lane_bounds(),
            shares_before=[round(float(x), 3) for x in before],
            shares_after=[round(float(x), 3) for x in after]).log()

    def _note_too_old(self, n):
        if n and self.base_version == self._rebound_fence:
            self.profile.count(rebound_fenced_txns=n)

    def lane_bounds(self):
        """The n − 1 lane bounds as printable keys (a bound cut from
        the sample is a key row; the first limb's split is not, and has
        no length); none where the lanes own by hash."""
        out = []
        for row in () if self._router is None else self._router.bounds:
            raw = row[:-1].astype(">u4").tobytes()
            key = raw[:int(row[-1])] if row[-1] else raw.rstrip(b"\x00")
            out.append(repr(key)[2:-1])
        return out

    def _route_step(self, state, batch):
        """Single-batch presharded step behind the ``self._resolve``
        signature: (state, numpy ResolveBatch) → (status, accepted,
        state). Accepted is not materialized separately (the status
        vector already encodes it; _step_kernel only reads status)."""
        stacked = jax.tree.map(lambda a: np.asarray(a)[None], batch)
        sb, k = self._split_counted(stacked)
        if k == 1:
            single = jax.tree.map(lambda a: a[0], sb)
            status, accepted, state = self._kernel._step(state, single)
            return status, accepted, state
        # rare over-capacity skew: the batch rides the scan as k slices
        state, st = self._kernel._scan_step(state, sb)
        status = self._router.reassemble(st, k)[0]
        return status, None, state

    def _make_scan_fn(self, use_fast):
        if self.sharding == "range":
            kern = self._kernel

            def routed_scan(state, stacked):
                sb, k = self._split_counted(stacked)
                state, st = kern._scan_step(state, sb)
                if k > 1:
                    st = self._router.reassemble(st, k)
                return state, st

            return routed_scan
        kernel = self._fast_kernel if use_fast else self._kernel
        return kernel._scan_step

    def _offer_interpreter(self, n):
        """Nothing to add on a mesh: its programs took one array in
        PR 33 and its cells' tails were measured as they are, and a
        dispatch's router gives the lock up at every large numpy call."""

    def _profile_lanes(self, statuses):
        """Per-lane dispatch wall for one mesh dispatch (ROADMAP item
        4's lane-utilization skew, measured). The verdicts are
        replicated (out_spec P()), so every lane holds its own finished
        copy: blocking each lane's shard in stable device order and
        timestamping its completion gives per-lane walls host-side —
        a straggler lane stretches its entry, balanced lanes land
        together. HOST-side only (materialize time, FL004-clean).

        Range mode records per-lane ENTRY COUNTS at split time instead
        (_split_counted) — one instrument per mode, never mixed units in
        the same rollup."""
        if self.sharding == "range" or not deviceprofile.enabled():
            return
        from foundationdb_tpu.parallel.mesh import lane_shards

        shards = lane_shards(statuses)
        if len(shards) <= 1:
            return
        t0 = deviceprofile.now()
        walls = []
        for s in shards:
            s.data.block_until_ready()
            walls.append(deviceprofile.now() - t0)
        self.profile.record_lanes(walls)

    def status(self):
        doc = super().status()
        doc["sharding"] = self.sharding
        doc["lane_bounds"] = self.lane_bounds()
        return doc

    def respawn(self, base_version):
        """Recruitment: a fresh fleet on the same mesh, fenced (the
        sharded history died with this instance); the lane bounds, the
        coarse buckets and the sample they were cut from live on."""
        new = MeshResolver(self.knobs, base_version=base_version,
                           mesh=self.mesh, heir_of=self)
        new._init_metrics(self.metrics)
        new.adopt_profile(self.profile)
        new._m_respawns.inc()
        return new
