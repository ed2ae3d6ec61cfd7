"""Multi-chip resolver: shard_map over a jax Mesh.

FDB scales conflict detection by key-range-sharding resolvers across
processes, with the commit proxy fanning out and AND-ing verdicts
(ref: fdbserver/CommitProxyServer.actor.cpp resolution fan-out,
fdbserver/Resolver.actor.cpp). The TPU analog keeps the whole resolver
fleet inside ONE jit program over a device mesh: ops/conflict.py's
``resolve_batch(axis_name='rs')`` runs as one SPMD program where every
device owns a shard of the conflict history (hash-sharded point table,
bucket-sharded range ring) and verdicts combine with psum/pmax over ICI —
the XLA-collective replacement for the reference's FlowTransport RPC.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from foundationdb_tpu.ops import conflict as ck

AXIS = "rs"


def default_mesh(n_devices=None):
    devs = jax.devices()
    if n_devices is not None:
        devs = devs[:n_devices]
    return Mesh(np.array(devs), (AXIS,))


def lane_shards(arr):
    """The per-device shards of ``arr`` in stable lane order (device
    id) — the HOST-side handle set the device profiler blocks one by
    one to measure per-lane dispatch wall (utils/deviceprofile.py).
    Empty for values without addressable shards (plain numpy, tracers),
    so callers can no-op on host backends."""
    try:
        shards = arr.addressable_shards
    except AttributeError:
        return []
    return sorted(shards, key=lambda s: s.device.id)


def _state_specs(axes=AXIS):
    return ck.ResolverState(
        window_start=P(),  # replicated scalar
        ht=P(axes),
        ring_b=P(axes),
        ring_e=P(axes),
        ring_v=P(axes),
        ring_lo=P(axes),
        ring_hi=P(axes),
        ring_mask=P(axes),
        ring_head=P(axes),  # [n] — one cursor per shard
        range_L=P(),  # replicated coarse summaries (pmax-synced)
        range_R=P(),
        point_coarse=P(),
    )


class ShardedResolverKernel:
    """The resolver fleet as one SPMD program.

    Per-device history capacity equals ``params`` sizes, so global
    capacity scales linearly with mesh size (hash table 2^HB * n, ring
    KR * n), while the batch is replicated — exactly the axis FDB scales
    resolvers on.
    """

    def __init__(self, params: ck.ResolverParams, mesh=None, donate=True,
                 make_state=True):
        ck.validate_params(params)
        self.params = params
        self.mesh = mesh if mesh is not None else default_mesh()
        self.n = self.mesh.devices.size
        # hybrid host×chip meshes (parallel/distributed.py) shard state
        # over every axis; the flat single-host mesh over the one axis
        self.axes = tuple(self.mesh.axis_names)
        self.spec_axes = self.axes if len(self.axes) > 1 else self.axes[0]

        fn = functools.partial(
            ck.resolve_batch, params=params, axis_name=self.spec_axes,
            n_shards=self.n,
        )

        # the batch is replicated: every lane unpacks the same row
        def resolve_batch(state, row, layout):
            return fn(state, ck.unpack_args(row, layout))

        self._step, self._scan_step = self._packed_programs(
            resolve_batch, (), donate)
        # make_state=False: a caller sharing state with a twin kernel
        # (MeshResolver's point-fast variant) skips the throwaway arrays
        self.state = self.init_state() if make_state else None

    def _packed_programs(self, step, row_spec, donate, lanes=0):
        """The kernel's two programs from ``step(state, row, layout)``,
        a lane's view of one packed batch (``ck.pack_args``): the step
        over ``P(*row_spec)`` and its scan over a stack of rows, each
        ``jit(shard_map(...))`` with the layout static, behind
        ``ck.PackedProgram``. Every resolve program takes ONE host
        array, the one device's too (``ck.make_resolve_fn``): the
        jitted call costs the dispatching thread work for each argument,
        contended or not, 0.04 ms on one device and, where the array is
        sharded over four chips, 0.55 ms (PERF.md §6, PR 33, PR 40).
        The XLA modules keep the names ``step`` and ``scan_step`` give
        them: the benchmark finds them by name."""
        state_specs = _state_specs(self.spec_axes)

        def program(body, buf_spec, out_specs):
            @functools.wraps(body)
            def sharded(state, buf, layout):
                return jax.shard_map(
                    functools.partial(body, layout=layout), mesh=self.mesh,
                    in_specs=(state_specs, buf_spec), out_specs=out_specs,
                    check_vma=False,
                )(state, buf)

            return ck.PackedProgram(sharded, donate, lanes=lanes)

        return (program(step, P(*row_spec), (P(), P(), state_specs)),
                program(ck.packed_scan_of(step), P(None, *row_spec),
                        (state_specs, P())))

    def init_state(self):
        p, n = self.params, self.n
        kr, c, w = p.ring_capacity, 1 << p.bucket_bits, p.key_width
        u32 = jnp.uint32
        axes = self.spec_axes

        def put(arr, spec):
            return jax.device_put(arr, NamedSharding(self.mesh, spec))

        return ck.ResolverState(
            window_start=put(jnp.zeros((), u32), P()),
            ht=put(jnp.zeros((n << p.hash_bits,), u32), P(axes)),
            ring_b=put(jnp.zeros((n * kr, w), u32), P(axes)),
            ring_e=put(jnp.zeros((n * kr, w), u32), P(axes)),
            ring_v=put(jnp.zeros((n * kr,), u32), P(axes)),
            ring_lo=put(jnp.zeros((n * kr,), jnp.int32), P(axes)),
            ring_hi=put(jnp.zeros((n * kr,), jnp.int32), P(axes)),
            ring_mask=put(jnp.zeros((n * kr,), bool), P(axes)),
            ring_head=put(jnp.zeros((n,), jnp.int32), P(axes)),
            range_L=put(jnp.zeros((c,), u32), P()),
            range_R=put(jnp.zeros((c,), u32), P()),
            point_coarse=put(jnp.zeros((c,), u32), P()),
        )

    def resolve(self, batch: ck.ResolveBatch):
        status, accepted, self.state = self._step(self.state, batch)
        return status, accepted

    def resolve_many(self, batches: ck.ResolveBatch):
        """Resolve a stack of batches (leading axis B) in one dispatch:
        lax.scan inside the sharded program, so the whole fleet stays on
        device for B consecutive commit batches. Returns statuses[B, T]."""
        self.state, statuses = self._scan_step(self.state, batches)
        return statuses


class PreshardedResolverKernel(ShardedResolverKernel):
    """The compacted-lane fleet: one SPMD program over host-presharded
    ShardBatches (ops/conflict.resolve_batch_presharded).

    The dense ``ShardedResolverKernel`` replicates the whole batch to
    every lane and carves ownership in-kernel — per-lane work never
    shrinks, so k lanes cost k× the FLOPs of one. Here the host router
    (resolver/packing.ShardRouter) sends each entry only to the lane(s)
    owning its keys, so a lane's history checks (the ring scan above
    all) run over ~1/n of the batch's entries while history capacity
    still scales n×; the intra-batch matrix is rebuilt per lane on a
    dense [T, K] grid and costs every lane what it costs one. State
    layout and placement are inherited unchanged (``ring_capacity`` is
    the PER-LANE ring size, as before); only the batch specs and the
    kernel body differ. Ref: CommitProxyServer.actor.cpp's resolution
    fan-out, collapsed into one collective dispatch.
    """

    def __init__(self, params: ck.ResolverParams, mesh=None, donate=True,
                 make_state=True):
        ck.validate_presharded_params(params)
        self.params = params
        self.mesh = mesh if mesh is not None else default_mesh()
        self.n = self.mesh.devices.size
        self.axes = tuple(self.mesh.axis_names)
        self.spec_axes = self.axes if len(self.axes) > 1 else self.axes[0]

        fn = functools.partial(
            ck.resolve_batch_presharded, params=params,
            axis_name=self.spec_axes,
        )

        # a lane's share of the packed batch is its own row: [1, N] of
        # [n, N] for a step, [B, 1, N] of [B, n, N] for a scan
        def resolve_batch_presharded(state, row, layout):
            return fn(state, ck.unpack_args(row[0], layout))

        self._step, self._scan_step = self._packed_programs(
            resolve_batch_presharded, (self.spec_axes,), donate,
            lanes=self.n)
        self.state = self.init_state() if make_state else None
