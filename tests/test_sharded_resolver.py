"""Sharded resolver (shard_map over 8 virtual CPU devices) vs the
single-device kernel: identical verdicts on collision-free workloads,
serializability invariant on everything else. SURVEY.md §4.5."""

import random

import numpy as np
import pytest

import jax

from foundationdb_tpu.ops import conflict as ck
from foundationdb_tpu.parallel.mesh import ShardedResolverKernel, default_mesh
from foundationdb_tpu.resolver.packing import BatchPacker
from foundationdb_tpu.resolver.skiplist import TxnRequest
from tests.test_resolver import (
    SMALL,
    exact_serializability_check,
    oracle_batches,
    plain_status,
    run_batches,
)


@pytest.fixture(scope="module")
def mesh8():
    assert len(jax.devices()) >= 8, "conftest should provide 8 CPU devices"
    return default_mesh(8)


def run_sharded(batches, mesh, params=SMALL, base=0):
    packer = BatchPacker(params)
    kern = ShardedResolverKernel(params, mesh=mesh, donate=False)
    out = []
    for txns, cv, ws in batches:
        b = packer.pack(txns, base, cv, ws)
        status, _ = kern.resolve(b)
        out.append(plain_status(status)[: len(txns)].tolist())
    return out


def make_point_batches(seed, nbatches=12):
    rng = random.Random(seed)
    version = 100
    batches = []
    for _ in range(nbatches):
        n = rng.randrange(1, SMALL.txns + 1)
        txns = []
        for _ in range(n):
            t = TxnRequest(read_version=version - rng.randrange(0, 25))
            for _ in range(rng.randrange(0, 3)):
                t.point_reads.append(b"key%03d" % rng.randrange(40))
            for _ in range(rng.randrange(0, 3)):
                t.point_writes.append(b"key%03d" % rng.randrange(40))
            txns.append(t)
        version += rng.randrange(1, 8)
        batches.append((txns, version, max(0, version - 60)))
    return batches


def test_sharded_matches_single_device_point_workload(mesh8):
    batches = make_point_batches(3)
    single = run_batches(batches)
    sharded = run_sharded(batches, mesh8)
    assert sharded == single


def test_sharded_matches_oracle(mesh8):
    batches = make_point_batches(11)
    sharded = run_sharded(batches, mesh8)
    # sharded hash lane has strictly fewer collisions than single-device;
    # on these keys both are collision-free, so oracle must match exactly
    assert sharded == oracle_batches(batches)


def test_sharded_mixed_serializability(mesh8):
    rng = random.Random(5)
    version = 100
    batches = []
    for _ in range(10):
        n = rng.randrange(1, SMALL.txns + 1)
        txns = []
        for _ in range(n):
            t = TxnRequest(read_version=version - rng.randrange(0, 20))
            if rng.random() < 0.5:
                t.point_reads.append(b"key%03d" % rng.randrange(30))
            if rng.random() < 0.5:
                t.point_writes.append(b"key%03d" % rng.randrange(30))
            if rng.random() < 0.25:
                a, b = sorted(rng.sample(range(30), 2))
                t.range_reads.append((b"key%03d" % a, b"key%03d" % b))
            if rng.random() < 0.25:
                a, b = sorted(rng.sample(range(30), 2))
                t.range_writes.append((b"key%03d" % a, b"key%03d" % b))
            txns.append(t)
        version += rng.randrange(1, 8)
        batches.append((txns, version, max(0, version - 50)))
    statuses = run_sharded(batches, mesh8)
    exact_serializability_check(batches, statuses)


def test_sharded_range_conflicts_cross_shard(mesh8):
    # a range write spanning every shard's buckets must still hit a point
    # read on any shard
    w = TxnRequest(read_version=10, range_writes=[(b"\x00", b"\xfe")])
    reads = [TxnRequest(read_version=10, point_reads=[bytes([b
        ])]) for b in (0x01, 0x55, 0xAA, 0xF0)]
    batches = [([w], 15, 0), (reads, 20, 0)]
    got = run_sharded(batches, mesh8)
    assert got[1] == [ck.CONFLICT] * 4


def test_hybrid_host_chip_mesh_matches_flat(mesh8):
    """A 2-D ('hosts','rs') mesh (the multi-host layout from
    parallel/distributed.py, here on virtual devices) must produce the
    same verdicts as the flat 8-shard mesh: the flattened coordinate is
    the shard id and collectives reduce over both axes."""
    import numpy as np
    from jax.sharding import Mesh

    devs = np.array(jax.devices()[:8]).reshape(2, 4)
    hybrid = Mesh(devs, ("hosts", "rs"))
    batches = make_point_batches(seed=5)
    assert run_sharded(batches, hybrid) == run_sharded(batches, mesh8)


def test_fleet_mesh_single_process(mesh8):
    from foundationdb_tpu.parallel.distributed import fleet_mesh, initialize

    idx, count = initialize()  # no coordinator configured -> no-op
    assert idx == 0 and count == 1
    m = fleet_mesh(8)
    assert m.devices.size == 8 and m.axis_names == ("rs",)


def test_resolve_many_matches_sequential(mesh8):
    """One scanned dispatch over B batches == B single dispatches."""
    import jax as _jax

    params = SMALL
    packer = BatchPacker(params)
    batches = make_point_batches(seed=9, nbatches=8)
    packed = [packer.pack(t, 0, cv, ws) for t, cv, ws in batches]

    kern1 = ShardedResolverKernel(params, mesh=mesh8, donate=False)
    want = []
    for b, (txns, _, _) in zip(packed, batches):
        status, _ = kern1.resolve(b)
        want.append(plain_status(status)[: len(txns)].tolist())

    kern2 = ShardedResolverKernel(params, mesh=mesh8, donate=False)
    stacked = _jax.tree.map(lambda *xs: np.stack(xs), *packed)
    statuses = plain_status(kern2.resolve_many(stacked))
    got = [
        statuses[i][: len(batches[i][0])].tolist() for i in range(len(batches))
    ]
    assert got == want
