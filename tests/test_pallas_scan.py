"""Fused Pallas accept kernel (ops/pallas_scan.py) — ISSUE 18.

The kernel replaces the WHOLE per-batch accept step (committed-write
ring check + intra-batch segment intersection + greedy acceptance) with
one ``pallas_call``, so the contract is total: interpreter mode off-TPU
must be BIT-IDENTICAL to the jnp path — statuses and the history the
next batch sees — on every fixture shape. Plus the operational half:
a forced lowering error lands in the ``pallas_to_jit`` fallback
taxonomy and the resolver keeps resolving (fenced), and two same-seed
sims with ``pallas_scan="on"`` emit byte-identical device docs.
"""

import json
import random

import pytest

from foundationdb_tpu.core import deterministic
from foundationdb_tpu.core.options import Knobs
from foundationdb_tpu.ops import pallas_scan as pallas_scan_mod
from foundationdb_tpu.resolver.resolver import Resolver
from foundationdb_tpu.resolver.skiplist import (
    COMMITTED,
    CONFLICT,
    TOO_OLD,
    TxnRequest,
)

KNOBS_KW = dict(
    resolver_backend="tpu", batch_txn_capacity=8, point_reads_per_txn=2,
    point_writes_per_txn=2, range_reads_per_txn=1, range_writes_per_txn=1,
    key_limbs=2, hash_table_bits=12, range_ring_capacity=32,
    coarse_buckets_bits=6,
)


def _key(rng, nk=40):
    return b"k%04d" % rng.randrange(nk)


def _span(rng, nk=40):
    a, b = sorted((_key(rng, nk), _key(rng, nk)))
    return (a, b + b"\xff")


def _txn(rng, v, kind):
    pt = kind in ("point", "mixed")
    rg = kind in ("range", "mixed")
    return TxnRequest(
        read_version=v - rng.randrange(0, 15),
        point_reads=[_key(rng) for _ in range(rng.randrange(3))] if pt else [],
        point_writes=[_key(rng) for _ in range(rng.randrange(3))] if pt else [],
        range_reads=[_span(rng) for _ in range(rng.randrange(2))] if rg else [],
        range_writes=[_span(rng) for _ in range(rng.randrange(2))] if rg else [],
    )


def _drive(mode, seed, knobs_kw=KNOBS_KW):
    """One full resolver life under ``pallas_scan=mode``: sequential
    point/range/mixed/empty batches, then backlog dispatches at depths
    landing on the B∈{2,4,8} buckets (and 12 → the extended ladder)."""
    rng = random.Random(seed)
    r = Resolver(Knobs(**knobs_kw, pallas_scan=mode))
    T = knobs_kw["batch_txn_capacity"]
    out = []
    v = 100

    def batch(kind, n):
        nonlocal v
        txns = [_txn(rng, v, kind) for _ in range(n)]
        v += rng.randrange(1, 5)
        return (txns, v, max(0, v - 60))

    for kind in ("point", "range", "mixed", "empty"):
        for _ in range(3):
            out.append(r.resolve(*batch(kind, rng.randrange(1, T + 1))))
    out.append(r.resolve(*batch("mixed", 0)))  # zero-txn batch
    for depth in (2, 3, 7, 12):  # buckets 2 / 4 / 8 / extended
        bs = [batch("mixed", rng.randrange(1, T + 1)) for _ in range(depth)]
        out.extend(r.resolve_many(bs))
    # history equivalence: one more batch probes the ring/table state
    # the sequence left behind
    out.append(r.resolve(*batch("mixed", T)))
    return r, out


@pytest.mark.parametrize("seed", [3, 11, 29])
def test_interpreter_bit_identical_to_jnp(seed):
    """pallas_scan="on" (interpreter off-TPU) vs "off": statuses must be
    bit-identical across point / range / mixed / empty / backlog-pad
    fixtures, AND the kernel route must actually have executed."""
    r_off, out_off = _drive("off", seed)
    r_on, out_on = _drive("on", seed)
    assert out_on == out_off
    assert r_on.params.use_pallas_scan and not r_off.params.use_pallas_scan
    snap = r_on.profile.snapshot()
    assert snap["kernel_routes"].get("pallas_scan", 0) > 0
    assert snap["fallback_causes"]["pallas_to_jit"] == 0
    assert r_off.profile.snapshot()["kernel_routes"].get("pallas_scan", 0) == 0


def test_ring_overflow_conservative_direction():
    """Overflowing the version ring may only ever ABORT MORE (the
    evicted entries fall into the coarse lanes): a stale read
    overlapping an evicted range write must CONFLICT, and the kernel
    path must match the jnp path exactly while doing so."""
    kw = dict(KNOBS_KW, range_ring_capacity=16)  # 16 slots, overflowed below

    def run(mode):
        r = Resolver(Knobs(**kw, pallas_scan=mode))
        v = 100
        # 3 batches x 8 txns x 2 range writes = 48 ring entries >> 16
        for b in range(3):
            txns = [
                TxnRequest(
                    read_version=v,
                    range_writes=[
                        (b"w%02d" % (b * 16 + 2 * i), b"w%02d" % (b * 16 + 2 * i + 1)),
                        (b"x%02d" % (b * 16 + 2 * i), b"x%02d" % (b * 16 + 2 * i + 1)),
                    ],
                )
                for i in range(8)
            ]
            v += 5
            r.resolve(txns, v, 0)
        # stale reader overlapping the FIRST (long-evicted) write span
        stale = TxnRequest(read_version=100, range_reads=[(b"w00", b"w01")])
        fresh = TxnRequest(read_version=v, range_reads=[(b"w00", b"w01")])
        return r.resolve([stale, fresh], v + 5, 0)

    got_on = run("on")
    assert got_on == run("off")
    assert got_on[0] == CONFLICT  # never a missed conflict
    assert got_on[1] == COMMITTED  # read version above every write


def test_forced_lowering_error_lands_in_pallas_to_jit(monkeypatch):
    """A kernel that fails to build engages the fenced fallback: the
    in-flight batch answers TOO_OLD, the failure is counted under the
    pallas_to_jit cause, both Pallas flags strip, and the resolver goes
    on resolving correctly on the jnp path."""

    def boom(*a, **kw):
        raise NotImplementedError("forced mosaic lowering failure")

    monkeypatch.setattr(pallas_scan_mod, "fused_accept", boom)
    r = Resolver(Knobs(**KNOBS_KW, pallas_scan="on"))
    assert r.params.use_pallas_scan
    # a range write forces the FULL variant (the only one with Pallas)
    first = [TxnRequest(read_version=100, range_writes=[(b"a", b"b")])]
    assert r.resolve(first, 110, 0) == [TOO_OLD]
    assert not r.params.use_pallas_scan and not r.params.use_pallas
    snap = r.profile.snapshot()
    assert snap["fallback_causes"]["pallas_to_jit"] == 1
    # fenced at the failed batch's commit version: older reads reject,
    # and post-fence semantics are intact on the jnp path
    w = TxnRequest(read_version=110, point_writes=[b"hot"])
    assert r.resolve([w], 120, 0) == [COMMITTED]
    stale = TxnRequest(read_version=110, point_reads=[b"hot"])
    fresh = TxnRequest(read_version=120, point_reads=[b"hot"])
    assert r.resolve([stale, fresh], 130, 0) == [CONFLICT, COMMITTED]


def test_forced_lowering_error_in_backlog_scan(monkeypatch):
    """The multi-batch scan bakes the fused step into its body: a
    lowering failure there fences the WHOLE backlog to TOO_OLD and
    counts once, and the next backlog rides the jnp scan."""

    def boom(*a, **kw):
        raise NotImplementedError("forced mosaic lowering failure")

    monkeypatch.setattr(pallas_scan_mod, "fused_accept", boom)
    r = Resolver(Knobs(**KNOBS_KW, pallas_scan="on"))
    mk = lambda v: [TxnRequest(read_version=v, range_writes=[(b"a", b"b")]),
                    TxnRequest(read_version=v, point_writes=[b"p"])]
    got = r.resolve_many([(mk(100), 110, 0), (mk(105), 115, 0)])
    assert got == [[TOO_OLD] * 2, [TOO_OLD] * 2]
    assert r.profile.snapshot()["fallback_causes"]["pallas_to_jit"] == 1
    assert not r.params.use_pallas_scan
    # post-fence: the jnp scan serves the next backlog normally
    got2 = r.resolve_many([(mk(115), 120, 0), (mk(116), 125, 0)])
    assert all(s != TOO_OLD for batch in got2 for s in batch)


def test_explicit_on_beyond_txn_budget_rejected():
    """pallas_scan="on" with txns > MAX_TXNS must fail loudly at
    construction (validate_params), not silently downgrade; "auto"
    never engages the kernel."""
    kw = dict(KNOBS_KW, batch_txn_capacity=pallas_scan_mod.MAX_TXNS * 2,
              hash_table_bits=14,
              range_ring_capacity=pallas_scan_mod.MAX_TXNS * 2)
    with pytest.raises(ValueError, match="MAX_TXNS|txns"):
        Resolver(Knobs(**kw, pallas_scan="on"))
    r = Resolver(Knobs(**kw, pallas_scan="auto"))
    assert not r.params.use_pallas_scan


# ───────────────── same-seed sim determinism (satellite) ─────────────────
def _sim_device_doc(seed, datadir):
    from foundationdb_tpu.sim.simulation import Simulation
    from foundationdb_tpu.sim.workloads import run_txn

    sim = Simulation(
        seed=seed, buggify=True, crash_p=0.0, datadir=datadir,
        resolver_backend="tpu", pallas_scan="on",
        batch_txn_capacity=8, point_reads_per_txn=2, point_writes_per_txn=2,
        range_reads_per_txn=1, range_writes_per_txn=1, key_limbs=2,
        hash_table_bits=12, range_ring_capacity=32, coarse_buckets_bits=6,
    )

    def workload(db, n_ops, rng):
        # point RMW + a range read + an occasional clear_range: every
        # conflict lane of the fused kernel sees sim traffic
        key = lambda i: b"ps/k%02d" % i
        for _ in range(n_ops):
            i = rng.randrange(6)

            def fn(tr, i=i):
                cur = tr.get(key(i)) or b"0"
                tr.get_range(key(0), key(3))
                tr.set(key(i), cur + b"x")
                if i == 0:
                    tr.clear_range(key(6), key(8))

            yield from run_txn(db, fn)

    try:
        for a in range(2):
            sim.add_workload(
                f"w{a}", workload(sim.db, 6, random.Random(seed * 13 + a)))
        sim.run()
        return json.dumps(sim.cluster.status()["cluster"]["device"],
                          sort_keys=True)
    finally:
        sim.close()
        deterministic.unseed()
        deterministic.registry().reset_clock()


def test_same_seed_sims_identical_with_pallas_scan_on(tmp_path):
    """Two same-seed sims with the fused kernel forced on (interpreter)
    emit byte-identical device docs — the kernel introduces no host
    nondeterminism (FL004: no clocks, no entropy inside the traced
    region), and the kernel_routes ledger proves it actually ran."""
    s1 = _sim_device_doc(5150, str(tmp_path / "d1"))
    s2 = _sim_device_doc(5150, str(tmp_path / "d2"))
    assert s1 == s2
    doc = json.loads(s1)
    agg = doc["aggregate"]
    assert agg["dispatches"] > 0
    assert agg["kernel_routes"].get("pallas_scan", 0) > 0
    assert agg["fallback_causes"]["pallas_to_jit"] == 0
