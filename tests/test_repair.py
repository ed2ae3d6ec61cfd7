"""Client-side transaction repair (txn/repair.py): replay vs seeded
fallback, cache soundness, the repaired retry protocol, and the sim
differential — repair+scheduling on vs restart-only produce
serializability-equivalent state on both storage engines."""

import random

import pytest

from foundationdb_tpu.core.errors import FDBError, err
from foundationdb_tpu.server.cluster import Cluster
from foundationdb_tpu.sim.simulation import Simulation
from foundationdb_tpu.sim.workloads import tpcc_check, tpcc_workload


@pytest.fixture
def cl():
    c = Cluster(resolver_backend="cpu", txn_repair=True)
    yield c
    c.close()


def _conflict(cl, db, tr, key=b"k", new_value=b"2"):
    """Make ``tr`` (which already read ``key``) conflict by committing
    a concurrent write; returns the 1020 it raises."""
    db.set(key, new_value)
    with pytest.raises(FDBError) as ei:
        tr.commit()
    assert ei.value.code == 1020
    return ei.value


# ───────────────────────── repair outcomes ─────────────────────────
def test_value_dependent_conflict_falls_back_seeded(cl):
    """Digest mismatch: the conflicting value changed, so the body must
    re-run — but at the rejecting commit version, with the verified
    cache seeded and the conflicting key already refreshed."""
    db = cl.database()
    db.set(b"k", b"1")
    db.set(b"c", b"const")
    tr = db.create_transaction()
    v = tr.get(b"k")
    assert tr.get(b"c") == b"const"
    tr.set(b"out", b"from-" + v)
    e = _conflict(cl, db, tr)
    assert e.conflicting_key_ranges == [(b"k", b"k\x00")]
    cv = e.conflict_version
    tr.on_error(e)
    assert not tr.repair_ready  # value-dependent: body re-runs
    assert tr._read_version == cv  # no GRV: anchored to the rejecter
    # cache holds the refreshed conflicting key + the verified read
    assert tr._repair_cache == {b"k": b"2", b"c": b"const"}
    v = tr.get(b"k")
    assert v == b"2"
    assert tr.get(b"c") == b"const"
    tr.set(b"out", b"from-" + v)
    tr.commit()
    assert db.get(b"out") == b"from-2"
    roll = cl.metrics_status()["rollups"]
    assert roll["repair_attempts"] == 1
    assert roll["repair_fallbacks"] == 1
    assert roll["repair_commits"] == 1


def test_spurious_conflict_replays_verbatim(cl):
    """Digest match (the conflicting write re-wrote the same value —
    a version conflict with no value change): the recorded op log
    replays; the body must NOT re-run."""
    db = cl.database()
    db.set(b"k", b"1")
    tr = db.create_transaction()
    v = tr.get(b"k")
    tr.set(b"out", b"saw-" + v)
    e = _conflict(cl, db, tr, new_value=b"1")  # same value rewritten
    tr.on_error(e)
    assert tr.repair_ready
    tr.commit()  # resubmit as-is: no body re-run
    assert db.get(b"out") == b"saw-1"
    roll = cl.metrics_status()["rollups"]
    assert roll["repair_commits"] == 1
    assert roll["repair_fallbacks"] == 0


def test_retry_loop_skips_body_on_replay(cl):
    """Database.run must not re-run the body of a replay-repaired txn
    (re-running would double-apply the restored mutations — here an
    atomic ADD would double-count)."""
    import struct

    db = cl.database()
    db.set(b"k", b"1")
    calls = []

    def fn(tr):
        calls.append(1)
        tr.get(b"k")
        tr.add(b"ctr", struct.pack("<q", 1))
        if len(calls) == 1:
            # concurrent same-value rewrite AFTER the read: the commit
            # conflicts, the repair digest matches → verbatim replay
            db.set(b"k", b"1")

    db.run(fn)
    assert calls == [1]  # one body run: the retry was the replay
    assert struct.unpack("<q", db.get(b"ctr"))[0] == 1


def test_cache_serves_nonconflicting_reads_without_storage(cl):
    """The seeded rerun's reads of resolver-verified keys never touch
    storage — the whole point of narrowing the re-read set."""
    db = cl.database()
    db.set(b"k", b"1")
    db.set(b"c", b"const")
    tr = db.create_transaction()
    tr.get(b"k")
    tr.get(b"c")
    tr.set(b"out", b"x")
    e = _conflict(cl, db, tr)
    tr.on_error(e)
    assert not tr.repair_ready
    reads = []
    orig = cl.router.get

    def counting_get(key, rv):
        reads.append(key)
        return orig(key, rv)

    cl.router.get = counting_get
    try:
        assert tr.get(b"c") == b"const"  # cache: verified at cv
        assert tr.get(b"k") == b"2"  # refreshed during repair
    finally:
        cl.router.get = orig
    assert reads == []  # not one storage round trip


def test_blanket_1020_without_conflict_info_restarts_cold(cl):
    db = cl.database()
    tr = db.create_transaction()
    tr.get(b"k")
    tr.set(b"o", b"x")
    assert not tr.try_repair(err("not_committed"))  # no report attached
    assert not tr.try_repair(err("commit_unknown_result"))


def test_repair_rounds_are_bounded():
    cl = Cluster(resolver_backend="cpu", txn_repair=True,
                 txn_repair_max_rounds=1)
    try:
        db = cl.database()
        db.set(b"k", b"1")
        tr = db.create_transaction()
        tr.get(b"k")
        tr.set(b"o", b"x")
        e1 = _conflict(cl, db, tr, new_value=b"2")
        assert tr.try_repair(e1)  # round 1: allowed
        tr.get(b"k")
        tr.set(b"o", b"x")
        e2 = _conflict(cl, db, tr, new_value=b"3")
        assert not tr.try_repair(e2)  # past the bound: cold restart
    finally:
        cl.close()


def test_unreplayable_op_log_never_replays(cl):
    """A selector read can't be re-verified at the repair version: even
    a digest-matching conflict must take the seeded-rerun path."""
    from foundationdb_tpu.core.keys import KeySelector

    db = cl.database()
    db.set(b"k", b"1")
    db.set(b"a", b"x")
    tr = db.create_transaction()
    tr.get(b"k")
    tr.get_key(KeySelector.first_greater_or_equal(b"a"))
    tr.set(b"o", b"x")
    e = _conflict(cl, db, tr, new_value=b"1")  # same-value: digest ok
    tr.on_error(e)
    assert not tr.repair_ready  # unreplayable: fell back to the rerun


def test_repair_default_on_and_knob_opt_out():
    # default ON since the defaults audit: the same-seed differential
    # (test_repair_and_scheduling_preserve_final_state) proved repaired
    # retries reach the restart loop's exact final state
    cl = Cluster(resolver_backend="cpu")
    try:
        tr = cl.database().create_transaction()
        assert tr._repair is not None
    finally:
        cl.close()
    # knob opt-out restores the restart-only client; the per-txn
    # option still opts a single transaction back in
    cl = Cluster(resolver_backend="cpu", txn_repair=False)
    try:
        tr = cl.database().create_transaction()
        assert tr._repair is None
        tr.options.set_transaction_repair()
        assert tr._repair is not None
    finally:
        cl.close()


# ─────────────────────────── satellites ────────────────────────────
def test_flat_batch_per_txn_decode_is_memoized():
    """report_conflicting_keys' flat-path per-txn decode caches on the
    batch object: repeated access must not re-parse the blobs."""
    from foundationdb_tpu.core import flatpack
    from foundationdb_tpu.core.commit import CommitRequest

    reqs = [
        CommitRequest(
            read_version=5, mutations=[],
            read_conflict_ranges=[(b"a", b"a\x00")],
            write_conflict_ranges=[(b"b", b"c")],
            flat_conflicts=flatpack.encode_conflicts(
                [(b"a", b"a\x00")], [(b"b", b"c")], 8),
        )
        for _ in range(2)
    ]
    batch = flatpack.build_flat_batch(reqs, 8)
    assert batch[1] is batch[1]  # the memo, not a fresh decode
    assert batch[0] is not batch[1]
    assert list(batch[0].read_ranges()) == [(b"a", b"a\x00")]


def test_wire_roundtrips_conflict_version():
    from foundationdb_tpu.rpc import wire

    e = FDBError(1020)
    e.conflicting_key_ranges = [(b"k", b"k\x00")]
    e.conflict_version = 1234
    d = wire.loads(wire.dumps(e))
    assert d.code == 1020
    assert d.conflicting_key_ranges == [(b"k", b"k\x00")]
    assert d.conflict_version == 1234
    # absent on errors with no report
    d2 = wire.loads(wire.dumps(FDBError(1021)))
    assert not hasattr(d2, "conflict_version")


# ──────────────────── sim differential (ISSUE 6) ───────────────────
def _run_tpcc_sim(seed, tmp_path, tag, repair, engine="memory",
                  rollups=None):
    sim = Simulation(
        seed=seed, buggify=False, crash_p=0.0, engine=engine,
        datadir=str(tmp_path / f"tpcc-{tag}"),
        commit_pipeline="manual",
        txn_repair=repair, commit_batch_scheduling=repair,
    )
    n_districts = 6
    stats = {}
    for a in range(3):
        rng = random.Random(seed * 31 + a)
        sim.add_workload(
            f"tpcc{a}",
            tpcc_workload(sim.db, n_districts, 18, rng, stats,
                          repair=repair),
        )
    sim.run()
    sim.quiesce()
    tpcc_check(sim.db, n_districts, stats)
    state = tuple(sim.db.get_range(b"tpcc/", b"tpcc0"))
    if rollups is not None:
        rollups.update(sim.cluster.metrics_status()["rollups"])
    sim.close()
    return stats, state


@pytest.mark.parametrize("engine", ["memory", "redwood"])
def test_repair_differential_serializability_equivalent(engine, tmp_path):
    """Same-seed tpcc-shaped contention, repair+scheduling ON vs the
    restart-only path: both pass the serializability-equivalence
    invariant (district counter == committed count == contiguous order
    rows) on both storage engines — and because every logical txn
    retries to completion, the final states are byte-identical."""
    s_rep, f_rep = _run_tpcc_sim(5, tmp_path, f"rep-{engine}",
                                 repair=True, engine=engine)
    s_off, f_off = _run_tpcc_sim(5, tmp_path, f"off-{engine}",
                                 repair=False, engine=engine)
    assert s_rep["committed"] == s_off["committed"] == 54
    assert f_rep == f_off
    # the repair path actually engaged: the contention produced
    # conflicts and at least some were repaired
    assert s_rep.get("conflicts", 0) > 0
    assert s_rep.get("repairs", 0) > 0


def test_repair_counters_reach_status_on_the_contended_tpcc_sim(tmp_path):
    """End to end on the contended tpcc shape: every conflict the
    clients met was a repair attempt the cluster counted, the
    value-dependent district read made some of them fall back to a
    re-run, and with repair off the counters stay at zero."""
    roll = {}
    stats, _ = _run_tpcc_sim(5, tmp_path, "roll-on", repair=True,
                             rollups=roll)
    assert stats["conflicts"] > 0
    assert roll["repair_attempts"] > 0
    assert roll["repair_attempts"] <= stats["conflicts"]
    assert 0 < roll["repair_fallbacks"] <= roll["repair_attempts"]
    assert roll["repair_commits"] > 0
    off = {}
    _run_tpcc_sim(5, tmp_path, "roll-off", repair=False, rollups=off)
    assert off["repair_attempts"] == off["repair_fallbacks"] == 0


def test_repair_sim_is_deterministic(tmp_path):
    """Two same-seed repair-on runs replay byte-identically — the
    engine draws no entropy and reads no clock (FL001)."""
    outs = [
        _run_tpcc_sim(9, tmp_path, f"det{i}", repair=True)
        for i in range(2)
    ]
    assert outs[0] == outs[1]
