"""The benchmark's range cells rehearsed on the CPU, as tier-1 tests
(owed since ISSUE 34: PERF.md §7 "Left out"): ``benchmark/run.py
--rehearse`` on the tiny twins of ``benchmark/rehearsal/``, a real
``fdbserver --resolver-backend tpu`` child on the CPU behind four client
threads, every answer held to ``benchmark/check.py``'s plain reference.

- ``rehearsal.range_rmw.c4`` (range read-modify-writes that collide) is
  ``correct``, and its traced line carries the metrics that read PR 35's
  counters; with ``--fault no_conflict`` it is not ``correct``;
- ``rehearsal.mako_range.c4``, the twin of ``mako_range.uniform.c64``
  (mako's gr / get / set / insert / cr transaction, in mako's order), is
  ``correct``.

A number of a CPU run is never a device metric: the lines say
``"rehearsal": true`` and ``"platform": "cpu"``.

Last, what these rehearsals found in the client once range reads were
no longer refused nine times in ten: a limited scan that ran out of rows
declared its conflict range only to its last row.
"""

import json
import os
import subprocess
import sys

import pytest

from foundationdb_tpu.core.errors import FDBError
from foundationdb_tpu.server.cluster import Cluster

from conftest import TEST_KNOBS

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RUN_PY = os.path.join(ROOT, "benchmark", "run.py")
CELLS = os.path.join(ROOT, "benchmark", "rehearsal", "cells.mako_range.json")
COUNTER_METRICS = ("resolver.coarse_only_conflict_pct",
                   "resolver.bucket_fullest_pct",
                   "resolver.range_entries_per_dispatch")
ANOMALIES = ("stale_reads", "phantom_rows", "batch_cycles", "wrong_rows",
             "unanswered", "compiles_in_window", "pallas_to_jit")


def run_cell(workload, seed, seconds, trace=0, fault=None):
    cmd = [sys.executable, RUN_PY, "--workload", workload, "--seed",
           str(seed), "--seconds", str(seconds), "--trace", str(trace),
           "--bench", CELLS, "--rehearse"]
    if fault:
        cmd += ["--fault", fault]
    env = {**os.environ, "PYTHONPATH": ROOT, "JAX_PLATFORMS": "cpu"}
    done = subprocess.run(cmd, capture_output=True, text=True, env=env,
                          timeout=300)
    assert done.returncode == 0, done.stderr[-2000:]
    return json.loads(done.stdout.splitlines()[-1])


@pytest.mark.parametrize("cell,kinds", [("rehearsal.range_rmw.c4", 2),
                                        ("rehearsal.mako_range.c4", 1)])
def test_a_range_cell_rehearses_correct_and_reads_the_bucket_counters(
        cell, kinds):
    line = run_cell(cell, 2**31 + 35, 4, trace=1)
    assert line["rehearsal"] is True and line["device"]["platform"] == "cpu"
    assert line["correct"] is True, {
        k: c for k, c in line["compared"].items() if c["value"]}
    assert line["attempted"] > 0 and line["failed"] == 0
    for number in ANOMALIES:
        assert line["compared"][number] == {"value": 0, "limit": 0}
    counts = line["window"]["acked_by_kind"]
    assert len(counts) == kinds and all(counts)
    metrics = line["metrics"]
    for name in COUNTER_METRICS:
        assert name in metrics, sorted(metrics)
    # the mix sent is the mix stated: a range read and a range write a
    # transaction (two reads of ranges and one write in half of
    # range_rmw's), so about two range entries a live transaction
    per_txn = (metrics["resolver.range_entries_per_dispatch"]["value"]
               / metrics["batcher.txns_per_dispatch"]["value"])
    assert 1.0 < per_txn <= 2.0, per_txn
    assert 0 <= metrics["resolver.coarse_only_conflict_pct"]["value"] < 50
    # 400 loaded keys cut the buckets: no longer one bucket for them all
    assert 0 < metrics["resolver.bucket_fullest_pct"]["value"] < 100
    # a CPU trace has no device plane: the full step's device time and
    # its roofline share are left out, never invented
    assert "range_step.device_ms" not in metrics
    assert "range_step_roofline" not in metrics


def test_the_control_reads_not_correct_under_range_read_modify_writes():
    line = run_cell("rehearsal.range_rmw.c4", 2**31 + 36, 4,
                    fault="no_conflict")
    assert line["fault"] == "no_conflict" and line["correct"] is False
    over = sum(line["compared"][n]["value"]
               for n in ("stale_reads", "phantom_rows", "batch_cycles"))
    assert over > 0, line["compared"]


def test_a_scan_that_ran_out_of_rows_conflicts_with_a_row_behind_its_last():
    for backend in ("cpu", "tpu"):
        for reverse in (False, True):
            _ran_out_of_rows(backend, reverse)


def _ran_out_of_rows(backend, reverse):
    """A ``get_range`` with a limit it did not reach has seen the whole
    of [begin, end): a record that appears behind its last row is a
    phantom, so the read conflict range runs to ``end`` (and to
    ``begin`` in reverse), not to the last row. Where the limit cut the
    scan it still ends at the last row: a record behind it is no
    business of this transaction. (Found by PR 35's range cell, whose
    scans of the table's end met an insert once in twenty rehearsals;
    until then nine range reads in ten were refused anyway.)"""
    db = Cluster(**{**TEST_KNOBS, "resolver_backend": backend}).database()
    for k in (b"r2", b"r4", b"r6"):
        db[k] = b"v"
    new = b"r1" if reverse else b"r7"  # beyond the scan's last row
    for limit, refused in ((5, True), (3, False), (2, False)):
        t1 = db.create_transaction()
        rows = t1.get_range(b"r0", b"r9", limit=limit, reverse=reverse)
        assert len(list(rows)) == min(limit, 3)
        t2 = db.create_transaction()
        t2[new] = b"x"
        t2.commit()
        t1[b"out"] = b"1"
        if refused:  # the range ran out before the limit did
            with pytest.raises(FDBError) as ei:
                t1.commit()
            assert ei.value.code == 1020
        elif backend == "cpu":
            t1.commit()
        else:  # the device may refuse more (its coarse lanes), never less
            try:
                t1.commit()
            except FDBError as e:
                assert e.code == 1020
        del db[new]
