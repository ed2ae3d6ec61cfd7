"""The four-lane range cell (``mako_range.uniform.c64.r4``) rehearsed on
the CPU, as tier-1 tests: ``benchmark/run.py --rehearse`` on its twin
``rehearsal.mako_range.c16.r4`` (``benchmark/rehearsal/
cells.mako_range_r4.json``), a real ``fdbserver --resolver-backend tpu
--resolvers 4`` child on four forced host devices behind sixteen client
threads, every answer held to ``benchmark/check.py``'s plain reference.

The twin loads 6,000 rows in key order, which fills the lane rule's
sample and cuts nothing (resolver/packing.py ``LaneBounds``); the
warm-up's traffic then cuts the bounds behind a fence, and the window
routes mako's range transaction over four lanes. No speed is read here,
and no number of a CPU run is a device metric: the line says
``"rehearsal": true`` and ``"platform": "cpu"``.
"""

import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RUN_PY = os.path.join(ROOT, "benchmark", "run.py")
CELLS = os.path.join(ROOT, "benchmark", "rehearsal",
                     "cells.mako_range_r4.json")
CELL = "rehearsal.mako_range.c16.r4"
ANOMALIES = ("stale_reads", "phantom_rows", "batch_cycles", "wrong_rows",
             "unanswered", "compiles_in_window", "pallas_to_jit")


def run_cell(seed, seconds, trace=0, fault=None):
    cmd = [sys.executable, RUN_PY, "--workload", CELL, "--seed", str(seed),
           "--seconds", str(seconds), "--trace", str(trace),
           "--bench", CELLS, "--rehearse"]
    if fault:
        cmd += ["--fault", fault]
    env = {**os.environ, "PYTHONPATH": ROOT, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    done = subprocess.run(cmd, capture_output=True, text=True, env=env,
                          timeout=400)
    assert done.returncode == 0, done.stderr[-2000:]
    return json.loads(done.stdout.splitlines()[-1])


def test_the_four_lane_range_cell_rehearses_correct_with_its_metrics():
    line = run_cell(2**31 + 37, 4, trace=1)
    assert line["rehearsal"] is True and line["device"]["platform"] == "cpu"
    assert line["device"]["count"] == 4
    assert line["correct"] is True, {
        k: c for k, c in line["compared"].items() if c["value"]}
    assert line["attempted"] > 0 and line["failed"] == 0
    for number in ANOMALIES:
        assert line["compared"][number] == {"value": 0, "limit": 0}
    metrics = line["metrics"]
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    mine = {m["name"] for m in bench["per_layer"]
            if "mako_range.uniform.c64.r4" in m["workloads"]}
    # a CPU has no device plane: what reads the trace stays out
    traced = {"range4.mesh_step.device_ms", "range4.mesh_step_roofline",
              "range4.device.idle_share"}
    assert traced < mine and not traced & set(metrics)
    # PR 38's two list the cell and not its twin: a PR that claims a
    # gain adds metric files and edits no file the benchmark has
    # (tests/test_rpc.py holds the counters they read)
    untwinned = {"rpc.commit.deferred_share_pct",
                 "rpc.deferred_replies_per_send"}
    # so do PR 39's fourteen, the stages' CPU split and the lock waits
    # (tests/test_bench_rehearsal_r4.py rehearses them on four lanes)
    untwinned |= {
        f"{stage}_{part}_ms" for stage in (
            "resolver.pack", "resolver.enqueue", "resolver.readback",
            "mesh.route") for part in ("cpu", "offcpu")} | {
        f"{lock}_{what}" for lock in (
            "storage.mu", "proxy.commit_mu", "grv.lock")
        for what in ("wait_ms", "blocked_pct")}
    assert untwinned < mine
    assert set(metrics) == mine - traced - untwinned, \
        sorted((mine - untwinned) ^ set(metrics))
    # both of this PR's counters are read: a fence costs its round in
    # the warm-up, and a range of 20 rows among 12,000 slots crosses one
    # of three bounds now and then
    assert 0 <= metrics["mesh.rebound_fenced_pct"]["value"] < 100
    assert 0 <= metrics["mesh.range_dup_pct"]["value"] < 50
    assert metrics["range4.mesh.route_ms"]["value"] > 0
    assert metrics["range4.mesh.slices_per_dispatch"]["value"] >= 1.0
    assert 25.0 < metrics["range4.mesh.fullest_lane_pct"]["value"] <= 100.0
    # the mix sent is the mix stated: a range read and a range write a
    # transaction
    per_txn = (metrics["range4.resolver.range_entries_per_dispatch"]["value"]
               / metrics["range4.batcher.txns_per_dispatch"]["value"])
    assert 1.0 < per_txn <= 2.0, per_txn


def test_the_control_reads_not_correct_behind_four_lanes():
    line = run_cell(2**31 + 38, 6, fault="no_conflict")
    assert line["fault"] == "no_conflict" and line["correct"] is False
    assert line["device"]["count"] == 4
    over = sum(line["compared"][n]["value"]
               for n in ("stale_reads", "phantom_rows", "batch_cycles"))
    assert over > 0, line["compared"]
