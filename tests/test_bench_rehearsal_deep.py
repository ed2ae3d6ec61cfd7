"""The deep-batch cell (``ycsb_a.zipf99.c512``) rehearsed on the CPU, as
a tier-1 test: ``benchmark/run.py --rehearse`` on its twin
``rehearsal.ycsb_deep.c64`` (``benchmark/rehearsal/cells.deep.json``),
a real ``fdbserver --resolver-backend tpu --commit-proxies 3`` child on
the CPU behind sixty-four client threads over a table of 2,000 rows,
every answer held to ``benchmark/check.py``'s plain reference.

The twin runs the cell's layout (three commit proxies, each with its own
batcher, whose batches meet at the one resolver in version order) and
sends the cell's mix (YCSB-A: half reads, half read-modify-writes of one
key, scrambled Zipfian 0.99): a proxy's batch holds a few updates of the
same hot key, and all but one of them are refused by the batch itself.
No speed is read here, and no number of a CPU run is a device metric:
the line says ``"rehearsal": true`` and ``"platform": "cpu"``.
"""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RUN_PY = os.path.join(ROOT, "benchmark", "run.py")
CELLS = os.path.join(ROOT, "benchmark", "rehearsal", "cells.deep.json")
CELL = "rehearsal.ycsb_deep.c64"
ANOMALIES = ("stale_reads", "batch_cycles", "lost_updates", "wrong_rows",
             "unanswered", "compiles_in_window", "pallas_to_jit")


def rehearse(trace, fault=None):
    cmd = [sys.executable, RUN_PY, "--workload", CELL, "--seed",
           str(2**31 + 45), "--seconds", "4", "--trace", str(trace),
           "--bench", CELLS, "--rehearse"]
    if fault:
        cmd += ["--fault", fault]
    env = {**os.environ, "PYTHONPATH": ROOT, "JAX_PLATFORMS": "cpu"}
    done = subprocess.run(cmd, capture_output=True, text=True, env=env,
                          timeout=300)
    assert done.returncode == 0, done.stderr[-2000:]
    return json.loads(done.stdout.splitlines()[-1])


def test_the_deep_batch_cell_rehearses_correct_with_its_metrics():
    line = rehearse(trace=1)
    assert line["rehearsal"] is True and line["device"]["platform"] == "cpu"
    assert line["correct"] is True, {
        k: c for k, c in line["compared"].items() if c["value"]}
    assert line["attempted"] > 0 and line["failed"] == 0
    for number in ANOMALIES:
        assert line["compared"][number] == {"value": 0, "limit": 0}
    metrics = line["metrics"]
    # the twin reads what the cell reads, but what reads the device's
    # plane: a CPU has none
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    mine = {m["name"] for m in bench["per_layer"]
            if "ycsb_a.zipf99.c512" in m["workloads"]}
    traced = {"deep.resolve_step.device_ms", "deep.resolve_step_roofline",
              "deep.device.idle_share", "deep.device.idle_no_request_pct"}
    assert traced < mine and set(metrics) == mine - traced, \
        sorted(mine ^ set(metrics))
    # a batch holds several updates: some of them collide on a hot key,
    # and the batch refuses all but the first
    assert metrics["deep.batcher.txns_per_dispatch"]["value"] > 2
    in_batch = metrics["resolver.batch_conflict_pct"]["value"]
    assert 0 < in_batch < metrics["deep.resolver.conflict_share"]["value"]


# the control and the fault the batch's own refusals are there for: with
# three proxies' batches interleaved at the resolver, ``correct`` still
# comes out false when the resolver checks nothing, or skips the pass
# over the batch itself
@pytest.mark.parametrize("fault,numbers", [
    ("no_conflict", ["stale_reads"]),
    ("no_intra_batch", ["batch_cycles", "lost_updates"]),
])
def test_a_fault_on_the_deep_batch_twin_reads_not_correct(fault, numbers):
    line = rehearse(trace=0, fault=fault)
    assert line["fault"] == fault and line["correct"] is False
    for number in numbers:
        c = line["compared"][number]
        assert c["value"] > c["limit"], (number, c)
