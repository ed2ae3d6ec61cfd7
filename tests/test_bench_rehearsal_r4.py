"""A CPU rehearsal of the four-lane cell (``ycsb_a.zipf99.c64.r4``): the
benchmark's harness as it stands, a four-lane copy of its rehearsal
config as new files in a scratch directory, four forced host devices.
No speed is read here: that the cell runs, is ``correct``, and prints
the router's per-layer metrics in its traced line.
"""

import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
CELL = "rehearsal.ycsb.c4.r4"
# PR 39's metrics of a four-lane cell beside ``mesh.route_{cpu,offcpu}_ms``:
# a stage's wall split by the dispatching thread's CPU clock, and the
# counted acquisitions of the three mutexes
SPLIT = {"resolver.pack": "pack_wall_ms", "resolver.enqueue":
         "enqueue_wall_ms", "resolver.readback": "verdict_reduce_wall_ms"}
LOCKS = ("storage.mu", "proxy.commit_mu", "grv.lock")
WAITS = ([f"{s}_{k}_ms" for s in SPLIT for k in ("cpu", "offcpu")]
         + [f"{m}_{k}" for m in LOCKS for k in ("wait_ms", "blocked_pct")])


def read_json(path):
    with open(path) as f:
        return json.load(f)


def four_lane_bench(tmp_path):
    """``rehearsal/cells.json`` plus one config, one cell and the
    mesh's metrics, as files of their own → the path of the list."""
    bench = read_json(os.path.join(BENCH, "rehearsal", "cells.json"))
    config = read_json(os.path.join(
        BENCH, "rehearsal", "configs", "rehearsal_ycsb.json"))
    flags = config["server"]["flags"]
    flags[flags.index("--resolvers") + 1] = "4"
    config.update(name="rehearsal_ycsb_r4", chips=4)
    config_path = tmp_path / "rehearsal_ycsb_r4.json"
    config_path.write_text(json.dumps(config))
    (tmp_path / "traffic").mkdir()
    (tmp_path / "traffic" / "rehearsal.ycsb.c4.json").write_text(json.dumps(
        read_json(os.path.join(BENCH, "rehearsal", "traffic",
                               "rehearsal.ycsb.c4.json"))))
    bench["configs"].append({
        "name": "rehearsal_ycsb_r4", "source": "rehearsal",
        "file": str(config_path), "reduced": [], "why": "rehearsal"})
    bench["workloads"].append({
        "name": CELL, "config": "rehearsal_ycsb_r4",
        "traffic": "rehearsal.ycsb.c4", "chips": 4, "why": "rehearsal"})
    for m in read_json(os.path.join(ROOT, "BENCHMARK.json"))["per_layer"]:
        if m["name"].startswith("mesh") or m["name"] in WAITS:
            bench["per_layer"].append({**m, "workloads": [CELL]})
    path = tmp_path / "cells.json"
    path.write_text(json.dumps(bench))
    return str(path)


def run_cell(bench, devices, trace):
    env = {**os.environ, "PYTHONPATH": ROOT, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": f"--xla_force_host_platform_device_count={devices}"}
    return subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", str(2**31 + 30), "--seconds", "3", "--trace", str(trace),
         "--bench", bench, "--rehearse"],
        capture_output=True, text=True, env=env, timeout=300)


def test_the_four_lane_cell_rehearses_correct_with_the_routers_metrics(
        tmp_path):
    done = run_cell(four_lane_bench(tmp_path), devices=4, trace=1)
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.splitlines()[-1])
    assert line["correct"] is True, line["compared"]
    assert line["rehearsal"] is True and line["failed"] == 0
    assert line["device"]["count"] == 4
    assert line["compared"]["compiles_in_window"] == {"value": 0, "limit": 0}
    metrics = line["metrics"]
    assert metrics["mesh.route_ms"]["value"] > 0
    assert metrics["mesh.slices_per_dispatch"]["value"] >= 1.0
    # this table's 2,000 rows stay under the lane rule's first look
    # (4,096 sampled rows: resolver/packing.py LaneBounds), so three
    # seconds on four CPU devices show the bounds a mesh starts with:
    # every user... key has one lane of the uniform first-limb split
    # (tests/test_bench_rehearsal_range4.py rehearses a table that is
    # cut). The entries beside them are the server's own keys, which
    # come by the clock: on a busy CPU the traced seconds hold ten
    # routed entries and one or two of those (80.0-90.0 under fourteen
    # spinning processes, which is what failed the driver's run of PR 30)
    assert 50.0 < metrics["mesh.fullest_lane_pct"]["value"] <= 100.0
    # no bound moved, so no fence refused anyone; and YCSB sends no range
    assert metrics["mesh.rebound_fenced_pct"]["value"] == 0
    assert "mesh.range_dup_pct" not in metrics
    # a CPU has no device plane: the trace's metrics stay out of the line
    assert "mesh_step.device_ms" not in metrics
    # fourteen numbers a four-lane cell: off-CPU is what the CPU sum
    # (one dispatch in two read, counted twice) leaves of the route's
    # wall, so the two are its parts; and every lock reads
    assert set(WAITS) <= set(metrics)
    route, on, off = (metrics[m]["value"] for m in (
        "mesh.route_ms", "mesh.route_cpu_ms", "mesh.route_offcpu_ms"))
    assert on > 0 and abs(off - max(0.0, route - on)) <= 0.02 * route
    for m in LOCKS:
        assert metrics[m + "_wait_ms"]["value"] >= 0
        assert 0 <= metrics[m + "_blocked_pct"]["value"] <= 100


def test_a_cell_whose_server_sees_fewer_devices_than_chips_is_refused(
        tmp_path):
    done = run_cell(four_lane_bench(tmp_path), devices=1, trace=0)
    assert done.returncode != 0
    assert "4 chips asked for, the server sees 1" in done.stderr
    assert not done.stdout.strip()
