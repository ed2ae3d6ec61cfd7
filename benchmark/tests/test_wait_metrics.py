"""PR 39's twenty per-layer metrics, held to the harness's own rules on
the CPU: ``python -m pytest benchmark/tests -q``.

- every new file under ``metrics/`` has its ``per_layer`` entry, says
  what the entry says, reads status counters (``status_delta_ratio``),
  is better lower and lists the cells ISSUE 39 gives it;
- a ``scan.`` file is another file's reading under a second name, by
  the rule ``test_benchmark_harness.py`` holds every ``like`` file to;
- each reader finds a number in the status pair of a traced rehearsal
  (the mesh router's two are rehearsed on four forced host devices by
  ``tests/test_bench_rehearsal_r4.py``, which tier-1 runs).
"""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH_DIR)
sys.path[:0] = [HERE, BENCH_DIR]

import readers  # noqa: E402
from test_benchmark_harness import read_json, run_cell  # noqa: E402

SIX = ["ycsb_a.zipf99.c64", "mako.uniform.c64", "ycsb_a.zipf99.c64.r4",
       "ycsb_b.zipf99.c64", "mako_range.uniform.c64",
       "mako_range.uniform.c64.r4"]
R4 = ["ycsb_a.zipf99.c64.r4", "mako_range.uniform.c64.r4"]
SCAN = ["ycsb_e.zipf99.c64"]
AGG = "cluster.device.aggregate."
LOCKS = "cluster.locks."

# name → (layer, source, numerator, denominator, scale, cells)
STAGES = {
    f"resolver.{st}_{k}_ms": (
        "resolver host", "program_span", f"{AGG}{st}_{k}_ms",
        AGG + "dispatches", 1.0, SIX)
    for st in ("pack", "enqueue", "readback") for k in ("cpu", "offcpu")}
ROUTE = {
    f"mesh.route_{k}_ms": (
        "mesh router (r4)", "program_span", f"{AGG}route_{k}_ms",
        AGG + "route_dispatches", 1.0, R4)
    for k in ("cpu", "offcpu")}
WAITS = {
    f"{metric}_{kind}": (
        layer, "program_counter", f"{LOCKS}{stat}.{num}",
        f"{LOCKS}{stat}.acquisitions", scale, SIX)
    for metric, layer, stat in (
        ("storage.mu", "tlog / storage", "storage_mu_read"),
        ("proxy.commit_mu", "commit proxy", "commit_mu"),
        ("grv.lock", "GRV proxy", "grv_lock"))
    for kind, num, scale in (("wait_ms", "wait_us", 0.001),
                             ("blocked_pct", "blocked", 100.0))}
READ = {**STAGES, **ROUTE, **WAITS}
MIRRORS = ["scan." + n for n in (
    "resolver.enqueue_cpu_ms", "resolver.enqueue_offcpu_ms",
    "storage.mu_wait_ms", "storage.mu_blocked_pct",
    "grv.lock_wait_ms", "grv.lock_blocked_pct")]


def spec_of(name):
    return read_json(os.path.join(BENCH_DIR, "metrics", name + ".json"))


def entry_of(name):
    bench = read_json(os.path.join(ROOT, "BENCHMARK.json"))
    (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
    return entry


def test_twenty_new_metrics_fourteen_read_and_six_mirror():
    assert len(READ) == 14 and len(MIRRORS) == 6
    bench = read_json(os.path.join(ROOT, "BENCHMARK.json"))
    names = [m["name"] for m in bench["per_layer"]]
    # appended at the end of the list, after PR 38's last
    assert set(names[-20:]) == set(READ) | set(MIRRORS)
    assert names[-21] == "rpc.deferred_replies_per_send"


@pytest.mark.parametrize("name", sorted(READ))
def test_a_new_metric_reads_the_counters_issue_39_names(name):
    layer, source, num, den, scale, cells = READ[name]
    spec, entry = spec_of(name), entry_of(name)
    assert {k: spec[k] for k in entry} == entry  # the file says the same
    assert (spec["layer"], spec["source"], spec["better"], spec["moves"]) \
        == (layer, source, "lower", "ops_per_s")
    assert spec["unit"] == ("%" if name.endswith("_pct") else "ms")
    assert spec["reader"] == "status_delta_ratio" and "like" not in spec
    assert spec["args"] == {"num": [num], "den": [den], **(
        {"scale": scale} if scale != 1.0 else {})}
    assert spec["workloads"] == cells


@pytest.mark.parametrize("name", MIRRORS)
def test_a_scan_mirror_passes_the_harness_like_rule(name):
    spec, entry = spec_of(name), entry_of(name)
    assert {k: spec[k] for k in entry} == entry
    base = spec_of(spec["like"])
    assert name == "scan." + base["name"] and base["name"] in READ
    assert {k: base[k] for k in ("layer", "unit", "better", "source")} \
        == {k: spec[k] for k in ("layer", "unit", "better", "source")}
    assert spec["moves"] == "read_p95_ms" != base["moves"]
    assert "reader" not in spec and "args" not in spec
    assert spec["workloads"] == SCAN
    assert not set(SCAN) & set(base["workloads"])


def test_the_readers_leave_a_metric_out_where_the_program_has_no_counter():
    """The parent commit's status has no ``cluster.locks`` and no split
    sums: every new reader returns nothing there and raises nothing."""
    old = {"cluster": {"device": {"aggregate": {
        "dispatches": 5, "route_dispatches": 5, "pack_wall_ms": 1.0}}}}
    new = {"cluster": {"device": {"aggregate": {
        "dispatches": 9, "route_dispatches": 9, "pack_wall_ms": 2.0}}}}
    ev = {"status0": old, "status1": new}
    for name in READ:
        assert readers.read_metric(spec_of(name), ev) is None, name


def test_the_new_readers_find_a_number_in_a_traced_rehearsal(tmp_path):
    cell = "rehearsal.ycsb.c4"
    reh = os.path.join(BENCH_DIR, "rehearsal")
    bench = read_json(os.path.join(reh, "cells.json"))
    for c in bench["configs"]:
        c["file"] = os.path.join(ROOT, c["file"])
    (tmp_path / "traffic").mkdir()
    (tmp_path / "traffic" / (cell + ".json")).write_text(json.dumps(
        read_json(os.path.join(reh, "traffic", cell + ".json"))))
    one_lane = [n for n in READ if n not in ROUTE] + MIRRORS
    for name in one_lane:
        bench["per_layer"].append({**entry_of(name), "workloads": [cell]})
    path = tmp_path / "cells.json"
    path.write_text(json.dumps(bench))
    line, _ = run_cell(os.path.join(BENCH_DIR, "run.py"), str(path), cell,
                       2**31 + 39, 3, trace=1)
    assert line["correct"] is True, line["compared"]
    metrics = line["metrics"]
    assert set(one_lane) <= set(metrics)
    for name in one_lane:
        assert isinstance(metrics[name]["value"], float), name
        assert metrics[name]["value"] >= 0, name
    for st in ("pack", "enqueue", "readback"):  # two parts of one wall
        assert metrics[f"resolver.{st}_cpu_ms"]["value"] > 0
    assert metrics["scan.grv.lock_wait_ms"]["value"] \
        == metrics["grv.lock_wait_ms"]["value"]
    assert metrics["storage.mu_blocked_pct"]["value"] <= 100
