"""The harness held to its contract on the CPU: ``python -m pytest
benchmark/tests -q`` (about a minute; no chip, no topology call).

- the contract's last line, from a cell that exists only as new files in
  a copy of ``benchmark/`` (a config, a mix, a status metric and one
  ``workloads`` entry): what a later PR may add without editing;
- the control and the faults of ``faults.py``: ``correct`` comes out
  false with the served path broken underneath;
- ``BENCHMARK.json``'s names, units and ``moves``;
- ``check.replay``, ``tracereduce.reduce_events`` and the roofline's
  bytes by hand-computed values.
"""

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH_DIR)
sys.path.insert(0, BENCH_DIR)

import check  # noqa: E402
import readers  # noqa: E402
import tracereduce  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
REHEARSAL = os.path.join(BENCH_DIR, "rehearsal", "cells.json")


def read_json(path):
    with open(path) as f:
        return json.load(f)


def run_cell(run_py, bench, workload, seed, seconds, trace=0, fault=None):
    cmd = [sys.executable, run_py, "--workload", workload, "--seed",
           str(seed), "--seconds", str(seconds), "--trace", str(trace),
           "--bench", bench, "--rehearse"]
    if fault:
        cmd += ["--fault", fault]
    env = {**os.environ, "PYTHONPATH": ROOT, "JAX_PLATFORMS": "cpu"}
    done = subprocess.run(cmd, capture_output=True, text=True, env=env,
                          timeout=300)
    assert done.returncode == 0, done.stderr[-2000:]
    return json.loads(done.stdout.splitlines()[-1]), done.stderr


# ── the contract's line, from a cell added as new files only ────────
def test_a_cell_added_as_new_files_runs_and_prints_the_contract_line(tmp_path):
    copy = tmp_path / "benchmark"
    shutil.copytree(BENCH_DIR, copy, ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    before = {p: p.read_bytes() for p in copy.rglob("*") if p.is_file()}
    reh = copy / "rehearsal"
    config = read_json(reh / "configs" / "rehearsal_mako.json")
    config.update(name="added_mako", rows=1500)
    (copy / "configs" / "added_mako.json").write_text(json.dumps(config))
    mix = read_json(reh / "traffic" / "rehearsal.mako.c4.json")
    mix.update(name="added.mix", client_processes=1, client_threads=3)
    mix["operations"] = [  # a mix of its own, from the steps there are
        {"name": "g2s1", "weight": 3,
         "steps": [["get", "a"], ["get", "b"], ["set", "b"]]},
        {"name": "read", "weight": 1, "steps": [["get", "a"]]}]
    mix["keys"] = {"distribution": "uniform", "draw": "iid"}
    (copy / "traffic" / "added.mix.json").write_text(json.dumps(mix))
    (copy / "metrics" / "added.reads.json").write_text(json.dumps({
        "name": "added.reads", "layer": "tlog / storage", "unit": "reads",
        "better": "higher", "source": "program_counter",
        "moves": "ops_per_s", "reader": "status_delta_ratio",
        "args": {"num": ["cluster.metrics.rollups.batched_reads"],
                 "den": ["cluster.device.aggregate.dispatches"]},
        "workloads": ["added.cell"]}))
    bench = read_json(reh / "cells.json")
    bench["configs"].append({
        "name": "added_mako", "source": "test", "reduced": [], "why": "test",
        "file": "benchmark/configs/added_mako.json"})
    bench["workloads"].append({
        "name": "added.cell", "config": "added_mako", "traffic": "added.mix",
        "chips": 1, "why": "test"})
    for m in bench["per_layer"] + bench["end_to_end"]:
        if "rehearsal.mako.c4" in m.get("workloads", []):
            m["workloads"].append("added.cell")
    bench["per_layer"].append({
        "name": "added.reads", "unit": "reads", "better": "higher",
        "source": "program_counter", "layer": "tlog / storage",
        "moves": "ops_per_s", "workloads": ["added.cell"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    # nothing that was there has been edited
    assert all(p.read_bytes() == b for p, b in before.items())

    run_py = str(copy / "run.py")
    bench_path = str(tmp_path / "BENCHMARK.json")
    line, err = run_cell(run_py, bench_path, "added.cell", 2**31 + 77, 2,
                         trace=1)
    assert list(line)[:3] == ["correct", "attempted", "failed"]
    assert {"metrics", "device", "breakdown", "compared"} <= set(line)
    assert list(line)[-1] == "compared"
    assert line["correct"] is True and line["rehearsal"] is True
    assert line["device"]["platform"] == "cpu"  # never a chip claim
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["metrics"]["added.reads"]["value"] > 0
    assert line["metrics"]["client.update_p50_ms"]["value"] > 0
    assert "batcher.txns_per_dispatch" in line["metrics"]
    # a CPU trace has no device plane: no device number is invented
    assert "device.idle_share" not in line["metrics"]
    assert "busy_s" not in line["device"]
    for c in line["compared"].values():
        assert set(c) == {"value", "limit"}
    assert err.rstrip().splitlines()[-1].startswith("compared ")

    line, _ = run_cell(run_py, bench_path, "added.cell", 5, 2, trace=0)
    assert set(line["metrics"]) == {"ops_per_s", "update_p95_ms", "setup_s"}
    assert line["window"]["conflicts_per_commit_attempt"] < 0.5
    assert all(m["value"] > 0 for m in line["metrics"].values())


# ── the control and the faults: correct must come out false ─────────
@pytest.mark.parametrize("cell,fault,numbers,blind", [
    # the control: no serializability
    ("rehearsal.ycsb.c4", "no_conflict", ["stale_reads"], []),
    # conflicts missed inside one batch only: two updates of one key
    # write the same bytes, so the reads and the rows say nothing
    ("rehearsal.ycsb.c4", "no_intra_batch", ["batch_cycles", "lost_updates"],
     ["stale_reads", "wrong_rows"]),
    # … and a write skew between blind sets, which only an order shows
    ("rehearsal.skew.c8", "no_intra_batch", ["batch_cycles"],
     ["stale_reads", "wrong_rows"]),
    # a step that leaves state unchanged
    ("rehearsal.ycsb.c4", "drop_apply", ["wrong_rows"], []),
    # an answer altered where produced
    ("rehearsal.ycsb.c4", "alter_read", ["stale_reads"], []),
])
def test_a_fault_under_the_served_path_reads_not_correct(
        cell, fault, numbers, blind):
    line, err = run_cell(os.path.join(BENCH_DIR, "run.py"), REHEARSAL,
                         cell, 2**31 + 11, 4, fault=fault)
    assert line["fault"] == fault
    assert line["correct"] is False
    for number in numbers:
        c = line["compared"][number]
        assert c["value"] > c["limit"]
        assert f"compared {number}: {c['value']} (limit {c['limit']})" in err
    for number in blind:
        assert line["compared"][number]["value"] == 0


def test_the_skew_rehearsal_is_correct_without_a_fault():
    line, _ = run_cell(os.path.join(BENCH_DIR, "run.py"), REHEARSAL,
                       "rehearsal.skew.c8", 2**31 + 11, 3)
    assert line["correct"] is True
    assert line["compared"]["batch_cycles"] == {"value": 0, "limit": 0}


# ── BENCHMARK.json: names, units, moves ─────────────────────────────
def test_benchmark_json_names_units_and_moves():
    bench = read_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    configs = {c["name"]: c for c in bench["configs"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    names = (list(cells) + list(configs) + list(e2e)
             + [m["name"] for m in bench["per_layer"]]
             + [w["traffic"] for w in cells.values()]
             + [k for c in configs.values() for k in c["reduced"]])
    assert all(NAME.match(n) for n in names), names
    assert len(set(cells)) == len(bench["workloads"])
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= set(cells)
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    assert all(0.01 <= m["bound"] <= 0.25 for m in e2e.values())
    for w in cells.values():
        assert w["config"] in configs and w["chips"] in (1, 4)
        # setup_s, one more end-to-end metric, and a layer's
        mine = [m["name"] for m in bench["end_to_end"]
                if w["name"] in m.get("workloads", cells)]
        assert "setup_s" in mine and len(mine) >= 2
        assert any(w["name"] in m["workloads"] for m in bench["per_layer"])
        assert os.path.exists(os.path.join(
            BENCH_DIR, "traffic", w["traffic"] + ".json"))
    for c in configs.values():
        doc = read_json(os.path.join(ROOT, c["file"]))
        assert doc["name"] == c["name"] and doc["source"] == c["source"]
        assert set(c["reduced"]) == set(doc["reduced"])
        assert c["file"].startswith(bench["paths"][0] + "/")
    for m in bench["per_layer"]:
        spec = read_json(os.path.join(BENCH_DIR, "metrics",
                                      m["name"] + ".json"))
        assert {k: spec[k] for k in m} == m  # the file says the same
        if "like" in spec:  # another metric's reading, moving another
            base = read_json(os.path.join(BENCH_DIR, "metrics",
                                          spec["like"] + ".json"))
            assert m["name"] == "scan." + base["name"]
            assert {k: base[k] for k in ("layer", "unit", "better", "source")
                    } == {k: m[k] for k in ("layer", "unit", "better",
                                            "source")}
            assert base["moves"] != m["moves"] and "reader" not in spec
            assert not set(m["workloads"]) & set(base["workloads"])
            spec = base
        assert spec["reader"] in readers.READERS
        moved = e2e[m["moves"]]
        # every cell of the metric reports the metric it moves
        assert set(m["workloads"]) <= set(moved.get("workloads", cells))
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    peaks = read_json(os.path.join(BENCH_DIR, "peaks.json"))
    assert peaks["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9


# ── the plain reference, by hand ────────────────────────────────────
def op(t0, t1, rv, cv, reads, writes, status=check.OK):
    return [0, t0, t1, status, 0, 0, rv, cv, reads, writes]


def replay(ops, final=None):
    numbers, _ = check.replay(ops, lambda k: 100 + k, final or {})
    return numbers


def test_replay_accepts_a_serial_history():
    ops = [op(0, 1, 10, 20, [[1, 101]], [[1, 7]]),
           op(2, 3, 20, 30, [[1, 7]], [[1, 8]]),
           op(4, 5, 30, 0, [[1, 8]], []),  # read-only at rv 30
           op(1.5, 5, 25, 0, [[1, 7]], [])]  # began before cv 30 was acked
    n = replay(ops, {1: 8, 2: 102})
    assert (n["stale_reads"], n["wrong_rows"], n["stale_read_versions"]) \
        == (0, 0, 0)
    assert n["reads_compared"] == 4 and n["rows_compared"] == 2


def test_replay_sees_a_lost_update_a_lost_write_and_a_stale_version():
    # both read 101 and both committed: the second missed its conflict
    lost = [op(0, 1, 10, 20, [[1, 101]], [[1, 7]]),
            op(0, 2, 10, 30, [[1, 101]], [[1, 8]])]
    assert replay(lost)["stale_reads"] == 1
    assert replay(lost[:1], {1: 101})["wrong_rows"] == 1  # write not there
    # began after cv 20 was acknowledged, yet read at 15
    late = [op(0, 1, 10, 20, [], [[1, 7]]), op(2, 3, 15, 0, [[1, 101]], [])]
    assert replay(late)["stale_read_versions"] == 1


def test_replay_sees_a_conflict_missed_inside_one_batch():
    # two updates of one key in one batch: both read count 0 (token
    # 101), both wrote count 1 (token 7): the same bytes
    counted = {0: 101, 1: 7, 2: 8}
    twice = [op(0, 1, 10, 20, [[1, 101]], [[1, 7]]),
             op(0, 1, 10, 20, [[1, 101]], [[1, 7]])]
    n, _ = check.replay(twice, lambda k: 100 + k, {1: 7},
                        counted_token=lambda k, c: counted[c])
    assert (n["stale_reads"], n["wrong_rows"]) == (0, 0)  # blind to it
    assert (n["batch_cycles"], n["lost_updates"]) == (2, 1)
    # the same two in two batches, the second having read the first
    serial = [twice[0], op(2, 3, 20, 30, [[1, 7]], [[1, 8]])]
    n, _ = check.replay(serial, lambda k: 100 + k, {1: 8},
                        counted_token=lambda k, c: counted[c])
    assert (n["batch_cycles"], n["lost_updates"]) == (0, 0)
    # an update that ended unknown may or may not count
    maybe = [twice[0], op(2, 3, 0, 0, [[1, 7]], [[1, 8]],
                          status=check.UNKNOWN)]
    for final in (7, 8):
        n, _ = check.replay(maybe, lambda k: 100 + k, {1: final},
                            counted_token=lambda k, c: counted[c])
        assert n["lost_updates"] == 0
    # write skew in one batch: each read what the other wrote
    skew = [op(0, 1, 10, 20, [[1, 101]], [[2, 7]]),
            op(0, 1, 10, 20, [[2, 102]], [[1, 8]])]
    assert replay(skew)["batch_cycles"] == 2
    assert replay(skew)["stale_reads"] == 0
    # one batch that an order does hold: a reads 1 and writes 2, b reads
    # 3 and writes 1 (a before b), c rewrites what only it read; and a
    # reader of what a cycle's member wrote is not itself on the cycle
    fine = [op(0, 1, 10, 20, [[1, 101]], [[2, 7]]),
            op(0, 1, 10, 20, [[3, 103]], [[1, 8]]),
            op(0, 1, 10, 20, [[5, 105]], [[5, 9]])]
    assert check.unorderable(fine) == 0
    assert check.unorderable(
        skew + [op(0, 1, 10, 20, [[1, 101]], [[7, 7]]),
                op(0, 1, 10, 20, [[7, 107]], [])]) == 2


def test_replay_ties_unknowns_and_the_unanswered():
    # one batch (cv 20) blindly wrote key 1 twice: either may stand …
    tie = [op(0, 1, 10, 20, [], [[1, 7]]), op(0, 1, 10, 20, [], [[1, 8]])]
    assert replay(tie, {1: 7})["wrong_rows"] == 0
    assert replay(tie, {1: 8})["wrong_rows"] == 0
    assert replay(tie, {1: 101})["wrong_rows"] == 1
    # … until a read settles it
    settled = tie + [op(2, 3, 20, 0, [[1, 8]], [])]
    assert replay(settled, {1: 7})["wrong_rows"] == 1
    # a 1021 may or may not have applied
    unknown = [op(0, 1, 10, 0, [], [[1, 9]], status=check.UNKNOWN)]
    assert replay(unknown, {1: 9})["wrong_rows"] == 0
    assert replay(unknown, {1: 101})["wrong_rows"] == 0
    assert replay([op(0, 40, 0, 0, [], [], status=check.LATE)])[
        "unanswered"] == 1
    ok, compared = check.verdict({"stale_reads": 0, "reads_compared": 0})
    assert not ok  # a check that compared nothing is not a pass
    assert compared["reads_compared"] == {"value": 0, "limit": 1}


# ── the traffic: both draws keep the source's marginals ─────────────
@pytest.mark.parametrize("draw", ["iid", "stratified"])
def test_op_stream_draws_the_mix_and_the_keys(draw):
    import datagen

    rows = 1000
    traffic = {"operations": [{"weight": 0.5}, {"weight": 0.5}],
               "keys": {"distribution": "zipfian", "theta": 0.99,
                        "scramble": True, "draw": draw, "block": 64}}
    kinds, a, b = datagen.op_stream(traffic, rows, 2**31 + 5, 0, 0)
    assert len(kinds) == len(a) == len(b) == datagen.STREAM
    assert abs(sum(kinds) / len(kinds) - 0.5) < 0.01
    cdf = datagen._zipfian_cdf(rows, 0.99)
    hottest = max(set(a), key=a.count)
    assert abs(a.count(hottest) / len(a) - cdf[0]) < 0.01
    again = datagen.op_stream(traffic, rows, 2**31 + 5, 0, 0)
    assert again == (kinds, a, b)  # the same seed, the same stream
    other = datagen.op_stream(traffic, rows, 2**31 + 5, 0, 1)
    assert other[1] != a  # another thread, another stream


# ── the trace reduction and the roofline's bytes, by hand ───────────
def test_reduce_events_busy_idle_and_programs():
    dev, ops, mods = "/device:TPU:0", "XLA Ops", "XLA Modules"
    events = [
        (dev, ops, "fusion.1", 0, 100), (dev, ops, "fusion.2", 50, 100),
        (dev, ops, "fusion.1", 1000, 200),  # gap 150..1000 = 850 ns
        (dev, ops, "copy", 2000, 50),  # gap 1200..2000 = 800 ns
        (dev, mods, "jit__lambda(123)", 0, 150),
        (dev, mods, "jit__lambda(123)", 1000, 250),
        (dev, mods, "jit_scan_step(9)", 2000, 50),
    ]
    out = tracereduce.reduce_events(events, window_s=1e-5)
    assert out["events_span_s"] == pytest.approx(2050e-9)
    assert out["busy_s"] == pytest.approx(400e-9)  # 150 + 200 + 50
    assert out["programs"]["jit__lambda"] == {
        "count": 2, "total_s": pytest.approx(400e-9)}
    assert out["programs"]["jit_scan_step"]["count"] == 1
    assert out["device_ops"][0] == ["fusion.1", pytest.approx(300e-9)]
    assert [g for _, g in out["idle_gaps"]] == [
        pytest.approx(850e-9), pytest.approx(800e-9)]
    assert out["idle_gaps"][0][0] == "host.unattributed"
    ev = {"trace": out}
    assert readers.trace_idle_share(ev) == pytest.approx(1 - 400e-9 / 1e-5)
    assert readers.trace_program_mean_ms(ev, ["jit__lambda"]) \
        == pytest.approx(200e-6)
    # nothing to read is nothing, never 0
    empty = tracereduce.reduce_events([], window_s=2.0)
    assert "busy_s" not in empty
    assert readers.trace_idle_share({"trace": empty}) is None
    assert readers.trace_program_mean_ms({"trace": empty}, ["x"]) is None


def test_roofline_bytes_and_share():
    def status(pr, pw, rr, rw, txns):
        return {"cluster": {"device": {"aggregate": {
            "entries_live": {"pr": pr, "pw": pw, "rr": rr, "rw": rw},
            "txns_live": txns}}}}

    ev = {"status0": status(10, 10, 0, 0, 10),
          "status1": status(110, 110, 2, 1, 110),
          "trace": {"programs": {"jit__lambda": {"count": 4,
                                                 "total_s": 1e-3}}},
          "peaks": {"hbm_bytes_per_s": 819e9}}
    # 100 point reads x 36 + 100 point writes x 40 + 3 ranges x 72
    # + 100 verdicts x 4, with 8 limbs
    need = 100 * 36 + 100 * 40 + 3 * 72 + 100 * 4
    assert readers.conflict_bytes(ev, key_limbs=8) == need == 8216
    share = readers.resolve_roofline_pct(ev, ["jit__lambda"], key_limbs=8)
    assert share == pytest.approx(100 * (need / 819e9) / 1e-3)
    ev["trace"] = {"programs": {}}
    assert readers.resolve_roofline_pct(ev, ["jit__lambda"], 8) is None
    # a mean over the traced seconds alone, from counts and running means
    def band(count, mean):
        return {"logs": [{"push": {"count": count, "mean_ms": mean}}]}

    bands = {"status0": band(100, 2.0), "status1": band(150, 3.0)}
    assert readers.status_delta_mean(
        bands, "logs.0.push.count", "logs.0.push.mean_ms") \
        == pytest.approx((450 - 200) / 50)
    bands["status1"] = band(100, 2.0)  # no sample inside: nothing, not 0
    assert readers.status_delta_mean(
        bands, "logs.0.push.count", "logs.0.push.mean_ms") is None
    assert readers.status_delta_ratio(
        ev, ["cluster.device.aggregate.txns_live"],
        ["cluster.device.aggregate.entries_live"]) \
        == pytest.approx(100 / 203)
