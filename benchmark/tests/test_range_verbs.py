"""The range verbs (``get_range``, ``insert``, ``clear``, ``clear_range``)
held to their contract on the CPU: ``python -m pytest benchmark/tests -q``.

- a cell made of the new verbs, added as new files only in a copy of
  ``benchmark/``, runs and prints the contract's line;
- ``check.replay`` over histories with scans, inserts and clears, by
  hand: what it accepts, and each anomaly it has a number for;
- ``datagen``: a table with room for fresh records, the drawn lengths,
  and the streams of the mixes that were there before, by a stored digest;
- the two rehearsal cells are ``correct``; under the control and the
  faults they are not.
"""

import hashlib
import json
import os
import shutil
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
sys.path[:0] = [HERE, BENCH_DIR]

import check  # noqa: E402
import datagen  # noqa: E402
import readers  # noqa: E402
from test_benchmark_harness import REHEARSAL, read_json, run_cell  # noqa: E402

RUN_PY = os.path.join(BENCH_DIR, "run.py")


# ── a cell of the new verbs, added as new files only ────────────────
def test_a_cell_of_range_verbs_added_as_new_files_runs(tmp_path):
    copy = tmp_path / "benchmark"
    shutil.copytree(BENCH_DIR, copy, ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    before = {p: p.read_bytes() for p in copy.rglob("*") if p.is_file()}
    reh = copy / "rehearsal"
    config = read_json(reh / "configs" / "rehearsal_range.json")
    config.update(name="added_range", rows=600)
    (copy / "configs" / "added_range.json").write_text(json.dumps(config))
    mix = read_json(reh / "traffic" / "rehearsal.ycsb_e.c4.json")
    mix.update(name="added.ranges", client_processes=1, client_threads=3)
    mix["operations"] = [  # a mix of its own, from the steps there are
        {"name": "scan", "weight": 6,
         "steps": [["get_range", "a", {"uniform": [2, 30]}]]},
        {"name": "insert", "weight": 2, "steps": [["insert", "a"]]},
        {"name": "trim", "weight": 1,
         "steps": [["get_range", "a", 5], ["get", "b"], ["clear", "b"],
                   ["clear_range", "a", 3]]},
        {"name": "put", "weight": 1, "steps": [["get", "a"], ["set", "a"]]}]
    mix["keys"] = {"distribution": "uniform", "draw": "stratified",
                   "block": 50}
    (copy / "traffic" / "added.ranges.json").write_text(json.dumps(mix))
    bench = read_json(reh / "cells.json")
    bench["configs"].append({
        "name": "added_range", "source": "test", "reduced": [],
        "why": "test", "file": "benchmark/configs/added_range.json"})
    bench["workloads"].append({
        "name": "added.cell", "config": "added_range",
        "traffic": "added.ranges", "chips": 1, "why": "test"})
    for m in bench["per_layer"] + bench["end_to_end"]:
        if "rehearsal.ycsb_e.c4" in m.get("workloads", []):
            m["workloads"].append("added.cell")
    # a reading under its second name (a metrics file with ``like``)
    twin = read_json(copy / "metrics" / "scan.batcher.txns_per_dispatch.json")
    twin.pop("like")
    bench["per_layer"].append({**twin, "workloads": ["added.cell"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    assert all(p.read_bytes() == b for p, b in before.items())

    line, err = run_cell(str(copy / "run.py"),
                         str(tmp_path / "BENCHMARK.json"), "added.cell",
                         2**31 + 78, 3, trace=1)
    assert (line["metrics"]["scan.batcher.txns_per_dispatch"]["value"]
            == line["metrics"]["batcher.txns_per_dispatch"]["value"] > 0)
    assert list(line)[:3] == ["correct", "attempted", "failed"]
    assert list(line)[-1] == "compared"
    assert line["correct"] is True, line["compared"]
    assert line["rehearsal"] is True and line["failed"] == 0
    assert line["compared"]["phantom_rows"] == {"value": 0, "limit": 0}
    assert line["compared"]["reads_compared"]["value"] > 100
    # scans of 2..30 and of 5: the mix sent is the mix stated
    assert 5 < line["metrics"]["client.scan_rows_mean"]["value"] < 30
    counts = line["window"]["acked_by_kind"]
    assert len(counts) == 4 and all(counts)
    assert err.rstrip().splitlines()[-1].startswith("compared ")


# ── the plain reference over ranges, by hand ────────────────────────
# key slots 0..9; the loaded table holds 0, 2, 4, 6, 8 with token 100 + slot
LOADED = [0, 2, 4, 6, 8]


def op(t0, t1, rv, cv, reads=(), writes=(), ranges=(), status=check.OK):
    row = [0, t0, t1, status, 0, 0, rv, cv, [list(r) for r in reads],
           [list(w) for w in writes]]
    if ranges:
        row.append([[f, n, [list(r) for r in rows]] for f, n, rows in ranges])
    return row


def replay(ops, final=None):
    base = {s: 100 + s for s in LOADED}
    numbers, _ = check.replay(
        ops, lambda s: 100 + s if s in LOADED else -1,
        base if final is None else final, loaded=LOADED)
    return numbers


def test_replay_accepts_a_serial_history_of_scans_inserts_and_clears():
    ops = [
        # a read-only scan of 3 from slot 1 at rv 10: 2, 4, 6
        op(0, 1, 10, 0, ranges=[(1, 3, [(2, 102), (4, 104), (6, 106)])]),
        op(1, 2, 0, 20, writes=[(3, 7)]),  # a blind insert into slot 3
        # a scan at rv 20 meets it; one at rv 15, begun before the
        # insert was acknowledged, does not
        op(3, 4, 20, 0, ranges=[(1, 3, [(2, 102), (3, 7), (4, 104)])]),
        op(1.5, 4, 15, 0, ranges=[(1, 3, [(2, 102), (4, 104), (6, 106)])]),
        # scan 2 from slot 2, clear [2, 4), set 8: one transaction
        op(5, 6, 20, 30, ranges=[(2, 2, [(2, 102), (3, 7)])],
           writes=[(2, -1), (3, -1), (8, 9)]),
        # the table's end cuts a scan short: 6 and 8 of a limit of 5
        op(7, 8, 30, 0, ranges=[(5, 5, [(6, 106), (8, 9)])]),
        op(7, 8, 30, 0, ranges=[(1, 2, [(4, 104), (6, 106)])]),
        op(8, 9, 30, 40, reads=[(6, 106)], writes=[(6, -1)]),  # a clear
        op(10, 11, 40, 0, ranges=[(9, 4, [])]),  # nothing behind slot 9
    ]
    n = replay(ops, {0: 100, 2: -1, 4: 104, 6: -1, 8: 9})
    assert (n["stale_reads"], n["phantom_rows"], n["wrong_rows"],
            n["batch_cycles"], n["stale_read_versions"]) == (0, 0, 0, 0, 0)
    assert n["reads_compared"] == 16
    assert n["rows_compared"] == 6  # five loaded records and slot 3


def test_replay_sees_a_phantom_a_stale_scan_and_a_scan_that_skips():
    insert = op(0, 1, 0, 20, writes=[(3, 7)])
    # a committed scan that missed the insert below its commit version
    missed = op(0, 2, 10, 30, ranges=[(1, 3, [(2, 102), (4, 104), (6, 106)])],
                writes=[(9, 5)])
    n = replay([insert, missed])
    assert n["phantom_rows"] == 2  # slot 3 is lacking, slot 6 is beyond
    assert n["stale_reads"] == 0
    # a read-only scan at rv 20 that returns a row nobody wrote
    extra = op(2, 3, 20, 0, ranges=[(1, 2, [(1, 55), (2, 102)])])
    assert replay([insert, extra])["phantom_rows"] == 2
    # a scan whose row carries another value than the model holds
    stale = op(2, 3, 20, 0, ranges=[(1, 2, [(2, 102), (3, 8)])])
    n = replay([insert, stale])
    assert (n["stale_reads"], n["phantom_rows"]) == (1, 0)
    # a scan that stops short of its limit with records still ahead
    short = op(2, 3, 20, 0, ranges=[(1, 3, [(2, 102)])])
    assert replay([insert, short])["phantom_rows"] == 2


def test_replay_sees_an_insert_lost_and_a_cleared_record_back():
    insert = op(0, 1, 0, 20, writes=[(3, 7)])
    base = {s: 100 + s for s in LOADED}
    assert replay([insert], {**base, 3: 7})["wrong_rows"] == 0
    lost = replay([insert], base)  # acknowledged, not there to read back
    assert (lost["wrong_rows"], lost["rows_compared"]) == (1, 6)
    clear = op(2, 3, 0, 30, writes=[(4, -1), (5, -1)])  # clear_range [4, 6)
    assert replay([clear], {**base, 4: -1})["wrong_rows"] == 0
    assert replay([clear], base)["wrong_rows"] == 1  # 4 came back
    assert replay([], {**base, 5: 42})["wrong_rows"] == 1  # nobody wrote it
    # a 1021 explains a row, or its absence; a scan may meet it or not
    maybe = op(0, 1, 0, 0, writes=[(3, 7)], status=check.UNKNOWN)
    for final in (base, {**base, 3: 7}):
        assert replay([maybe], final)["wrong_rows"] == 0
    for rows in ([(2, 102), (4, 104)], [(2, 102), (3, 7)]):
        n = replay([maybe, op(2, 3, 10, 0, ranges=[(1, 2, rows)])])
        assert (n["phantom_rows"], n["stale_reads"]) == (0, 0)
    # … but once a scan has met it, it stays
    seen = [maybe, op(2, 3, 10, 0, ranges=[(1, 2, [(2, 102), (3, 7)])]),
            op(4, 5, 20, 0, ranges=[(1, 2, [(2, 102), (4, 104)])])]
    assert replay(seen)["phantom_rows"] == 2


def test_replay_sees_a_range_conflict_missed_inside_one_batch():
    # one batch (cv 20): a reader of [2, 6] that writes 9, and an
    # inserter into slot 3 that read 9: each before the other
    reader = op(0, 1, 10, 20, ranges=[(2, 3, [(2, 102), (4, 104), (6, 106)])],
                writes=[(9, 5)])
    inserter = op(0, 1, 10, 20, reads=[(9, -1)], writes=[(3, 7)])
    n = replay([reader, inserter])
    assert n["batch_cycles"] == 2
    assert (n["stale_reads"], n["phantom_rows"]) == (0, 0)  # blind to it
    # the reader alone goes first: an order holds the batch
    assert check.unorderable([reader, op(0, 1, 10, 20, writes=[(3, 7)])]) == 0
    # an insert behind the scan's last row is outside what it covered …
    beyond = op(0, 1, 10, 20, reads=[(9, -1)], writes=[(7, 7)])
    assert check.unorderable([reader, beyond]) == 0
    # … unless the scan ran to the table's end, short of its limit
    to_end = op(0, 1, 10, 20, ranges=[(5, 9, [(6, 106), (8, 108)])],
                writes=[(9, 5)])
    behind = op(0, 1, 10, 20, reads=[(9, -1)], writes=[(9, 6)])
    assert check.unorderable([to_end, behind]) == 2
    # two range read-modify-writes that clear into each other's range
    a = op(0, 1, 10, 20, ranges=[(0, 2, [(0, 100), (2, 102)])],
           writes=[(2, -1), (3, -1)])
    b = op(0, 1, 10, 20, ranges=[(2, 2, [(2, 102), (4, 104)])],
           writes=[(0, -1), (1, -1)])
    assert check.unorderable([a, b]) == 2


def test_a_blind_write_is_not_held_to_a_read_version():
    # an insert read nothing: its log carries no read version
    ops = [op(0, 1, 10, 20, reads=[(2, 102)], writes=[(2, 5)]),
           op(2, 3, 0, 30, writes=[(3, 7)])]
    n = replay(ops, {0: 100, 2: 5, 3: 7, 4: 104, 6: 106, 8: 108})
    assert (n["stale_reads"], n["stale_read_versions"]) == (0, 0)
    ops.append(op(4, 5, 20, 0, reads=[(2, 5)]))  # a read is, as ever
    assert replay(ops, {})["stale_read_versions"] == 1


@pytest.mark.parametrize("reads,ranges", [
    ([(2, 102)], []),  # a get + set
    ([], [(2, 1, [(2, 102)])]),  # a get_range + set
])
def test_an_operation_that_read_is_still_held_to_its_read_version(
        reads, ranges):
    # only "no read and no range logged" lifts the two checks
    final = {0: 100, 2: 5, 4: 104, 6: 106, 8: 108}
    late = [op(0, 1, 20, 20, reads=reads, ranges=ranges, writes=[(2, 5)])]
    assert replay(late, final)["stale_reads"] == 1  # read at its own cv
    behind = [op(0, 1, 10, 20, reads=[(4, 104)], writes=[(4, 104)]),
              # began after cv 20 was acknowledged, read below it
              op(2, 3, 15, 30, reads=reads, ranges=ranges, writes=[(2, 5)])]
    n = replay(behind, final)
    assert (n["stale_reads"], n["stale_read_versions"]) == (0, 1)


def test_a_cleared_record_is_left_out_of_the_update_count():
    counted = {0: 102, 1: 7}
    ops = [op(0, 1, 10, 20, reads=[(2, 102)], writes=[(2, 7)]),
           op(2, 3, 20, 30, writes=[(2, -1)])]
    for rows, lost in ((ops[:1], 1), (ops, 0)):
        n, _ = check.replay(rows, lambda s: 100 + s, {2: -1},
                            counted_token=lambda s, c: counted[c])
        assert n["lost_updates"] == lost


# ── the table and the streams ───────────────────────────────────────
def table(room, rows=50, seed=2**31 + 9):
    spec = {"rows_key": "rows", "key_format": "user%08d",
            "value": {"kind": "stamp", "bytes": 16}}
    if room:
        spec["insert"] = {"order": "hashed", "room": room}
    return datagen.Table({"rows": rows, "table": spec}, seed)


def test_a_table_with_room_spreads_fresh_records_among_loaded_ones():
    plain, roomy = table(0), table(150)
    assert plain.slots == 50 and plain.loaded() == list(range(50))
    assert [plain.slot(i) for i in (0, 7, 49)] == [0, 7, 49]
    assert plain.end_key() == b"user00000049\x00"  # as the read-back had it
    assert roomy.slots == 200 and roomy.end_key() == b"user00000199\x00"
    slots = [roomy.slot(i) for i in range(200)]
    assert sorted(slots) == list(range(200))  # one permutation
    assert roomy.loaded() == sorted(slots[:50])
    fresh = slots[50:]
    # fresh records fall among the loaded ones, not behind the last
    assert min(fresh) < max(roomy.loaded()) and len(set(fresh)) == 150
    assert [table(150).slot(i) for i in range(200)] == slots  # from the seed
    assert [table(150, seed=5).slot(i) for i in range(200)] != slots
    for s in (0, 63, 199):
        assert roomy.slot_of_key(roomy.key(s)) == s
    with pytest.raises(ValueError):
        roomy.slot(200)  # no room left
    with pytest.raises(ValueError):
        plain.slot(50)


@pytest.mark.parametrize("draw", ["iid", "stratified"])
def test_op_stream_draws_lengths_in_their_stated_share(draw):
    traffic = {"operations": [
        {"weight": 0.95, "steps": [["get_range", "a", {"uniform": [1, 100]}]]},
        {"weight": 0.05, "steps": [["insert", "a"]]}],
        "keys": {"distribution": "zipfian", "theta": 0.99, "scramble": True,
                 "draw": draw, "block": 100}}
    kinds, a, b, lengths = datagen.op_stream(traffic, 1000, 2**31 + 5, 0, 0)
    n = len(kinds)
    assert len(a) == len(b) == len(lengths) == n
    assert n == (65500 if draw == "stratified" else datagen.STREAM)
    scans = [x for k, x in zip(kinds, lengths) if k == 0]
    assert all(x == 0 for k, x in zip(kinds, lengths) if k == 1)
    assert abs(len(scans) / n - 0.95) < 0.005
    assert min(scans) == 1 and max(scans) == 100
    assert abs(sum(scans) / len(scans) - 50.5) < 0.5
    share = [scans.count(x) / len(scans) for x in range(1, 101)]
    assert max(abs(s - 0.01) for s in share) < 0.003
    if draw == "stratified":  # every block holds the mix exactly
        assert all(sum(kinds[i:i + 100]) == 5 for i in range(0, n, 100))
        assert all(abs(sum(lengths[i:i + 100]) / 95 - 50.5) < 1.1
                   for i in range(0, n, 100))
    assert datagen.op_stream(traffic, 1000, 2**31 + 5, 0, 0)[3] == lengths
    assert datagen.op_stream(traffic, 1000, 2**31 + 5, 0, 1)[3] != lengths
    # the lengths have a generator of their own: the rest is as without
    plain = json.loads(json.dumps(traffic))
    plain["operations"][0]["steps"] = [["get_range", "a", 10]]
    assert datagen.op_stream(plain, 1000, 2**31 + 5, 0, 0) == (kinds, a, b)


@pytest.mark.parametrize("mix,first_block,whole", [
    ("ycsb_a.zipf99.c64", "ef588cc0e8f3344370f3aea8",
     "fedaf12c2c67d2c26c25472c"),
    ("ycsb_b.zipf99.c64", "6dbe3bbcf8b69cfb6a21c454",
     "ef049c8941779c3e69eff041"),
    ("mako.uniform.c64", "85955eb45449728163df2332",
     "fde5de608af13c1d82fa8516"),
])
def test_the_streams_of_the_mixes_there_were_are_unchanged(
        mix, first_block, whole):
    """Digests taken from the tree before the range verbs (PR 33)."""
    traffic = read_json(os.path.join(BENCH_DIR, "traffic", mix + ".json"))
    stream = datagen.op_stream(traffic, 100000, 2**31 + 7, 1, 2)
    assert len(stream) == 3 and len(stream[0]) == datagen.STREAM

    def digest(lists):
        return hashlib.sha256(json.dumps(lists).encode()).hexdigest()[:24]

    assert digest([x[:64] for x in stream]) == first_block
    assert digest(list(stream)) == whole


def test_steps_are_checked_before_any_client_waits():
    import client

    fine = [["get_range", "a", 10], ["get", "b"], ["clear_range", "a", 2],
            ["insert", "a"], ["clear", "b"], ["set", "b"]]
    assert client.check_steps(fine) is fine
    for bad in ([["scan", "a"]], [["get", "c"]], [["get_range", "a"]],
                [["get", "a", 3]], [["clear_range", "a", 0]],
                [["get_range", "a", {"uniform": [5, 2]}]],
                [["set", "b"], ["get_range", "a", 3]]):
        with pytest.raises(ValueError):
            client.check_steps(bad)


def test_the_scan_rows_reader_reads_the_clients_log():
    ops = [op(0, 1, 10, 0, ranges=[(1, 3, [(2, 1), (4, 1), (6, 1)])]),
           op(0, 1, 10, 20, ranges=[(1, 3, [(2, 1)])], writes=[(3, 7)]),
           op(0, 1, 10, 0, reads=[(2, 1)])]
    assert readers.client_range_rows_mean({"ops": ops}) == 2.0
    assert readers.client_range_rows_mean({"ops": ops[2:]}) is None


# ── the rehearsal cells, sound and broken ───────────────────────────
@pytest.mark.parametrize("cell", ["rehearsal.ycsb_e.c4",
                                  "rehearsal.range_rmw.c4"])
def test_a_range_rehearsal_is_correct(cell):
    line, _ = run_cell(RUN_PY, REHEARSAL, cell, 2**31 + 12, 4)
    assert line["correct"] is True, line["compared"]
    assert line["failed"] == 0
    for number in ("stale_reads", "phantom_rows", "batch_cycles",
                   "wrong_rows", "unanswered"):
        assert line["compared"][number] == {"value": 0, "limit": 0}
    counts = line["window"]["acked_by_kind"]
    assert len(counts) == 2 and all(counts)
    if cell == "rehearsal.ycsb_e.c4":
        assert line["compared"]["reads_compared"]["value"] > 10000
        assert 0.03 < counts[1] / sum(counts) < 0.07
        # every acknowledged insert is among the rows compared
        assert line["compared"]["rows_compared"]["value"] >= 3000 + counts[1]


@pytest.mark.parametrize("cell,fault,numbers", [
    # the control on a mix that reads ranges and writes into them
    ("rehearsal.range_rmw.c4", "no_conflict",
     ["stale_reads", "phantom_rows", "batch_cycles"]),
    # a scan that skips a record which is there
    ("rehearsal.ycsb_e.c4", "drop_range_row", ["phantom_rows"]),
    # a scan's row altered where it is produced
    ("rehearsal.ycsb_e.c4", "alter_read", ["stale_reads"]),
    # an acknowledged insert (and a loaded record) not there
    ("rehearsal.ycsb_e.c4", "drop_apply", ["wrong_rows"]),
])
def test_a_fault_under_a_range_mix_reads_not_correct(cell, fault, numbers):
    line, err = run_cell(RUN_PY, REHEARSAL, cell, 2**31 + 13, 4, fault=fault)
    assert line["fault"] == fault
    assert line["correct"] is False
    over = sum(line["compared"][n]["value"] for n in numbers)
    assert over > 0, line["compared"]
    if len(numbers) == 1:
        c = line["compared"][numbers[0]]
        assert f"compared {numbers[0]}: {c['value']} (limit 0)" in err
