"""``mako_range.uniform.c64`` (PR 35) held to what it states, on the
CPU: ``python -m pytest benchmark/tests -q``.

- the deployment's files say mako's transaction, in mako's order, on
  mako_32b_100k's table with room for inserts;
- a transaction that inserts a record and then clears a range over it
  is logged, and replayed by ``check.py``, in step order: the clear
  wins; the other way round the insert does;
- its tiny twin ``rehearsal.mako_range.c4`` runs the cell's own steps
  and is ``correct`` (tier-1 runs it traced:
  ``tests/test_bench_rehearsal_ranges.py``).
"""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
sys.path[:0] = [HERE, BENCH_DIR]

import check  # noqa: E402
import client  # noqa: E402
import datagen  # noqa: E402
from test_benchmark_harness import read_json, run_cell  # noqa: E402

RUN_PY = os.path.join(BENCH_DIR, "run.py")
CELLS = os.path.join(BENCH_DIR, "rehearsal", "cells.mako_range.json")
STEPS = [["get_range", "a", 20], ["get", "b"], ["set", "b"],
         ["insert", "a"], ["clear_range", "a", 2]]


def test_the_deployment_states_makos_transaction_on_makos_table():
    config = read_json(os.path.join(
        BENCH_DIR, "configs", "mako_range_32b_100k.json"))
    mako = read_json(os.path.join(BENCH_DIR, "configs", "mako_32b_100k.json"))
    assert len(config["source"]) == 199
    assert config["table"] == {**mako["table"],
                               "insert": {"order": "hashed", "room": 100000}}
    assert config["rows"] == mako["rows"] and config["chips"] == 1
    assert config["server"] == mako["server"]
    assert config["reduced"] == mako["reduced"]
    for k in ("durability", "flush_policy"):
        assert config["guarantees"][k] == mako["guarantees"][k]
    assert config["guarantees"]["isolation"].startswith(
        mako["guarantees"]["isolation"])
    assert "no phantom" in config["guarantees"]["isolation"]
    assert "phantom_rows" in config["guarantees"]["held_by"]
    for name in ("mako_range.uniform.c64", "rehearsal/traffic/"
                 "rehearsal.mako_range.c4"):
        base, _, leaf = name.rpartition("/")
        mix = read_json(os.path.join(BENCH_DIR, base or "traffic",
                                     leaf + ".json"))
        (op,) = mix["operations"]
        assert client.check_steps(op["steps"]) == STEPS
        assert mix["open"] == {} and mix["keys"] == {
            "distribution": "uniform", "draw": "iid"}
    assert mix["warmup_s"] < 10  # the twin's; the cell's is the full
    # step's first build on a cold cache and is said in its own file


class _Tr:
    """What ``client.transact`` asks of a transaction, over a dict."""

    def __init__(self, table, rows):
        self.table, self.rows = table, dict(rows)

    def get(self, key):
        return self.rows.get(self.table.slot_of_key(key))

    def set(self, key, value):
        self.rows[self.table.slot_of_key(key)] = value

    def get_range(self, begin, end, limit):
        lo = self.table.slot_of_key(begin)
        live = sorted(s for s in self.rows if s >= lo)[:limit]
        return [(self.table.key(s), self.rows[s]) for s in live]

    def clear_range(self, begin, end):
        lo, hi = (self.table.slot_of_key(k[:32]) + (len(k) > 32)
                  for k in (begin, end))
        for s in [s for s in self.rows if lo <= s < hi]:
            del self.rows[s]


class _W:
    process, thread, seq = 1, 2, 0


def test_an_insert_then_a_clear_over_it_are_replayed_in_step_order():
    config = read_json(os.path.join(
        BENCH_DIR, "rehearsal", "configs", "rehearsal_mako_range.json"))
    table = datagen.Table(config, 7)
    w = _W()
    w.table = table
    loaded = table.loaded()
    a, b = loaded[10], loaded[20]
    start = {s: table.initial(s) for s in loaded}
    token = datagen.token
    for steps, fresh_left in ((STEPS, False),
                              (STEPS[:3] + [STEPS[4], STEPS[3]], True)):
        steps = client.check_steps(steps) if not fresh_left else steps
        tr = _Tr(table, start)
        at_insert = [n for n, s in enumerate(steps) if s[0] == "insert"][0]
        fresh = {at_insert: a + 1}  # the fresh record falls in [a, a + 2)
        assert a + 1 not in start
        reads, writes, ranges = client.transact(
            w, tr, steps, {"a": a, "b": b}, 0, fresh)
        wrote = dict(map(tuple, writes))
        assert wrote[a] == -1 and wrote[b] == token(tr.rows[b])
        # the server's state and the log agree on who came last
        assert (a + 1 in tr.rows) is fresh_left
        assert (wrote[a + 1] != -1) is fresh_left
        assert len(ranges) == 1 and len(ranges[0][2]) == 20
        op = [0, 0.0, 1.0, check.OK, 0, 0, 10, 20, reads, writes, ranges]
        final = {s: token(v) for s, v in tr.rows.items()}
        final.update({s: -1 for s in loaded if s not in tr.rows})
        numbers, _ = check.replay(
            [op], lambda s: token(table.initial(s)) if s in start else -1,
            final, loaded=loaded)
        for n in ("stale_reads", "phantom_rows", "wrong_rows",
                  "batch_cycles"):
            assert numbers[n] == 0, (n, numbers)
        # had the log kept the other order, the read-back would differ
        flipped = [[s, (-1 if t != -1 else 5)] if s == a + 1 else [s, t]
                   for s, t in writes]
        numbers, _ = check.replay(
            [op[:9] + [flipped, ranges]],
            lambda s: token(table.initial(s)) if s in start else -1,
            final, loaded=loaded)
        assert numbers["wrong_rows"] == 1


def test_the_twin_of_the_cell_is_correct():
    line, _ = run_cell(RUN_PY, CELLS, "rehearsal.mako_range.c4",
                       2**31 + 14, 4)
    assert line["correct"] is True, line["compared"]
    assert line["failed"] == 0 and line["window"]["acked_by_kind"][0] > 0
    assert set(line["metrics"]) == {"ops_per_s", "update_p50_ms", "setup_s"}
