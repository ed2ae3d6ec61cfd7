"""``hostspans``: the idle time of the device, named — by hand-computed
cases, against a real profiler trace taken on the CPU, and through a
rehearsal run that reads every per-layer metric PR 28 added.

    python -m pytest benchmark/tests/test_hostspans.py -q
"""

import json
import os
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH_DIR)
sys.path.insert(0, BENCH_DIR)

import hostspans  # noqa: E402
import tracereduce  # noqa: E402

MS = 1_000_000  # ns
DEV = "/device:TPU:0"
REHEARSAL = os.path.join(BENCH_DIR, "rehearsal", "cells.json")
NEW_METRICS = (
    "server.cpu_cores", "rpc.queue_wait_ms", "rpc.read.queue_wait_ms",
    "rpc.read.handler_ms", "rpc.grv.handler_ms",
    "rpc.commit.handler_ms", "rpc.wire_ms", "batcher.queue_wait_ms",
    "proxy.commit_batch_ms", "proxy.build_ms", "proxy.resolve_ms",
    "proxy.assemble_ms", "proxy.report_ms", "resolver.pack_ms",
    "resolver.enqueue_ms", "resolver.readback_ms")


def span(thread, name, start_ms, dur_ms):
    return (thread, name, int(start_ms * MS), int(dur_ms * MS))


def op(start_ms, dur_ms, name="fusion"):
    return (DEV, tracereduce.OPS_LINE, name, int(start_ms * MS),
            int(dur_ms * MS))


def ms(by):
    return {n: round(s * 1e3, 6) for n, s in by.items()}


# ── attribute: the precedence, by hand ──────────────────────────────
CASES = {
    # one gap [0, 10) ms; spans → expected ms by name
    "no span at all: nobody is inside the server": (
        [], {"host.no_request": 10}),
    "a read handler alone": (
        [span("t1", "fdb.rpc.read", 2, 3)],
        {"fdb.rpc.read": 3, "host.no_request": 7}),
    "a commit handler beats a read handler": (
        [span("t1", "fdb.rpc.read", 0, 10),
         span("t2", "fdb.rpc.commit", 4, 2)],
        {"fdb.rpc.read": 8, "fdb.rpc.commit": 2}),
    "a stage beats the commit handler around it": (
        [span("t1", "fdb.rpc.commit", 0, 10),
         span("t1", "fdb.commit.batch", 1, 8)],
        {"fdb.rpc.commit": 2, "fdb.commit.batch": 8}),
    "the innermost of nested stages": (
        [span("t1", "fdb.commit.batch", 0, 10),
         span("t1", "fdb.commit.resolve", 2, 6),
         span("t1", "fdb.resolver.enqueue", 3, 2),
         span("t1", "fdb.resolver.readback", 5, 2)],
        {"fdb.commit.batch": 4, "fdb.commit.resolve": 2,
         "fdb.resolver.enqueue": 2, "fdb.resolver.readback": 2}),
    "two threads: the stage opened last": (
        [span("t1", "fdb.commit.log_push", 0, 10),
         span("t2", "fdb.batcher.window", 6, 2)],
        {"fdb.commit.log_push": 8, "fdb.batcher.window": 2}),
    "a gap half covered": (
        [span("t1", "fdb.storage.apply", -5, 10)],
        {"fdb.storage.apply": 5, "host.no_request": 5}),
    "tlog.push and storage.apply are commit-path stages": (
        [span("t1", "fdb.rpc.commit", 0, 10),
         span("t1", "fdb.tlog.push", 0, 4),
         span("t1", "fdb.storage.apply", 4, 6)],
        {"fdb.tlog.push": 4, "fdb.storage.apply": 6}),
    "grv.grant names nothing: its handler does": (
        [span("t1", "fdb.rpc.grv", 0, 4),
         span("t1", "fdb.grv.grant", 1, 2)],
        {"fdb.rpc.grv": 4, "host.no_request": 6}),
    "a stage on one thread beats a handler on another": (
        [span("t1", "fdb.rpc.admin", 0, 10),
         span("t2", "fdb.resolver.pack", 9, 5)],
        {"fdb.rpc.admin": 9, "fdb.resolver.pack": 1}),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_attribute_by_hand(case):
    spans, want = CASES[case]
    (by,) = hostspans.attribute([(0, 10 * MS)], spans)
    assert ms(by) == {n: float(v) for n, v in want.items()}
    assert round(sum(by.values()), 9) == 0.010  # the gap, all of it


def test_attribute_keeps_gaps_apart_and_ignores_time_outside_them():
    spans = [span("t1", "fdb.commit.batch", 0, 30)]
    a, b, c = hostspans.attribute(
        [(5 * MS, 10 * MS), (20 * MS, 40 * MS), (50 * MS, 50 * MS)], spans)
    assert ms(a) == {"fdb.commit.batch": 5.0}
    assert ms(b) == {"fdb.commit.batch": 10.0, "host.no_request": 10.0}
    assert c == {}  # an empty gap holds nothing


# ── name_gaps ───────────────────────────────────────────────────────
def reduce_named(events, window_s, spans):
    """What ``reduce_events(events, window_s, host_spans)`` is to
    return once it takes the spans (PERF.md §7)."""
    return hostspans.name_gaps(tracereduce.reduce_events(events, window_s),
                               events, spans)


EVENTS = [op(0, 1), op(11, 1), op(32, 1), op(35, 1),
          (DEV, tracereduce.MODULES_LINE, "jit__lambda(1)", 0, 1 * MS)]


def test_no_spans_leaves_the_reduction_exactly_as_it_was():
    plain = tracereduce.reduce_events(EVENTS, 0.04)
    assert reduce_named(EVENTS, 0.04, []) == plain
    assert [g[0] for g in plain["idle_gaps"]] == ["host.unattributed"] * 3
    assert "idle_by_name" not in plain


def test_reduce_named_names_each_gap_and_sums_all_idle_time():
    spans = [span("t1", "fdb.rpc.commit", 0, 31),
             span("t1", "fdb.commit.resolve", 1, 7),     # gap 1: 7 of 10
             span("t2", "fdb.rpc.read", 14, 2)]          # under rpc.commit
    out = reduce_named(EVENTS, 0.04, spans)
    plain = tracereduce.reduce_events(EVENTS, 0.04)
    # the gaps and their lengths are the plain reduction's, named
    assert [g[1] for g in out["idle_gaps"]] == \
        [g[1] for g in plain["idle_gaps"]] == [0.02, 0.01, 0.002]
    assert [g[0] for g in out["idle_gaps"]] == [
        "fdb.rpc.commit",        # 12–32: 19 ms of it under the handler
        "fdb.commit.resolve",    # 1–11: 7 ms of 10
        "host.no_request"]       # 33–35: nobody
    assert ms(out["idle_by_name"]) == {
        "fdb.rpc.commit": 22.0, "fdb.commit.resolve": 7.0,
        "host.no_request": 3.0}
    assert {k: v for k, v in out.items()
            if k not in ("idle_gaps", "idle_by_name")} == \
        {k: v for k, v in plain.items() if k != "idle_gaps"}
    assert "host.unattributed" not in json.dumps(out)


def test_idle_name_pct():
    trace = {"idle_by_name": {"fdb.commit.resolve": 3.0,
                              "fdb.resolver.enqueue": 1.0,
                              "fdb.rpc.read": 4.0, "host.no_request": 2.0}}
    assert hostspans.idle_name_pct(trace, hostspans.COMMIT_PATH) == 40.0
    assert hostspans.idle_name_pct(trace, ["host.no_request"]) == 20.0
    assert hostspans.idle_name_pct({}, ["host.no_request"]) is None
    assert hostspans.idle_name_pct(None, ["host.no_request"]) is None


def test_clock_check_by_hand():
    def module(start_ms, dur_ms):
        return (DEV, tracereduce.MODULES_LINE, "jit__lambda(7)",
                int(start_ms * MS), int(dur_ms * MS))

    spans = [span("t1", hostspans.ENQUEUE, 0, 2),
             span("t1", hostspans.READBACK, 2, 1),      # window 0–3
             span("t2", hostspans.ENQUEUE, 10, 2),
             span("t2", hostspans.READBACK, 12, 2),     # window 10–14
             span("t1", "fdb.commit.batch", 0, 20)]
    events = [module(1, 1.5),       # inside the first, 1 ms after enqueue
              module(10.5, 3),      # inside the second, 0.5 ms after
              module(13.5, 1),      # ends at 14.5: outside
              module(20, 1),        # no dispatch at all
              op(1, 1)]
    got = hostspans.clock_check(events, spans)
    assert got == {"modules": 4, "dispatches": 2, "inside": 2,
                   "inside_share": 0.5, "median_offset_us": 1000.0}
    assert hostspans.clock_check([], spans)["inside_share"] is None
    assert hostspans.clock_check(events, [])["inside_share"] is None


# ── against the profiler itself, on the CPU ─────────────────────────
TRACED_SESSION = r"""
import json, sys
sys.path[:0] = [sys.argv[2], sys.argv[3]]
import jax
from foundationdb_tpu.utils import deviceprofile
deviceprofile.enter_process()       # installs the annotation, as fdbserver
import foundationdb_tpu as fdb
import hostspans, tracereduce
db = fdb.open(resolver_backend="tpu", commit_pipeline="thread",
              batch_txn_capacity=16, hash_table_bits=14,
              range_ring_capacity=64, coarse_buckets_bits=8)
db[b"warm"] = b"up"                 # compiles outside the trace
options = jax.profiler.ProfileOptions()
options.python_tracer_level = 0     # served.py's options, to the letter
options.host_tracer_level = 1
jax.profiler.start_trace(sys.argv[1], profiler_options=options)
for i in range(5):
    db[b"k%d" % i] = b"v"
jax.profiler.stop_trace()
spans = hostspans.load(sys.argv[1])
events, seen = tracereduce.load_events(sys.argv[1])
names = {}
for _thread, name, _s, _d in spans:
    names[name] = names.get(name, 0) + 1
print(json.dumps({"names": names, "threads": len({s[0] for s in spans}),
                  "device_events": len(events),
                  "check": hostspans.clock_check(events, spans)}))
"""


def test_host_tracer_level_1_keeps_the_fdb_annotations(tmp_path):
    """A real trace with ``served.py``'s profiler options: the host
    plane lists the ``fdb.*`` names, ``load`` reads them back nested as
    they ran, and a CPU trace (no device plane) attributes nothing."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache")}
    done = subprocess.run(
        [sys.executable, "-c", TRACED_SESSION, str(tmp_path / "xplane"),
         BENCH_DIR, ROOT],
        capture_output=True, text=True, env=env, timeout=300)
    assert done.returncode == 0, done.stderr[-2000:]
    got = json.loads(done.stdout.splitlines()[-1])
    names = got["names"]
    for name in ("fdb.batcher.window", "fdb.commit.batch",
                 "fdb.commit.build", "fdb.commit.resolve",
                 "fdb.commit.assemble", "fdb.commit.log_push",
                 "fdb.commit.storage_apply", "fdb.commit.report",
                 "fdb.resolver.pack", "fdb.resolver.enqueue",
                 "fdb.resolver.readback", "fdb.tlog.push",
                 "fdb.storage.apply"):
        assert names.get(name, 0) >= 5, (name, names)
    assert all(n.startswith("fdb.") for n in names)
    assert got["device_events"] == 0  # a CPU has no device plane
    assert got["check"]["dispatches"] >= 5
    assert got["check"]["inside_share"] is None


# ── a rehearsal run reads every metric this PR added ────────────────
def test_rehearsal_run_reads_the_new_per_layer_metrics(tmp_path):
    """The accepted files, unedited: a cells list that points the new
    metrics at a rehearsal cell is all it takes."""
    bench = json.load(open(REHEARSAL))
    real = {m["name"]: m for m in json.load(open(
        os.path.join(ROOT, "BENCHMARK.json")))["per_layer"]}
    for name in NEW_METRICS:
        entry = dict(real[name], workloads=["rehearsal.ycsb.c4"])
        # the rehearsal's end-to-end names are the benchmark's own
        bench["per_layer"].append(entry)
    # a cell's mix is found beside its cells list, a metric there or in
    # benchmark/metrics: the list goes where the rehearsal's mixes are
    shutil.copytree(os.path.join(BENCH_DIR, "rehearsal", "traffic"),
                    tmp_path / "traffic")
    path = tmp_path / "cells.json"
    path.write_text(json.dumps(bench))
    cmd = [sys.executable, os.path.join(BENCH_DIR, "run.py"),
           "--workload", "rehearsal.ycsb.c4", "--seed", str(2**31 + 28),
           "--seconds", "3", "--trace", "1", "--bench", str(path),
           "--rehearse"]
    env = {**os.environ, "PYTHONPATH": ROOT, "JAX_PLATFORMS": "cpu"}
    done = subprocess.run(cmd, capture_output=True, text=True, env=env,
                          timeout=300)
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.splitlines()[-1])
    assert line["correct"] is True and line["rehearsal"] is True
    got = {n: line["metrics"][n]["value"] for n in NEW_METRICS
           if n in line["metrics"]}
    assert set(got) == set(NEW_METRICS), set(NEW_METRICS) - set(got)
    assert all(v >= 0 for v in got.values()), got
    assert 0 < got["server.cpu_cores"] < 16
    # sequential stages inside the batch: the parts stay under the whole
    assert got["proxy.build_ms"] + got["proxy.resolve_ms"] \
        + got["proxy.assemble_ms"] + got["proxy.report_ms"] \
        <= got["proxy.commit_batch_ms"] * 1.001
    assert got["resolver.enqueue_ms"] > 0 and got["resolver.pack_ms"] > 0
    # a commit handler holds its batch, a batch its resolve
    assert got["rpc.commit.handler_ms"] >= got["proxy.resolve_ms"]
