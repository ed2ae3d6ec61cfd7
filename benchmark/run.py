"""One run of one cell: ``python3 benchmark/run.py --workload <cell>
--seed <n> --seconds <s> --trace <0|1>``.

This process never imports JAX: a chip belongs to one process, and that
is the server child (``served.py``), a real ``fdbserver
--resolver-backend tpu``. This process finds the cell's files by the
names in ``BENCHMARK.json``, starts the server and the client processes
(``client.py``), loads the table through the served path, lets the
clients warm up every program, measures the window, drains, reads the
table back, holds every logged answer to the plain reference
(``check.py``), stops everything and prints one JSON line.

``setup_s`` runs from this process's start to the window's start. A
rate is every operation acknowledged in the window over the window's
seconds; a tail is taken over the raw samples of all clients.
"""

import argparse
import collections
import concurrent.futures
import glob
import json
import os
import re
import shutil
import signal
import subprocess
import sys
import tempfile
import threading
import time

T_PROCESS_START = time.monotonic()

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [HERE, ROOT]

import check  # noqa: E402
import datagen  # noqa: E402
import readers  # noqa: E402

LOAD_THREADS = 4
LOAD_WINDOW = 2048  # commits in flight per loader thread (chip_smoke's)
LOAD_SETS = 4  # sets per load transaction: the packed point-write lanes
PAGE_ROWS = 5000
SERVER_START_S = 600


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def read_json(path):
    with open(path) as f:
        return json.load(f)


def write_json(path, doc):
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(doc, f)
    os.replace(tmp, path)


class Cell:
    """The files of one ``workloads`` entry, found by name."""

    def __init__(self, bench_path, name):
        self.bench = read_json(bench_path)
        self.bench_dir = os.path.dirname(os.path.abspath(bench_path))
        entry = [w for w in self.bench["workloads"] if w["name"] == name]
        if not entry:
            raise SystemExit(f"no workload {name!r} in {bench_path}; it has "
                             f"{[w['name'] for w in self.bench['workloads']]}")
        self.entry = entry[0]
        self.name = name
        conf = [c for c in self.bench["configs"]
                if c["name"] == self.entry["config"]][0]
        self.config_path = os.path.join(ROOT, conf["file"])
        self.config = read_json(self.config_path)
        self.traffic_path = self.find("traffic", self.entry["traffic"])
        self.traffic = read_json(self.traffic_path)

    def find(self, kind, name):
        for base in (self.bench_dir, HERE):
            path = os.path.join(base, kind, name + ".json")
            if os.path.exists(path):
                return path
        raise SystemExit(f"no {kind}/{name}.json")

    def reports(self, metric):
        return self.name in metric.get("workloads", [self.name])

    def end_to_end(self):
        return [m for m in self.bench["end_to_end"] if self.reports(m)]

    def metric_spec(self, name):
        """``metrics/<name>.json``. A file with ``like`` gives another
        metric's reading a name of its own, for the cells that report
        another end-to-end metric for it to move: the reader and its
        arguments are that metric's."""
        spec = read_json(self.find("metrics", name))
        if "like" in spec:
            base = read_json(self.find("metrics", spec["like"]))
            spec = {**spec, "reader": base["reader"], "args": base["args"]}
        return spec

    def per_layer(self):
        return [(m, self.metric_spec(m["name"]))
                for m in self.bench["per_layer"] if self.reports(m)]


class Children:
    """Every process this run starts; ``stop`` ends and reaps them."""

    def __init__(self):
        self.all = []

    def start(self, cmd, **kw):
        p = subprocess.Popen(cmd, **kw)
        self.all.append(p)
        return p

    def stop(self):
        for p in self.all:
            if p.poll() is None:
                p.kill()
        for p in self.all:
            p.wait()


def start_server(children, cell, tmp, trace, fault, rehearse):
    cf = os.path.join(tmp, "fdb.cluster")
    cmd = [sys.executable, os.path.join(HERE, "served.py"),
           "--device-json", os.path.join(tmp, "device.json")]
    if trace:
        os.makedirs(os.path.join(tmp, "trace"))
        cmd += ["--trace-dir", os.path.join(tmp, "trace")]
    if fault:
        cmd += ["--fault", fault]
    cmd += list(cell.config["server"]["flags"])
    cmd += ["--dir", os.path.join(tmp, "db"), "--cluster-file", cf]
    env = dict(os.environ)
    if rehearse:
        env["JAX_PLATFORMS"] = "cpu"
    server = children.start(cmd, cwd=ROOT, stdout=subprocess.PIPE, text=True,
                            env=env)
    up = threading.Event()

    def pump():
        for line in server.stdout:
            if "FDBD listening" in line:
                up.set()
        up.set()  # the server is gone

    threading.Thread(target=pump, daemon=True).start()
    if not up.wait(SERVER_START_S) or server.poll() is not None:
        raise SystemExit("fdbserver did not come up")
    return server, cf


def start_clients(children, cell, tmp, cf, seed):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}  # a client never needs JAX
    procs = []
    for p in range(int(cell.traffic["client_processes"])):
        procs.append(children.start(
            [sys.executable, os.path.join(HERE, "client.py"),
             "--cluster-file", cf, "--config", cell.config_path,
             "--traffic", cell.traffic_path, "--seed", str(seed),
             "--process", str(p), "--control", tmp,
             "--out", os.path.join(tmp, f"client.{p}.json")],
            cwd=ROOT, env=env, stdout=subprocess.DEVNULL))
    return procs


def load(db, table):
    """The table, through the served path: blind sets, four to a
    transaction, in windows of asynchronous commits. The values are
    fixed, so a commit that drew a retryable error (a 1021 while the
    server builds its first program) is simply committed again.
    → the number of such retries."""
    from foundationdb_tpu.core.errors import FDBError

    slots = table.loaded()
    groups = [slots[i:i + LOAD_SETS] for i in range(0, table.rows, LOAD_SETS)]

    def sets(group):
        def body(tr):
            for i in group:
                tr.set(table.key(i), table.initial(i))
        return body

    def share(mine):
        retried = 0
        while mine:
            again = []
            for w in range(0, len(mine), LOAD_WINDOW):
                futs = []
                for group in mine[w:w + LOAD_WINDOW]:
                    tr = db.create_transaction()
                    sets(group)(tr)
                    futs.append((group, tr, tr.commit_async()))
                for group, tr, fut in futs:
                    fut.result(timeout=300)
                    try:
                        tr.commit_finish(fut)
                    except FDBError as e:
                        if not e.is_retryable:
                            raise
                        again.append(group)
            retried += len(again)
            mine = again
        return retried

    db.run(sets(groups[0]))  # the first program, behind a retry loop
    with concurrent.futures.ThreadPoolExecutor(LOAD_THREADS) as pool:
        return sum(f.result() for f in
                   [pool.submit(share, groups[t::LOAD_THREADS])
                    for t in range(LOAD_THREADS)])


def read_back(db, table):
    """The table's whole key range, in pages through ordinary
    transactions → {slot: token}: every row that is there, and -1 for
    a loaded record that is missing."""
    rows = dict.fromkeys(table.loaded(), -1)
    begin, end = table.key(0), table.end_key()
    while True:
        page = db.run(lambda tr: tr.get_range(begin, end, limit=PAGE_ROWS))
        for k, v in page:
            rows[table.slot_of_key(bytes(k))] = datagen.token(bytes(v))
        if len(page) < PAGE_ROWS:
            return rows
        begin = bytes(page[-1][0]) + b"\x00"


def wait_all(paths, procs, timeout):
    deadline = time.monotonic() + timeout
    while not all(os.path.exists(p) for p in paths):
        if any(p.poll() not in (None, 0) for p in procs):
            raise SystemExit("a client process failed")
        if time.monotonic() > deadline:
            raise SystemExit(f"waited {timeout} s for {paths}")
        time.sleep(0.01)


TAIL = re.compile(r"^(update|read)_p(\d+)_ms$")


def end_to_end_values(names, ops, t_start, t_end, setup_s):
    """The window's numbers from the raw log rows of every client:
    ``ops_per_s`` over every operation acknowledged in the window,
    ``update_p<q>_ms`` / ``read_p<q>_ms`` over every update / every
    read-only transaction acknowledged in it; an operation that writes
    (a set, an insert, a clear) is an update. ``acked_by_kind`` counts
    them by their place in the mix's ``operations``, and
    ``ops_per_s_by_fifth`` gives the rate in each fifth of the window."""
    acked = [op for op in ops if op[check.STATUS] == check.OK
             and t_start <= op[check.T1] < t_end]
    out = {"setup_s": setup_s,
           "ops_per_s": len(acked) / (t_end - t_start)}
    counts = collections.Counter(op[check.KIND] for op in acked)
    out["acked_by_kind"] = [counts[k] for k in
                            range(max(counts, default=-1) + 1)]
    fifth = (t_end - t_start) / 5  # is the window steady? the rate by fifths
    fifths = collections.Counter(int((op[check.T1] - t_start) / fifth)
                                 for op in acked)
    out["ops_per_s_by_fifth"] = [fifths[n] / fifth for n in range(5)]
    by_kind = {True: [], False: []}
    for op in acked:
        by_kind[bool(op[check.WRITES])].append(
            1e3 * (op[check.T1] - op[check.T0]))
    for name in names:
        m = TAIL.match(name)
        ms = sorted(by_kind[m.group(1) == "update"]) if m else []
        if ms:
            out[name] = readers.percentile(ms, int(m.group(2)) / 100)
    updates = [op for op in acked if op[check.WRITES]]
    if updates:  # the client's view of the conflicts: one retry each
        retries = sum(op[check.RETRIES] for op in updates)
        out["conflicts_per_commit_attempt"] = retries / (retries
                                                         + len(updates))
    started = [op for op in ops if t_start <= op[check.T0] < t_end]
    failed = sum(1 for op in started if op[check.STATUS] != check.OK)
    return out, len(started), failed


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--bench", default=os.path.join(ROOT, "BENCHMARK.json"),
                   help="the cells' list (default: the repo's)")
    p.add_argument("--rehearse", action="store_true",
                   help="accept a CPU; the line then says rehearsal: true")
    p.add_argument("--fault", help="plant a fault of faults.py in the "
                   "server (control and fault tests only)")
    args = p.parse_args(argv)

    import foundationdb_tpu as fdb  # starts no JAX backend

    cell = Cell(args.bench, args.workload)
    traffic = cell.traffic
    table = datagen.Table(cell.config, args.seed)
    peaks = read_json(os.path.join(HERE, "peaks.json"))
    children = Children()
    tmp = tempfile.mkdtemp(prefix="fdbbench.")
    try:
        server, cf = start_server(children, cell, tmp, args.trace,
                                  args.fault, args.rehearse)
        t_up = time.monotonic()
        db = fdb.open(cluster_file=cf, commit_pipeline="thread")
        dev = db.status()["cluster"]["device"]
        if dev["platform"] != "tpu" and not args.rehearse:
            raise SystemExit(f"the resolver is on {dev['platform']!r}: "
                             "no TPU, no result")
        if dev["device_count"] < cell.entry["chips"]:
            raise SystemExit(f"{cell.entry['chips']} chips asked for, the "
                             f"server sees {dev['device_count']}")
        if dev["device_kind"] not in peaks and not args.rehearse:
            raise SystemExit(f"no peaks for {dev['device_kind']!r}")
        clients = start_clients(children, cell, tmp, cf, args.seed)
        load_retries = load(db, table)
        t_loaded = time.monotonic()
        wait_all([os.path.join(tmp, f"ready.{i}")
                  for i in range(len(clients))], clients, 120)
        t_start = time.monotonic() + 0.05 + float(traffic["warmup_s"])
        t_end = t_start + args.seconds
        drain_s = float(traffic["drain_s"])
        write_json(os.path.join(tmp, "go"),
                   {"t_end": t_end, "t_give_up": t_end + drain_s})
        time.sleep(max(0.0, t_start - time.monotonic()))
        status0 = db.status()
        t_start = time.monotonic()  # the window opens here
        setup_s = t_start - T_PROCESS_START
        if args.trace:
            write_json(os.path.join(tmp, "trace", "trace.go"),
                       {"t_stop": min(t_end - 0.5, t_start
                                      + float(traffic["trace_seconds"]))})
        time.sleep(max(0.0, t_end - time.monotonic()))
        status1 = db.status()
        wait_all([os.path.join(tmp, f"client.{i}.json")
                  for i in range(len(clients))], clients,
                 drain_s + 60)
        for c in clients:
            c.wait(timeout=60)
        t_drained = time.monotonic()
        logs = [read_json(f) for f in
                sorted(glob.glob(os.path.join(tmp, "client.*.json")))]
        ops = [op for doc in logs for op in doc["ops"]]
        client_errors = [e for doc in logs for e in doc["errors"]]

        trace = None
        if args.trace:
            open(os.path.join(tmp, "trace", "trace.reduce"), "w").close()
            wait_all([os.path.join(tmp, "trace", "trace.json")], [], 240)
            trace = read_json(os.path.join(tmp, "trace", "trace.json"))
            if trace["error"]:
                raise SystemExit(f"the trace failed: {trace['error']}")

        final_rows = read_back(db, table)
        t_read = time.monotonic()
        loaded = table.loaded()
        held = set(loaded)
        numbers, examples = check.replay(
            ops, lambda s: datagen.token(table.initial(s))
            if s in held else -1, final_rows,
            counted_token=(lambda s, n: datagen.token(table.counted(s, n)))
            if table.kind == "counted" else None, loaded=loaded)
        agg0 = status0["cluster"]["device"]
        agg1 = status1["cluster"]["device"]
        numbers["compiles_in_window"] = (
            agg1["compile"]["backend_compiles"]
            - agg0["compile"]["backend_compiles"])
        numbers["pallas_to_jit"] = (
            agg1["aggregate"]["fallback_causes"]["pallas_to_jit"])
        numbers["client_errors"] = len(client_errors)
        correct, compared = check.verdict(numbers)
        t_checked = time.monotonic()

        db._cluster.close()
        server.send_signal(signal.SIGTERM)
        server.wait(timeout=120)
        device = read_json(os.path.join(tmp, "device.json"))
    finally:
        children.stop()
        shutil.rmtree(tmp, ignore_errors=True)

    values, attempted, failed = end_to_end_values(
        [m["name"] for m in cell.end_to_end()], ops, t_start, t_end, setup_s)
    result = {"correct": correct, "attempted": attempted, "failed": failed}
    if args.trace:
        ev = {"status0": trace["status0"], "status1": trace["status1"],
              "trace": trace, "config": cell.config,
              "ops": [op for op in ops if op[check.STATUS] == check.OK
                      and t_start <= op[check.T1] < t_end],
              "peaks": peaks.get(device["kind"])}
        metrics = {}
        for entry, spec in cell.per_layer():
            v = readers.read_metric(spec, ev)
            if v is not None:
                metrics[entry["name"]] = {"value": v, "unit": entry["unit"]}
        device["window_s"] = trace["window_s"]
        if "busy_s" in trace:
            device["busy_s"] = trace["busy_s"]
        programs = sorted(trace["programs"].items(),
                          key=lambda kv: -kv[1]["total_s"])
        result["breakdown"] = {
            "device_ops": ([["program:" + n, p["total_s"]]
                            for n, p in programs][:4]
                           + [["op:" + n[:120], s]
                              for n, s in trace["device_ops"]])[:10],
            "idle_gaps": trace["idle_gaps"][:10]}
        result["traced"] = {k: trace.get(k) for k in (
            "events_span_s", "events", "start_trace_s", "stop_trace_s",
            "reduce_s")}
    else:
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end() if m["name"] in values}
    result.update(metrics=metrics, device=device)
    result["phases_s"] = {
        "server_up": t_up - T_PROCESS_START, "load": t_loaded - t_up,
        "warm_up": t_start - t_loaded, "drain": t_drained - t_end,
        "read_back": t_read - t_drained, "check": t_checked - t_read}
    result["load_retries"] = load_retries
    result["recoveries"] = status1["cluster"]["health"]["recovery"]["count"]
    result["window"] = values
    if args.rehearse:
        result["rehearsal"] = True
    if args.fault:
        result["fault"] = args.fault
    result["compared"] = compared
    for line in examples + client_errors[:5]:
        log("check:", line)
    for name, c in compared.items():
        log(f"compared {name}: {c['value']} (limit {c['limit']})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
