"""One closed-loop load-generator process.

``run.py`` starts ``client_processes`` of these; each runs
``client_threads`` threads, each thread one operation at a time against
``fdb.open(cluster_file=..., **traffic["open"])``. A thread times every
operation itself, from the start of its first attempt to the
acknowledgement, conflict retries and their back-off included, and logs
what it read and wrote. Nothing is summarised here: the raw samples go
to the parent, which merges all clients before it takes a tail.

Protocol, all through files in ``--control``: the process writes
``ready.<p>`` once it is connected and its streams are drawn, waits for
``go`` (a JSON object with ``t_end`` and ``t_give_up``, readings of
``time.monotonic()`` — one clock for every process of a host), starts
no operation after ``t_end``, goes on retrying those in flight until
they are acknowledged or ``t_give_up`` has passed, and writes ``--out``.

The shape is ``bench.py:run_e2e_client``'s; the timing, the retry loop
and the log are this file's own.
"""

import argparse
import json
import os
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.dirname(HERE)]

import datagen  # noqa: E402
from check import FAILED, LATE, OK, UNKNOWN  # noqa: E402  (an operation's status)


class Worker:
    """One client thread: its stream, its log, its operations."""

    def __init__(self, db, table, traffic, seed, process, thread, sampler):
        self.db = db
        self.table = table
        self.process = process
        self.thread = thread
        self.ops = [o["steps"] for o in traffic["operations"]]
        for verb, slot in (step for steps in self.ops for step in steps):
            if verb not in VERBS or slot not in "ab":
                raise ValueError(f"unknown step {[verb, slot]}")
        self.kinds, self.keys_a, self.keys_b = datagen.op_stream(
            traffic, table.rows, seed, process, thread, sampler)
        self.seq = 0
        self.log = []
        self.error = None

    def run(self, t_end, t_give_up):
        try:
            j = 0
            while time.monotonic() < t_end:
                i = j % datagen.STREAM
                self.one(self.kinds[i], self.keys_a[i], self.keys_b[i],
                         t_give_up)
                j += 1
        except BaseException as e:  # reported by the process, which fails
            self.error = f"{type(e).__name__}: {e}"
            raise

    def one(self, kind, a, b, t_give_up):
        """One operation: attempts until acknowledged, refused for good,
        unknown (1021) or still unanswered at ``t_give_up``. An answer
        that comes late is late, not wrong: its latency counts the
        wait. Logs [kind, t_begin, t_ack, status,
        retries, error code, read version, commit version, reads,
        writes]; reads and writes are [record id, token] pairs of the
        acknowledged attempt."""
        from foundationdb_tpu.core.errors import FDBError

        steps, ids = self.ops[kind], {"a": a, "b": b}
        tr = self.db.create_transaction()
        t0 = time.monotonic()
        status, code, retries = OK, 0, 0
        reads, writes = [], []
        while True:
            try:
                if not getattr(tr, "repair_ready", False):
                    reads, writes = transact(self, tr, steps, ids)
                tr.commit()
                break
            except FDBError as e:
                code = e.code
                if code == 1021:  # may or may not have applied
                    status = UNKNOWN
                    break
                if time.monotonic() > t_give_up:
                    status = LATE
                    break
                try:
                    tr.on_error(e)  # back-off; re-raises what is final
                except FDBError:
                    status = FAILED
                    break
                retries += 1
        t1 = time.monotonic()
        rv = cv = 0
        if status == OK:
            code = 0
            rv = tr.get_read_version()
            if writes:
                cv = tr.get_committed_version()
        self.log.append([kind, t0, t1, status, retries, code, rv, cv,
                         reads, writes])


# ── the steps a mix's operations are made of ────────────────────────
VERBS = ("get", "set")


def transact(w, tr, steps, ids):
    """One attempt of one operation: the mix's ``steps`` in order, each
    a [verb, slot]; ``ids`` gives the record of slot ``a`` and ``b``.
    ``get`` reads the record; ``set`` writes what the table's value
    kind says (a counted record bumped from this transaction's own get
    of it, as YCSB's update through FDB's binding; or the writer's
    stamp, as mako's blind set). → (reads, writes), [record id, token]
    pairs."""
    reads, writes, seen = [], [], {}
    for verb, slot in steps:
        i = ids[slot]
        key = w.table.key(i)
        if verb == "get":
            v = tr.get(key)
            seen[i] = v = None if v is None else bytes(v)
            reads.append([i, datagen.token(v)])
        else:
            w.seq += 1
            v = w.table.written(i, seen, w.process, w.thread, w.seq)
            tr.set(key, v)
            writes.append([i, datagen.token(v)])
    return reads, writes


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--cluster-file", required=True)
    p.add_argument("--config", required=True)
    p.add_argument("--traffic", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--process", type=int, required=True)
    p.add_argument("--control", required=True)
    p.add_argument("--out", required=True)
    args = p.parse_args(argv)
    with open(args.config) as f:
        config = json.load(f)
    with open(args.traffic) as f:
        traffic = json.load(f)

    import foundationdb_tpu as fdb

    table = datagen.Table(config, args.seed)
    sampler = datagen.key_sampler(traffic["keys"], table.rows, args.seed)
    db = fdb.open(cluster_file=args.cluster_file, **traffic.get("open", {}))
    workers = [Worker(db, table, traffic, args.seed, args.process, t, sampler)
               for t in range(int(traffic["client_threads"]))]
    open(os.path.join(args.control, f"ready.{args.process}"), "w").close()
    go = os.path.join(args.control, "go")
    while not os.path.exists(go):
        time.sleep(0.005)
    with open(go) as f:
        go = json.load(f)
    threads = [threading.Thread(target=w.run, daemon=True,
                                args=(go["t_end"], go["t_give_up"]))
               for w in workers]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    db._cluster.close()
    errors = [w.error for w in workers if w.error]
    tmp = args.out + ".tmp"
    with open(tmp, "w") as f:
        json.dump({"process": args.process, "errors": errors,
                   "ops": [op for w in workers for op in w.log]}, f)
    os.replace(tmp, args.out)
    return 1 if errors else 0


if __name__ == "__main__":
    sys.exit(main())
