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

The timing, the retry loop and the log are this file's own.
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
        self.ops = [check_steps(o["steps"]) for o in traffic["operations"]]
        stream = datagen.op_stream(
            traffic, table.rows, seed, process, thread, sampler)
        self.kinds, self.keys_a, self.keys_b = stream[:3]
        self.lengths = stream[3] if len(stream) > 3 else None
        # fresh records are disjoint by (process, thread, n-th insert)
        self.threads = (int(traffic["client_processes"])
                        * int(traffic["client_threads"]))
        self.index = process * int(traffic["client_threads"]) + thread
        self.inserts = 0
        self.inserts_at = [[n for n, step in enumerate(steps)
                            if step[0] == "insert"] for steps in self.ops]
        self.seq = 0
        self.log = []
        self.error = None

    def run(self, t_end, t_give_up):
        try:
            j, n = 0, len(self.kinds)
            while time.monotonic() < t_end:
                i = j % n
                self.one(self.kinds[i], self.keys_a[i], self.keys_b[i],
                         self.lengths[i] if self.lengths else 0, t_give_up)
                j += 1
        except BaseException as e:  # reported by the process, which fails
            self.error = f"{type(e).__name__}: {e}"
            raise

    def fresh_slot(self):
        """The key slot of this thread's next fresh record. An operation
        takes it once, keeps it through its retries, and never gives it
        back: after a 1021 the record may or may not be there."""
        record = (self.table.rows + self.inserts * self.threads + self.index)
        self.inserts += 1
        return self.table.slot(record)

    def one(self, kind, a, b, length, t_give_up):
        """One operation: attempts until acknowledged, refused for good,
        unknown (1021) or still unanswered at ``t_give_up``. An answer
        that comes late is late, not wrong: its latency counts the
        wait. Logs [kind, t_begin, t_ack, status,
        retries, error code, read version, commit version, reads,
        writes] and, where the operation read ranges, an eleventh field:
        those ranges; reads and writes are [slot, token] pairs of the
        acknowledged attempt, a range [first slot, limit, the rows it
        returned as such pairs]."""
        from foundationdb_tpu.core.errors import FDBError

        steps = self.ops[kind]
        at = {"a": self.table.slot(a), "b": self.table.slot(b)}
        fresh = {n: self.fresh_slot() for n in self.inserts_at[kind]}
        tr = self.db.create_transaction()
        t0 = time.monotonic()
        status, code, retries = OK, 0, 0
        reads, writes, ranges = [], [], []
        while True:
            try:
                if not getattr(tr, "repair_ready", False):
                    reads, writes, ranges = transact(
                        self, tr, steps, at, length, fresh)
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
            if reads or ranges:  # a blind write has no read version
                rv = tr.get_read_version()
            if writes:
                cv = tr.get_committed_version()
        row = [kind, t0, t1, status, retries, code, rv, cv, reads, writes]
        if ranges:
            row.append(ranges)
        self.log.append(row)


# ── the steps a mix's operations are made of ────────────────────────
VERBS = ("get", "set", "get_range", "insert", "clear", "clear_range")
WITH_LENGTH = ("get_range", "clear_range")


def check_steps(steps):
    """A mix's steps as ``transact`` takes them, or a ValueError: each
    ``[verb, slot]`` or, for the two range verbs, ``[verb, slot, n]``.
    A ``get_range`` comes before the operation's writes: the check holds
    a range read to the table as it was before its own transaction."""
    wrote = False
    for step in steps:
        verb, slot = step[0], step[1]
        if (verb not in VERBS or slot not in ("a", "b")
                or len(step) != 2 + (verb in WITH_LENGTH)):
            raise ValueError(f"unknown step {step}")
        datagen.step_length(step)
        if verb == "get_range" and wrote:
            raise ValueError("a get_range step comes before the writes")
        wrote |= verb not in ("get", "get_range")
    return steps


def transact(w, tr, steps, at, length, fresh):
    """One attempt of one operation: the mix's ``steps`` in order;
    ``at`` gives the table's key slot for the step slots ``a`` and
    ``b``, ``length`` the operation's drawn ``n``, ``fresh`` the key
    slot of each ``insert`` step by its place in ``steps``.

    - ``get`` reads the record; ``set`` writes what the table's value
      kind says (a counted record bumped from this transaction's own
      get of it, as YCSB's update through FDB's binding; or the
      writer's stamp, as mako's blind set).
    - ``get_range`` reads at most ``n`` records in key order from the
      slot to the table's end: YCSB's scan(startkey, recordcount),
      mako's ``gr``.
    - ``insert`` is a blind set of a fresh record with the value a
      loaded one starts with; the step's drawn key is not used.
    - ``clear`` / ``clear_range`` clear the slot, or the ``n`` slots
      from it (mako's ``cr``); a cleared record's token is -1.

    → (reads, writes, ranges): [slot, token] pairs, and for each range
    read [first slot, limit, its rows as such pairs]."""
    table = w.table
    reads, writes, ranges, seen = [], [], [], {}
    for n, step in enumerate(steps):
        verb, s = step[0], at[step[1]]
        if verb == "get":
            v = tr.get(table.key(s))
            seen[s] = v = None if v is None else bytes(v)
            reads.append([s, datagen.token(v)])
        elif verb == "set":
            w.seq += 1
            v = table.written(s, seen, w.process, w.thread, w.seq)
            tr.set(table.key(s), v)
            writes.append([s, datagen.token(v)])
        elif verb == "insert":
            v = table.initial(fresh[n])
            tr.set(table.key(fresh[n]), v)
            writes.append([fresh[n], datagen.token(v)])
        elif verb == "clear":
            tr.clear(table.key(s))
            writes.append([s, -1])
        else:
            limit = step[2] if isinstance(step[2], int) else length
            if verb == "get_range":
                rows = tr.get_range(table.key(s), table.end_key(),
                                    limit=limit)
                ranges.append([s, limit, [
                    [table.slot_of_key(bytes(k)), datagen.token(bytes(v))]
                    for k, v in rows]])
            else:
                end = s + limit
                tr.clear_range(table.key(s), table.key(end)
                               if end < table.slots else table.end_key())
                writes.extend([c, -1] for c in
                              range(s, min(end, table.slots)))
    if len(writes) > 1:  # a slot written twice holds what was written last
        writes = [list(w) for w in dict(writes).items()]
    return reads, writes, ranges


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
