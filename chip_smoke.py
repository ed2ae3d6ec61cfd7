"""The quickest proof that the served commit path still starts on the chip.

Drives client → GRV proxy → commit proxy → host pack → device resolve →
tlog → storage → read through the two entry points the README names, at
default ``Knobs()``, and holds every answer to a plain reference: the
same seeded script against a ``resolver_backend="cpu"`` cluster (the
python skiplist, no device in it).

    python chip_smoke.py             # one chip: phases inproc, then served
    python chip_smoke.py --chips 4   # four chips: the sharded path only
    python chip_smoke.py --rehearse  # CPU, shrunk; never passes for a chip run

A chip belongs to one process at a time, so this process never starts a
JAX backend: each phase runs in a child, one after the other.

- ``inproc`` (a child of this script): ``fdb.open()`` — the library
  default, one single-step dispatch per commit — then
  ``fdb.open(commit_pipeline="thread")``, whose batcher dispatches deep
  backlogs as one scanned program.
- ``served``: a child ``python -m foundationdb_tpu.tools.fdbserver
  --resolver-backend tpu``; this process is only its RPC client.
- ``mesh`` (``--chips 4``): ``fdb.open(n_resolvers=4,
  commit_pipeline="thread")``, one program over four devices, compared
  with the reference and with one lane.

The device lanes are conservative by design (ops/conflict.py: a hash
collision or a coarse summary may add a conflict, never lose one), so a
phase passes when (i) every transaction the reference refuses with 1020
the device path refuses too, (ii) the final read of the whole keyspace
is byte-identical to the reference's and holds every acknowledged
write, and (iii) device-only aborts stay under 1% of the script. The
script's outcomes do not depend on how the batcher cuts batches: the
transactions of a round touch disjoint keys, a round ends before the
next begins, and conflicting pairs are sequenced (both read, the first
commits, then the second).

Every phase also proves where it ran: its platform must be ``tpu``, the
``pallas_to_jit`` fallback must never have fired, the kernel routes
must be the ones the resolver selects on that platform, and nothing may
be compiled inside the scripted window. One JSON object per line; the
last line is the verdict.
"""

import argparse
import concurrent.futures
import json
import os
import queue
import random
import shutil
import signal
import struct
import subprocess
import sys
import tempfile
import threading
import time
from typing import NamedTuple

# importing the package starts no JAX backend, nor does a "cpu" cluster
import foundationdb_tpu as fdb
from foundationdb_tpu.core.errors import FDBError
from foundationdb_tpu.core.options import Knobs
from foundationdb_tpu.txn.database import retry_loop

HERE = os.path.dirname(os.path.abspath(__file__))
VALUE_BYTES = 1024  # YCSB's record: 1 KB
MAX_EXTRA_CONFLICTS = 0.01
PAGE_ROWS = 2000


class Sizes(NamedTuple):
    rows: int  # records loaded, four sets to a transaction
    threads: int  # client threads sharing a round (1 = commit in turn)
    point_rounds: int  # rounds of read-modify-write transactions …
    point_txns: int  # … of this many transactions, two keys each
    range_txns: int  # get_range + clear_range transactions per round
    range_span: int  # keys under each of those ranges
    occ_pairs: int  # scripted OCC races (second committer gets 1020)
    scan_pairs: int  # scripted scan-versus-clear conflicts


# A thread-mode round must leave more than batch_txn_capacity (1024)
# commits pending for the batcher to cut a backlog of several batches;
# sync and served pay one dispatch per commit or per RPC window.
CHIP_SIZES = {
    "sync": Sizes(10_000, 1, 3, 300, 150, 8, 40, 20),
    "thread": Sizes(100_000, 8, 3, 4096, 2048, 8, 256, 128),
    "served": Sizes(100_000, 4, 2, 1024, 512, 8, 64, 32),
}
REHEARSAL_SIZES = {
    "sync": Sizes(400, 1, 2, 40, 20, 4, 6, 4),
    "thread": Sizes(1_000, 4, 2, 160, 48, 4, 8, 4),
    "served": Sizes(400, 2, 1, 48, 16, 4, 4, 2),
}
# The rehearsal's in-process clusters shrink the device tables so that a
# CPU compiles and steps them quickly; fdbserver has no such option, so
# the served rehearsal runs default knobs at a small count.
REHEARSAL_KNOBS = dict(
    batch_txn_capacity=64, hash_table_bits=20, range_ring_capacity=512,
    coarse_buckets_bits=10)


def emit(**facts):
    print(json.dumps(facts), flush=True)


def key(i):
    return b"user%08d" % i  # the keys of benchmark/configs/ycsb_1kb_100k.json


class Records:
    """The data, made from the seed: record ``i`` holds an 8-byte update
    count and a filler cut from one random pool."""

    def __init__(self, seed):
        self._pool = random.Random(seed).randbytes(1 << 20)

    def value(self, i, count):
        off = (i * 7919) % (len(self._pool) - VALUE_BYTES)
        return struct.pack(">Q", count) + self._pool[off + 8:off + VALUE_BYTES]


class Txn:
    """One scripted transaction: a body that reads and buffers writes,
    and what its acknowledgement does to the model of the data."""

    def __init__(self, index, body, ack):
        self.index = index
        self.body = body
        self.ack = ack
        self.tr = None
        self.error = None


class Script:
    """The seeded script over one database handle. ``model`` maps each
    record id to its expected update count (None = cleared) and moves
    only on acknowledgement; every read a body makes is checked against
    it, so an acknowledged write that cannot be read back fails at once."""

    def __init__(self, db, sizes, seed):
        self.db = db
        self.sizes = sizes
        self.records = Records(seed)
        self.rng = random.Random(seed + 1)
        self.model = {}
        self.conflicted = set()  # indexes whose first commit drew 1020
        self.n_txns = 0
        self._pool = concurrent.futures.ThreadPoolExecutor(sizes.threads)
        self._async = sizes.threads > 1

    def close(self):
        self._pool.shutdown()

    # ── running transactions ────────────────────────────────────────
    def _shares(self, txns):
        n = self.sizes.threads
        return [txns[i::n] for i in range(n) if txns[i::n]]

    def _each_share(self, fn, txns):
        for f in [self._pool.submit(fn, s) for s in self._shares(txns)]:
            f.result()  # re-raises what a worker raised

    def _read(self, txns):
        def run(share):
            for t in share:
                t.tr = self.db.create_transaction()
                t.body(t.tr)

        self._each_share(run, txns)

    def _commit(self, txns):
        """First commit of every transaction, concurrently where the
        handle batches; then the ordinary retry loop for the refused."""
        def run(share):
            if not self._async:
                for t in share:
                    try:
                        t.tr.commit()
                    except FDBError as e:
                        t.error = e
                return
            futs = [(t, t.tr.commit_async()) for t in share]
            for t, fut in futs:
                fut.result(timeout=300)
                try:
                    t.tr.commit_finish(fut)
                except FDBError as e:
                    t.error = e

        self._each_share(run, txns)
        for t in txns:
            if t.error is not None:
                if t.error.code != 1020:
                    raise t.error
                self.conflicted.add(t.index)
                t.tr.on_error(t.error)
                retry_loop(t.tr, t.body)
            t.ack()

    def _new(self, body, ack):
        self.n_txns += 1
        return Txn(self.n_txns - 1, body, ack)

    def _round(self, txns):
        self._read(txns)
        self._commit(txns)

    def _pairs(self, pairs):
        """Both read, the firsts commit, then the seconds."""
        self._read([t for p in pairs for t in p])
        self._commit([first for first, _ in pairs])
        self._commit([second for _, second in pairs])

    # ── the transactions ────────────────────────────────────────────
    def _load_txn(self, ids):
        def body(tr):
            for i in ids:
                tr.set(key(i), self.records.value(i, 0))

        def ack():
            for i in ids:
                self.model[i] = 0

        return self._new(body, ack)

    def _rmw_txn(self, ids):
        """Point-only read-modify-write: bump each record's count."""
        def bumped(i):
            return 0 if self.model.get(i) is None else self.model[i] + 1

        def body(tr):
            for i in ids:
                self._check_read(i, tr.get(key(i)))
                tr.set(key(i), self.records.value(i, bumped(i)))

        def ack():
            for i in ids:
                self.model[i] = bumped(i)

        return self._new(body, ack)

    def _scan_clear_txn(self, lo, hi):
        def body(tr):
            rows = tr.get_range(key(lo), key(hi))
            want = [(key(i), self.records.value(i, self.model[i]))
                    for i in range(lo, hi) if self.model.get(i) is not None]
            if [(bytes(k), bytes(v)) for k, v in rows] != want:
                raise AssertionError(f"scan of records {lo}..{hi} is not "
                                     "what was acknowledged")
            tr.clear_range(key(lo), key(hi))

        def ack():
            for i in range(lo, hi):
                self.model[i] = None

        return self._new(body, ack)

    def _clear_txn(self, lo, hi):
        def body(tr):
            tr.clear_range(key(lo), key(hi))

        def ack():
            for i in range(lo, hi):
                self.model[i] = None

        return self._new(body, ack)

    def _check_read(self, i, got):
        count = self.model.get(i)
        want = None if count is None else self.records.value(i, count)
        if (None if got is None else bytes(got)) != want:
            raise AssertionError(
                f"record {i} read back is not what was acknowledged")

    # ── the script ──────────────────────────────────────────────────
    def load(self):
        """Ordinary transactions of four sets (a wider one would spill
        into a covering range write), in windows the batcher's watchdog
        and the RPC deadline can stomach."""
        ids = range(self.sizes.rows)
        txns = [self._load_txn(ids[i:i + 4]) for i in range(0, len(ids), 4)]
        window = 2048 * self.sizes.threads
        for i in range(0, len(txns), window):
            self._round(txns[i:i + window])
        self.n_txns = 0  # loading is set-up, not script
        if self.conflicted:
            raise AssertionError("blind loads over fresh keys conflicted")

    def _spans(self, n):
        s = self.sizes
        return [(r * s.range_span, (r + 1) * s.range_span)
                for r in self.rng.sample(range(s.rows // s.range_span), n)]

    def _point_round(self, n):
        ids = self.rng.sample(range(self.sizes.rows), 2 * n)
        self._round([self._rmw_txn(ids[2 * j:2 * j + 2]) for j in range(n)])

    def run(self):
        s = self.sizes
        for _ in range(s.point_rounds):
            self._point_round(s.point_txns)
        cleared = self._spans(s.range_txns)
        self._round([self._scan_clear_txn(lo, hi) for lo, hi in cleared])
        # blind sets put half of the cleared records back
        self._round([self._load_txn(range(lo, lo + min(4, hi - lo)))
                     for lo, hi in cleared[::2]])
        self._point_round(s.point_txns)
        # OCC race: both read one record, both bump it
        ids = self.rng.sample(range(s.rows), s.occ_pairs)
        self._pairs([(self._rmw_txn([i]), self._rmw_txn([i])) for i in ids])
        # scan versus clear: a scan's range loses its middle to a clear
        # that commits first
        self._pairs([
            (self._clear_txn(lo + 1, hi - 1), self._scan_clear_txn(lo, hi))
            for lo, hi in self._spans(s.scan_pairs)])
        self._round([self._scan_clear_txn(lo, hi)
                     for lo, hi in self._spans(s.range_txns // 2)])

    def final_state(self):
        """The whole keyspace, read in pages through ordinary
        transactions, checked against every acknowledged write."""
        rows, begin = [], b""
        while True:
            page = self.db.run(
                lambda tr: tr.get_range(begin, b"\xff", limit=PAGE_ROWS))
            rows.extend((bytes(k), bytes(v)) for k, v in page)
            if len(page) < PAGE_ROWS:
                break
            begin = rows[-1][0] + b"\x00"
        want = [(key(i), self.records.value(i, c))
                for i, c in sorted(self.model.items()) if c is not None]
        if rows != want:
            raise AssertionError(
                "the final state does not hold every acknowledged write")
        return rows


def reference(sizes, seed):
    """The plain reference: the script, one commit at a time, against
    the python skiplist. → (conflicted indexes, final rows)."""
    db = fdb.open(resolver_backend="cpu")
    script = Script(db, sizes._replace(threads=1), seed)
    try:
        script.load()
        script.run()
        return script.conflicted, script.final_state()
    finally:
        script.close()
        db._cluster.close()


def warm_up(db, capacity, threads):
    """Build every program the scripted window can meet, after the load
    has built the point-only ones: a lone range write flips the resolver
    to its full variant for good, then (``threads`` > 1) windows of
    blind writes, wide enough to outrun the batcher, until it has
    scanned a backlog with that variant. Returns the device section of
    status."""
    db.clear_range(b"warm/", b"warm0")

    def window(t):
        futs = []
        for j in range(capacity):
            tr = db.create_transaction()
            tr.set(b"warm/%d/%06d" % (t, j), b"w")
            futs.append((tr, tr.commit_async()))
        for tr, fut in futs:
            fut.result(timeout=300)
            tr.commit_finish(fut)

    if threads > 1:
        with concurrent.futures.ThreadPoolExecutor(threads) as pool:
            for _ in range(20):
                if any(k.startswith("(False,") for k in
                       device_doc(db)["aggregate"]["compile_keys"]):
                    break
                for f in [pool.submit(window, t) for t in range(threads)]:
                    f.result()
        db.clear_range(b"warm/", b"warm0")  # leave only the records
    return device_doc(db)


def device_doc(db):
    return db.status()["cluster"]["device"]


def drive(name, db, sizes, seed, want, rehearse, scans_backlogs,
          sharded=False):
    """Load, warm up, run the script and hold the outcome to ``want``,
    the reference's. Emits the phase's facts; raises on any failure."""
    t_start = time.perf_counter()
    start = device_doc(db)
    if start["platform"] != "tpu" and not rehearse:
        raise SystemExit(f"{name}: the resolver's state is on "
                         f"{start['platform']!r}, not on a TPU")
    knobs = db._cluster.knobs
    if not rehearse and knobs != Knobs():
        raise AssertionError(f"{name}: not the default Knobs()")
    db[b"warm/first"] = b"w"  # the first program, behind a retry loop
    script = Script(db, sizes, seed)
    try:
        script.load()
        t_loaded = time.perf_counter()
        before = warm_up(db, knobs.batch_txn_capacity,
                         sizes.threads if scans_backlogs else 1)
        t_warm = time.perf_counter()
        script.run()
        t_run = time.perf_counter()
        rows = script.final_state()
    finally:
        script.close()
    after = device_doc(db)
    agg = after["aggregate"]
    ref_conflicts, ref_rows = want
    missed = sorted(ref_conflicts - script.conflicted)
    extra = sorted(script.conflicted - ref_conflicts)
    routes = agg["kernel_routes"]
    on_tpu = after["platform"] == "tpu"

    def builds(since):
        return {k: round(v - since["compile"][k], 3)
                for k, v in after["compile"].items()}

    emit(phase=name, platform=after["platform"],
         device_kind=after["device_kind"],
         device_count=after["device_count"], rows=sizes.rows,
         txns=script.n_txns, ref_conflicts=len(ref_conflicts),
         conflicts=len(script.conflicted), missed_conflicts=len(missed),
         extra_conflicts=len(extra), final_rows=len(rows),
         kernel_routes=routes, bucket_histogram=agg["bucket_histogram"],
         lane_entries=agg["lane_entries"],
         fallbacks={k: v for k, v in agg["fallback_causes"].items() if v},
         compile=builds(start),
         builds_in_script=builds(before)["backend_compiles"],
         load_s=round(t_loaded - t_start, 3),
         warm_up_s=round(t_warm - t_loaded, 3),
         script_s=round(t_run - t_warm, 3),
         wall_s=round(time.perf_counter() - t_start, 3))
    problems = []
    if missed:
        problems.append(f"missed conflicts: transactions {missed[:8]}")
    if rows != ref_rows:
        problems.append("final state differs from the reference's")
    if len(extra) > MAX_EXTRA_CONFLICTS * script.n_txns:
        problems.append(f"{len(extra)} device-only aborts in "
                        f"{script.n_txns} transactions")
    if agg["fallback_causes"]["pallas_to_jit"]:
        problems.append("the pallas_to_jit fallback fired")
    # what Resolver.__init__ selects: the point-only variant and every
    # scan run the jnp lanes ("jit"); on a TPU the single-step full
    # variant, which a lone batch rides once there is range history,
    # runs the ring kernel — except in a mesh, which has no Pallas
    # lanes.
    ring = on_tpu and not sharded
    allowed = {"jit", "pallas_ring"} if ring else {"jit"}
    required = allowed if not scans_backlogs else {"jit"}
    if not required <= set(routes) <= allowed:
        problems.append(f"kernel routes {routes}")
    if scans_backlogs and not any(
            int(b) > 1 for b in agg["bucket_histogram"]):
        problems.append("no backlog deeper than one batch was dispatched")
    # A TPU resolver pads every backlog to one width, so the warm-up has
    # met every shape. On a CPU the ladder has three, and a mesh's
    # router slices a batch by its fill: there the count is only shown.
    if on_tpu and not sharded and builds(before)["backend_compiles"]:
        problems.append("programs were built inside the script")
    if problems:
        raise AssertionError(f"{name}: " + "; ".join(problems))
    return rows


def child_prologue(rehearse, chips):
    """First thing in a process that owns the chip: place the compile
    cache, then look at the platform before anything is loaded."""
    import jax

    from foundationdb_tpu.utils import deviceprofile

    deviceprofile.enter_process()
    d = jax.devices()
    if not rehearse and d[0].platform != "tpu":
        raise SystemExit(f"JAX found no TPU, only {d[0].platform!r}")
    if len(d) < chips:
        raise SystemExit(f"{chips} chips asked for, JAX sees {len(d)}")
    return {"platform": d[0].platform, "kind": d[0].device_kind,
            "count": len(d)}


def phase_inproc(args):
    device = child_prologue(args.rehearse, 1)
    table = REHEARSAL_SIZES if args.rehearse else CHIP_SIZES
    knobs = REHEARSAL_KNOBS if args.rehearse else {}
    for name, how in (("sync", {}), ("thread", {"commit_pipeline": "thread"})):
        want = reference(table[name], args.seed)
        db = fdb.open(**how, **knobs)  # sync: exactly the README's open()
        try:
            drive(f"inproc.{name}", db, table[name], args.seed, want,
                  args.rehearse, scans_backlogs=name == "thread")
        finally:
            db._cluster.close()
    emit(phase="inproc", ok=True, device=device)


def phase_mesh(args):
    """The sharded path and what it is compared with: the reference,
    one lane, then four lanes as one program over four devices."""
    device = child_prologue(args.rehearse, 4)
    sizes = (REHEARSAL_SIZES if args.rehearse else CHIP_SIZES)["thread"]
    knobs = REHEARSAL_KNOBS if args.rehearse else {}
    want = reference(sizes, args.seed)
    finals = {}
    for lanes in (1, 4):
        db = fdb.open(n_resolvers=lanes, commit_pipeline="thread", **knobs)
        try:
            finals[lanes] = drive(f"mesh.{lanes}", db, sizes, args.seed,
                                  want, args.rehearse, scans_backlogs=True,
                                  sharded=lanes > 1)
            n_lanes = db.status()["cluster"]["resolvers"]
            dev = device_doc(db)
            if lanes == 4 and (
                    n_lanes != 4 or dev["device_count"] != 4
                    or dev["aggregate"]["fallback_causes"][
                        "sharded_to_local"]):
                raise AssertionError(
                    f"mesh.4: {n_lanes} lanes over "
                    f"{dev['device_count']} devices")
        finally:
            db._cluster.close()
    if finals[4] != finals[1]:
        raise AssertionError("four lanes and one lane end in different states")
    emit(phase="mesh", ok=True, device=device)


def run_child(phase, args, env=None):
    """Run one phase of this script in a child, relay its lines, and
    return the last: its verdict. The child has exited on return."""
    cmd = [sys.executable, os.path.join(HERE, "chip_smoke.py"),
           "--phase", phase, "--seed", str(args.seed)]
    if args.rehearse:
        cmd.append("--rehearse")
    done = subprocess.run(cmd, cwd=HERE, stdout=subprocess.PIPE, text=True,
                          env=env)
    lines = done.stdout.splitlines()
    for line in lines:
        print(line, flush=True)
    if done.returncode != 0:
        raise SystemExit(f"phase {phase} exited with {done.returncode}")
    return json.loads(lines[-1])


def phase_served(args):
    """fdbserver in a child; this process is its RPC client only."""
    sizes = (REHEARSAL_SIZES if args.rehearse else CHIP_SIZES)["served"]
    want = reference(sizes, args.seed)
    tmp = tempfile.mkdtemp(prefix="chip_smoke.")
    cf = os.path.join(tmp, "fdb.cluster")
    server = subprocess.Popen(
        [sys.executable, "-m", "foundationdb_tpu.tools.fdbserver",
         "--resolver-backend", "tpu", "--dir", os.path.join(tmp, "db"),
         "--cluster-file", cf],
        cwd=HERE, stdout=subprocess.PIPE, text=True)
    lines = queue.Queue()

    def pump():
        for line in server.stdout:
            lines.put(line)
        lines.put(None)  # the server is gone

    threading.Thread(target=pump, daemon=True).start()
    try:
        while True:
            line = lines.get(timeout=300)
            if line is None:
                raise SystemExit("fdbserver exited before it listened")
            if "FDBD listening" in line:
                break
        db = fdb.open(cluster_file=cf, commit_pipeline="thread")
        try:
            drive("served", db, sizes, args.seed, want, args.rehearse,
                  scans_backlogs=False)
            dev = device_doc(db)
        finally:
            db._cluster.close()
        server.send_signal(signal.SIGTERM)
        rc = server.wait(timeout=120)
        if rc != 0:
            raise SystemExit(f"fdbserver exited with {rc}")
    finally:
        if server.poll() is None:
            server.kill()
            server.wait()
        shutil.rmtree(tmp, ignore_errors=True)
    return {"platform": dev["platform"], "kind": dev["device_kind"],
            "count": dev["device_count"]}


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--chips", type=int, choices=(1, 4), default=1,
                   help="4 runs the sharded path and its comparison only")
    p.add_argument("--rehearse", action="store_true",
                   help="shrink sizes and knobs and accept a CPU; the "
                        "verdict then says ok: false, rehearsal: true")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--phase", choices=("inproc", "mesh"),
                   help=argparse.SUPPRESS)  # this script, as a child
    args = p.parse_args(argv)
    if args.phase:
        {"inproc": phase_inproc, "mesh": phase_mesh}[args.phase](args)
        return 0
    if args.chips == 4:
        env = None
        if args.rehearse:  # four host devices stand in for the chips
            flags = os.environ.get("XLA_FLAGS", "")
            env = {**os.environ, "XLA_FLAGS": (
                flags + " --xla_force_host_platform_device_count=4").strip()}
        device = run_child("mesh", args, env)["device"]
    else:
        device = run_child("inproc", args)["device"]
        served = phase_served(args)
        if served["platform"] != device["platform"]:
            raise SystemExit(f"served ran on {served}, inproc on {device}")
    if args.rehearse:
        emit(ok=False, rehearsal=True, device=device)
    else:
        emit(ok=True, device=device)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except BaseException as e:
        if not (isinstance(e, SystemExit) and e.code == 0):
            emit(ok=False, error=f"{type(e).__name__}: {e}")
        raise
