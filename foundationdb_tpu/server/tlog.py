"""Transaction log: ordered durable record of committed mutations.

Ref parity: fdbserver/TLogServer.actor.cpp — commit proxies push
version-ordered mutation batches; storage servers peek from their durable
version and pop when applied. Durability here is an optional append-only
file WAL with length-framed records (the reference fsyncs a DiskQueue).

TAG PARTITIONING (ref: tag streams in TLogServer.actor.cpp +
TagPartitionedLogSystem.actor.cpp): the commit proxy routes each
mutation to its owning storages (tags) before the push and hands the
log the per-tag split; ``peek(from_version, tag=...)`` then serves ONE
storage's stream — a worker that owns 1/k of the keyspace pulls ~1/k of
the bytes instead of the whole firehose. Tags live in memory alongside
the records (the WAL keeps the untagged batch: recovery re-routes by
the restored shard map, and a tag-less recovered record legally serves
the full batch to every cursor — conservative, never lossy).

``TLogSystem`` is the replicated tier (ref: TagPartitionedLogSystem):
k TLog replicas, a push is acked once a quorum made it durable, peeks
merge across live replicas, and recovery unions the surviving WALs —
losing a minority of logs loses no acked commit.
"""

import bisect
import os
import pickle
import struct
import threading

import zlib

from foundationdb_tpu.utils import lockdep
from foundationdb_tpu.utils import metrics as metrics_mod
from foundationdb_tpu.utils import span as span_mod


class TLogDown(Exception):
    """This log replica is dead (simulation kill or process loss)."""


class TLog:
    def __init__(self, wal_path=None, fsync=False):
        self._log = []  # list[(version, mutations)]
        self._tags = {}  # version -> {tag: [mutations]} (memory only)
        self._first_version = 0
        self.index = 0  # replica id (TLogSystem numbers its members)
        # placement tag (ref: the region/locality of a TLog recruit in
        # DatabaseConfiguration region blocks): the cluster stamps its
        # primary-region id here, the RegionReplicator stamps its
        # satellite replicas with the remote region id. None = regions
        # not configured.
        self.region = None
        self.wal_path = wal_path
        self.fsync = fsync
        self.alive = True
        self._wal = open(wal_path, "ab") if wal_path else None
        self._pop_holds = {}  # name -> version: keep records > version
        # holds mutate on RPC handler threads (remote storage workers)
        # while the commit pipeline's pop iterates them — lock the dict
        self._holds_mu = lockdep.lock("TLog._holds_mu")
        # long-polling peekers (rpc/storageworker.py LogFeed) park here
        # instead of sleep-polling last_version
        self._data_cond = lockdep.condition("TLog._data_cond")
        # push-latency bands + volume counters for the status document
        # (ref: TLogMetrics in TLogServer.actor.cpp). Durations come off
        # the injected clock, so sim snapshots replay deterministically.
        self.metrics = metrics_mod.MetricsRegistry("tlog")
        self._m_push = self.metrics.latency("tlog_push")
        self._m_pushes = self.metrics.counter("pushes")
        self._m_mutations = self.metrics.counter("mutations")

    def _wal_append(self, record):
        """Length+CRC-framed durable append (one framing for push and
        rollback markers — recovery depends on them agreeing)."""
        if self._wal is None:
            return
        payload = pickle.dumps(record, protocol=4)
        self._wal.write(
            struct.pack(">II", len(payload), zlib.crc32(payload)) + payload
        )
        self._wal.flush()
        if self.fsync:
            os.fsync(self._wal.fileno())

    def push(self, version, mutations, tags=None):
        """``tags``: optional {tag: [mutations]} split of this batch by
        destination storage (the proxy's routing); enables per-tag
        peeks. The WAL stores the untagged batch only."""
        if not self.alive:
            raise TLogDown()
        if self._log and version <= self._log[-1][0]:
            raise ValueError("tlog push out of order")
        # one stage feeds the tlog_push band, the profiler annotation
        # and, for a traced batch (the proxy's ambient batch-span
        # context), a per-REPLICA push span — the hop the critical-path
        # tool attributes WAL/fsync time to
        with span_mod.stage("tlog.push", replica=self.index,
                            version=version,
                            mutations=len(mutations)) as psp:
            self._log.append((version, mutations))
            if tags is not None:
                self._tags[version] = tags
            self._wal_append((version, mutations))
        self._m_push.record(psp.seconds)
        self._m_pushes.inc()
        self._m_mutations.inc(len(mutations))
        with self._data_cond:
            self._data_cond.notify_all()

    def wait_for_version(self, version, timeout):
        """Park until a record at/after ``version`` exists (or timeout).
        The long-poll half of peek: a tailing storage worker blocks here
        at zero CPU instead of the lead burning a thread at 1 kHz
        wakeups per idle worker. Death/close wakes waiters immediately
        (kill()/close() notify) so shutdown never stalls on the timeout."""
        with self._data_cond:
            return self._data_cond.wait_for(
                lambda: self.last_version >= version or not self.alive,
                timeout=timeout,
            )

    def kill(self):
        """Process death (simulation / failure injection): wake parked
        long-pollers so they observe the dead log now, not at timeout."""
        self.alive = False
        with self._data_cond:
            self._data_cond.notify_all()

    def rollback(self, version):
        """Undo a just-pushed tail record that failed to reach its
        replication quorum: drop it from the live log and append an
        abort marker so WAL recovery drops it too. Without this, a
        record on a minority of replicas materializes at recovery AFTER
        later commits were applied without it — a consistency anomaly,
        not just the legal 1021 ambiguity."""
        if not self.alive:
            raise TLogDown()
        if self._log and self._log[-1][0] == version:
            self._log.pop()
            self._tags.pop(version, None)
            self._wal_append(("abort", version))

    def peek(self, from_version, tag=None):
        """All records with version > from_version, in order. The log
        is version-sorted, so this bisects to the start instead of
        filtering the whole retained window (storage workers poll).

        With ``tag``: each record carries only that tag's mutations (the
        per-storage stream — ref: TLog tag cursors). Every version still
        appears (possibly empty) so cursors advance; records pushed
        without tags (recovered WALs) serve the full batch —
        conservative, never lossy."""
        if not self.alive:
            raise TLogDown()
        # snapshot once: pop() swaps the list on the commit thread, and a
        # bisect index computed against the OLD list applied to the NEW
        # one would silently skip still-retained records
        log = self._log
        i = bisect.bisect_right(log, from_version, key=lambda r: r[0])
        recs = log[i:]
        if tag is None:
            return recs
        tags = self._tags
        return [
            (v, tags[v].get(tag, []) if v in tags else m)
            for v, m in recs
        ]

    def hold_pop(self, name, version):
        """Register a peek cursor: records newer than ``version`` survive
        pop until the holder advances or releases (ref: backup workers'
        pop locks on the tlog)."""
        with self._holds_mu:
            self._pop_holds[name] = version

    def release_pop(self, name):
        with self._holds_mu:
            self._pop_holds.pop(name, None)

    def pop(self, up_to_version):
        """Discard records <= up_to_version (applied durably downstream),
        clamped so no registered peek cursor loses unread records."""
        with self._holds_mu:
            holds = list(self._pop_holds.values())
        if holds:
            up_to_version = min(up_to_version, *holds)
        self._log = [(v, m) for v, m in self._log if v > up_to_version]
        if self._tags:
            self._tags = {
                v: t for v, t in self._tags.items() if v > up_to_version
            }
        self._first_version = max(self._first_version, up_to_version)

    @property
    def last_version(self):
        return self._log[-1][0] if self._log else self._first_version

    def status(self):
        """This replica's status RPC payload (leaf of the status doc)."""
        self.metrics.gauge("retained_records").set(len(self._log))
        self.metrics.gauge("last_version").set(self.last_version)
        return {
            "alive": self.alive,
            "region": self.region,
            "metrics": self.metrics.snapshot(),
        }

    def close(self):
        self.alive = False
        with self._data_cond:
            self._data_cond.notify_all()
        if self._wal is not None:
            self._wal.close()
            self._wal = None

    @staticmethod
    def recover(wal_path):
        """Replay a WAL file → list[(version, mutations)], tolerating a
        torn tail (ref: DiskQueue recovery)."""
        out = []
        try:
            with open(wal_path, "rb") as f:
                data = f.read()
        except FileNotFoundError:
            return out
        off = 0
        while off + 8 <= len(data):
            ln, crc = struct.unpack_from(">II", data, off)
            if off + 8 + ln > len(data):
                break  # torn tail
            payload = data[off + 8 : off + 8 + ln]
            if zlib.crc32(payload) != crc:
                break
            rec = pickle.loads(payload)
            if rec[0] == "abort":
                # rollback marker undoes the PRECEDING record with that
                # version only (positional: a later re-grant of the same
                # version number is a distinct, valid record)
                for i in range(len(out) - 1, -1, -1):
                    if out[i][0] == rec[1]:
                        del out[i]
                        break
            else:
                out.append(rec)
            off += 8 + ln
        return out


class TLogSystem:
    """k replicated TLogs with quorum-acked pushes.

    Ref parity: TagPartitionedLogSystem — the proxy's push is durable
    once enough replicas logged it; the chosen quorum (majority by
    default) means any surviving majority holds every acked commit, so
    recovery (union of surviving WALs) loses nothing when a minority of
    logs dies. Exposes the single-TLog interface, so the proxy, backup
    agent, and storage recovery are replication-agnostic.
    """

    def __init__(self, n=3, wal_path=None, fsync=False, quorum=None):
        self.n = n
        self.quorum = quorum if quorum is not None else n // 2 + 1
        self.wal_path = wal_path  # base path; replica i appends .i
        self.logs = [
            TLog(wal_path=f"{wal_path}.{i}" if wal_path else None, fsync=fsync)
            for i in range(n)
        ]
        for i, log in enumerate(self.logs):
            log.index = i  # replica id on each push span
        self._pop_holds = {}
        self._data_cond = lockdep.condition("TLogSystem._data_cond")

    @staticmethod
    def replica_paths(wal_path, n):
        return [f"{wal_path}.{i}" for i in range(n)]

    # ── replica lifecycle (simulation / failure detection hooks) ──
    def kill(self, i):
        self.logs[i].kill()
        with self._data_cond:
            self._data_cond.notify_all()

    def revive(self, i):
        """A rebooted replica rejoins caught-up from a live peer (ref: a
        new tlog generation starting from the recovery version). Without
        a live donor it STAYS dead and returns None — rejoining with a
        gap would make merged peeks silently lose acked records that
        other (now-dead) replicas hold."""
        log = self.logs[i]
        donor = next(
            (l for l in self.logs if l.alive and l is not log), None
        )
        if donor is None:
            return None
        log.alive = True
        log._log = []
        log._tags = {}
        log._first_version = donor._first_version
        for v, m in donor.peek(0):
            log.push(v, m, tags=donor._tags.get(v))
        return log

    @property
    def live_count(self):
        return sum(1 for l in self.logs if l.alive)

    # ── single-TLog facade ──
    @property
    def _first_version(self):
        if self.live_count == 0:
            raise TLogDown("no live tlog replicas")
        return min(l._first_version for l in self.logs if l.alive)

    @_first_version.setter
    def _first_version(self, v):
        for l in self.logs:
            l._first_version = v

    def push(self, version, mutations, tags=None):
        """Replicate to every live log; durable at ``quorum`` acks.
        Raises TLogDown when a quorum is unreachable — the partial
        replicas roll the record back (abort-marked in their WALs) so it
        cannot resurface at recovery after later commits landed without
        it; the proxy turns the failure into commit_unknown_result."""
        accepted = []
        for log in self.logs:
            try:
                log.push(version, mutations, tags=tags)
                accepted.append(log)
            except TLogDown:
                continue
        if len(accepted) < self.quorum:
            for log in accepted:  # best-effort undo of the partial push
                try:
                    log.rollback(version)
                except TLogDown:
                    pass
            raise TLogDown(
                f"{len(accepted)}/{self.n} tlogs acked (need {self.quorum})"
            )
        with self._data_cond:
            self._data_cond.notify_all()

    def wait_for_version(self, version, timeout):
        """Park until a quorum-acked record at/after ``version`` exists
        (long-poll support; see TLog.wait_for_version)."""
        with self._data_cond:
            return self._data_cond.wait_for(
                lambda: self.live_count == 0
                or self.last_version >= version,
                timeout=timeout,
            )

    def peek(self, from_version, tag=None):
        """Merged view across live replicas: the union of their records
        (any acked record is on ≥ quorum of them; a dead replica's gaps
        are covered by the others)."""
        merged = {}
        for log in self.logs:
            if not log.alive:
                continue
            for v, m in log.peek(from_version, tag=tag):
                merged.setdefault(v, m)
        return sorted(merged.items())

    def hold_pop(self, name, version):
        self._pop_holds[name] = version
        for log in self.logs:
            log.hold_pop(name, version)

    def release_pop(self, name):
        self._pop_holds.pop(name, None)
        for log in self.logs:
            log.release_pop(name)

    def pop(self, up_to_version):
        for log in self.logs:
            if log.alive:
                log.pop(up_to_version)

    @property
    def last_version(self):
        if self.live_count == 0:
            raise TLogDown("no live tlog replicas")
        return max(l.last_version for l in self.logs if l.alive)

    def status(self):
        """Per-replica status payloads (the status doc's logs section)."""
        return [log.status() for log in self.logs]

    def close(self):
        for log in self.logs:
            log.close()
        with self._data_cond:
            self._data_cond.notify_all()

    @classmethod
    def recover(cls, wal_path, n):
        """Union the surviving replica WALs → list[(version, mutations)].
        Any record acked at quorum survives the loss of a minority; a
        record present on only a minority was never acked (its client saw
        commit_unknown_result) — including it is the legal 1021 outcome."""
        merged = {}
        for path in cls.replica_paths(wal_path, n):
            for v, m in TLog.recover(path):
                merged.setdefault(v, m)
        return sorted(merged.items())
