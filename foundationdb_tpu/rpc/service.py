"""Cluster service endpoints + the remote client.

Ref parity: the client↔server split in FoundationDB — fdbclient's
NativeAPI speaks to fdbserver processes found through the cluster file
(fdbclient/ClusterConnectionFile, MonitorLeader). Here `ClusterService`
exposes a running `server.cluster.Cluster`'s role interfaces as RPC
endpoints, and `RemoteCluster` implements the exact cluster surface
`txn/transaction.py` consumes (grv_proxy / read_storage / commit_proxy /
knobs / status), so `Database(RemoteCluster(...))` IS the remote client —
the whole transaction, layer, and directory stack runs against a real
network without a line of change.

Failure semantics on a dead connection (ref: NativeAPI's handling of
broken proxy connections):
- reads / GRVs: retry on a fresh connection; if no server is reachable
  the error surfaces as `transaction_too_old`-style retryable only after
  reconnect succeeds — otherwise ConnectionLost propagates (the cluster
  is gone, not the transaction).
- commit: NEVER auto-retried at this layer. A connection that dies with
  a commit outstanding returns `commit_unknown_result` (1021) — the
  transaction may or may not have committed, exactly the reference's
  contract; the client retry loop owns the disambiguation.
"""

import dataclasses
import itertools
import os
import string
import threading

import time

from foundationdb_tpu.core import deterministic
from foundationdb_tpu.core.errors import FDBError
from foundationdb_tpu.core.options import DEFAULT_KNOBS, Knobs
from foundationdb_tpu.rpc import failuremon
from foundationdb_tpu.rpc.transport import (
    WEDGED_STRIKE_LIMIT,
    ConnectionLost,
    DeadlineExceeded,
    Deferred,
    Park,
    RpcServer,
    connect_any,
    rpc_class,
)
from foundationdb_tpu.utils.backoff import Backoff
from foundationdb_tpu.txn.futures import FutureRange, FutureValue
from foundationdb_tpu.rpc.wire import PROTOCOL_VERSION
from foundationdb_tpu.utils import lockdep
from foundationdb_tpu.utils import span as span_mod
from foundationdb_tpu.utils.trace import SEV_ERROR, TraceEvent


# ───────────────────────────── cluster files ─────────────────────────────
def write_cluster_file(path, addresses, description="tpu", cluster_id=None):
    """``description:id@host:port,host:port`` (ref: ClusterConnectionFile
    format in fdbclient/ConnectionString)."""
    if cluster_id is None:
        # drawn from the injected stream so a seeded sim writes the same
        # cluster file every run (FL001: cluster-visible entropy)
        id_rng = deterministic.rng("cluster-id")
        cluster_id = "".join(
            id_rng.choice(string.ascii_lowercase + string.digits)
            for _ in range(8)
        )
    body = f"{description}:{cluster_id}@{','.join(addresses)}\n"
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        f.write(body)
    os.replace(tmp, path)
    return body.strip()


def parse_cluster_file(path):
    """Returns (description, cluster_id, [addresses])."""
    with open(path) as f:
        line = f.read().strip()
    head, _, addrs = line.partition("@")
    desc, _, cid = head.partition(":")
    addresses = [a.strip() for a in addrs.split(",") if a.strip()]
    if not addresses:
        raise ValueError(f"cluster file {path!r} has no addresses: {line!r}")
    return desc, cid, addresses


# ───────────────────────────── server side ───────────────────────────────
class ClusterService:
    """Endpoint table over a live Cluster (the fdbserver worker's RPC
    surface). One instance per served cluster; handlers are thread-safe
    to the same degree the underlying roles are (thread-mode clusters
    take their own locks)."""

    WATCH_TTL_S = 900  # orphaned watches (client gone) age out
    MAX_WATCH_WAIT_S = 30.0  # server-side clamp on one blocking chunk

    def __init__(self, cluster):
        self.cluster = cluster
        # the RpcServer these handlers are attached to (serve_cluster /
        # fdbserver set it): its per-class request counters ride the
        # status document as cluster.rpc
        self.rpc_server = None
        self._watches = {}  # watch_id -> (Watch, threading.Event, born)
        self._watch_ids = itertools.count(1)
        self._watch_lock = lockdep.lock("ClusterService._watch_lock")
        # The plain synchronous CommitProxy (commit_pipeline="sync") has
        # no internal synchronization — the in-process deployments that
        # use it are single-threaded. Concurrent RPC clients are not:
        # serialize their commits here. The "thread" pipeline's batching
        # proxy takes concurrent submissions natively (that's its job),
        # so it skips the lock and actually batches across clients.
        if getattr(cluster, "commit_pipeline", "sync") == "thread":
            self._commit_lock = None
        else:
            self._commit_lock = lockdep.lock("ClusterService._commit_lock")

    def handlers(self):
        return {
            "hello": self.hello,
            # failure-monitor keepalive: cheapest possible liveness probe
            # (ref: FailureMonitor's ping loop) — answers even while the
            # storage/commit paths are busy, so it measures process
            # liveness, not load
            "ping": lambda: "pong",
            "knobs": self.knobs,
            "status": self.status,
            # the metrics section alone (monitoring agents poll this
            # without paying for the whole status document)
            "metrics": self.metrics,
            # cluster doctor: verdict + probe bands + recovery timeline
            # + lag rollups alone (fdbcli `doctor`, tools/doctor.py)
            "health": self.health,
            # workload attribution: hot ranges + per-tag rollup alone
            # (fdbcli `top`, tools/heatmap.py split-point advice)
            "metrics_hot": self.metrics_hot,
            # device-path execution profile alone (fdbcli `profile`):
            # resolver dispatch/pad/fallback accounting + lane walls
            "device_profile": self.device_profile,
            # metrics history: the retention layer's per-metric windows
            # + verdict timeline alone (fdbcli `history`, the trend
            # consumers in tools/doctor.py and tools/heatmap.py)
            "history": self.history,
            # flight recorder: dump summary + newest black-box artifact
            # (tools/flight.py post-mortems against a live cluster)
            "flight": self.flight,
            # continuous consistency scan: round/progress/verdict alone
            # (fdbcli `scan status`, tools/doctor.py --scan), plus the
            # kill-switch control behind fdbcli `scan on|off`
            "consistency_scan": self.consistency_scan,
            "set_consistency_scan": self.set_consistency_scan,
            "get_read_version": self.get_read_version,
            "storage_get": self.storage_get,
            "resolve_selector": self.resolve_selector,
            "get_range": self.get_range,
            "read_batch": self.read_batch,
            "commit": self.commit,
            "commit_batch": self.commit_batch,
            "watch_register": self.watch_register,
            "watch_poll": self.watch_poll,
            "watch_wait": self.watch_wait,
            # exclusion returns DD move records (arbitrary role objects);
            # the wire carries just the relocation count
            "exclude_storage": lambda sid: len(
                self.cluster.exclude_storage(sid) or ()
            ),
            "include_storage": self.cluster.include_storage,
            "list_excluded": self.cluster.list_excluded,
            "consistency_check": self.cluster.consistency_check,
            "estimated_range_size": self.cluster.estimated_range_size_bytes,
            "range_split_points": self.cluster.range_split_points,
            "lock_database": self.cluster.lock_database,
            "unlock_database": self.cluster.unlock_database,
            "lock_uid": self.cluster.lock_uid,
            # distributed tracing config (fdbcli `tracing`, the
            # \xff\xff/tracing/ special keys against a remote cluster)
            "tracing_config": self.cluster.tracing_config,
            "set_tracing": self._set_tracing,
            "set_tenant_mode": self.cluster.set_tenant_mode,
            "configure": self._configure,
            "tenant_mode": self.cluster.tenant_mode,
            "set_tag_quota": self.cluster.set_tag_quota,
            "feed_register": self.cluster.change_feeds.register,
            "feed_read": self.cluster.change_feeds.read,
            "feed_pop": self.cluster.change_feeds.pop,
            "feed_deregister": self.cluster.change_feeds.deregister,
            "feed_list": self.cluster.change_feeds.list,
        }

    # watch_wait parks until its watch fires: the blocking pool
    LONG_METHODS = frozenset({"watch_wait"})

    def inline_methods(self):
        """The endpoints the RpcServer may answer on a connection's own
        thread: their handlers wait on no other thread. ``ping`` is a
        constant; a read takes the storage's mutex for one lookup —
        where the read surface is this process's own (a surface over
        the wire would park the connection in its round trip: then
        reads stay on the pool); a GRV is granted now or hands the
        server a ``Park`` (``get_read_version``); a commit, where the
        cluster batches commits on a thread of its own, is submitted
        (a locked append) and answered when its batch settles
        (``commit``: a ``Deferred``). Under the ``"sync"`` pipeline a
        commit runs the whole pipeline under ``_commit_lock``; watches
        wait for their key, admin calls and ``commit_batch`` for
        whatever they manage: not declared, so they ride the pools."""
        from foundationdb_tpu.server.storage import RangeReadInterface

        inline = {"ping", "get_read_version"}
        if self._commit_lock is None:
            inline.add("commit")
        if isinstance(self.cluster.read_storage(), RangeReadInterface):
            inline |= {"storage_get", "get_range", "resolve_selector",
                       "read_batch"}
        return inline

    def hello(self, client_protocol):
        if client_protocol != PROTOCOL_VERSION:
            raise FDBError.from_name("incompatible_protocol_version")
        return {
            "protocol": PROTOCOL_VERSION,
            "generation": self.cluster.generation,
        }

    def knobs(self):
        return dataclasses.asdict(self.cluster.knobs)

    def status(self):
        doc = self.cluster.status()
        if self.rpc_server is not None:
            doc["cluster"]["rpc"] = self.rpc_server.stats()
        return doc

    def metrics(self):
        return self.cluster.metrics_status()

    def health(self):
        return self.cluster.health_status()

    def metrics_hot(self, top=None):
        return self.cluster.hot_ranges_status(top=top)

    def device_profile(self):
        return self.cluster.device_profile_status()

    def history(self):
        return self.cluster.history_status()

    def flight(self):
        return self.cluster.flight_status()

    def consistency_scan(self):
        return self.cluster.consistency_scan_status()

    def set_consistency_scan(self, on):
        return self.cluster.set_consistency_scan(bool(on))

    def get_read_version(self, priority="default", tags=()):
        proxy = self.cluster.grv_proxy
        grant_now = getattr(proxy, "grant_now", None)
        if grant_now is None:
            # the synchronous proxy grants or refuses; it never waits
            return proxy.get_read_version(priority, tags=tuple(tags))
        v = grant_now(priority, tuple(tags))
        if v is None:
            # the request has to queue behind the grant loop: that wait
            # must not hold the connection it came in on
            return Park(lambda: proxy.wait_for_grant(priority))
        return v

    def storage_get(self, key, rv):
        return self.cluster.read_storage(key).get(key, rv)

    def resolve_selector(self, selector, rv):
        return self.cluster.read_storage().resolve_selector(selector, rv)

    def get_range(self, begin, end, rv, limit, reverse):
        rows = self.cluster.read_storage().get_range(
            begin, end, rv, limit=limit, reverse=reverse
        )
        return [(k, v) for k, v in rows]

    def read_batch(self, ops):
        """One multiplexed read RPC (the client ReadBatcher's flush):
        N coalesced reads, decoded once, served under ONE storage lock
        acquisition (StorageServer.read_batch). Slots are per-op —
        FDBError values ride the wire natively, so one too-old key
        fails alone, never the batch."""
        ops = list(ops)
        sp = span_mod.from_context(
            "storage.read_batch", span_mod.current(), ops=len(ops)
        )
        try:
            st = self.cluster.read_storage()
            rb = getattr(st, "read_batch", None)
            if rb is not None:
                return rb(ops)
            # storage tier without a vectorized serve: same slots, one
            # op at a time (semantics identical, just more crossings)
            out = []
            for op in ops:
                try:
                    if op[0] == "g":
                        out.append(
                            self.cluster.read_storage(op[1]).get(
                                op[1], op[2]
                            )
                        )
                    elif op[0] == "r":
                        out.append([
                            (k, v) for k, v in st.get_range(
                                op[1], op[2], op[3],
                                limit=op[4], reverse=op[5],
                            )
                        ])
                    elif op[0] == "s":
                        out.append(st.resolve_selector(op[1], op[2]))
                    else:
                        raise FDBError.from_name(
                            "client_invalid_operation"
                        )
                except FDBError as e:
                    out.append(e)
            return out
        finally:
            sp.finish()

    def commit(self, request):
        # the proxy returns (never raises) FDBError verdicts; the wire
        # carries them as values so the client transaction sees the exact
        # in-process contract
        proxy = self.cluster.commit_proxy
        if self._commit_lock is not None:
            with self._commit_lock:
                return proxy.commit(request)
        submit = getattr(proxy, "submit", None)
        if submit is None:
            # a proxy that cannot take a completion: its wait must not
            # hold the connection this request came in on
            return Park(lambda: proxy.commit(request))
        # no thread waits for the batch: the batcher's settle completes
        # the request, and the reply leaves with its batch's others
        fut = submit(request)
        return Deferred(fut.add_done_callback, fut.poll)

    def _configure(self, commit_proxies=None, resolvers=None):
        """Live reconfiguration over the wire (fdbcli `configure`);
        returns the achieved shape so a remote operator can confirm."""
        return self.cluster.configure(commit_proxies=commit_proxies,
                                      resolvers=resolvers)

    def _set_tracing(self, sample_rate=None, enabled=None):
        return self.cluster.set_tracing(sample_rate=sample_rate,
                                        enabled=enabled)

    def commit_batch(self, requests):
        """A client-batched window of commits in ONE RPC (the remote
        BatchingCommitProxy's flush): decoded once, pipelined once —
        per-commit RPCs round-trip-bound multi-process deployments
        (ref: clients streaming batched commits at the proxy).

        Span accounting: this route bypasses any server-side batching
        wrapper (deliberately — the window is already batched), so when
        the bare proxy has ceded commit_e2e ownership to that wrapper,
        nobody else would record the span; record it here (decode →
        reply, the server-side view of the client's window)."""
        from foundationdb_tpu.utils import metrics as metrics_mod

        target = getattr(self.cluster.commit_proxy, "inner",
                         self.cluster.commit_proxy)
        owner = target.inners[0] if hasattr(target, "inners") else target
        t0 = metrics_mod.now() \
            if getattr(owner, "spans_owned_externally", False) \
            and metrics_mod.enabled() else None
        try:
            if self._commit_lock is not None:
                with self._commit_lock:
                    return target.commit_batch(requests)
            return target.commit_batch(requests)
        finally:
            if t0 is not None:
                owner._m_e2e.record(max(0.0, metrics_mod.now() - t0))

    def watch_register(self, key, seen_value):
        w = self.cluster.read_storage(key).watch(key, seen_value)
        fired = threading.Event()
        w.on_fire(fired.set)
        # on_fire's fired-check and its callback append are not atomic
        # against a concurrent commit's _fire (which runs on another pool
        # thread): re-checking after registration closes the window where
        # _fire iterated the callback list before ours landed
        if w.fired:
            fired.set()
        wid = next(self._watch_ids)
        now = time.monotonic()
        with self._watch_lock:
            self._watches[wid] = (w, fired, now)
            if len(self._watches) % 256 == 0:
                self._sweep_locked(now)
        return wid

    def _sweep_locked(self, now):
        """Drop aged-out watches whose client never came back for them —
        they pin both this registry and storage._watches forever
        otherwise (a disconnect leaves no signal at this layer)."""
        dead = [
            wid for wid, (_, _, born) in self._watches.items()
            if now - born > self.WATCH_TTL_S
        ]
        for wid in dead:
            del self._watches[wid]

    def _watch_fired(self, entry):
        w, fired, _ = entry
        return w.fired or fired.is_set()

    def watch_poll(self, wid):
        with self._watch_lock:
            entry = self._watches.get(wid)
            if entry is None:
                return True  # forgotten watches count as fired (re-read)
            if self._watch_fired(entry):
                del self._watches[wid]  # one-shot, like the reference
                return True
        return False

    def watch_wait(self, wid, timeout):
        with self._watch_lock:
            entry = self._watches.get(wid)
        if entry is None:
            return True
        if timeout is None or timeout > self.MAX_WATCH_WAIT_S:
            timeout = self.MAX_WATCH_WAIT_S  # a client cannot park a
            # server thread forever; waiters re-issue chunks
        entry[1].wait(timeout=timeout)
        if self._watch_fired(entry):
            with self._watch_lock:
                self._watches.pop(wid, None)
            return True
        return False


def serve_cluster(cluster, host="127.0.0.1", port=0, max_workers=16,
                  secret=None):
    """Expose a cluster on the network; returns the RpcServer. Also
    attaches the log-feed endpoints storage-worker processes pull from
    (rpc/storageworker.py). ``secret`` enables the transport's
    shared-secret handshake — required before listening on a
    non-loopback interface (the surface includes management access)."""
    from foundationdb_tpu.rpc.storageworker import LogFeed

    # test chaos arming by knob: a non-empty seed wraps every NEW
    # client socket this process opens in the seeded fault injector
    # (rpc/chaos.py stays un-imported on the default "" path)
    chaos_seed = getattr(cluster.knobs, "rpc_chaos_seed", "")
    if chaos_seed:
        from foundationdb_tpu.rpc import chaos

        chaos.arm(chaos_seed)
    service = ClusterService(cluster)
    server = RpcServer(host, port, service.handlers(),
                       max_workers=max_workers,
                       long_methods=service.LONG_METHODS,
                       inline_methods=service.inline_methods(),
                       secret=secret)
    service.rpc_server = server
    # tlog_peek long-polls; it must not occupy the short-RPC pool
    server.add_handlers(LogFeed(cluster).handlers(),
                        long_methods={"tlog_peek"})
    TraceEvent("RpcServerStarted").detail(address=server.address).log()
    return server


# ───────────────────────────── client side ───────────────────────────────
# RPC deadline classes: every method maps to one of the four per-class
# deadline knobs (rpc_deadline_*_s) through transport.rpc_class, the
# table the server's per-class counters share.
def _class_deadline(knobs, cls):
    return {
        "read": knobs.rpc_deadline_read_s,
        "grv": knobs.rpc_deadline_grv_s,
        "commit": knobs.rpc_deadline_commit_s,
        "admin": knobs.rpc_deadline_admin_s,
    }[cls]
class _RemoteWatch:
    """Client handle satisfying the Watch surface _WatchHandle polls."""

    __slots__ = ("_rc", "_wid", "_fired")

    def __init__(self, rc, wid):
        self._rc = rc
        self._wid = wid
        self._fired = False

    @property
    def fired(self):
        if not self._fired:
            try:
                self._fired = bool(self._rc._call("watch_poll", self._wid))
            except ConnectionLost:
                # server gone: treat as fired so the waiter re-reads (and
                # gets the real error from the read path)
                self._fired = True
        return self._fired

    def wait_remote(self, timeout=None):
        """Block until fired, in bounded server-side chunks (a pool worker
        on the server blocks for at most CHUNK_S per RPC, so parked
        watches cannot starve the handler pool)."""
        CHUNK_S = 5.0
        deadline = None if timeout is None else time.monotonic() + timeout
        while not self._fired:
            chunk = CHUNK_S
            if deadline is not None:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    return False
                chunk = min(chunk, remaining)
            try:
                self._fired = bool(
                    self._rc._call("watch_wait", self._wid, chunk)
                )
            except ConnectionLost:
                self._fired = True  # server gone: re-read via the read path
        return True


class _RemoteChangeFeeds:
    """Client stub for the change-feed registry endpoints."""

    __slots__ = ("_rc",)

    def __init__(self, rc):
        self._rc = rc

    def register(self, feed_id, begin, end):
        return self._rc._call("feed_register", feed_id, begin, end)

    def read(self, feed_id, begin_version, end_version=None, limit=0):
        return self._rc._call(
            "feed_read", feed_id, begin_version, end_version, limit
        )

    def pop(self, feed_id, version):
        return self._rc._call("feed_pop", feed_id, version)

    def deregister(self, feed_id):
        return self._rc._call("feed_deregister", feed_id)

    def list(self):
        return self._rc._call("feed_list")


class _RemoteGrvProxy:
    __slots__ = ("_rc",)

    def __init__(self, rc):
        self._rc = rc

    def get_read_version(self, priority="default", tags=()):
        return self._rc._call("get_read_version", priority, tuple(tags))


class _CoalescingGrvProxy:
    """Client-side read-version batching (ref: NativeAPI's
    readVersionBatcher): concurrent default-priority transactions share
    GRV RPCs instead of paying one wire round trip each. A request
    rides the NEXT rpc to START after it arrives — a version granted by
    an rpc already in flight could miss a commit that completed after
    that rpc began, which would break external consistency."""

    __slots__ = ("_rc", "_cond", "_started", "_done", "_last", "_leader",
                 "_max_wanted")

    def __init__(self, rc):
        self._rc = rc
        self._cond = lockdep.condition("_CoalescingGrvProxy._cond")
        self._started = 0  # GRV rounds begun
        self._done = 0  # GRV rounds completed
        self._last = None  # value of the newest completed round
        self._max_wanted = 0
        self._leader = False

    def get_read_version(self, priority="default", tags=()):
        if tags or priority != "default":
            # tagged/priority requests carry their own admission
            # semantics: never coalesced into an untagged round
            return self._rc._call("get_read_version", priority,
                                  tuple(tags))
        cond = self._cond
        with cond:
            if self._leader:
                want = self._started + 1  # the NEXT round covers me
                if want > self._max_wanted:
                    self._max_wanted = want
                cond.wait_for(lambda: self._done >= want)
                v = self._last
                if v is not None:
                    return v
                # my round's rpc failed: fall through to a direct call
            else:
                self._leader = True
                want = None
        if want is not None:
            return self._rc._call("get_read_version", "default", ())
        # leader: run rounds until no one is waiting for a newer one
        while True:
            with cond:
                self._started += 1
            try:
                v = self._rc._call("get_read_version", "default", ())
            except BaseException:
                with cond:
                    # release EVERY registered waiter, not just the next
                    # round's: no leader survives to run later rounds,
                    # so a waiter parked on want > done+1 would hang
                    # forever (round-5 review). They see _last None and
                    # fall back to direct calls.
                    self._done = max(self._done + 1, self._max_wanted)
                    self._started = self._done
                    self._last = None
                    self._leader = False
                    cond.notify_all()
                raise
            with cond:
                self._done += 1
                self._last = v
                cond.notify_all()
                # exit decision under the SAME lock registrations take:
                # either a waiter already wants a newer round (loop) or
                # later arrivals will see _leader False and lead
                if self._max_wanted <= self._done:
                    self._leader = False
                    return v


class _RemoteCommitProxy:
    __slots__ = ("_rc",)

    def __init__(self, rc):
        self._rc = rc

    @property
    def knobs(self):
        # the client-side BatchingCommitProxy wrapper sizes its batches
        # from the SERVER's knobs
        return self._rc.knobs

    def commit(self, request):
        try:
            return self._rc._call_once("commit", request)
        except ConnectionLost:
            # the request may have reached the server: 1021, not a retry
            return FDBError.from_name("commit_unknown_result")
        except FDBError as e:
            if e.code != 1021:
                raise
            # deadline-expired commit (converted in _call_once): same
            # maybe-committed contract, returned as a verdict because
            # the proxy surface never raises
            return e

    def commit_batch(self, requests):
        try:
            return self._rc._call_once("commit_batch", list(requests))
        except ConnectionLost:
            return [FDBError.from_name("commit_unknown_result")
                    for _ in requests]
        except FDBError as e:
            if e.code != 1021:
                raise
            return [FDBError.from_name("commit_unknown_result")
                    for _ in requests]


class _RemoteStorage:
    """Read-side surface (router analog) over the wire.

    With worker read-balancing enabled (``RemoteCluster(...,
    read_workers=True)``), reads round-robin across the lead and any
    registered storage-worker processes (ref: LoadBalance over storage
    interfaces); a worker that vanishes is dropped and the read retried
    on the lead. Watches and writes always go to the lead.
    """

    __slots__ = ("_rc",)

    def __init__(self, rc):
        self._rc = rc

    def _read(self, method, *args, span=None):
        from foundationdb_tpu.rpc.transport import RemoteError

        worker = self._rc._next_worker(span)
        if worker is not None:
            try:
                result = worker.call(
                    method, *args,
                    deadline_s=self._rc._deadline_for(method),
                )
                self._rc._worker_ok(worker)
                return result
            except DeadlineExceeded:
                # the worker is wedged, not dead: with the monitor on,
                # mark it — the router skips it until a half-open probe
                # clears; every other caller pays NOTHING. Monitor off
                # (the pre-monitor behavior): it stays in rotation and
                # each round-robin hit re-pays the deadline.
                if self._rc._monitor_enabled():
                    failuremon.monitor().note_timeout(
                        f"{worker.host}:{worker.port}",
                        f"{method} deadline",
                    )
            except (ConnectionLost, OSError, RemoteError):
                # dead socket OR a handler that faults server-side: this
                # worker is not serving; stop routing to it
                self._rc._drop_worker(worker)
            except FDBError as e:
                if e.code != 1009:
                    raise
                # future_version = the worker is lagging. Serve this read
                # from the lead; a worker that keeps lagging (frozen tail
                # thread) strikes out and is dropped rather than adding a
                # version-wait stall to every round-robin hit forever.
                self._rc._worker_strike(worker)
        return self._rc._call(method, *args)

    def get(self, key, rv):
        return self._read("storage_get", key, rv,
                          span=(key, key + b"\x00"))

    def resolve_selector(self, selector, rv):
        # selectors can walk past their anchor key: only a worker
        # serving the WHOLE keyspace may resolve one (span=None)
        return self._read("resolve_selector", selector, rv)

    def get_range(self, begin, end, rv, limit=0, reverse=False):
        return self._read("get_range", begin, end, rv, limit, reverse,
                          span=(begin, end))

    # ── async forms: futures settled by the connection's ReadBatcher
    # (txn/futures.py) — N outstanding reads ride one read_batch RPC ──
    def get_async(self, key, rv, finalize=None, ctx=None):
        b = self._rc.read_batcher
        fut = FutureValue(batcher=b, finalize=finalize)
        b.submit(("g", key, rv), fut, ctx)
        return fut

    def get_range_async(self, begin, end, rv, limit=0, reverse=False,
                        finalize=None, ctx=None):
        b = self._rc.read_batcher
        fut = FutureRange(batcher=b, finalize=finalize)
        b.submit(("r", begin, end, rv, limit, reverse), fut, ctx)
        return fut

    def resolve_selector_async(self, selector, rv, finalize=None,
                               ctx=None):
        b = self._rc.read_batcher
        fut = FutureValue(batcher=b, finalize=finalize)
        b.submit(("s", selector, rv), fut, ctx)
        return fut

    def watch(self, key, seen_value):
        wid = self._rc._call("watch_register", key, seen_value)
        return _RemoteWatch(self._rc, wid)


class RemoteCluster:
    """The client-side cluster: same attribute surface as
    server.cluster.Cluster, every role call an RPC."""

    def __init__(self, addresses, connect_timeout=5.0, read_workers=False,
                 secret=None, commit_pipeline="sync",
                 commit_batch_max=None):
        if isinstance(addresses, str):
            addresses = [addresses]
        self.addresses = list(addresses)
        self._connect_timeout = connect_timeout
        self._secret = secret
        self._lock = lockdep.lock("RemoteCluster._lock")
        self._client = None
        self._closed = False
        self._knobs = None
        self._workers = []  # RpcClients to storage-worker processes
        self._worker_rr = 0
        self._worker_strikes = {}  # client -> consecutive 1009 lags
        self._read_batcher = None  # lazy: built on first async read
        # jittered reconnect pacing shared by every idempotent retry on
        # this handle (flow Backoff parity; reset on success)
        self._reconnect_backoff = Backoff(initial_s=0.01, max_s=0.5)
        self.grv_proxy = _RemoteGrvProxy(self)
        self.commit_proxy = _RemoteCommitProxy(self)
        self.change_feeds = _RemoteChangeFeeds(self)
        self._storage = _RemoteStorage(self)
        self._connect()
        # keepalive pinger: probes links that have gone quiet so the
        # failure monitor learns about a wedged peer from the ping, not
        # from the next real request's deadline (ref: FailureMonitor's
        # ping loop). Cadence is jittered off the "ping-cadence" named
        # stream; rpc_ping_interval_s == 0 disables the thread.
        self._ping_stop = threading.Event()
        self._ping_thread = None
        if DEFAULT_KNOBS.rpc_ping_interval_s > 0:
            self._ping_thread = threading.Thread(
                target=self._ping_loop, name="rpc-keepalive", daemon=True
            )
            self._ping_thread.start()
        self.commit_pipeline = commit_pipeline
        if commit_pipeline == "thread":
            # concurrent client threads share GRV rounds too (ref:
            # NativeAPI batching read-version requests)
            self.grv_proxy = _CoalescingGrvProxy(self)
            # CLIENT-side commit batching (ref: NativeAPI batching
            # commits toward the proxies): concurrent transactions in
            # this process share commit_batch RPCs — one wire round
            # trip per WINDOW instead of per commit, which is what
            # makes a multi-process deployment throughput-bound on the
            # server pipeline rather than on per-commit RTTs. Also
            # enables commit_async (submit) against remote clusters.
            from foundationdb_tpu.server.batcher import BatchingCommitProxy

            self.commit_proxy = BatchingCommitProxy(
                self.commit_proxy, max_batch=commit_batch_max,
            )
        if read_workers:
            self.refresh_workers()

    @classmethod
    def from_cluster_file(cls, path, **kw):
        _, _, addresses = parse_cluster_file(path)
        return cls(addresses, **kw)

    def _ping_loop(self):
        rng = deterministic.rng("ping-cadence")
        while True:
            interval = self._effective_knobs().rpc_ping_interval_s
            if interval <= 0:
                # knob disabled server-side: stay parked but re-check
                if self._ping_stop.wait(2.0):
                    return
                continue
            # jittered cadence (0.5x..1.5x) so a fleet of clients does
            # not ping a server in lockstep; the draw rides the named
            # stream, so seeded runs schedule identically
            if self._ping_stop.wait(interval * (0.5 + rng.random())):
                return
            try:
                self._ping_idle_links(interval)
            except Exception as e:
                # the pinger is advisory: it must never kill itself —
                # a failed probe round just runs again next tick
                TraceEvent("KeepalivePingRoundFailed",
                           severity=SEV_ERROR).detail(
                    error=type(e).__name__).log()

    def _ping_idle_links(self, interval):
        from foundationdb_tpu.rpc.transport import RemoteError

        if not self._monitor_enabled():
            return
        with self._lock:
            clients = [self._client] + [c for c, _ in self._workers]
        mon = failuremon.monitor()
        for c in clients:
            if c is None or not c.alive:
                continue
            if time.monotonic() - c.last_activity < interval:
                continue  # link is carrying traffic; liveness is known
            addr = f"{c.host}:{c.port}"
            try:
                c.call("ping",
                       deadline_s=min(1.0, self._deadline_for("ping")))
                mon.mark_ok(addr)
            except DeadlineExceeded:
                mon.note_timeout(addr, "keepalive ping")
            except (ConnectionLost, OSError) as e:
                mon.mark_failed(addr, f"keepalive: {e}")
            except RemoteError:
                pass  # peer predates the ping endpoint: no health signal

    def _connect(self):
        with self._lock:
            if self._closed:
                # a closed handle must stay closed: a racing waiter thread
                # must not silently resurrect the connection
                raise ConnectionLost("RemoteCluster is closed")
            if self._client is not None and self._client.alive:
                return self._client
            if self._client is not None:
                self._client.close()  # release the dead socket's fd
            self._client = connect_any(
                self.addresses, self._connect_timeout, secret=self._secret
            )
            try:
                # the admin deadline bounds the handshake: a freshly
                # accepted but black-holed connection must surface as
                # unreachable, not park the caller forever
                hello = self._client.call(
                    "hello", PROTOCOL_VERSION,
                    deadline_s=self._deadline_for("hello"),
                )
            except DeadlineExceeded as e:
                self._client.close()
                raise ConnectionLost(
                    f"handshake with {self._client.host}:"
                    f"{self._client.port} timed out: {e}"
                ) from e
            generation = hello["generation"]
            prior = getattr(self, "server_generation", None)
            if prior is not None and generation != prior:
                # the cluster recovered behind our back: cached knobs may
                # be stale. Read versions pinned before the recovery need
                # no client-side fencing — the recovered storage rejects
                # them TOO_OLD server-side.
                self._knobs = None
                TraceEvent("ClusterGenerationChanged").detail(
                    old=prior, new=generation).log()
            self.server_generation = generation
            return self._client

    def _effective_knobs(self):
        """Cached server knobs when we have them, DEFAULT_KNOBS before —
        NEVER the ``knobs`` property: the deadline for the knobs fetch
        itself must not recurse into a knobs fetch."""
        return self._knobs if self._knobs is not None else DEFAULT_KNOBS

    def _deadline_for(self, method):
        return _class_deadline(
            self._effective_knobs(), rpc_class(method)
        )

    def _monitor_enabled(self):
        kn = self._effective_knobs()
        return kn.failure_monitor

    def _call_once(self, method, *args):
        """One attempt, no reconnect — the commit path's no-double-send
        rule. Every attempt carries its class deadline; an expiry is
        converted here: commit-class → commit_unknown_result (1021, the
        request MAY have reached the server), anything else →
        process_behind (1037, plainly retryable) — and the endpoint is
        marked in the failure monitor either way."""
        client = self._client
        if client is None or not client.alive:
            client = self._connect()
        addr = f"{client.host}:{client.port}"
        try:
            result = client.call(
                method, *args, deadline_s=self._deadline_for(method)
            )
        except DeadlineExceeded as e:
            failuremon.monitor().note_timeout(addr, f"{method} deadline")
            if client.deadline_strikes >= WEDGED_STRIKE_LIMIT:
                # a black-holed link looks exactly like a slow one until
                # several consecutive deadlines expire with no frame in
                # either direction: stop re-paying the deadline on every
                # retry — kill the socket so the NEXT attempt reconnects
                # fresh (connection-level escape; the retry itself still
                # belongs to the caller's on_error loop)
                TraceEvent("RpcLinkWedged", severity=SEV_ERROR).detail(
                    address=addr, method=method,
                    strikes=client.deadline_strikes).log()
                client.close()
            if rpc_class(method) == "commit":
                raise FDBError.from_name("commit_unknown_result") from e
            raise FDBError.from_name("process_behind") from e
        except (ConnectionLost, OSError) as e:
            failuremon.monitor().mark_failed(addr, f"{method}: {e}")
            raise ConnectionLost(str(e)) from e
        failuremon.monitor().mark_ok(addr)
        return result

    def _call(self, method, *args):
        """Idempotent call: one transparent reconnect+retry (reads, GRVs,
        watches are all safe to re-send), with a jittered backoff sleep
        before the reconnect so a fleet of clients doesn't stampede a
        recovering server (flow Backoff parity; resets on success)."""
        try:
            result = self._call_once(method, *args)
        except ConnectionLost:
            self._reconnect_backoff.sleep()
            self._connect()  # raises ConnectionLost if nobody is reachable
            result = self._call_once(method, *args)
        self._reconnect_backoff.reset()
        return result

    @property
    def knobs(self):
        if self._knobs is None:
            self._knobs = Knobs(**self._call("knobs"))
        return self._knobs

    @property
    def read_batcher(self):
        """This connection's read multiplexer (txn/futures.py), built
        lazily so read-free clients never pay the knobs fetch or the
        flusher thread. Thread-mode pipelines get the windowed flusher;
        sync/manual flush synchronously inside submit (deterministic —
        a sim's RPC sequence is a pure function of its schedule)."""
        rb = self._read_batcher
        if rb is not None:
            return rb
        kn = self.knobs  # outside _lock: _call reconnects under it
        from foundationdb_tpu.txn.futures import ReadBatcher

        with self._lock:
            if self._read_batcher is None:
                self._read_batcher = ReadBatcher(
                    self._send_read_batch,
                    max_keys=kn.read_batch_max_keys,
                    window_s=kn.read_batch_window_ms / 1e3,
                    thread=(self.commit_pipeline == "thread"),
                    # a batch retried once on the lead may pay the read
                    # deadline twice before the watchdog should step in
                    deadline_s=2 * kn.rpc_deadline_read_s,
                )
            return self._read_batcher

    @staticmethod
    def _batch_span(ops):
        """Bounding [begin, end) of a batch's ops, or None when any op
        needs full keyspace coverage (selectors walk) — the coverage
        key for routing a whole batch at one tag-scoped worker."""
        lo = hi = None
        for op in ops:
            if op[0] == "g":
                b, e = op[1], op[1] + b"\x00"
            elif op[0] == "r" and isinstance(op[1], bytes) \
                    and isinstance(op[2], bytes):
                b, e = op[1], op[2]
            else:
                return None
            if lo is None or b < lo:
                lo = b
            if hi is None or e > hi:
                hi = e
        return None if lo is None else (lo, hi)

    def _send_read_batch(self, ops):
        """One multiplexed read RPC (the ReadBatcher's send): worker
        round-robin by the batch's bounding span; a lagging worker's
        per-op 1009 slots are re-served from the lead and the worker
        strikes (the _RemoteStorage._read policy, batch-shaped)."""
        from foundationdb_tpu.rpc.transport import RemoteError

        ops = list(ops)
        worker = self._next_worker(self._batch_span(ops))
        if worker is not None:
            try:
                slots = worker.call(
                    "read_batch", ops,
                    deadline_s=self._deadline_for("read_batch"),
                )
            except DeadlineExceeded:
                # wedged worker: mark (monitor on) and serve the whole
                # batch from the lead — same policy as _RemoteStorage
                if self._monitor_enabled():
                    failuremon.monitor().note_timeout(
                        f"{worker.host}:{worker.port}",
                        "read_batch deadline",
                    )
            except (ConnectionLost, OSError, RemoteError):
                self._drop_worker(worker)
            else:
                lagging = [
                    i for i, s in enumerate(slots)
                    if isinstance(s, FDBError) and s.code == 1009
                ]
                if not lagging:
                    self._worker_ok(worker)
                    return slots
                self._worker_strike(worker)
                redo = self._call(
                    "read_batch", [ops[i] for i in lagging]
                )
                for i, slot in zip(lagging, redo):
                    slots[i] = slot
                return slots
        return self._call("read_batch", ops)

    def read_storage(self, key=b""):
        return self._storage

    def status(self):
        return self._call("status")

    def metrics_status(self):
        return self._call("metrics")

    def health_status(self):
        doc = self._call("health")
        # overlay THIS client's endpoint-health view (the server's own
        # monitor can't see our links): states + counters only
        if isinstance(doc, dict):
            doc["rpc_client"] = failuremon.monitor().snapshot()
        return doc

    def hot_ranges_status(self, top=None):
        return self._call("metrics_hot", top)

    def device_profile_status(self):
        return self._call("device_profile")

    def history_status(self):
        return self._call("history")

    def flight_status(self):
        return self._call("flight")

    def consistency_scan_status(self):
        return self._call("consistency_scan")

    def set_consistency_scan(self, on):
        return self._call("set_consistency_scan", bool(on))

    # management surface (the special key space's commit-time handles)
    def exclude_storage(self, sid):
        return self._call("exclude_storage", sid)

    def include_storage(self, sid):
        return self._call("include_storage", sid)

    def list_excluded(self):
        return self._call("list_excluded")

    def consistency_check(self, max_keys_per_shard=None):
        return self._call("consistency_check", max_keys_per_shard)

    def estimated_range_size_bytes(self, begin, end):
        return self._call("estimated_range_size", begin, end)

    def range_split_points(self, begin, end, chunk_size):
        return self._call("range_split_points", begin, end, chunk_size)

    def lock_database(self, uid=b"lock"):
        return self._call("lock_database", uid)

    def unlock_database(self):
        return self._call("unlock_database")

    def lock_uid(self):
        return self._call("lock_uid")

    def set_tenant_mode(self, mode):
        return self._call("set_tenant_mode", mode)

    def configure(self, commit_proxies=None, resolvers=None):
        return self._call("configure", commit_proxies, resolvers)

    def tenant_mode(self):
        return self._call("tenant_mode")

    def set_tag_quota(self, tag, tps):
        return self._call("set_tag_quota", tag, tps)

    def tracing_config(self):
        return self._call("tracing_config")

    def set_tracing(self, sample_rate=None, enabled=None):
        out = self._call("set_tracing", sample_rate, enabled)
        # the sampling knob lives server-side in the knobs doc: drop the
        # cached copy so this client's next transaction sees the change
        self._knobs = None
        return out

    # ── storage-worker read balancing ──
    def refresh_workers(self):
        """Discover registered storage-worker processes and open read
        connections (round-robined with the lead thereafter). Each
        entry may carry the worker's served key ranges (tag-scoped
        workers — rpc/storageworker.py); reads route by coverage."""
        from foundationdb_tpu.rpc.transport import connect_any

        entries = self._call("list_workers")
        clients = []
        addresses = []
        for entry in entries:
            if isinstance(entry, (list, tuple)):
                addr, ranges = entry
                ranges = ([tuple(r) for r in ranges]
                          if ranges is not None else None)
            else:  # legacy bare-address registration
                addr, ranges = entry, None
            addresses.append(addr)
            try:
                clients.append((connect_any(
                    [addr], self._connect_timeout, secret=self._secret
                ), ranges))
            except ConnectionLost:
                continue
        with self._lock:
            old, self._workers = self._workers, clients
            for c, _ in old:
                self._worker_strikes.pop(c, None)
            # retire rather than close: a concurrent reader may be
            # mid-call on an old client — closing now would abort a
            # healthy read. Retired clients close on the NEXT refresh
            # (in-flight calls are long finished by then) or at close().
            retiring, self._retired_workers = (
                getattr(self, "_retired_workers", []), [c for c, _ in old]
            )
        for c in retiring:
            c.close()
        return addresses

    @staticmethod
    def _covers(ranges, span):
        """Whether a worker serving ``ranges`` can answer a read over
        ``span`` ([begin, end), or None = requires the full keyspace).
        Ranges arrive merged, so containment in ONE range suffices."""
        if ranges is None:
            return True
        if span is None:
            return False
        b, e = span
        return any(rb <= b and e <= re_ for rb, re_ in ranges)

    def _next_worker(self, span=None):
        """Round-robin over lead + covering workers: returns None for
        'the lead's turn' (callers fall through to _call). With the
        failure monitor on, known-failed workers are skipped instead of
        serially timed out against — except for the one caller per probe
        window that ``available`` elects to carry the recovery probe."""
        monitor_on = self._monitor_enabled()
        mon = failuremon.monitor() if monitor_on else None
        with self._lock:
            eligible = [
                c for c, ranges in self._workers
                if self._covers(ranges, span)
                and (mon is None or mon.available(f"{c.host}:{c.port}"))
            ]
            if not eligible:
                return None
            self._worker_rr = (self._worker_rr + 1) % (len(eligible) + 1)
            if self._worker_rr == 0:
                return None
            return eligible[self._worker_rr - 1]

    def _drop_worker(self, client):
        with self._lock:
            self._workers = [
                (c, r) for c, r in self._workers if c is not client
            ]
            self._worker_strikes.pop(client, None)
        client.close()

    WORKER_STRIKE_LIMIT = 3
    WORKER_REFRESH_MIN_S = 1.0

    def _worker_ok(self, client):
        with self._lock:
            self._worker_strikes.pop(client, None)
        # a successful read doubles as the recovery probe's verdict
        failuremon.monitor().mark_ok(f"{client.host}:{client.port}")

    def _worker_strike(self, client):
        with self._lock:
            n = self._worker_strikes.get(client, 0) + 1
            self._worker_strikes[client] = n
        if n >= self.WORKER_STRIKE_LIMIT:
            self._drop_worker(client)
            # A struck-out worker may be healthy with a STALE coverage
            # map on our side: a DD move makes its ownership backstop
            # answer 1009 for spans we still think it serves. Re-snapshot
            # the registry (throttled) so workers rejoin with fresh
            # ranges instead of staying evicted for the session.
            now = time.monotonic()
            if now - getattr(self, "_last_worker_refresh", 0.0) \
                    >= self.WORKER_REFRESH_MIN_S:
                self._last_worker_refresh = now
                try:
                    self.refresh_workers()
                except (ConnectionLost, OSError):
                    pass  # lead unreachable: reads already fall back

    def connection_string(self):
        return ",".join(self.addresses)

    def database(self):
        from foundationdb_tpu.txn.database import Database

        return Database(self)

    def close(self):
        self._ping_stop.set()
        if self._ping_thread is not None:
            self._ping_thread.join(timeout=1)
        rb = self._read_batcher
        if rb is not None:
            rb.close()  # settles queued reads retryably (FL002)
        if hasattr(self.commit_proxy, "close"):
            self.commit_proxy.close()  # client-side batcher thread
        with self._lock:
            self._closed = True
            if self._client is not None:
                self._client.close()
                self._client = None
            workers, self._workers = self._workers, []
            retired = getattr(self, "_retired_workers", [])
            self._retired_workers = []
        for c, _ in workers:
            c.close()
        for c in retired:
            c.close()
