"""Framed TCP transport with multiplexed request/reply endpoints.

Ref parity: fdbrpc/FlowTransport.actor.cpp — connections carry
length-prefixed packets addressed to endpoint tokens; replies are matched
to requests by id; a connection failure fails every outstanding request
on it. The reference multiplexes actor futures over one socket per peer;
here a reader thread per connection completes `concurrent.futures`
futures. The server reads a connection in bursts (one ``recv``, every
complete frame in it) and answers a request on the thread that read it
where its endpoint was declared unable to wait on another thread
(``inline_methods``: a storage read, a GRV that can be granted now);
every other handler runs on a shared pool, so a blocking endpoint (a
watch wait, a queued GRV) never stalls the socket. A handler whose
result another thread will have later (a commit: its batch settles on
the batcher's thread) returns a :class:`Deferred` and holds no thread
at all: the replies of one settle go out together, one send a
connection, from the server's reply thread.

Frame: 4-byte big-endian length + wire payload.
Request: ("q", seq, method, args-tuple)  Reply: ("r", seq, ok, payload).

Authentication: with a shared ``secret`` configured, every connection
starts with a challenge/response — the server sends a random nonce, the
client must answer HMAC-SHA256(secret, nonce) before any request is
read (ref: FlowTransport's TLS handshake gating endpoint access; ours
is a shared-secret MAC rather than certificates). Without a secret the
transport is open: listening on a non-loopback interface without one
exposes full read/write/management access and is unsafe.
"""

import hashlib
import hmac
import os
from collections import deque
import socket
import struct
import threading
import time

from concurrent.futures import Future, ThreadPoolExecutor

from foundationdb_tpu.core.errors import FDBError
from foundationdb_tpu.rpc import wire
from foundationdb_tpu.utils import lockdep
from foundationdb_tpu.utils import span as span_mod
from foundationdb_tpu.utils.trace import SEV_ERROR, TraceEvent

MAX_FRAME = 64 * 1024 * 1024
_FRAME_LEN = struct.Struct(">I")
_AUTH_CONTEXT = b"fdbtpu-rpc-auth-v1:"
_AUTH_HANDSHAKE_TIMEOUT_S = 5.0
# deadline-sweep cadence: the client reader blocks in recv at most this
# long before checking outstanding requests against their deadlines, so
# a wedged peer costs one deadline + one tick, never a hung thread
_DEADLINE_TICK_S = 0.05
# the server's reply thread looks this often at the requests whose
# reply is deferred and unanswered (``Deferred.poll``): a registrant's
# own deadline (the batcher's stranded-batch watchdog) is kept to a tick
_DEFERRED_POLL_S = 0.25
# A reply provokes its client's next request. The replies of one settle
# sent back to back come back as one burst of requests, and every
# request in flight beside them queues behind it for the interpreter;
# so the reply thread pauses between one connection's send and the
# next, off everybody's path (it sleeps), this long for each reply it
# has just sent: about what the interpreter needs to serve the request
# a reply provokes (≈ 6,500 short requests a second, PERF.md §5).
# Measured on a TPU v5 lite's host, ycsb_a's 64 clients over eight
# connections, two replies a send (PERF.md §6, PR 38): read p95 8.84 ms
# with no pause, 6.77 / 6.69 / 6.40 / 6.73 / 6.79 with 0.10 / 0.15 /
# 0.20 / 0.25 / 0.60 ms a connection (6.66 while sixteen parked workers
# sent one reply each as they got their turns); the update median pays
# 2.6 ms of its 18 gained for any pause at all and 5 for the longest.
_REPLY_SPACING_S = 0.00015
# consecutive deadline sweeps (with zero frames received in between)
# after which a connection is presumed black-holed rather than slow:
# callers close it and reconnect on a fresh socket instead of paying
# the full deadline again on a link that will never answer
WEDGED_STRIKE_LIMIT = 3

# RPC classes: every method maps to one of four — the client's per-class
# deadline knobs (rpc_deadline_*_s, rpc/service.py) and the server's
# per-class counters (RpcServer.stats) share the table. Unlisted
# methods are admin-class — management/status calls tolerate the
# longest bound. watch_wait blocks server-side in 5s chunks, safely
# under the admin deadline.
RPC_CLASSES = ("read", "grv", "commit", "admin")
_RPC_CLASS = {
    "storage_get": "read",
    "resolve_selector": "read",
    "get_range": "read",
    "read_batch": "read",
    "ping": "read",
    "get_read_version": "grv",
    "commit": "commit",
    "commit_batch": "commit",
}


def rpc_class(method):
    return _RPC_CLASS.get(method, "admin")


# totals RpcServer keeps per RPC class (integer microseconds in status).
# ``requests`` counts every request; the four durations are summed over
# every TIME_EVERY-th request of a class (``timed_requests`` of them),
# so a mean is <sum> / timed_requests. On the v5e's host the
# interpreter is the server's bottleneck and a microsecond added to
# every request costs several of throughput (PERF.md, PR 28). The
# thread's CPU clock is not read at all: ``time.thread_time`` is a real
# system call there, 6 µs against a read handler of 36 µs, and two on
# every request cost a tenth of the ycsb cell's ops_per_s.
RPC_COUNTERS = ("requests", "timed_requests", "decode_us", "queue_wait_us",
                "handler_wall_us", "reply_us", "inline_requests",
                "deferred_requests")
TIME_EVERY = 4
_RPC_STAGE = {c: "rpc." + c for c in RPC_CLASSES}

# Chaos transport hook (rpc/chaos.py): when armed, every NEW client
# socket is wrapped in the seeded fault injector. None on the default
# path — chaos code is never even imported unless a seed arms it via
# chaos.arm()/the rpc_chaos_seed knob/FDB_TPU_CHAOS_SEED.
SOCKET_WRAP = None


def _socket_wrap():
    global SOCKET_WRAP
    if SOCKET_WRAP is None:
        seed = os.environ.get("FDB_TPU_CHAOS_SEED")
        if seed:
            from foundationdb_tpu.rpc import chaos

            chaos.arm(seed)  # sets SOCKET_WRAP
    return SOCKET_WRAP


class DeadlineExceeded(TimeoutError):
    """A request outlived its deadline; the connection itself is fine.

    The service layer maps this by RPC class: commit-class calls become
    ``commit_unknown_result`` (1021 — the txn MAY have committed),
    read/GRV/admin calls become plainly retryable errors.
    """

    def __init__(self, method, deadline_s, address=""):
        super().__init__(
            f"rpc {method!r} to {address or '?'} exceeded its "
            f"{deadline_s:.3f}s deadline"
        )
        self.method = method
        self.deadline_s = deadline_s
        self.address = address


def _auth_proof(secret, nonce):
    if isinstance(secret, str):
        secret = secret.encode()
    return hmac.new(secret, _AUTH_CONTEXT + nonce, hashlib.sha256).digest()


class ConnectionLost(ConnectionError):
    """The peer vanished with requests outstanding."""


def _frame(payload: bytes):
    if len(payload) > MAX_FRAME:
        raise ValueError(f"frame too large: {len(payload)}")
    return _FRAME_LEN.pack(len(payload)) + payload


def _send_frame(sock, lock, payload: bytes):
    msg = _frame(payload)
    with lock:
        # this per-socket lock EXISTS to serialize whole-frame sends —
        # interleaved partial frames would corrupt the stream; nothing
        # else is ever guarded by it, so no convoy can form
        sock.sendall(msg)  # flowlint: disable=FL003


def _recv_exact(sock, n):
    parts = []
    while n:
        chunk = sock.recv(n)
        if not chunk:
            raise ConnectionLost("peer closed")
        parts.append(chunk)
        n -= len(chunk)
    return b"".join(parts)


def _recv_frame(sock):
    (n,) = struct.unpack(">I", _recv_exact(sock, 4))
    if n > MAX_FRAME:
        raise ConnectionLost(f"oversized frame: {n}")
    return _recv_exact(sock, n)


class Park:
    """What a handler returns in place of a result when the rest of its
    request may wait on another thread: ``resume()`` gives the result.
    On a pool thread the server runs it at once; on a connection's own
    thread (an ``inline_methods`` endpoint) it goes to the pool, so a
    request that has to wait never holds the connection."""

    __slots__ = ("resume",)

    def __init__(self, resume):
        self.resume = resume


class Deferred:
    """What a handler returns in place of a result that another thread
    will have later: ``register(complete)`` arranges that
    ``complete(payload)`` is called exactly once, on any thread (at
    once, if the result is in hand). The request then holds no thread,
    on the pool or off it. ``complete`` queues the reply and hands back
    the server's flush; a caller that completes several requests in a
    row calls the flush once, behind the last, and their replies share
    a send per connection. ``register`` returns what ``complete``
    returned if it ran at once, else None. ``poll``, where given, is
    called every ``_DEFERRED_POLL_S`` or so while the request is
    unanswered, for a registrant with a deadline of its own to keep."""

    __slots__ = ("register", "poll")

    def __init__(self, register, poll=None):
        self.register = register
        self.poll = poll


class _DeferredReply:
    """One request whose handler returned a :class:`Deferred`: where
    its reply goes, and its stamps so far."""

    __slots__ = ("server", "sock", "send_lock", "seq", "method", "cls",
                 "stamps", "poll")

    def __init__(self, server, sock, send_lock, seq, method, cls, stamps,
                 poll):
        self.server = server
        self.sock = sock
        self.send_lock = send_lock
        self.seq = seq
        self.method = method
        self.cls = cls
        self.stamps = stamps  # (t_recv, t_decoded, t0) of a timed one
        self.poll = poll

    def complete(self, payload, ok=True):
        """The result is in hand: encode the reply and queue it for
        the next flush. Never raises (``_encode_reply``)."""
        server = self.server
        stamps = self.stamps
        if stamps is not None:
            stamps += (span_mod.now(),)
        frame = server._encode_reply(self.seq, self.method, ok, payload)
        server._deferred.discard(self)
        server._outbox.append(
            (self.sock, self.send_lock, frame, self.cls, stamps))
        return server._outbox_ready.set


class _FrameReader:
    """Buffered frame reader: one ``recv`` of up to 64 KB, every
    complete frame parsed out of the buffer.

    It survives ``socket.timeout`` mid-frame. The client reader runs its
    socket with a short timeout so it can sweep request deadlines
    between frames; ``_recv_exact`` would LOSE partially-received bytes
    on a timeout and desync the stream. A partial frame, header
    included, stays in the buffer across ticks, so a timeout is always a
    clean "nothing complete yet — go sweep" signal.
    """

    def __init__(self, sock):
        self._sock = sock
        self._buf = bytearray()
        self.recvs = 0  # socket reads that returned bytes

    def recv_burst(self):
        """Every complete frame buffered, at least one: blocks in
        ``recv`` (which may raise ``socket.timeout``) until a frame is
        whole. A length over ``MAX_FRAME`` fails the connection at its
        header, before a byte of the payload is buffered."""
        buf = self._buf
        frames = []
        while True:
            off, have, want = 0, len(buf), 65536
            while have - off >= 4:
                (n,) = _FRAME_LEN.unpack_from(buf, off)
                if n > MAX_FRAME:
                    if frames:
                        break  # the frames in front of it are served first
                    raise ConnectionLost(f"oversized frame: {n}")
                end = off + 4 + n
                if end > have:
                    # a large frame: ask for the rest of it in one read
                    want = max(want, end - have)
                    break
                frames.append(bytes(memoryview(buf)[off + 4:end]))
                off = end
            if off:
                del buf[:off]
            if frames:
                return frames
            chunk = self._sock.recv(want)
            if not chunk:
                raise ConnectionLost("peer closed")
            self.recvs += 1
            buf += chunk


class RpcServer:
    """Listens for connections; dispatches requests to named handlers.

    ``handlers`` is the endpoint table: method name → callable(*args).
    A handler raising FDBError sends the error to the client intact
    (the client re-raises it); any other exception becomes a generic
    remote failure string.

    ``inline_methods`` names the endpoints whose handler cannot wait
    on another thread, declared by the code that registers them and
    knows what they touch: a request to one is handled and answered on
    the thread that owns its connection, with no hand-off to the pool.
    A connection multiplexes its client's threads, so nothing that can
    park may be declared; a handler that finds it has to wait after all
    returns a :class:`Park` and its request moves to the pool. An
    endpoint not declared runs on the pool. On either kind of thread a
    handler may return a :class:`Deferred`: the request then waits on
    no thread, and the reply thread sends its reply with the others
    that were completed beside it.

    Every fourth request of a class is stamped on the injected clock at
    its layer boundaries — frame read, decoded, handler start (on the
    pool thread, or this one), handler end, reply sent — and the
    differences accumulate per RPC class (:meth:`stats`): what a request
    cost in decode, in the pool's queue, in its handler and in the
    reply. Every request is counted, and annotated for the profiler.
    One locked add a request on a pool thread, one a burst on a
    connection's own, one a flush of deferred replies. A deferred
    request's handler time runs from its handler's start to its
    ``complete``, its reply time from there to the return of the send
    that carried it.
    """

    def __init__(self, host, port, handlers, max_workers=16,
                 long_methods=(), inline_methods=(), secret=None):
        self.secret = secret
        self.handlers = dict(handlers)
        # endpoints that legitimately block (watch waits) run on their
        # own pool so parked waiters cannot starve short RPCs
        self.long_methods = set(long_methods)
        self.inline_methods = set(inline_methods) - self.long_methods
        self._listener = socket.create_server(
            (host, port), reuse_port=False, backlog=64
        )
        self._listener.settimeout(0.2)
        self.host, self.port = self._listener.getsockname()[:2]
        self.max_workers = max_workers
        self._pool = ThreadPoolExecutor(
            max_workers=max_workers, thread_name_prefix="rpc-handler"
        )
        self._stats_lock = lockdep.lock("RpcServer._stats_lock")
        # per class, in RPC_COUNTERS' order; seconds until stats()
        self._acc = {c: [0, 0, 0.0, 0.0, 0.0, 0.0, 0, 0]
                     for c in RPC_CLASSES}
        self._deferred_sends = 0  # sends that carried deferred replies
        # requests decoded per class, for the sampling alone: bumped by
        # every connection thread without a lock (a lost count moves a
        # sample by one request)
        # flowlint: shared(sampling phase only; requests are counted under _stats_lock)
        self._seen = dict.fromkeys(RPC_CLASSES, 0)
        # flowlint: shared(a maximum; a lost race costs one stale reading)
        self._queued_high_water = 0
        self._born = time.monotonic()
        self._long_pool = (
            ThreadPoolExecutor(
                max_workers=256, thread_name_prefix="rpc-blocking"
            )
            if self.long_methods
            else None
        )
        # deferred replies: the requests waiting for their ``complete``
        # (set operations are atomic; the reply thread copies it to
        # poll), the replies completed and not yet sent, and the event
        # a completer sets once behind the last of its row
        self._deferred = set()
        self._outbox = deque()
        self._outbox_ready = threading.Event()
        self._reply_thread = None  # started by the first deferred request
        self._conns = {}  # socket -> its frame reader, once authenticated
        self._recv_retired = 0  # socket reads of connections since closed
        self._lock = lockdep.lock("RpcServer._lock")
        self._closed = threading.Event()
        self._accept_thread = threading.Thread(
            target=self._accept_loop, name="rpc-accept", daemon=True
        )
        self._accept_thread.start()

    @property
    def address(self):
        return f"{self.host}:{self.port}"

    def add_handlers(self, handlers, long_methods=(), inline_methods=()):
        """Register more endpoints on a live server (an fdbserver process
        brings its coordinator endpoints up first so peers can reach the
        quorum, then attaches the cluster service after recovery).

        Long-method routing is installed BEFORE the handlers become
        callable: a blocking endpoint must never be reachable while it
        would still dispatch onto the short-RPC pool. Inline routing is
        installed AFTER them: until then the endpoint rides the pool."""
        new_long = set(long_methods) - self.long_methods
        if new_long:
            if self._long_pool is None:
                self._long_pool = ThreadPoolExecutor(
                    max_workers=256, thread_name_prefix="rpc-blocking"
                )
            self.long_methods |= new_long
        self.handlers.update(handlers)
        self.inline_methods |= set(inline_methods) - self.long_methods

    def _accept_loop(self):
        while not self._closed.is_set():
            try:
                sock, peer = self._listener.accept()
            except socket.timeout:
                continue
            except OSError:
                return
            sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            with self._lock:
                self._conns[sock] = None
            threading.Thread(
                target=self._serve_conn, args=(sock, peer),
                name=f"rpc-conn-{peer}", daemon=True,
            ).start()

    def _authenticate(self, sock, send_lock, peer):
        """Challenge/response before the first request frame. The
        handshake runs under a timeout so an idle port-scanner cannot
        park a connection thread forever."""
        # crypto material must NOT come from the seeded determinism
        # registry: a replayable nonce is a replayable handshake
        nonce = os.urandom(16)  # flowlint: disable=FL001
        _send_frame(sock, send_lock, nonce)
        sock.settimeout(_AUTH_HANDSHAKE_TIMEOUT_S)
        try:
            # pre-auth frames are capped at the proof size (32 bytes):
            # an unauthenticated peer must not be able to make us buffer
            # a MAX_FRAME allocation before the HMAC check rejects it
            (n,) = struct.unpack(">I", _recv_exact(sock, 4))
            if n > 64:
                raise ConnectionLost(f"oversized auth proof: {n}")
            proof = _recv_exact(sock, n)
        finally:
            sock.settimeout(None)
        if not hmac.compare_digest(proof, _auth_proof(self.secret, nonce)):
            TraceEvent("RpcAuthFailed", severity=30).detail(
                peer=str(peer)).log()
            raise ConnectionLost("authentication failed")
        # confirmation frame: the client learns its proof was accepted
        # before sending requests, so a secret mismatch surfaces as a
        # deterministic handshake failure, not a later dead socket
        _send_frame(sock, send_lock, b"\x00ok")

    def _serve_conn(self, sock, peer):
        send_lock = lockdep.lock("RpcServer._serve_conn.send_lock")
        try:
            if self.secret is not None:
                self._authenticate(sock, send_lock, peer)
            reader = _FrameReader(sock)
            with self._lock:
                self._conns[sock] = reader
            while not self._closed.is_set():
                frames = reader.recv_burst()
                # every frame of a burst was read by this recv: a
                # frame's wait behind the earlier ones is queue wait
                t_recv = span_mod.now()
                burst = []
                try:
                    for frame in frames:
                        burst.append(self._decode(frame, t_recv))
                finally:
                    # a frame that cannot be decoded fails the
                    # connection once those in front of it are served
                    self._answer(sock, send_lock, burst)
        except (ConnectionLost, ConnectionError, OSError, ValueError):
            pass
        finally:
            with self._lock:
                reader = self._conns.pop(sock, None)
                if reader is not None:
                    self._recv_retired += reader.recvs
            try:
                sock.close()
            except OSError:
                pass

    def _decode(self, frame, t_recv):
        """A request frame → (seq, method, args, trace_ctx, cls, t_recv,
        t_decoded); ``t_decoded`` only where the request is a timed one:
        the first of its class and every fourth after it."""
        msg = wire.loads(frame)
        # protocol v5: an optional TRACING frame rides as a 5th element
        # (the caller's SpanContext); shorter tuples are the untraced
        # form — peers ignore what isn't there
        kind, seq, method, args = msg[0], msg[1], msg[2], msg[3]
        if kind != "q":
            raise ConnectionLost(f"unexpected message kind {kind!r}")
        cls = rpc_class(method)
        seen = self._seen[cls]
        self._seen[cls] = seen + 1
        return (seq, method, args, msg[4] if len(msg) > 4 else None, cls,
                t_recv, span_mod.now() if seen % TIME_EVERY == 0 else None)

    def _answer(self, sock, send_lock, burst):
        """A burst's requests in their order: each handed to a pool or
        answered here, and what was answered here sent in one send (each
        reply a whole frame) and counted in one locked add."""
        answered = []  # by this thread: (frame, cls, stamps)
        for request in burst:
            self._route(sock, send_lock, request, answered)
        if answered:
            self._send(sock, send_lock, b"".join([a[0] for a in answered]))
            self._count(answered, span_mod.now(), inline=1)

    def _route(self, sock, send_lock, request, answered):
        """One decoded request to the thread that answers it: this one
        for a declared endpoint, unless its handler parks; else a pool."""
        seq, method, _args, trace_ctx, cls, t_recv, t_decoded = request
        fn = self.handlers.get(method)
        if method in self.inline_methods:
            parked = self._dispatch(sock, send_lock, fn, request, answered)
            if parked is None:
                return
            fn = parked.resume
            request = (seq, method, (), trace_ctx, cls, t_recv, t_decoded)
        pool = (
            self._long_pool
            if self._long_pool is not None and method in self.long_methods
            else self._pool
        )
        pool.submit(self._dispatch, sock, send_lock, fn, request)
        if t_decoded is not None and pool is self._pool:
            queued = self._queue_depth()
            if queued > self._queued_high_water:
                self._queued_high_water = queued

    def _queue_depth(self):
        """Requests decoded and not yet running on the short pool: the
        executor's own queue (counting them here would take two more
        locked adds a request)."""
        return self._pool._work_queue.qsize()

    def _dispatch(self, sock, send_lock, fn, request, answered=None):
        """Handle one request on this thread. A pool thread sends the
        reply and counts the request. A connection's own thread hands in
        ``answered``, where the burst's replies gather for one send and
        one count; a handler that parks there gets its :class:`Park`
        back unanswered, for the caller to hand on. A request whose
        handler defers is answered and counted by the flush that sends
        its reply (``_defer``), on neither kind of thread."""
        seq, method, args, trace_ctx, cls, t_recv, t_decoded = request
        timed = t_decoded is not None
        prior_ctx = None
        if trace_ctx is not None:
            # install the caller's SpanContext as this handler thread's
            # ambient context: role code (grv grant, storage reads)
            # opens child spans off span.current() without every
            # handler signature growing a tracing parameter
            prior_ctx = span_mod.set_current(tuple(trace_ctx))
        # rpc.<class>, handler start → reply sent (or, of a burst's,
        # encoded), on this thread: a stage where its stamps or its
        # span are wanted, else the profiler annotation alone
        scope = span_mod.stage if timed or trace_ctx is not None \
            else span_mod.annotation
        stamps = None
        try:
            with scope(_RPC_STAGE[cls]) as st:
                ok, payload = self._handle(fn, method, args)
                if type(payload) is Park:
                    if answered is not None:
                        return payload
                    ok, payload = self._handle(payload.resume, method, ())
                if type(payload) is Deferred:
                    self._defer(_DeferredReply(
                        self, sock, send_lock, seq, method, cls,
                        (t_recv, t_decoded, st.t0) if timed else None,
                        payload.poll), payload.register)
                    return None
                if timed:
                    stamps = (t_recv, t_decoded, st.t0, span_mod.now())
                frame = self._encode_reply(seq, method, ok, payload)
                if answered is None:
                    self._send(sock, send_lock, frame)
        finally:
            if trace_ctx is not None:
                span_mod.set_current(prior_ctx)
        if answered is None:
            self._count(((frame, cls, stamps),), st.t1 if timed else 0.0)
        else:
            answered.append((frame, cls, stamps))

    def _defer(self, reply, register):
        """A handler's result will come later: note the request as
        unanswered and hand the registrant its ``complete``."""
        if self._reply_thread is None:
            with self._lock:
                if self._reply_thread is None and not self._closed.is_set():
                    self._reply_thread = threading.Thread(
                        target=self._reply_loop, name="rpc-reply",
                        daemon=True)
                    self._reply_thread.start()
        self._deferred.add(reply)
        try:
            flush = register(reply.complete)
        except Exception as e:
            flush = reply.complete(
                self._remote_failure(reply.method, e)[1], ok=False)
        if flush is not None:
            flush()

    def _reply_loop(self):
        """The reply thread: woken once by whoever completed a row of
        deferred requests, it sends their replies; between wakes it
        polls the unanswered ones' registrants."""
        ready = self._outbox_ready
        next_poll = time.monotonic() + _DEFERRED_POLL_S
        while not self._closed.is_set():
            ready.wait(_DEFERRED_POLL_S)
            # cleared BEFORE the drain: a reply queued after the drain
            # sets it again, one queued before the clear is drained
            ready.clear()
            self._flush_deferred()
            now = time.monotonic()
            if now < next_poll:
                continue
            next_poll = now + _DEFERRED_POLL_S
            for poll in {r.poll for r in list(self._deferred)} - {None}:
                try:
                    poll()
                except Exception as e:
                    TraceEvent("RpcDeferredPollError",
                               severity=SEV_ERROR).detail(
                        etype=type(e).__name__, error=str(e)[:200]).log()

    def _flush_deferred(self):
        """Every reply completed so far, grouped by connection: one
        send a connection (each reply a whole frame), a pause of
        ``_REPLY_SPACING_S`` a reply between connections, one locked
        add."""
        outbox = self._outbox
        by_conn = {}
        while outbox:  # this thread is its one consumer
            sock, send_lock, frame, cls, stamps = outbox.popleft()
            conn = by_conn.get(sock)
            if conn is None:
                by_conn[sock] = conn = (send_lock, [])
            conn[1].append((frame, cls, stamps))
        if not by_conn:
            return
        sent = []
        for sock, (send_lock, replies) in by_conn.items():
            if sent:
                time.sleep(_REPLY_SPACING_S * len(sent[-1][0]))
            # fdb.rpc.<class> of the first: a flush's replies are one
            # class's (the commits of a batch) but for a coincidence
            with span_mod.annotation(_RPC_STAGE[replies[0][1]]):
                self._send(sock, send_lock,
                           b"".join([r[0] for r in replies]))
            sent.append((replies, span_mod.now()))
        with self._stats_lock:
            self._deferred_sends += len(sent)
            for replies, t_sent in sent:
                self._add(replies, t_sent, 0, 1)

    def _count(self, answered, t_sent, inline=0):
        """The one locked add, after the send: a pool thread's request,
        or the burst a connection's own thread answered."""
        with self._stats_lock:
            self._add(answered, t_sent, inline, 0)

    def _add(self, answered, t_sent, inline, deferred):
        """Under ``_stats_lock``: requests answered by one send."""
        for _frame, cls, stamps in answered:
            acc = self._acc[cls]
            acc[0] += 1
            acc[6] += inline
            acc[7] += deferred
            if stamps is not None:
                t_recv, t_decoded, t0, t_handled = stamps
                acc[1] += 1
                acc[2] += t_decoded - t_recv
                acc[3] += t0 - t_decoded
                acc[4] += t_handled - t0
                acc[5] += t_sent - t_handled

    @staticmethod
    def _remote_failure(method, e):
        # the client only receives a flattened string — the server
        # trace is the record with the real type/context (FL005)
        TraceEvent("RpcHandlerError", severity=SEV_ERROR).detail(
            method=method, etype=type(e).__name__,
            error=str(e)[:200]).log()
        return False, f"{type(e).__name__}: {e}"

    def _handle(self, fn, method, args):
        """Run the handler → (ok, payload); never raises."""
        try:
            if fn is None:
                raise KeyError(f"no such endpoint: {method}")
            return True, fn(*args)
        except FDBError as e:
            return False, e
        except Exception as e:  # generic remote failure
            return self._remote_failure(method, e)

    def stats(self):
        """``cluster.rpc`` of the status document: the per-class totals
        (``<counter>.<class>``), the socket reads that returned bytes
        (``recv_calls``: requests / recv_calls is how many requests a
        read brings), the sends that carried deferred replies
        (``deferred_sends``: deferred_requests / deferred_sends is how
        many replies a send takes) and the deferred requests unanswered
        now (``deferred_pending``), the short pool's size and the deepest
        its queue has been seen (looked at with every timed request),
        and this process's CPU and wall time, read
        now (no hot-path cost): Δcpu/Δwall ≈ 1.0 over a busy interval
        means the interpreter lock is the machine."""
        with self._stats_lock:
            acc = {c: list(v) for c, v in self._acc.items()}
            deferred_sends = self._deferred_sends
        with self._lock:
            # a reader's count is its connection thread's alone to write
            recv_calls = self._recv_retired + sum(
                r.recvs for r in self._conns.values() if r is not None)
        # a clock that stepped backwards counts 0, never negative
        doc = {counter: {c: v[i] if isinstance(v[i], int)
                         else round(max(0.0, v[i]) * 1e6)
                         for c, v in acc.items()}
               for i, counter in enumerate(RPC_COUNTERS)}
        doc["recv_calls"] = recv_calls
        doc["deferred_sends"] = deferred_sends
        doc["deferred_pending"] = len(self._deferred)
        doc["pool"] = {"workers": self.max_workers,
                       "queued": self._queue_depth(),
                       "queued_high_water": self._queued_high_water}
        doc["process"] = {
            "cpu_us": round(time.process_time() * 1e6),
            "wall_us": round((time.monotonic() - self._born) * 1e6),
        }
        return doc

    def _encode_reply(self, seq, method, ok, payload):
        """One whole reply frame. A result the wire cannot carry, or one
        over ``MAX_FRAME``, becomes a generic remote failure, like a
        handler that raised: the client must still get an answer or its
        future hangs forever."""
        try:
            return _frame(wire.dumps(("r", seq, ok, payload)))
        except Exception as e:
            return _frame(wire.dumps(
                ("r", seq, *self._remote_failure(method, e))))

    @staticmethod
    def _send(sock, send_lock, frames):
        """Whole frames under the connection's send lock, which EXISTS
        to keep them whole (see ``_send_frame``); a client that vanished
        has nothing to be told."""
        try:
            with send_lock:
                sock.sendall(frames)  # flowlint: disable=FL003
        except (ConnectionError, OSError):
            pass

    def close(self):
        self._closed.set()
        try:
            self._listener.close()
        except OSError:
            pass
        with self._lock:
            conns = list(self._conns)
        for c in conns:
            try:
                c.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            try:
                c.close()
            except OSError:
                pass
        self._pool.shutdown(wait=False)
        if self._long_pool is not None:
            self._long_pool.shutdown(wait=False)
        self._accept_thread.join(timeout=2)
        with self._lock:
            reply_thread = self._reply_thread
        if reply_thread is not None:
            self._outbox_ready.set()
            reply_thread.join(timeout=2)


class RemoteError(RuntimeError):
    """A non-FDBError exception raised inside a remote handler."""


class RpcClient:
    """One connection to an RpcServer; thread-safe, multiplexed calls."""

    def __init__(self, host, port, connect_timeout=5.0, secret=None):
        self.host, self.port = host, port
        self._sock = socket.create_connection((host, port), connect_timeout)
        self._sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        wrap = _socket_wrap()
        if wrap is not None:
            self._sock = wrap(self._sock, f"{host}:{port}")
        self._send_lock = lockdep.lock("RpcClient._send_lock")
        if secret is not None:
            # the server's first frame is the auth nonce; answer before
            # the reader thread starts interpreting frames as replies
            self._sock.settimeout(_AUTH_HANDSHAKE_TIMEOUT_S)
            try:
                nonce = _recv_frame(self._sock)
                _send_frame(self._sock, self._send_lock,
                            _auth_proof(secret, nonce))
                if _recv_frame(self._sock) != b"\x00ok":
                    raise ConnectionLost("bad auth confirmation")
                self._sock.settimeout(None)
            except (OSError, ConnectionLost) as e:
                # a server not configured for auth never sends a nonce:
                # fail fast with the real cause (and no leaked socket)
                # instead of surfacing as generic unreachability
                try:
                    self._sock.close()
                except OSError:
                    pass
                raise ConnectionLost(
                    f"auth handshake with {host}:{port} failed — secret "
                    f"mismatch or server not configured for auth: {e!r}"
                ) from e
        self._state_lock = lockdep.lock("RpcClient._state_lock")
        # seq -> (Future, expires_monotonic|None, method, deadline_s)
        self._pending = {}
        self._seq = 0
        self._closed = False
        # consecutive deadline expiries with NO intervening reply: a
        # black-holed link looks exactly like a slow one, so callers use
        # this to stop re-paying full deadlines on a dead connection
        # (see WEDGED_STRIKE_LIMIT). Single int under the GIL; the
        # reader thread writes, callers only compare against the limit.
        # flowlint: shared(GIL-atomic counter; a stale read delays one reconnect)
        self.deadline_strikes = 0
        # monotonic stamp of the last frame sent or received: the
        # keepalive pinger only probes links that have gone quiet
        # monotonic heartbeat for keepalive idleness: a single float
        # store under the GIL — a stale read only delays or duplicates
        # one advisory ping, so writers stay lockless by design.
        # flowlint: shared(GIL-atomic heartbeat; staleness is benign)
        self.last_activity = time.monotonic()
        self._reader = threading.Thread(
            target=self._read_loop, name="rpc-client-reader", daemon=True
        )
        self._reader.start()

    def _read_loop(self):
        reader = _FrameReader(self._sock)
        try:
            # short recv timeout = the deadline-sweep tick; a wedged or
            # silent peer can no longer park this thread forever
            self._sock.settimeout(_DEADLINE_TICK_S)
            while True:
                try:
                    frames = reader.recv_burst()
                except socket.timeout:
                    self._sweep_deadlines()
                    continue
                self.last_activity = time.monotonic()
                self.deadline_strikes = 0  # the link demonstrably moves data
                for frame in frames:
                    _kind, seq, ok, payload = wire.loads(frame)
                    self._settle(seq, ok, payload)
        except (ConnectionLost, ConnectionError, OSError, ValueError) as e:
            self._fail_all(e)

    def _settle(self, seq, ok, payload):
        with self._state_lock:
            entry = self._pending.pop(seq, None)
        if entry is None:
            return  # cancelled/timed-out request
        fut = entry[0]
        if fut.done():
            return  # already deadline-settled
        if ok:
            fut.set_result(payload)
        elif isinstance(payload, FDBError):
            fut.set_exception(payload)
        else:
            fut.set_exception(RemoteError(str(payload)))

    def _sweep_deadlines(self):
        """Settle every request past its deadline with DeadlineExceeded.

        The connection stays up: a slow reply to a swept seq is dropped
        by the reader, and unexpired requests keep waiting. Futures are
        settled OUTSIDE the state lock (FL003: callbacks may block)."""
        now = time.monotonic()
        expired = []
        with self._state_lock:
            for seq, entry in list(self._pending.items()):
                expires = entry[1]
                if expires is not None and now >= expires:
                    expired.append(entry)
                    del self._pending[seq]
        if expired:
            self.deadline_strikes += 1
        for fut, _expires, method, deadline_s in expired:
            if not fut.done():
                fut.set_exception(DeadlineExceeded(
                    method, deadline_s,
                    address=f"{self.host}:{self.port}",
                ))

    def _fail_all(self, exc):
        with self._state_lock:
            self._closed = True
            pending, self._pending = self._pending, {}
        try:
            self._sock.close()  # no fd leak across reconnect cycles
        except OSError:
            pass
        for entry in pending.values():
            fut = entry[0]
            if not fut.done():
                fut.set_exception(ConnectionLost(str(exc)))

    @property
    def alive(self):
        return not self._closed

    def call_async(self, method, *args, deadline_s=None) -> Future:
        fut = Future()
        expires = (
            time.monotonic() + deadline_s if deadline_s is not None else None
        )
        with self._state_lock:
            if self._closed:
                raise ConnectionLost("connection closed")
            self._seq += 1
            seq = self._seq
            self._pending[seq] = (fut, expires, method, deadline_s)
        # the thread's ambient SpanContext (a sampled client span) rides
        # as the optional v5 tracing frame; untraced calls keep the
        # 4-tuple form byte-for-byte
        ctx = span_mod.current()
        msg = ("q", seq, method, tuple(args)) if ctx is None \
            else ("q", seq, method, tuple(args), ctx)
        try:
            _send_frame(self._sock, self._send_lock, wire.dumps(msg))
            self.last_activity = time.monotonic()
        except (ConnectionError, OSError) as e:
            with self._state_lock:
                self._pending.pop(seq, None)
            self._fail_all(e)
            raise ConnectionLost(str(e)) from e
        except (ValueError, TypeError):
            # encoding failure / oversized request: the connection is fine,
            # only this call is bad — don't fail other in-flight requests
            with self._state_lock:
                self._pending.pop(seq, None)
            raise
        return fut

    def call(self, method, *args, timeout=None, deadline_s=None):
        return self.call_async(
            method, *args, deadline_s=deadline_s
        ).result(timeout=timeout)

    def close(self):
        with self._state_lock:
            self._closed = True
        try:
            self._sock.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass
        try:
            self._sock.close()
        except OSError:
            pass
        # the shutdown above unblocks the reader's recv; join so close()
        # returns with no thread still touching the dead socket
        if self._reader is not threading.current_thread():
            self._reader.join(timeout=5)


def connect_any(addresses, connect_timeout=5.0, secret=None):
    """Try each ``host:port`` in turn; first reachable wins (ref: the
    client walking the coordinator list in the cluster file)."""
    last = None
    for addr in addresses:
        host, _, port = addr.rpartition(":")
        try:
            return RpcClient(host, int(port), connect_timeout, secret=secret)
        except OSError as e:
            last = e
    raise ConnectionLost(
        f"no server reachable among {addresses!r}: {last}"
    )
