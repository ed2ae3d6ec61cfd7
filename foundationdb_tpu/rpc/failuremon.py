"""Per-endpoint failure monitor — shared health memory for real RPC.

Ref parity: fdbrpc/FailureMonitor.actor.cpp — every process keeps one
``IFailureMonitor`` that all its connections consult and feed: a
request timing out or a connection resetting marks the endpoint
failed; subsequent senders skip it instead of serially rediscovering
the outage; recovery is probed with exponentially spaced half-open
attempts rather than hammered.

One :class:`FailureMonitor` per process (``monitor()``), keyed by
``"host:port"`` address. The read router (`service._RemoteStorage`)
filters known-failed workers, the keepalive pinger marks idle links,
and the monitor's snapshot surfaces in ``cluster.health``
(``rpc_timeouts`` / ``endpoints_failed``).

Probe timing reads the injected clock (core/deterministic.py), so a
simulated monitor — if one is ever driven — replays with the seed.
Sims never touch the real transport, so production marks can't leak
nondeterminism into same-seed health docs: a sim's snapshot is empty.
"""

from foundationdb_tpu.core import deterministic
from foundationdb_tpu.utils import lockdep
from foundationdb_tpu.utils.trace import TraceEvent


class FailureMonitor:
    """Endpoint health table with half-open exponential recovery probes.

    ``available(addr)`` is the router's question: True for healthy
    endpoints, False for failed ones — EXCEPT that once per probe
    window a failed endpoint answers True exactly once (the half-open
    probe), so recovery is discovered without a thundering herd. The
    probe's outcome must be reported back via ``mark_ok`` /
    ``mark_failed`` to close the loop.
    """

    def __init__(self, probe_initial_s=0.25, probe_max_s=5.0):
        self.probe_initial_s = float(probe_initial_s)
        self.probe_max_s = float(probe_max_s)
        self._lock = lockdep.lock("FailureMonitor._lock")
        self._failed = {}  # addr -> {since, reason, probe_at, probe_delay}
        # cumulative counters for cluster.health (never reset by marks)
        self._rpc_timeouts = 0
        self._endpoints_failed = 0

    def mark_failed(self, addr, reason=""):
        """An RPC against ``addr`` timed out / its connection died."""
        with self._lock:
            ent = self._failed.get(addr)
            now = deterministic.now()
            if ent is None:
                self._endpoints_failed += 1
                self._failed[addr] = {
                    "since": now,
                    "reason": str(reason)[:120],
                    "probe_at": now + self.probe_initial_s,
                    "probe_delay": self.probe_initial_s,
                }
                newly = True
            else:
                # a failed probe: widen the window exponentially
                delay = min(ent["probe_delay"] * 2.0, self.probe_max_s)
                ent["probe_delay"] = delay
                ent["probe_at"] = now + delay
                ent["reason"] = str(reason)[:120]
                newly = False
        if newly:
            TraceEvent("EndpointFailed", severity=30).detail(
                address=addr, reason=str(reason)[:120]).log()

    def note_timeout(self, addr, reason="deadline"):
        """A deadline expired against ``addr``: count it AND mark."""
        with self._lock:
            self._rpc_timeouts += 1
        self.mark_failed(addr, reason)

    def mark_ok(self, addr):
        """A call (or probe) against ``addr`` succeeded."""
        with self._lock:
            cleared = self._failed.pop(addr, None) is not None
        if cleared:
            TraceEvent("EndpointRecovered").detail(address=addr).log()

    def is_failed(self, addr):
        with self._lock:
            return addr in self._failed

    def available(self, addr):
        """Router check: may a request be sent to ``addr`` right now?

        Healthy → True. Failed → False, except exactly one True per
        probe window (half-open): claiming the probe pushes the next
        window out so concurrent callers don't all pile on.
        """
        with self._lock:
            ent = self._failed.get(addr)
            if ent is None:
                return True
            now = deterministic.now()
            if now >= ent["probe_at"]:
                delay = min(ent["probe_delay"] * 2.0, self.probe_max_s)
                ent["probe_delay"] = delay
                ent["probe_at"] = now + delay
                return True  # this caller carries the recovery probe
            return False

    def failed_addresses(self):
        with self._lock:
            return sorted(self._failed)

    def snapshot(self):
        """Deterministic-friendly health surface: states + counters
        only, no wall times (same-seed health docs must stay
        byte-identical, and sims never populate this table)."""
        with self._lock:
            return {
                "failed": {
                    addr: ent["reason"]
                    for addr, ent in sorted(self._failed.items())
                },
                "endpoints_failed": self._endpoints_failed,
                "rpc_timeouts": self._rpc_timeouts,
            }

    def counters(self):
        with self._lock:
            return {
                "rpc_timeouts": self._rpc_timeouts,
                "endpoints_failed": self._endpoints_failed,
            }

    def reset(self):
        """Test isolation: forget marks AND counters."""
        with self._lock:
            self._failed.clear()
            self._rpc_timeouts = 0
            self._endpoints_failed = 0


_monitor = FailureMonitor()


def monitor():
    """The process-global monitor every connection shares."""
    return _monitor
