"""Injectable entropy + clock — the sim-determinism seam.

Ref parity: FoundationDB's deterministic simulation works only because
every source of nondeterminism the cluster can OBSERVE flows through
``deterministicRandom()`` and ``g_network->now()``, which sim2 seeds and
replays (flow/IRandom.h, fdbrpc/sim2.actor.cpp). Cluster-visible code
never calls the OS clock or OS entropy directly; it asks the injected
authority, so a seed replays byte-identically.

This module is that authority for the Python port. Cluster-visible code
draws randomness from a NAMED stream (``rng("proposer-id")``) and reads
time via ``now()``:

- **Production** (default): streams are seeded from OS entropy and
  ``now()`` is the wall clock — behavior is unchanged from calling
  ``random`` / ``time.time`` directly.
- **Simulation**: ``sim/simulation.py`` calls ``seed(master_seed)`` and
  ``set_clock(step_clock)`` at cluster build; every stream re-seeds to a
  value derived from (master seed, stream name), so two same-seed runs
  draw identical proposer ids, directory prefixes, idempotency ids, …

Named streams (rather than one shared stream) keep call sites
independent: adding a draw in one subsystem does not shift another
subsystem's sequence, which keeps seed replays stable across unrelated
code changes — the same reason the reference hands each actor its own
DeterministicRandom fork.

flowlint's FL001 rule enforces the seam: direct ``time.time()`` /
``os.urandom`` / module-level ``random.*`` calls outside ``sim/`` (and
this module) are findings. Deliberately non-deterministic sites —
crypto material like the RPC auth nonce — stay on ``os.urandom`` with
an inline ``# flowlint: disable=FL001`` and a stated reason: feeding an
attacker-predictable seeded stream into authentication would be a
vulnerability, and the sim never exercises the real transport.
"""

import random
import threading
import time
from foundationdb_tpu.utils import lockdep


class DeterminismRegistry:
    """Named RNG streams + an injectable clock, one per process."""

    def __init__(self):
        self._lock = lockdep.lock("DeterminismRegistry._lock")
        self._streams = {}
        self._seed = None  # None = production mode (OS entropy)
        self._clock = time.time
        self._cpu_clock = time.thread_time

    # ── entropy ──
    def rng(self, name):
        """The named stream (a persistent ``random.Random``). The same
        name always returns the same object, so a later ``seed()``
        re-seeds every stream handed out earlier — construction order
        and seeding order cannot race."""
        with self._lock:
            stream = self._streams.get(name)
            if stream is None:
                if self._seed is None:
                    stream = random.Random()  # OS-entropy seeded
                else:
                    stream = random.Random(f"{self._seed}:{name}")
                self._streams[name] = stream
            return stream

    def token_bytes(self, n, name="token"):
        """``n`` random bytes from a named stream (idempotency ids,
        generated cluster ids). Deterministic under a seed; OS-entropy
        quality in production. NOT for cryptographic material — auth
        nonces must stay on ``os.urandom``."""
        return self.rng(name).getrandbits(8 * n).to_bytes(n, "big")

    def seed(self, master_seed):
        """Enter deterministic mode: every existing stream re-seeds to
        hash(master_seed, name); streams created later derive the same
        way. Two processes seeding the same value draw identical
        sequences from identically-named streams."""
        with self._lock:
            self._seed = master_seed
            for name, stream in self._streams.items():
                stream.seed(f"{master_seed}:{name}")

    def unseed(self):
        """Back to production mode: streams re-seed from OS entropy."""
        with self._lock:
            self._seed = None
            for stream in self._streams.values():
                stream.seed()

    @property
    def seeded(self):
        return self._seed is not None

    # ── time ──
    def now(self):
        """The injected clock (wall clock in production; the sim's step
        clock under simulation)."""
        return self._clock()

    def set_clock(self, fn):
        """Inject the clock. The thread CPU clock follows it: under an
        injected clock CPU time IS that clock (a stage's off-CPU time
        reads 0) and nothing of the real one reaches a Span event."""
        self._clock = fn
        self._cpu_clock = fn

    def set_cpu_clock(self, fn):
        """A CPU clock of its own beside an injected clock (tests that
        hold a stage to a known wall and a known CPU time)."""
        self._cpu_clock = fn

    def reset_clock(self):
        self._clock = time.time
        self._cpu_clock = time.thread_time


_registry = DeterminismRegistry()


def registry():
    return _registry


def rng(name):
    return _registry.rng(name)


def token_bytes(n, name="token"):
    return _registry.token_bytes(n, name)


def seed(master_seed):
    _registry.seed(master_seed)


def unseed():
    _registry.unseed()


def now():
    # reads the clock through the registry's live slot (not a cached
    # fn) so set_clock/reset_clock swaps take effect, while skipping
    # the method hop — this sits on per-operation hot paths (metrics
    # stamps, span begin/end)
    return _registry._clock()


def thread_cpu():
    """The calling thread's CPU seconds, through the same seam (sim: the
    injected clock itself). ``time.thread_time`` is a real system call
    on the chip's host, 6 µs: a few reads a resolver DISPATCH, never one
    on a request's path (utils/span.stage ``cpu=True``)."""
    return _registry._cpu_clock()


def set_clock(fn):
    _registry.set_clock(fn)


def set_cpu_clock(fn):
    _registry.set_cpu_clock(fn)
