"""Horizontally scaled transaction frontend: commit-proxy + GRV fleets.

Ref parity: the reference runs a FLEET of commit proxies and GRV proxies
(fdbserver/CommitProxyServer.actor.cpp, GrvProxyServer.actor.cpp), with
the sequencer chaining each batch's version to the one granted before it
(masterserver.actor.cpp getVersion prevVersion) so batches from
different proxies interleave into one serial order. Here the chaining
lives in ``Sequencer.next_commit_versions`` and two ``VersionGate``s
order the stateful pipeline stages (resolve history; log+storage apply)
across the fleet — see ``server/proxy.py``. These facades give the fleet
the same surface a single proxy has, so the client stack, status json,
recovery, and management paths are fleet-agnostic:

- ``ProxyFleet``: round-robins client commits across members, fans
  management state (database lock, tenant mode) out to every member,
  and aggregates counters.
- ``GrvFleet``: round-robins read-version requests across GRV proxies.
"""

import itertools


class ProxyFleet:
    """``members`` are the client-facing proxies (batching wrappers in
    thread pipelines, the bare proxies otherwise); ``inners`` are the
    bare ``CommitProxy`` instances the members drive."""

    def __init__(self, members, inners):
        self.members = members
        self.inners = inners
        self._rr = itertools.count()

    def _pick(self):
        return self.members[next(self._rr) % len(self.members)]

    # ── client surface (round-robined) ──
    def commit(self, request):
        return self._pick().commit(request)

    def submit(self, request):
        return self._pick().submit(request)

    def commit_batch(self, requests):
        return self._pick().commit_batch(requests)

    def commit_batches(self, request_batches):
        return self.inners[next(self._rr) % len(self.inners)].commit_batches(
            request_batches
        )

    # ── management surface ──
    @property
    def inner(self):
        # _commit_target() unwraps batching pipelines via .inner; the
        # fleet IS its own management target (state fans out below)
        return self

    @property
    def alive(self):
        return all(p.alive for p in self.inners)

    def kill(self):
        for p in self.inners:
            p.kill()

    @property
    def lock_uid(self):
        return getattr(self.inners[0], "lock_uid", None)

    @lock_uid.setter
    def lock_uid(self, uid):
        # every member enforces the lock: a commit through ANY proxy of
        # a locked database must fail 1038
        for p in self.inners:
            p.lock_uid = uid

    @property
    def tenant_mode(self):
        return getattr(self.inners[0], "tenant_mode", "optional")

    @tenant_mode.setter
    def tenant_mode(self, mode):
        for p in self.inners:
            p.tenant_mode = mode

    def update_resolver_ranges(self, fence=True):
        """One member derives (and, on a boundary move, fences) the
        resolver ranges; the rest copy the bounds — re-deriving per
        member would fence the shared resolvers once per proxy."""
        self.inners[0].update_resolver_ranges(fence=fence)
        for p in self.inners[1:]:
            p.resolver_bounds = self.inners[0].resolver_bounds

    # ── lifecycle / pipeline plumbing ──
    def flush(self):
        for m in self.members:
            if hasattr(m, "flush"):
                m.flush()

    def pump(self, step):
        for m in self.members:
            if hasattr(m, "pump"):
                m.pump(step)

    def fail_pending(self, error):
        for m in self.members:
            if hasattr(m, "fail_pending"):
                m.fail_pending(error)

    def close(self):
        for m in self.members:
            if hasattr(m, "close"):
                m.close()
        for p in self.inners:
            p.close()

    # ── aggregated counters (status json) ──
    @property
    def commit_count(self):
        return sum(p.commit_count for p in self.inners)

    @property
    def conflict_count(self):
        return sum(p.conflict_count for p in self.inners)

    @property
    def txns_batched(self):
        return sum(getattr(m, "txns_batched", 0) for m in self.members)

    @property
    def batches_committed(self):
        return sum(getattr(m, "batches_committed", 0) for m in self.members)

    @property
    def max_batch_seen(self):
        return max(
            (getattr(m, "max_batch_seen", 0) for m in self.members),
            default=0,
        )

    @property
    def _backlog_target(self):
        # the most-throttled member's depth: the honest contention signal
        return min(
            (getattr(m, "_backlog_target", 1) for m in self.members),
            default=1,
        )

    def metrics_snapshots(self):
        """Per-member metric snapshots (the status doc's commit-proxy
        members section; each member shares its inner proxy's registry
        so batcher spans and proxy counters land in one document)."""
        return [p.metrics.snapshot() for p in self.inners]

    def __len__(self):
        return len(self.inners)


class GrvFleet:
    def __init__(self, members):
        self.members = members
        self._rr = itertools.count()

    def get_read_version(self, priority="default", tags=()):
        return self.members[next(self._rr) % len(self.members)] \
            .get_read_version(priority, tags)

    @property
    def grv_count(self):
        return sum(m.grv_count for m in self.members)

    def metrics_snapshots(self):
        return [m.metrics.snapshot() for m in self.members]

    def close(self):
        for m in self.members:
            if hasattr(m, "close"):
                m.close()

    def __getattr__(self, name):  # sequencer, ratekeeper, ... pass through
        return getattr(self.members[0], name)

    def __len__(self):
        return len(self.members)
