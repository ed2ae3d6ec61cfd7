"""Faults planted underneath the served path, in the server child only,
for the control and the fault tests of ``correct``. None of them is
reachable from the benchmark's command without ``--fault``.

- ``no_conflict`` (the control): the resolver's CONFLICT verdicts are
  turned into COMMITTED on their way to the commit proxy — the
  deployment without the guarantee its config states first, strict
  serializability. What the device computed is untouched; the answer
  is what a resolver that checks nothing would give.
- ``no_intra_batch``: the resolver without its pass over the batch
  itself. Every verdict is given again from a plain table of the last
  committed write of each key: a transaction conflicts only with
  writes of *earlier* batches above its read version, never with a
  transaction of its own batch. It is exact across batches (point keys;
  a transaction with a range keeps the device's verdict), so it plants
  the one fault that ``batch_cycles`` and ``lost_updates`` are for.
- ``drop_apply``: storage drops every 50th SET of a user key (a step
  that leaves its state unchanged): an acknowledged write is not there
  to read back.
- ``alter_read``: storage alters one byte of every 50th value it
  serves for a user key, by a point read or as a row of a range read
  (an answer altered where it is produced).
- ``drop_range_row``: storage leaves out every 50th row of the range
  reads it serves for user keys: a scan that skips a record which is
  there (the read-back too, as any range read).
- ``no_conflict`` on a mix that reads ranges and writes into them turns
  the range conflicts off with the point conflicts: a committed scan
  that missed an insert or a clear reads ``phantom_rows``.
"""

import itertools
import threading

EVERY = 50


def no_conflict():
    from foundationdb_tpu.core.status import COMMITTED, CONFLICT
    from foundationdb_tpu.resolver.resolver import Resolver, ResolveHandle

    def commit_all(statuses):
        return [COMMITTED if s == CONFLICT else s for s in statuses]

    resolve, resolve_many = Resolver.resolve, Resolver.resolve_many

    def patched_resolve(self, *a, **kw):
        return commit_all(resolve(self, *a, **kw))

    def patched_many(self, batches, lazy=False):
        got = resolve_many(self, batches, lazy=lazy)
        if not lazy:
            return [commit_all(s) for s in got]
        return ResolveHandle(
            materialize=lambda: [commit_all(s) for s in got.wait()])

    Resolver.resolve, Resolver.resolve_many = patched_resolve, patched_many


def no_intra_batch():
    from foundationdb_tpu.core.status import COMMITTED, CONFLICT
    from foundationdb_tpu.resolver.resolver import Resolver, ResolveHandle

    last = {}  # key → commit version of its last committed write
    nested = threading.local()  # resolve() may call itself (fallbacks)

    def across_batches_only(txns, commit_version, statuses):
        out = list(statuses)
        for i, s in enumerate(statuses):
            t = txns[i]
            if s in (COMMITTED, CONFLICT) and not (t.range_reads
                                                   or t.range_writes):
                out[i] = CONFLICT if any(
                    last.get(k, 0) > t.read_version
                    for k in t.point_reads) else COMMITTED
        for i, s in enumerate(out):
            if s == COMMITTED:
                last.update(dict.fromkeys(txns[i].point_writes,
                                          commit_version))
        return out

    def outermost(fn):
        def call(self, *a, **kw):
            if getattr(nested, "on", False):
                return fn(self, *a, **kw), False
            nested.on = True
            try:
                return fn(self, *a, **kw), True
            finally:
                nested.on = False
        return call

    resolve = outermost(Resolver.resolve)
    resolve_many = outermost(Resolver.resolve_many)

    def patched_resolve(self, txns, commit_version, new_window_start):
        got, mine = resolve(self, txns, commit_version, new_window_start)
        return across_batches_only(txns, commit_version, got) if mine else got

    def patched_many(self, batches, lazy=False):
        got, mine = resolve_many(self, batches, lazy=lazy)
        if not mine:
            return got

        def again(per_batch):
            return [across_batches_only(txns, cv, s)
                    for (txns, cv, _), s in zip(batches, per_batch)]

        if not lazy:
            return again(got)
        return ResolveHandle(materialize=lambda: again(got.wait()))

    Resolver.resolve, Resolver.resolve_many = patched_resolve, patched_many


def drop_apply():
    from foundationdb_tpu.core.mutations import Op
    from foundationdb_tpu.server.storage import StorageServer

    apply, n = StorageServer.apply, itertools.count(1)

    def patched(self, version, mutations):
        kept = [m for m in mutations
                if not (m.op is Op.SET and m.key < b"\xff"
                        and next(n) % EVERY == 0)]
        return apply(self, version, kept)

    StorageServer.apply = patched


def _rows_served(change):
    """Patch the rows storage serves a range read of user keys with:
    ``StorageServer._iter_live`` is what every range read pulls its
    rows from, the router's across shards too. ``change(row)`` → the
    row to serve, or None to leave it out."""
    from foundationdb_tpu.server.storage import StorageServer

    iter_live = StorageServer._iter_live

    def patched(self, begin, end, version, reverse=False):
        rows = iter_live(self, begin, end, version, reverse=reverse)
        if begin >= b"\xff":
            return rows
        return (row for row in map(change, rows) if row is not None)

    StorageServer._iter_live = patched


def alter_read():
    from foundationdb_tpu.server.storage import StorageServer

    get, n = StorageServer.get, itertools.count(1)

    def altered(value):
        value = bytes(value)
        return value[:-1] + bytes([value[-1] ^ 1])

    def patched(self, key, version):
        value = get(self, key, version)
        if value is not None and key < b"\xff" and next(n) % EVERY == 0:
            value = altered(value)
        return value

    StorageServer.get = patched
    _rows_served(lambda row: (row[0], altered(row[1]))
                 if next(n) % EVERY == 0 else row)


def drop_range_row():
    n = itertools.count(1)
    _rows_served(lambda row: row if next(n) % EVERY else None)


FAULTS = {"no_conflict": no_conflict, "no_intra_batch": no_intra_batch,
          "drop_apply": drop_apply, "alter_read": alter_read,
          "drop_range_row": drop_range_row}
