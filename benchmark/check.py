"""The comparison that decides ``correct``.

The plain reference is a dict, and beside it the sorted list of the key
slots that hold a record. It imports nothing of the program: it
replays what the clients logged — every operation they ran from their
first to their last, warm-up included — in the order the database
itself gave the commits (their commit versions), over a model of the
table made from the seed, and holds every answer the window produced to
what a strictly serializable store must have said:

- ``stale_reads``: a read inside a committed transaction must return
  the last value written before that transaction's commit version (a
  conflict the resolver missed shows here as a committed transaction
  that read an overwritten value: a lost update); a read-only
  transaction must return the last value written at or before its read
  version. A row of a range read is held to the same, by its token.
  Limit 0.
- ``phantom_rows``: a range read ``[first slot, limit]`` must return
  exactly the first ``limit`` records that are live, in key order
  from its first slot, as of the same state (a committed
  transaction's: before its commit version; a read-only one's: at its
  read version): the same slots in the same order. A row the model has
  and the answer lacks, or the reverse, counts here. Limit 0.
- ``stale_read_versions``: a transaction that began after another's
  commit was acknowledged (one host, one ``time.monotonic()``) must
  read at or above that commit's version. Limit 0.
- ``wrong_rows``: after the drain the table's whole key range is read
  back and must hold what the replay ends with: an acknowledged write
  or insert that is lost, a cleared record that is still there, or a
  row nobody acknowledged (and no 1021 explains), shows here.
  Limit 0.
- ``batch_cycles``: transactions of one commit batch share a version,
  and the client cannot see their order in the batch. All of them read
  the state before the batch (``stale_reads`` holds them to it), so the
  batch is serializable only if its transactions can be put in an order
  in which nobody reads a key that a transaction before it wrote: a
  reader of a key goes before every other transaction of the batch
  that writes it, and a range read is a read of every slot it
  covered, present or absent: from its first slot to its last row,
  or to the table's end where it returned fewer rows than its limit. Transactions that no such order can hold (two updates
  of one key that both committed: each must go before the other; a
  write skew) are conflicts the resolver missed inside one batch.
  Limit 0.
- ``lost_updates`` (tables whose records count their updates): every
  record read back holds exactly as many updates as were acknowledged
  to it, whatever their versions, plus at most those that got 1021.
  Two updates of one batch that both read count c and both wrote c+1
  give the same bytes; only the count shows that one was lost. A
  record that was ever cleared counts from its clear, and is left out.
  Limit 0.
- ``unanswered``: operations still unacknowledged when the clients
  gave up, ``drain_s`` (60 s) after the window closed. An answer that
  comes late is late, not wrong: its latency counts the wait. Limit 0. (A 1021 is an answer: "unknown". It counts under ``failed``
  and its write is accepted either way.)

Where two transactions of a batch blindly wrote one key, either value
may stand until a later read or the final read-back says which.
"""

import bisect

OK, FAILED, UNKNOWN, LATE = 0, 1, 2, 3  # an operation's status in the log
KIND, T0, T1, STATUS, RETRIES, CODE, RV, CV, READS, WRITES, RANGES = range(11)
ABSENT = -1  # the token of a key slot that holds no record


def ranges_of(op):
    """The range reads of a log row: [first slot, limit, rows]. The
    field is there only where the operation read a range."""
    return op[RANGES] if len(op) > RANGES else ()


class Model:
    """Key slot → the set of tokens the slot may hold (one, except
    after a batch that blindly wrote the key twice; ``ABSENT`` for no
    record), and the slots that may hold a record, in key order."""

    def __init__(self, initial_token, loaded=()):
        self._initial = initial_token
        self._now = {}
        self.unknown = {}  # slot → tokens of writes that got 1021
        self.written = set()  # slots some commit wrote
        self._order = sorted(loaded)
        self._in_order = set(self._order)

    def get(self, k):
        held = self._now.get(k)
        if held is None:
            held = self._now[k] = {self._initial(k)}
        return held

    def _sync(self, k):
        """Keep ``k``'s place in the order to what it may hold."""
        may = (self.get(k) | self.unknown.get(k, set())) != {ABSENT}
        if may and k not in self._in_order:
            bisect.insort(self._order, k)
            self._in_order.add(k)
        elif not may and k in self._in_order:
            self._order.pop(bisect.bisect_left(self._order, k))
            self._in_order.discard(k)

    def may(self, k, tok):
        """A write that got 1021: ``k`` may hold ``tok`` from now on."""
        self.unknown.setdefault(k, set()).add(tok)
        self._sync(k)

    def read(self, k, tok):
        """True where ``tok`` is what a reader must see; settles a tie."""
        held = self.get(k)
        if tok in held:
            if len(held) > 1:
                self._now[k] = {tok}
                self._sync(k)
            return True
        if tok in self.unknown.get(k, ()):
            self.unknown[k].discard(tok)
            self._now[k] = {tok}
            self._sync(k)
            return True
        return False

    def read_range(self, first, limit, rows):
        """Hold the ``rows`` ([slot, token], in key order) that a range
        read of at most ``limit`` records from slot ``first`` returned
        to the first ``limit`` live slots from ``first`` → (rows with
        another token than the model's, rows that only one of the two
        has). A slot that may or may not hold a record (a 1021, a tie
        with a clear) is settled by the answer."""
        order, stale, phantom = self._order, 0, 0
        i, j, there = bisect.bisect_left(order, first), 0, 0
        while there < limit and (i < len(order) or j < len(rows)):
            c = order[i] if i < len(order) else None
            g = rows[j][0] if j < len(rows) else None
            if c is None or (g is not None and g < c):
                phantom += 1  # the answer has a row, the model none
                j += 1
            elif g == c:
                stale += not self.read(c, rows[j][1])
                i, j, there = i + 1, j + 1, there + 1
            elif self.read(c, ABSENT):
                # not there; it keeps its place only while a 1021 may
                # yet put a record there
                i += i < len(order) and order[i] == c
            else:
                phantom += 1  # the model has a row, the answer none
                i, there = i + 1, there + 1
        return stale, phantom + len(rows) - j

    def write_group(self, writes):
        """Apply one commit version's writes: [(slot, token)]."""
        by_key = {}
        for k, tok in writes:
            by_key.setdefault(k, set()).add(tok)
        self._now.update(by_key)
        self.written.update(by_key)
        for k in by_key:
            self._sync(k)


def covered(first, limit, rows):
    """The slots a range read covered → (first, last): up to its last
    row, or without end where it returned fewer than its limit."""
    return first, rows[-1][0] if len(rows) >= limit else float("inf")


def unorderable(group):
    """The transactions of one commit version that no order of the
    batch can hold → their number. An edge runs from the reader of a
    key to every other transaction of the group that writes it (the
    reader goes first), and from the reader of a range to every other
    that writes, inserts or clears a slot inside it; what is left when
    every transaction with no edge in or no edge out has been taken
    away, again and again, lies on a cycle or between two."""
    writers = {}
    for n, op in enumerate(group):
        for k, _ in op[WRITES]:
            writers.setdefault(k, set()).add(n)
    after = [set() for _ in group]  # n goes before these
    before = [set() for _ in group]
    for n, op in enumerate(group):
        for k, _ in op[READS]:
            after[n] |= writers.get(k, set()) - {n}
        for lo, hi in (covered(*r) for r in ranges_of(op)):
            for k, ws in writers.items():
                if lo <= k <= hi:
                    after[n] |= ws - {n}
        for m in after[n]:
            before[m].add(n)
    left = {n for n in range(len(group)) if after[n] and before[n]}
    while True:
        free = {n for n in left
                if not (after[n] & left and before[n] & left)}
        if not free:
            return len(left)
        left -= free


def replay(ops, initial_token, final_rows, counted_token=None, loaded=None):
    """Hold the logged operations and the final rows to the model.

    ``ops``: every client's log rows, merged. ``initial_token(slot)``:
    what the loaded table holds there (-1: no record). ``final_rows``:
    {slot: token} as read back after the drain: every row that was
    there, and -1 for a loaded record that was not.
    ``counted_token(slot, count)``: the token of a record that
    holds ``count`` updates, where the table's records count them.
    ``loaded``: the slots that hold a record before the first
    operation (default: those of ``final_rows``).
    → the numbers compared, as {name: value}, and a few examples."""
    model = Model(initial_token,
                  final_rows if loaded is None else loaded)
    examples = []
    commits = {}  # commit version → [op]
    read_only = []
    for op in ops:
        if op[STATUS] == UNKNOWN:
            for k, tok in op[WRITES]:
                model.may(k, tok)
        elif op[STATUS] == OK:
            if op[WRITES]:
                commits.setdefault(op[CV], []).append(op)
            else:
                read_only.append(op)
    read_only.sort(key=lambda op: op[RV])

    stale = phantom = checked = cycles = 0

    def hold(op, where):
        nonlocal stale, phantom, checked
        for k, tok in op[READS]:
            checked += 1
            if not model.read(k, tok):
                stale += 1
                if len(examples) < 5:
                    examples.append(
                        f"{where} read of record {k} at rv {op[RV]} "
                        f"cv {op[CV]}: token {tok}, model {sorted(model.get(k))}")
        for first, limit, rows in ranges_of(op):
            checked += len(rows)
            s, p = model.read_range(first, limit, rows)
            stale += s
            phantom += p
            if (s or p) and len(examples) < 5:
                examples.append(
                    f"{where} range read of {limit} from slot {first} at rv "
                    f"{op[RV]} cv {op[CV]}: {len(rows)} rows, {s} with "
                    f"another token than the model's, {p} that only the "
                    "answer or only the model has")

    r = 0
    for cv in sorted(commits):
        while r < len(read_only) and read_only[r][RV] < cv:
            hold(read_only[r], "read-only")
            r += 1
        group = commits[cv]
        for op in group:
            if op[RV] >= cv and (op[READS] or ranges_of(op)):
                stale += 1
                examples.append(f"commit at {cv} read at {op[RV]}")
            hold(op, "committed")
        if len(group) > 1:
            n = unorderable(group)
            cycles += n
            if n and len(examples) < 8:
                examples.append(f"commit version {cv}: {n} of {len(group)} "
                                "transactions cannot be ordered")
        model.write_group([w for op in group for w in op[WRITES]])
    for op in read_only[r:]:
        hold(op, "read-only")

    wrong = 0
    # a slot some commit wrote and the read-back lacks must be cleared
    gone = dict.fromkeys(model.written - final_rows.keys(), ABSENT)
    for k, tok in [*final_rows.items(), *gone.items()]:
        if not model.read(k, tok):
            wrong += 1
            if len(examples) < 10:
                examples.append(f"final row {k}: token {tok}, model "
                                f"{sorted(model.get(k))}")

    # strictness: acknowledged before it began ⇒ visible to it
    acked = sorted((op[T1], op[CV]) for g in commits.values() for op in g)
    times = [t for t, _ in acked]
    high, top = [], 0
    for _, cv in acked:
        top = max(top, cv)
        high.append(top)
    behind = 0
    for op in ops:
        if op[STATUS] != OK or not (op[READS] or ranges_of(op)):
            continue  # a blind write read nothing, at no version
        n = bisect.bisect_left(times, op[T0])
        if n and op[RV] < high[n - 1]:
            behind += 1
            if len(examples) < 15:
                examples.append(f"began after cv {high[n - 1]} was "
                                f"acknowledged, read at {op[RV]}")
    numbers = {
        "stale_reads": stale,
        "phantom_rows": phantom,
        "batch_cycles": cycles,
        "stale_read_versions": behind,
        "wrong_rows": wrong,
        "unanswered": sum(1 for op in ops if op[STATUS] == LATE),
        "reads_compared": checked,
        "rows_compared": len(final_rows) + len(gone),
    }
    if counted_token:
        numbers["lost_updates"] = lost_updates(
            ops, final_rows, counted_token, examples)
    return numbers, examples


def lost_updates(ops, final_rows, counted_token, examples):
    """Records whose count read back is not the number of updates
    (transactions that read and wrote the record) acknowledged to it,
    give or take those that ended unknown. A record that was ever
    cleared counts from there, and is left out."""
    sure, maybe, cleared = {}, {}, set()
    for op in ops:
        tally = {OK: sure, UNKNOWN: maybe}.get(op[STATUS])
        if tally is None:
            continue
        read = {k for k, _ in op[READS]}
        for k, tok in op[WRITES]:
            if k in read:
                tally[k] = tally.get(k, 0) + 1
            if tok == ABSENT:
                cleared.add(k)
    lost = 0
    for k in (sure.keys() | maybe.keys()) - cleared:
        n = sure.get(k, 0)
        if final_rows.get(k) not in {counted_token(k, n + j)
                                     for j in range(maybe.get(k, 0) + 1)}:
            lost += 1
            if len(examples) < 12:
                examples.append(f"record {k}: {n} updates acknowledged, "
                                "the count read back is another")
    return lost


# name → (limit, "max" = at most / "min" = at least)
LIMITS = {
    "stale_reads": (0, "max"),
    "phantom_rows": (0, "max"),
    "batch_cycles": (0, "max"),
    "lost_updates": (0, "max"),
    "stale_read_versions": (0, "max"),
    "wrong_rows": (0, "max"),
    "unanswered": (0, "max"),
    "reads_compared": (1, "min"),
    "rows_compared": (1, "min"),
    "compiles_in_window": (0, "max"),
    "pallas_to_jit": (0, "max"),
    "client_errors": (0, "max"),
}


def verdict(numbers):
    """→ (correct, {name: {"value", "limit"}}) over every number
    compared; a name without a limit is a fault of the harness."""
    compared, ok = {}, True
    for name, value in numbers.items():
        limit, sense = LIMITS[name]
        compared[name] = {"value": value, "limit": limit}
        ok &= value <= limit if sense == "max" else value >= limit
    return bool(ok), compared
