"""Data and traffic made from the seed: the table's keys and values, the
key distributions and the operation stream of one client thread.

Everything here is a pure function of a cell's config file, its traffic
file and ``--seed``; the loader, the clients and the check all import it,
so the three agree on every byte without passing data around.
"""

import random
import re
import struct
import zlib

import numpy as np

STREAM = 1 << 16  # operations pre-drawn per client thread, then cycled


class Table:
    """The deployment's table: ``rows`` records loaded before the
    window, the record in key slot ``s`` under ``key_format % s``.

    Without ``table.insert`` the table has ``rows`` key slots and the
    loaded record ``i`` holds slot ``i``. With ``"insert": {"order":
    "hashed", "room": N}`` it has ``rows + N``: one permutation of them,
    made from the seed alone (loader, clients and check share it), says
    which slot record ``i`` holds: ``i < rows`` is loaded, ``rows + n``
    is the n-th fresh record a client inserts. In key order fresh
    records so fall evenly among the loaded ones, as YCSB's default
    ``insertorder=hashed`` puts them; with ``key_format`` of a running
    id every insert would land behind the last key and no scan would
    ever meet one. Everything below ``slot`` speaks of slots: keys,
    values, the clients' logs and the check's model, in which the order
    of the slots is the order of the keys.

    Value kinds:

    - ``counted``: an 8-byte big-endian update count, then a filler cut
      from one seeded pool (YCSB's fields stand as one opaque record);
      an update writes the same record with the count bumped.
    - ``stamp``: 16 bytes naming the writer (process, thread, sequence),
      as mako's blind sets carry nothing of the old value.
    """

    def __init__(self, config, seed):
        spec = config["table"]
        self.rows = int(config[spec["rows_key"]])
        self.key_format = spec["key_format"].encode()
        self.kind = spec["value"]["kind"]
        self.value_bytes = int(spec["value"]["bytes"])
        if self.kind == "counted":
            self._pool = random.Random(seed).randbytes(1 << 20)
        elif self.kind != "stamp":
            raise ValueError(f"unknown value kind {self.kind!r}")
        if self.kind == "stamp" and self.value_bytes != 16:
            raise ValueError("a stamp value has 16 bytes")
        insert = spec.get("insert")
        self.room = int(insert["room"]) if insert else 0
        self.slots = self.rows + self.room
        self._slot_of = None  # record → slot; None: the identity
        if insert:
            if insert["order"] != "hashed":
                raise ValueError(f"unknown insert order {insert['order']!r}")
            self._slot_of = np.random.default_rng(
                [seed, 0x1A5E]).permutation(self.slots)
        shape = re.fullmatch(rb"([^%]*)%0(\d+)d", self.key_format)
        self._key_prefix = len(shape.group(1)) if shape else None

    def slot(self, record):
        """The key slot of record ``record``: a loaded one below
        ``rows``, the n-th fresh one at ``rows + n``."""
        if not 0 <= record < self.slots:
            raise ValueError(f"record {record}: the table has room for "
                             f"{self.room} fresh records")
        return record if self._slot_of is None else int(self._slot_of[record])

    def loaded(self):
        """The slots that hold a record once the table is loaded, in
        key order."""
        if self._slot_of is None:
            return list(range(self.rows))
        return sorted(self._slot_of[:self.rows].tolist())

    def key(self, s):
        return self.key_format % s

    def end_key(self):
        """Just past the table's last key slot."""
        return self.key(self.slots - 1) + b"\x00"

    def slot_of_key(self, key):
        """``key``'s inverse, for the keys a range read returns."""
        if self._key_prefix is None:
            raise ValueError("a table whose rows are read by range has a "
                             "key_format of a prefix and one %0<n>d")
        return int(key[self._key_prefix:])

    def counted(self, i, count):
        off = (i * 7919) % (len(self._pool) - self.value_bytes)
        return (struct.pack(">Q", count)
                + self._pool[off + 8:off + self.value_bytes])

    @staticmethod
    def stamp(process, thread, seq):
        return struct.pack(">IIQ", process, thread, seq)

    def initial(self, i):
        if self.kind == "counted":
            return self.counted(i, 0)
        return self.stamp(0xFFFFFFFF, 0, i)  # the loader's stamp

    def written(self, i, seen, process, thread, seq):
        """What a ``set`` step writes to record ``i``: a counted record
        with its count bumped from what this transaction read of it
        (``seen``: record id → value), or the writer's stamp."""
        if self.kind == "stamp":
            return self.stamp(process, thread, seq)
        if i not in seen:
            raise ValueError("a counted record is set only after its get")
        old = seen[i]
        return self.counted(i, 1 + (int.from_bytes(old[:8], "big")
                                    if old else 0))


def token(value):
    """What the logs and the check carry of a value: its length and
    CRC-32 in one number (-1 for a missing key)."""
    if value is None:
        return -1
    return (len(value) << 32) | zlib.crc32(value)


def _zipfian_cdf(n, theta):
    # P(rank r) ∝ 1 / r**theta, rank 1 the hottest
    w = 1.0 / np.arange(1, n + 1, dtype=np.float64) ** theta
    return np.cumsum(w / w.sum())


def key_sampler(spec, rows, seed):
    """→ sample(u): record ids for quantiles ``u`` in [0, 1) of the
    mix's key distribution.

    ``zipfian`` turns quantiles into ranks and, with ``scramble``, maps
    ranks to ids by a permutation made from the seed alone (every
    client shares it), so the hot keys are spread over the keyspace as
    YCSB's scrambled Zipfian spreads them."""
    kind = spec["distribution"]
    if kind == "uniform":
        return lambda u: np.minimum((u * rows).astype(np.int64), rows - 1)
    if kind == "zipfian":
        cdf = _zipfian_cdf(rows, float(spec["theta"]))
        perm = (np.random.default_rng([seed, 0x5C4A]).permutation(rows)
                if spec.get("scramble") else np.arange(rows))
        return lambda u: perm[np.minimum(np.searchsorted(cdf, u), rows - 1)]
    raise ValueError(f"unknown key distribution {kind!r}")


def _shares(weights, n):
    """``n`` split by ``weights``, largest remainders first."""
    exact = weights / weights.sum() * n
    counts = np.floor(exact).astype(int)
    for i in np.argsort(exact - counts)[::-1][:n - counts.sum()]:
        counts[i] += 1
    return counts


def step_length(step):
    """The ``n`` of a step ``[verb, slot, n]``: → (lo, hi), the same
    twice for a plain integer, None for a step without one."""
    if len(step) < 3:
        return None
    n = step[2]
    lo, hi = n["uniform"] if isinstance(n, dict) else (n, n)
    if not (isinstance(lo, int) and isinstance(hi, int) and 1 <= lo <= hi):
        raise ValueError(f"a step's n is an integer >= 1 or "
                         f'{{"uniform": [lo, hi]}}, not {n!r}')
    return lo, hi


def _drawn_lengths(traffic):
    """Per operation the (lo, hi) of its step with a drawn length, None
    for an operation without one; None where no operation has any."""
    drawn = []
    for o in traffic["operations"]:
        spans = [step_length(s) for s in o.get("steps", ())
                 if len(s) > 2 and isinstance(s[2], dict)]
        if len(spans) > 1:
            raise ValueError("one drawn length an operation")
        drawn.append(spans[0] if spans else None)
    return drawn if any(drawn) else None


def op_stream(traffic, rows, seed, process, thread, sampler=None):
    """The pre-drawn stream of one client thread: → (kinds, keys_a,
    keys_b), ``kinds`` indexing ``traffic["operations"]``, the keys
    (records of the loaded table) filling the slots ``a`` and ``b`` of
    an operation's steps; and, only where an operation has a step whose
    ``n`` is ``{"uniform": [lo, hi]}``, a fourth list: that step's
    length for each operation (0 for an operation without one), drawn
    by a generator of its own, so the other three lists are what they
    are without it.

    ``traffic["keys"]["draw"]`` says how:

    - ``iid``: every operation's kind, keys and length are independent
      draws, as YCSB's and mako's own generators make them.
    - ``stratified``: blocks of ``block`` operations, each holding
      every kind in its exact share and, within a kind, keys (and
      lengths) at evenly spaced quantiles of the distribution from a
      random offset (systematic sampling: every key still drawn with
      its own probability), in shuffled order. Every seed then sends
      the same mix in another order; the marginals are the source's,
      the joint process is not (fewer bursts on a hot key). The stream
      is a whole number of blocks, at most ``STREAM`` operations."""
    rng = np.random.default_rng([seed, 0x0B5, process, thread])
    sampler = sampler or key_sampler(traffic["keys"], rows, seed)
    weights = np.array([float(o["weight"]) for o in traffic["operations"]])
    drawn = _drawn_lengths(traffic)
    draw = traffic["keys"]["draw"]
    if draw == "iid":
        kinds = rng.choice(len(weights), size=STREAM, p=weights / weights.sum())
        keys_a = sampler(rng.random(STREAM))
        keys_b = sampler(rng.random(STREAM))
        out = (kinds.tolist(), keys_a.tolist(), keys_b.tolist())
        if drawn:
            out += (_lengths(drawn, kinds, seed, process, thread, None),)
        return out
    if draw != "stratified":
        raise ValueError(f"unknown draw {draw!r}")
    block = int(traffic["keys"]["block"])
    counts = _shares(weights, block)
    kinds, keys_a, keys_b = [], [], []
    for _ in range(STREAM // block):
        k = np.repeat(np.arange(len(counts)), counts)
        a = np.concatenate([sampler((np.arange(n) + rng.random()) / n)
                            for n in counts if n])
        b = np.concatenate([sampler((np.arange(n) + rng.random()) / n)
                            for n in counts if n])
        order = rng.permutation(block)
        kinds.append(k[order])
        keys_a.append(a[order])
        keys_b.append(b[rng.permutation(block)])
    kinds = np.concatenate(kinds)
    out = (kinds.tolist(), np.concatenate(keys_a).tolist(),
           np.concatenate(keys_b).tolist())
    if drawn:
        out += (_lengths(drawn, kinds, seed, process, thread, block),)
    return out


def _lengths(drawn, kinds, seed, process, thread, block):
    """The fourth list of ``op_stream``: for each operation of ``kinds``
    a length uniform over its kind's [lo, hi]. ``block`` None: every
    one an independent draw. Else, in each block, a kind's n lengths
    lie at the quantiles (j + u) / n of its span, u one draw a block,
    handed to the kind's operations in shuffled order."""
    rng = np.random.default_rng([seed, 0x1E5, process, thread])
    out = np.zeros(len(kinds), dtype=np.int64)
    for start in range(0, len(kinds), block or len(kinds)):
        part = kinds[start:start + (block or len(kinds))]
        for kind, span in enumerate(drawn):
            at = np.flatnonzero(part == kind)
            if span is None or not len(at):
                continue
            n = len(at)
            u = (rng.random(n) if block is None
                 else rng.permutation((np.arange(n) + rng.random()) / n))
            lo, hi = span
            out[start + at] = np.minimum(lo + (u * (hi - lo + 1)).astype(
                np.int64), hi)
    return out.tolist()
