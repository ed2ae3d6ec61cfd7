"""Data and traffic made from the seed: the table's keys and values, the
key distributions and the operation stream of one client thread.

Everything here is a pure function of a cell's config file, its traffic
file and ``--seed``; the loader, the clients and the check all import it,
so the three agree on every byte without passing data around.
"""

import random
import struct
import zlib

import numpy as np

STREAM = 1 << 16  # operations pre-drawn per client thread, then cycled


class Table:
    """The deployment's table: ``rows`` records, key ``key_format % id``.

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

    def key(self, i):
        return self.key_format % i

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
    # as bench.py:zipfian_sampler: P(rank r) ∝ 1 / r**theta
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


def op_stream(traffic, rows, seed, process, thread, sampler=None):
    """The pre-drawn stream of one client thread: → (kinds, keys_a,
    keys_b), ``kinds`` indexing ``traffic["operations"]``, the keys
    filling the slots ``a`` and ``b`` of an operation's steps.

    ``traffic["keys"]["draw"]`` says how:

    - ``iid``: every operation's kind and keys are independent draws,
      as YCSB's and mako's own generators make them.
    - ``stratified``: blocks of ``block`` operations, each holding
      every kind in its exact share and, within a kind, keys at evenly
      spaced quantiles of the distribution from a random offset
      (systematic sampling: every key still drawn with its own
      probability), in shuffled order. Every seed then sends the same
      mix in another order; the marginals are the source's, the joint
      process is not (fewer bursts on a hot key)."""
    rng = np.random.default_rng([seed, 0x0B5, process, thread])
    sampler = sampler or key_sampler(traffic["keys"], rows, seed)
    weights = np.array([float(o["weight"]) for o in traffic["operations"]])
    draw = traffic["keys"]["draw"]
    if draw == "iid":
        kinds = rng.choice(len(weights), size=STREAM, p=weights / weights.sum())
        keys_a = sampler(rng.random(STREAM))
        keys_b = sampler(rng.random(STREAM))
        return kinds.tolist(), keys_a.tolist(), keys_b.tolist()
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
    return (np.concatenate(kinds).tolist(), np.concatenate(keys_a).tolist(),
            np.concatenate(keys_b).tolist())
