"""Flat columnar conflict-range encoding — the commit hot path's wire
and packing format.

The legacy commit path re-parses every conflict range at every layer:
the client ships ``[(begin, end)]`` byte pairs, the proxy splits points
from ranges per transaction and builds ``TxnRequest`` objects, and the
packer walks those objects gathering keys before one batched limb
encode. At tens of thousands of commits/sec the per-transaction Python
churn — object construction, per-range slicing, per-txn list appends —
was the dominant commit-pipeline stage (``stage_pack_ms``).

The flat path encodes ONCE, client-side, into the exact bytes every
downstream layer consumes:

    entry(k)  = k padded to C=4*L bytes with \\x00  ||  >I(len(k))

which is precisely the resolver's limb encoding (core/keys.py KeyCodec):
``np.frombuffer(entry, '>u4')`` IS ``encode_lower(k)`` — the padded key
bytes are the big-endian limbs and the trailing word is the length limb.
For in-capacity keys ``encode_upper`` agrees with ``encode_lower``, so a
range packs as ``entry(begin) || entry(end)`` with no successor math.
A point key's end bound ``k+\\x00`` needs no entry of its own anywhere:
on the device path the point lanes store only the lower encoding, and on
the native path the padding byte AFTER the key inside its own entry is
the ``\\x00`` — ``blob[off : off+len+1]`` is ``k+b"\\x00"`` verbatim
(when ``len == C`` the first byte of the length word is 0, because
C < 2^24).

Per transaction the client ships four blobs (read/write × point/range)
plus counts; the proxy concatenates blobs across the batch with
``b"".join`` and derives every offset from cumsums — no per-key touch
server-side. Keys longer than C bytes don't flatten (the conservative
prefix widening would be lossy on the wire); those transactions ride
the legacy path unchanged.

Kept in ``core`` so the wire codec can name :class:`FlatConflicts`
without importing the resolver stack (and with it JAX).
"""

import struct
from typing import NamedTuple

import numpy as np

_U32 = struct.Struct(">I")

# per-num_limbs encode tables: (zero padding, length words 0..C)
_ENC_TABS = {}


def _tabs(num_limbs):
    t = _ENC_TABS.get(num_limbs)
    if t is None:
        cap = 4 * num_limbs
        t = (b"\x00" * cap, [_U32.pack(n) for n in range(cap + 1)])
        _ENC_TABS[num_limbs] = t
    return t


def entry_width(num_limbs):
    """Bytes per encoded key entry: C key bytes + the 4-byte length."""
    return 4 * num_limbs + 4


class FlatConflicts(NamedTuple):
    """One transaction's conflict ranges, pre-encoded client-side.

    ``*_points`` count point keys (single-key ranges ``[k, k+\\x00)``),
    each one ``entry_width`` bytes in its blob; ``*_ranges`` count true
    ranges, each ``2 * entry_width`` bytes (lower || upper). A tuple
    subclass so the proxy's batch build can unzip a whole request batch
    with one C-speed ``zip(*...)``."""

    num_limbs: int
    read_points: int
    read_point_blob: bytes
    read_ranges: int
    read_range_blob: bytes
    write_points: int
    write_point_blob: bytes
    write_ranges: int
    write_range_blob: bytes


def encode_entry(key, num_limbs):
    """``entry(key)``, or None when the key exceeds limb capacity."""
    pad, lens = _tabs(num_limbs)
    n = len(key)
    if n > 4 * num_limbs:
        return None
    return key + pad[n:] + lens[n]


def _encode_side(ranges, num_limbs, pad, lens):
    """One side's (points, point_blob, ranges, range_blob), or None on
    an over-capacity key. The point test mirrors proxy._split_ranges:
    ``[k, k+\\x00)`` without building the successor bytes."""
    cap = 4 * num_limbs
    pts = []
    rgs = []
    for b, e in ranges:
        nb = len(b)
        if len(e) == nb + 1 and e[-1] == 0 and e.startswith(b):
            # a point stores only its begin entry, so only the KEY must
            # fit — an exactly-capacity point's end (cap+1 bytes) costs
            # nothing (the entry's length word supplies its \x00)
            if nb > cap:
                return None
            pts.append(b + pad[nb:] + lens[nb])
        else:
            if nb > cap or len(e) > cap:
                return None
            rgs.append(b + pad[nb:] + lens[nb])
            ne = len(e)
            rgs.append(e + pad[ne:] + lens[ne])
    return len(pts), b"".join(pts), len(rgs) // 2, b"".join(rgs)


def encode_conflicts(read_ranges, write_ranges, num_limbs):
    """Encode a transaction's conflict ranges → FlatConflicts, or None
    when any key exceeds the 4*num_limbs-byte limb capacity (the legacy
    path handles those with its conservative widening)."""
    pad, lens = _tabs(num_limbs)
    r = _encode_side(read_ranges, num_limbs, pad, lens)
    if r is None:
        return None
    w = _encode_side(write_ranges, num_limbs, pad, lens)
    if w is None:
        return None
    return FlatConflicts(num_limbs, *r, *w)


def point_limbs(blob, num_limbs):
    """uint32[n_entries, W] native-order limb rows (one frombuffer
    pass — this IS KeyCodec.encode_lower_batch's output)."""
    W = num_limbs + 1
    if not blob:
        return np.zeros((0, W), dtype=np.uint32)
    return np.frombuffer(blob, dtype=">u4").reshape(-1, W).astype(
        np.uint32)


def range_limbs(blob, num_limbs):
    """(lower uint32[n, W], upper uint32[n, W]) limb rows."""
    W = num_limbs + 1
    if not blob:
        z = np.zeros((0, W), dtype=np.uint32)
        return z, z
    a = np.frombuffer(blob, dtype=">u4").reshape(-1, 2, W).astype(
        np.uint32)
    return a[:, 0], a[:, 1]


def _decode_entries(blob, num_limbs):
    """entry blob → list[bytes] raw keys (exact: in-capacity only)."""
    w = entry_width(num_limbs)
    if not blob:
        return []
    lens = np.frombuffer(blob, dtype=">u4").reshape(-1,
                                                    num_limbs + 1)[:, -1]
    return [
        blob[o: o + n]
        for o, n in zip(range(0, len(blob), w), lens.tolist())
    ]


def decode_side(point_blob, range_blob, num_limbs):
    """Reconstruct ``[(begin, end)]`` from one side's blobs (points as
    ``[k, k+\\x00)``) — the wire's lazy fallback for consumers that
    still want byte ranges (cpu resolver, conflicting-keys reports)."""
    out = [(k, k + b"\x00") for k in _decode_entries(point_blob,
                                                     num_limbs)]
    ks = _decode_entries(range_blob, num_limbs)
    out.extend(zip(ks[0::2], ks[1::2]))
    return out


class FlatTxnBatch:
    """One commit batch, columnar: per-txn counts + concatenated entry
    blobs (the proxy's ``b"".join`` over FlatConflicts). Consumed
    directly by BatchPacker.pack_flat_group (limb view) and
    NativeConflictSet.resolve_flat (raw-byte view into the same
    blobs)."""

    __slots__ = ("num_limbs", "rv", "prc", "pwc", "rrc", "rwc",
                 "pr_blob", "pw_blob", "rr_blob", "rw_blob", "_txn_memo")

    def __init__(self, num_limbs, rv, prc, pwc, rrc, rwc,
                 pr_blob, pw_blob, rr_blob, rw_blob):
        self._txn_memo = {}  # i -> decoded TxnRequest (see __getitem__)
        self.num_limbs = num_limbs
        self.rv = rv  # int64[n] absolute read versions
        self.prc = prc  # int64[n] point-read counts
        self.pwc = pwc
        self.rrc = rrc  # int64[n] range-read counts
        self.rwc = rwc
        self.pr_blob = pr_blob
        self.pw_blob = pw_blob
        self.rr_blob = rr_blob
        self.rw_blob = rw_blob

    def __len__(self):
        return len(self.rv)

    def point_limbs(self, blob):
        return point_limbs(blob, self.num_limbs)

    def range_limbs(self, blob):
        return range_limbs(blob, self.num_limbs)

    # ── fallback decode (rare: lane overflow, too-old txns,
    #    report_conflicting_keys) ──
    def __getitem__(self, i):
        memo = self._txn_memo.get(i)
        if memo is not None:
            # per-txn decode memo: report_conflicting_keys (and the
            # repair engine's repeated access behind it) hits each
            # failed index more than once — never re-parse the blobs
            return memo
        from foundationdb_tpu.resolver.skiplist import TxnRequest

        W4 = entry_width(self.num_limbs)
        po = (int(self.prc[:i].sum()), int(self.pwc[:i].sum()))
        ro = (int(self.rrc[:i].sum()), int(self.rwc[:i].sum()))
        pr = _decode_entries(
            self.pr_blob[po[0] * W4: (po[0] + int(self.prc[i])) * W4],
            self.num_limbs)
        pw = _decode_entries(
            self.pw_blob[po[1] * W4: (po[1] + int(self.pwc[i])) * W4],
            self.num_limbs)
        rr = decode_side(b"",
                         self.rr_blob[ro[0] * 2 * W4:
                                      (ro[0] + int(self.rrc[i])) * 2 * W4],
                         self.num_limbs)
        rw = decode_side(b"",
                         self.rw_blob[ro[1] * 2 * W4:
                                      (ro[1] + int(self.rwc[i])) * 2 * W4],
                         self.num_limbs)
        out = self._txn_memo[i] = TxnRequest(
            read_version=int(self.rv[i]),
            point_reads=pr, point_writes=pw,
            range_reads=rr, range_writes=rw,
        )
        return out

    def to_txn_requests(self):
        """The whole batch as legacy TxnRequests (the rare-path escape
        hatch; per-key Python, so callers reserve it for batches the
        flat path can't serve)."""
        return [self[i] for i in range(len(self))]


def build_flat_batch(requests, num_limbs, idmp_key_of=None):
    """Concatenate a request batch's FlatConflicts into one columnar
    FlatTxnBatch — the proxy's flat twin of ``_build_txns``. Returns
    None when any request lacks a matching-width FlatConflicts (the
    caller falls back to the legacy build).

    ``idmp_key_of(request)`` returns the idempotency system row an
    id-carrying request must conflict on (or None); its point entry is
    appended to BOTH sides, mirroring legacy ``_idmp_point``."""
    n = len(requests)
    if n == 0:
        z = np.zeros(0, dtype=np.int64)
        return FlatTxnBatch(num_limbs, z, z, z, z, z, b"", b"", b"", b"")
    fcs = [r.flat_conflicts for r in requests]
    if None in fcs:
        return None
    has_ids = any(
        getattr(r, "idempotency_id", None) is not None for r in requests
    ) and idmp_key_of is not None
    if not has_ids:
        # the hot shape: unzip every column with ONE C-speed zip, no
        # per-request Python beyond the comprehension above
        (nls, rps, rpbs, rrs, rrbs, wps, wpbs, wrs, wrbs) = zip(*fcs)
        if any(nl != num_limbs for nl in nls):
            return None
        rv = np.fromiter(
            (r.read_version for r in requests), dtype=np.int64, count=n
        )
        return FlatTxnBatch(
            num_limbs, rv,
            np.fromiter(rps, np.int64, count=n),
            np.fromiter(wps, np.int64, count=n),
            np.fromiter(rrs, np.int64, count=n),
            np.fromiter(wrs, np.int64, count=n),
            b"".join(rpbs), b"".join(wpbs),
            b"".join(rrbs), b"".join(wrbs),
        )
    prc = np.empty(n, dtype=np.int64)
    pwc = np.empty(n, dtype=np.int64)
    rrc = np.empty(n, dtype=np.int64)
    rwc = np.empty(n, dtype=np.int64)
    rv = np.empty(n, dtype=np.int64)
    pr_parts = []
    pw_parts = []
    rr_parts = []
    rw_parts = []
    for i, r in enumerate(requests):
        f = r.flat_conflicts
        if f.num_limbs != num_limbs:
            return None
        ik = idmp_key_of(r)
        if ik is None:
            prc[i] = f.read_points
            pwc[i] = f.write_points
            pr_parts.append(f.read_point_blob)
            pw_parts.append(f.write_point_blob)
        else:
            e = encode_entry(ik, num_limbs)
            if e is None:
                return None  # over-capacity idmp key: legacy path
            prc[i] = f.read_points + 1
            pwc[i] = f.write_points + 1
            pr_parts.append(f.read_point_blob + e)
            pw_parts.append(f.write_point_blob + e)
        rrc[i] = f.read_ranges
        rwc[i] = f.write_ranges
        rr_parts.append(f.read_range_blob)
        rw_parts.append(f.write_range_blob)
        rv[i] = r.read_version
    return FlatTxnBatch(
        num_limbs, rv, prc, pwc, rrc, rwc,
        b"".join(pr_parts), b"".join(pw_parts),
        b"".join(rr_parts), b"".join(rw_parts),
    )
