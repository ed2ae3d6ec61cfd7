"""Per-layer metric readers. A metric is one file
``metrics/<name>.json`` = {layer, unit, better, source, moves, reader,
args, workloads}; ``reader`` names a function of this table and ``args``
are its parameters (a new kind of reader comes with a ``benchmark``
PR, as a new kind of step or of value does). A reader takes the run's evidence and returns a
number, or ``None`` where it found nothing to read — the harness then
leaves the metric out of the line; it never invents a 0.

Evidence (``ev``): ``status0`` / ``status1``, the status documents at
the two ends of the traced seconds (fetched by the tracer thread, so
they span exactly what the trace spans); ``ops``, the clients' log
rows of the operations acknowledged in the window (``client.py``'s
layout: [kind, t_begin, t_ack, …, reads, writes] and, where the
operation read ranges, those ranges); ``trace``, what
``tracereduce.reduce_events`` returned; ``peaks``, the entry of
``peaks.json`` for this device kind; ``config``, the cell's config.
"""


import math

import hostspans


def percentile(sorted_values, q):
    """Nearest rank: the smallest value with a share ``q`` at or below."""
    return sorted_values[max(0, math.ceil(q * len(sorted_values)) - 1)]


def dig(doc, path):
    """``"cluster.device.aggregate.dispatches"`` → the value, or None;
    a number in the path indexes a list (``…logs.replicas.0.metrics``).
    A dict at the end of the path is summed over its numeric values
    (``bucket_histogram``, ``kernel_routes``)."""
    for part in path.split("."):
        if isinstance(doc, list) and part.isdigit() and int(part) < len(doc):
            doc = doc[int(part)]
        elif isinstance(doc, dict) and part in doc:
            doc = doc[part]
        else:
            return None
    if isinstance(doc, dict):
        doc = sum(v for v in doc.values() if isinstance(v, (int, float)))
    return doc


def _delta(ev, paths):
    total = 0.0
    for path in paths:
        a, b = dig(ev["status0"], path), dig(ev["status1"], path)
        if a is None or b is None:
            return None
        total += b - a
    return total


def status_delta_mean(ev, count, mean):
    """The mean of the samples a latency band took in the traced
    seconds alone, from its ``count`` and its running ``mean`` at both
    ends: (c1·m1 − c0·m0) / (c1 − c0). The status document rounds a
    mean to 0.001 ms, so the result is off by at most 0.0005 ms ·
    (c0 + c1) / (c1 − c0). Nothing where no sample fell inside."""
    c0, c1 = dig(ev["status0"], count), dig(ev["status1"], count)
    m0, m1 = dig(ev["status0"], mean), dig(ev["status1"], mean)
    if None in (c0, c1, m0, m1) or c1 <= c0:
        return None
    return (c1 * m1 - c0 * m0) / (c1 - c0)


def status_delta_ratio(ev, num, den, scale=1.0, one_minus=False):
    """scale · Δ(sum of ``num``) / Δ(sum of ``den``) over the traced
    seconds; ``one_minus`` gives scale · (1 − ratio). Nothing where the
    denominator did not move."""
    n, d = _delta(ev, num), _delta(ev, den)
    if n is None or not d:
        return None
    r = n / d
    return scale * (1.0 - r if one_minus else r)


def _programs(ev, names):
    progs = (ev.get("trace") or {}).get("programs", {})
    hit = [progs[n] for n in names if n in progs]
    count = sum(p["count"] for p in hit)
    return count, sum(p["total_s"] for p in hit)


def trace_program_mean_ms(ev, programs):
    """Mean device duration of one execution of the named programs."""
    count, total = _programs(ev, programs)
    return 1e3 * total / count if count else None


def trace_idle_share(ev):
    """1 − busy / traced seconds, from the device operations' union."""
    tr = ev.get("trace") or {}
    if "busy_s" not in tr or not tr.get("window_s"):
        return None
    return 1.0 - tr["busy_s"] / tr["window_s"]


def conflict_bytes(ev, key_limbs):
    """Bytes the real (unpadded) conflict ranges of the traced seconds
    need, whatever program checks them: with L = key_limbs, a point
    read 4L+4 (key in, one history word read), a point write 4L+8 (key
    in, history word read and written), a range read or write 8L+8, and
    4 bytes of verdict a transaction."""
    base = "cluster.device.aggregate."
    live = {side: _delta(ev, [base + "entries_live." + side])
            for side in ("pr", "pw", "rr", "rw")}
    txns = _delta(ev, [base + "txns_live"])
    if txns is None or None in live.values():
        return None
    L = key_limbs
    return (live["pr"] * (4 * L + 4) + live["pw"] * (4 * L + 8)
            + (live["rr"] + live["rw"]) * (8 * L + 8) + txns * 4)


def resolve_roofline_pct(ev, programs, key_limbs):
    """100 · (bytes needed / peak HBM bytes/s) / Σ device time of the
    resolve programs over the same seconds. HBM-bound: the integer
    work of a conflict check is below the bytes it moves."""
    count, total = _programs(ev, programs)
    need = conflict_bytes(ev, key_limbs)
    if not count or not total or not need:
        return None
    return 100.0 * (need / ev["peaks"]["hbm_bytes_per_s"]) / total


def client_latency_ms(ev, writes, q):
    """Quantile ``q`` of first attempt → acknowledgement over the
    window's updates (``writes``) or read-only transactions, retries
    included: the run's own clients, in the traced run."""
    ms = sorted(1e3 * (op[2] - op[1]) for op in ev["ops"]
                if bool(op[9]) == writes)
    return percentile(ms, q) if ms else None


def client_range_rows_mean(ev):
    """Mean number of rows a range read returned, over every range
    read of the window's operations: that the mix sent is the mix
    stated (YCSB-E's scans of 1–100 records: 50.5 less what the
    table's end cuts off). Nothing where no operation read a range."""
    rows = [len(r[2]) for op in ev["ops"] if len(op) > 10 for r in op[10]]
    return sum(rows) / len(rows) if rows else None


def trace_idle_name_pct(ev, prefixes):
    """100 · the device's idle seconds under the host annotations that
    start with ``prefixes`` / all its idle seconds
    (``hostspans.idle_name_pct`` over ``name_gaps``' ``idle_by_name``).
    Nothing where the trace carried no ``fdb.*`` annotation or no
    device plane (a CPU)."""
    return hostspans.idle_name_pct(ev.get("trace"), prefixes)


READERS = {
    "client_latency_ms": client_latency_ms,
    "client_range_rows_mean": client_range_rows_mean,
    "trace_idle_name_pct": trace_idle_name_pct,
    "status_delta_mean": status_delta_mean,
    "status_delta_ratio": status_delta_ratio,
    "trace_program_mean_ms": trace_program_mean_ms,
    "trace_idle_share": trace_idle_share,
    "resolve_roofline_pct": resolve_roofline_pct,
}


def read_metric(spec, ev):
    return READERS[spec["reader"]](ev, **spec.get("args", {}))
