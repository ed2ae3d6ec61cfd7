"""Names for the device's idle time: the ``fdb.*`` host annotations of
a profiler trace, laid over the idle gaps of the device planes.

The program's ``utils/span.stage`` annotates every layer boundary of
the served request path as ``fdb.<stage>`` (``jax.profiler.
TraceAnnotation``, installed by ``deviceprofile.enter_process`` in the
server process). They land on the host plane of the same ``.xplane.pb``
as the device planes, on one clock. ``load`` reads them (it needs JAX,
so it runs in the server child, like ``tracereduce.load_events``);
everything else here is plain arithmetic over tuples, like
``tracereduce.reduce_events``, and is what the tests check against
hand-computed cases.

At every instant of an idle gap exactly one name holds, by precedence:

1. the innermost open commit-path stage on any thread (``fdb.resolver.*``,
   ``fdb.commit.*``, ``fdb.batcher.*``, ``fdb.tlog.push``,
   ``fdb.storage.apply``) — of several, the one opened last: on one
   thread that is the innermost, across threads the most recent;
2. else ``fdb.rpc.commit``, if a commit handler is open (its lock wait
   and its reply, around the stages);
3. else ``fdb.rpc.read`` / ``fdb.rpc.grv`` / ``fdb.rpc.admin``, the one
   opened last;
4. else ``host.no_request``: no request is inside the server.

A trace that holds no ``fdb.*`` event at all (the parent commit's
program, which has no annotation) keeps ``host.unattributed``.

``served.py`` hands ``load``'s spans to ``tracereduce.reduce_events``,
which ends in ``name_gaps``; ``readers.trace_idle_name_pct`` reads
``idle_by_name`` through ``idle_name_pct``.
"""

import glob
import os
import re

import tracereduce

HOST_PLANE = re.compile(r"^/host:")
PREFIX = "fdb."
COMMIT_PATH = ("fdb.resolver.", "fdb.commit.", "fdb.batcher.",
               "fdb.tlog.push", "fdb.storage.apply")
RPC_COMMIT = "fdb.rpc.commit"
RPC_PREFIX = "fdb.rpc."
NO_REQUEST = "host.no_request"
ENQUEUE = "fdb.resolver.enqueue"
READBACK = "fdb.resolver.readback"
# what the host planes' clock runs ahead of the device planes' on this
# machine, subtracted by ``load``: 0 — measured on the v5e, the resolve
# programs lie inside their dispatch's host spans (PERF.md §3)
CLOCK_OFFSET_NS = 0


def load(trace_dir, offset_ns=CLOCK_OFFSET_NS):
    """Every host-plane event named ``fdb.*`` of the newest trace under
    ``trace_dir`` → [(thread, name, start_ns, duration_ns)]. A thread
    is a line of a host plane; lines share names (``python``), so the
    plane and the line's position make it unique."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        return []
    spans = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        if not HOST_PLANE.match(plane.name):
            continue
        for i, line in enumerate(plane.lines):
            thread = f"{plane.name}/{line.name}#{i}"
            spans.extend(
                (thread, e.name, int(e.start_ns) - offset_ns,
                 int(e.duration_ns))
                for e in line.events if e.name.startswith(PREFIX))
    return spans


def _rank(name):
    """Lower wins: 0 commit-path stage, 1 commit handler, 2 another
    handler; None for a name that names no idle time (``fdb.grv.grant``
    lies inside its handler and is not on the commit path)."""
    if name.startswith(COMMIT_PATH):
        return 0
    if name == RPC_COMMIT:
        return 1
    if name.startswith(RPC_PREFIX):
        return 2
    return None


def attribute(gaps, spans):
    """``gaps``: [(start_ns, end_ns)] idle stretches of a device;
    ``spans``: what ``load`` returned. → for each gap, {name: seconds
    of it under that name} by the precedence of the module text; the
    values of one gap sum to its length."""
    out = [{} for _ in gaps]
    ranked = [(s, s + d, r, n) for _t, n, s, d in spans
              for r in (_rank(n),) if r is not None and d > 0]
    # one sweep over every boundary: (time, order, ...) with closes
    # before opens at one instant, so a span that ends where the next
    # begins is never counted as overlapping it
    points = []
    for i, (s, e, _r, _n) in enumerate(ranked):
        points.append((s, 1, i))
        points.append((e, 0, i))
    for g, (a, b) in enumerate(gaps):
        if b > a:
            points.append((a, 3, g))
            points.append((b, 2, g))
    points.sort()
    open_spans, open_gaps, last = set(), set(), None
    for t, kind, idx in points:
        if open_gaps and last is not None and t > last:
            if open_spans:
                # lowest rank, then the latest opened, then the name
                name = min((ranked[i][2], -ranked[i][0], ranked[i][3])
                           for i in open_spans)[2]
            else:
                name = NO_REQUEST
            for g in open_gaps:
                out[g][name] = out[g].get(name, 0.0) + (t - last) / 1e9
        last = t
        if kind == 1:
            open_spans.add(idx)
        elif kind == 0:
            open_spans.discard(idx)
        elif kind == 3:
            open_gaps.add(idx)
        else:
            open_gaps.discard(idx)
    return out


def device_gaps(events):
    """The idle stretches of every device plane, as ``reduce_events``
    takes them (between two merged busy intervals of one device) →
    [(start_ns, end_ns)]."""
    by_plane = {}
    for plane, line, _name, start, dur in events:
        if line == tracereduce.OPS_LINE:
            by_plane.setdefault(plane, []).append((start, start + dur))
    gaps = []
    for intervals in by_plane.values():
        merged = tracereduce._union(intervals)
        gaps.extend((a[1], b[0]) for a, b in zip(merged, merged[1:]))
    return gaps


def name_gaps(out, events, spans):
    """Give ``reduce_events``' output ``out`` its names, in place: each
    of the ten ``idle_gaps`` carries the name that covers most of it,
    and ``idle_by_name`` the seconds of ALL idle time by name. Without
    a single ``fdb.*`` span nothing changes (``host.unattributed``)."""
    if not spans:
        return out
    gaps = device_gaps(events)
    named = attribute(gaps, spans)
    total = {}
    for by in named:
        for name, s in by.items():
            total[name] = total.get(name, 0.0) + s
    top = sorted(range(len(gaps)),
                 key=lambda g: gaps[g][0] - gaps[g][1])[:10]
    out["idle_gaps"] = [
        [min(named[g], key=lambda n: (-named[g][n], n)),
         (gaps[g][1] - gaps[g][0]) / 1e9] for g in top]
    out["idle_by_name"] = dict(sorted(total.items(),
                                      key=lambda kv: (-kv[1], kv[0])))
    return out


def idle_name_pct(trace, prefixes):
    """100 · Σ ``idle_by_name`` under ``prefixes`` / Σ ``idle_by_name``
    — the reader ``trace_idle_name_pct``; None without the names."""
    by = (trace or {}).get("idle_by_name")
    if not by or not sum(by.values()):
        return None
    hit = sum(s for n, s in by.items() if n.startswith(tuple(prefixes)))
    return 100.0 * hit / sum(by.values())


def clock_check(events, spans):
    """Do the host planes and the device planes share a clock? A
    resolve program runs between its dispatch's enqueue (the jitted
    call) and the end of its readback. → {"modules", "inside",
    "inside_share", "median_offset_us": module start − enqueue start,
    over the modules that lie inside}; the share is None without
    modules or without dispatch spans."""
    by_thread = {}
    for thread, name, start, dur in spans:
        if name in (ENQUEUE, READBACK):
            by_thread.setdefault(thread, []).append((start, name, dur))
    windows = []
    for marks in by_thread.values():
        marks.sort()
        for (s0, n0, _d0), (s1, n1, d1) in zip(marks, marks[1:]):
            if n0 == ENQUEUE and n1 == READBACK:
                windows.append((s0, s1 + d1))
    windows.sort()
    modules = sorted((s, s + d) for _p, line, _n, s, d in events
                     if line == tracereduce.MODULES_LINE)
    offsets, w = [], 0
    for start, end in modules:
        while w < len(windows) and windows[w][1] < start:
            w += 1  # this dispatch closed before the module began
        if w < len(windows) and windows[w][0] <= start \
                and end <= windows[w][1]:
            offsets.append((start - windows[w][0]) / 1e3)
    offsets.sort()
    return {
        "modules": len(modules), "dispatches": len(windows),
        "inside": len(offsets),
        "inside_share": len(offsets) / len(modules)
        if modules and windows else None,
        "median_offset_us": offsets[len(offsets) // 2] if offsets else None,
    }
