"""From a profiler trace to the numbers the per-layer readers take.

``load_events`` reads an ``.xplane.pb`` (it needs JAX, so it runs in the
server child, the one process that holds the chip); ``reduce_events``
is plain arithmetic over (plane, line, name, start_ns, duration_ns)
tuples and is what the tests check by hand-computed values.

On a TPU the device plane ``/device:TPU:<n>`` carries a line ``XLA Ops``
(every operation that ran on the device: the union of its intervals is
the busy time) and a line ``XLA Modules`` (one event for each execution
of a compiled program, named ``<program>(<fingerprint>)``). A CPU
rehearsal has no device plane, and the reduction then returns no device
numbers at all: the readers leave those metrics out.
"""

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
GAP_NAME = "host.unattributed"  # a trace without a single fdb.* annotation


def load_events(trace_dir):
    """Every event of the device planes of the newest trace under
    ``trace_dir`` → (events, {plane: {line: [events, a few names]}})."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        return [], {}
    data = ProfileData.from_file(paths[-1])
    events, seen = [], {}
    for plane in data.planes:
        lines = seen.setdefault(plane.name, {})
        device = bool(DEVICE_PLANE.match(plane.name))
        for line in plane.lines:
            got = [(plane.name, line.name, e.name, e.start_ns, e.duration_ns)
                   for e in line.events]
            # for the reader of a first trace: how many, and what like
            lines[line.name] = [len(got), sorted({g[2] for g in got[:200]})[:8]]
            if device and line.name in (OPS_LINE, MODULES_LINE):
                events.extend(got)
    return events, seen


def program_name(event_name):
    """``jit_scan_step(1234567)`` → ``jit_scan_step``."""
    return re.sub(r"\(\d+\)$", "", event_name)


def _union(intervals):
    """Merged [start, end) intervals, sorted."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return merged


def reduce_events(events, window_s, host_spans=None):
    """→ {"window_s", "busy_s", "programs": {name: {"count",
    "total_s"}}, "device_ops": [[name, s]…], "idle_gaps": [[name, s]…],
    "events_span_s": first start to last end of what was recorded}.
    With ``host_spans`` (``hostspans.load``) each of the ten gaps
    carries the name of what the host was doing for most of it, and
    ``idle_by_name`` holds the seconds of all idle time by that name.

    ``window_s`` is the traced span: from ``start_trace``'s return to
    ``stop_trace``'s call, which is when the profiler records (on the
    v5e the first event lay 6 ms after the one and the last ended at
    the other, PERF.md §3; ``events_span_s`` is there to show it).
    ``busy_s`` is the union of the device operations' intervals,
    averaged over the device planes seen; with no device operation in
    the trace there is no ``busy_s`` (never 0). ``idle_gaps`` are the
    longest stretches between two busy intervals of one device."""
    out = {"window_s": window_s, "programs": {}, "device_ops": [],
           "idle_gaps": []}
    if events:
        out["events_span_s"] = (max(e[3] + e[4] for e in events)
                                - min(e[3] for e in events)) / 1e9
    by_plane, programs, ops = {}, {}, {}
    for plane, line, name, start, dur in events:
        if line == OPS_LINE:
            by_plane.setdefault(plane, []).append((start, start + dur))
            ops[name] = ops.get(name, 0) + dur
        elif line == MODULES_LINE:
            p = programs.setdefault(program_name(name),
                                    {"count": 0, "total_s": 0.0})
            p["count"] += 1
            p["total_s"] += dur / 1e9
    out["programs"] = programs
    if not by_plane:
        return out
    busy, gaps = 0.0, []
    for intervals in by_plane.values():
        merged = _union(intervals)
        busy += sum(e - s for s, e in merged) / 1e9
        gaps.extend((b[0] - a[1]) / 1e9 for a, b in zip(merged, merged[1:]))
    out["busy_s"] = busy / len(by_plane)
    top = sorted(ops.items(), key=lambda kv: -kv[1])[:10]
    out["device_ops"] = [[name, ns / 1e9] for name, ns in top]
    out["idle_gaps"] = [[GAP_NAME, g] for g in sorted(gaps, reverse=True)[:10]]
    if host_spans:
        import hostspans  # it imports this module

        hostspans.name_gaps(out, events, host_spans)
    return out
