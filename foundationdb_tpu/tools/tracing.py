"""Critical-path analysis over emitted Span events.

The offline half of the distributed-tracing subsystem (utils/span.py):
finished spans land as ``type="Span"`` JSON lines in the ordinary trace
files (rolled like everything else), and this tool reconstructs the
span trees and answers "where did the slow commits spend their time" —
per-hop count/p50/p99/total, the hottest parent→child EDGE by total
wall time, and the hottest pipeline STAGE (the ``stage.*`` spans mirror
server/batcher.py's StageStats split, so the attribution here is
cross-checkable against status json's ``hottest_stage`` rollup).

Usage::

    python -m foundationdb_tpu.tools.tracing trace.json

Rolled siblings are stitched automatically: the rolling file sink
(utils/trace.py) rotates ``path`` → ``path.1`` → … → ``path.N`` with
``path.N`` the oldest, so giving the live path reads the WHOLE history
oldest-first instead of silently analyzing only the newest fragment.

Programmatically: ``report(spans)`` over ``load_spans(...)`` /
in-memory ``events("Span")`` dicts from a TraceLog ring buffer.
"""

import json
import os
import sys

STAGE_PREFIX = "stage."


def rolled_files(path):
    """The rolled family of a live trace path, oldest first:
    ``path.N … path.1 path`` (the rolling sink shifts contiguously, so
    the scan stops at the first missing index). A path with no rolls —
    or an explicitly-given ``path.K`` sibling — returns just itself."""
    rolls = []
    i = 1
    while os.path.exists(f"{path}.{i}"):
        rolls.append(f"{path}.{i}")
        i += 1
    out = list(reversed(rolls))
    if os.path.exists(path) or not out:
        out.append(path)
    return out


def stitch(paths):
    """Expand each given path through its rolled family, deduplicated
    and ordered oldest-first per family."""
    out = []
    for p in paths:
        for q in rolled_files(p):
            if q not in out:
                out.append(q)
    return out


def load_spans(paths):
    """Span events from trace files (JSON lines; non-Span and
    unparseable lines are skipped — trace files interleave everything)."""
    spans = []
    for path in paths:
        with open(path) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    ev = json.loads(line)
                except ValueError:
                    continue
                if ev.get("type") == "Span":
                    spans.append(ev)
    return spans


def build_trees(spans):
    """{trace_id: {"spans": {sid: span}, "children": {sid: [sid]},
    "roots": [sid]}} — the per-trace tree index. A span whose parent is
    missing from the capture (sampling started mid-trace, rolled-away
    file) is treated as a root of its own subtree."""
    traces = {}
    for ev in spans:
        t = traces.setdefault(
            ev["trace"], {"spans": {}, "children": {}, "roots": []}
        )
        t["spans"][ev["sid"]] = ev
    for t in traces.values():
        for sid, ev in t["spans"].items():
            parent = ev.get("parent", "0" * 16)
            if parent in t["spans"]:
                t["children"].setdefault(parent, []).append(sid)
            else:
                t["roots"].append(sid)
    return traces


def _percentile(ordered, q):
    if not ordered:
        return 0.0
    i = min(len(ordered) - 1, int(q * (len(ordered) - 1) + 0.5))
    return ordered[i]


def hop_stats(spans):
    """Per span-name latency bands: {name: {count, p50_ms, p99_ms,
    max_ms, total_ms, self_ms}} — the "which hop is slow" table.
    ``self_ms`` is EXCLUSIVE time (duration minus captured direct
    children), the honest per-hop attribution when hops nest."""
    child_sum = {}
    for ev in spans:
        key = (ev["trace"], ev.get("parent"))
        child_sum[key] = child_sum.get(key, 0.0) + ev.get("dur_ms", 0.0)
    by_name = {}
    self_by_name = {}
    for ev in spans:
        name = ev["span"]
        dur = ev.get("dur_ms", 0.0)
        by_name.setdefault(name, []).append(dur)
        own = max(0.0, dur - child_sum.get((ev["trace"], ev["sid"]), 0.0))
        self_by_name[name] = self_by_name.get(name, 0.0) + own
    out = {}
    for name, durs in sorted(by_name.items()):
        durs.sort()
        out[name] = {
            "count": len(durs),
            "p50_ms": round(_percentile(durs, 0.50), 3),
            "p99_ms": round(_percentile(durs, 0.99), 3),
            "max_ms": round(durs[-1], 3),
            "total_ms": round(sum(durs), 3),
            "self_ms": round(self_by_name[name], 3),
        }
    return out


def hottest_edge(spans):
    """The parent→child edge with the most TOTAL child wall time — the
    commit pipeline's critical path as the traces measured it. Returns
    (edge_name, total_ms) or (None, 0.0)."""
    by_sid = {(ev["trace"], ev["sid"]): ev for ev in spans}
    totals = {}
    for ev in spans:
        parent = by_sid.get((ev["trace"], ev.get("parent")))
        if parent is None:
            # a root's duration is the whole trace, not an attribution
            # — only real parent→child edges say WHERE the time went
            continue
        edge = f"{parent['span']}->{ev['span']}"
        totals[edge] = totals.get(edge, 0.0) + ev.get("dur_ms", 0.0)
    if not totals:
        return None, 0.0
    # deterministic tie-break: by total desc, then name
    edge = min(totals, key=lambda e: (-totals[e], e))
    return edge, round(totals[edge], 3)


def hottest_stage(spans):
    """Among the ``stage.*`` spans (the batcher's pack/dispatch/
    resolve/apply split), the stage with the most total wall time —
    comparable 1:1 with status json's ``hottest_stage`` rollup."""
    totals = {}
    for ev in spans:
        name = ev["span"]
        if name.startswith(STAGE_PREFIX):
            stage = name[len(STAGE_PREFIX):]
            totals[stage] = totals.get(stage, 0.0) + ev.get("dur_ms", 0.0)
    if not totals:
        return None
    return min(totals, key=lambda s: (-totals[s], s))


def report(spans):
    """The full analysis document: tree counts, per-hop bands, hottest
    edge/stage, and the single slowest trace's hop breakdown."""
    trees = build_trees(spans)
    edge, edge_ms = hottest_edge(spans)
    slowest = None
    for trace_id, t in trees.items():
        for rid in t["roots"]:
            root = t["spans"][rid]
            if slowest is None or root.get("dur_ms", 0.0) > \
                    slowest[1].get("dur_ms", 0.0):
                slowest = (trace_id, root, t)
    slowest_doc = None
    if slowest is not None:
        trace_id, root, t = slowest
        slowest_doc = {
            "trace": trace_id,
            "root": root["span"],
            "dur_ms": root.get("dur_ms", 0.0),
            "hops": {
                ev["span"]: ev.get("dur_ms", 0.0)
                for ev in t["spans"].values()
            },
        }
    return {
        "spans": len(spans),
        "traces": len(trees),
        "hops": hop_stats(spans),
        "hottest_edge": edge,
        "hottest_edge_total_ms": edge_ms,
        "hottest_stage": hottest_stage(spans),
        "slowest_trace": slowest_doc,
    }


def main(argv=None):
    import argparse

    ap = argparse.ArgumentParser(
        prog="python -m foundationdb_tpu.tools.tracing",
        description="reconstruct span trees from trace files and "
                    "report per-hop latency + critical-path attribution",
    )
    ap.add_argument("files", nargs="+",
                    help="trace files (JSON lines); rolled .1….N "
                         "siblings are stitched in automatically")
    ns = ap.parse_args(argv)
    spans = load_spans(stitch(ns.files))
    print(json.dumps(report(spans), indent=2, sort_keys=True))
    return 0


if __name__ == "__main__":
    sys.exit(main())
