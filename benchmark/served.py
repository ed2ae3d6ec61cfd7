"""The server child: the one process of a run that holds the chip.

It is ``foundationdb_tpu.tools.fdbserver.main`` on its main thread (the
server installs signal handlers), with the flags the cell's config
gives. Without ``--trace-dir`` it starts no thread of its own: the
untraced run is the plain server. With it, one daemon thread waits for
``<trace-dir>/trace.go``, traces the seconds asked for with
``jax.profiler``, fetches the status document at both ends of them (as
an RPC client of this very server: the same surface any operator has),
and after ``<trace-dir>/trace.reduce`` appears reduces the trace here —
only this process can, it has JAX — into ``<trace-dir>/trace.json``.

When the server has shut down (SIGTERM), the device as JAX reports it
and the peak of its memory go to ``--device-json``.

``--fault`` breaks a guarantee underneath the served path, for the
control and the fault tests only (``faults.py``); the result line of
such a run names the fault.
"""

import argparse
import json
import os
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.dirname(HERE)]


def write_json(path, doc):
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(doc, f)
    os.replace(tmp, path)


def wait_for(path, poll=0.01):
    while not os.path.exists(path):
        time.sleep(poll)
    with open(path) as f:
        text = f.read()
    return json.loads(text) if text else {}


def tracer(trace_dir, cluster_file):
    """Trace the seconds ``trace.go`` asks for; see the module text."""
    import jax

    import foundationdb_tpu as fdb
    import hostspans
    import tracereduce

    go = wait_for(os.path.join(trace_dir, "trace.go"))
    out = {"error": None}
    try:
        db = fdb.open(cluster_file=cluster_file)
        xplane = os.path.join(trace_dir, "xplane")
        t_req = time.monotonic()
        # device planes are what the reduction reads: the Python tracer
        # is off (it slows the server it measures and makes stop_trace
        # as long as the trace), the host tracer keeps annotations only
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        jax.profiler.start_trace(xplane, profiler_options=options)
        # the traced span is [t0, t1], start_trace's return to
        # stop_trace's call: what the profiler records; the status
        # documents are fetched inside it, at its two ends
        t0 = time.monotonic()
        status0 = db.status()
        time.sleep(max(0.0, go["t_stop"] - time.monotonic()))
        status1 = db.status()
        t1 = time.monotonic()
        jax.profiler.stop_trace()
        t_stopped = time.monotonic()
        db._cluster.close()
        wait_for(os.path.join(trace_dir, "trace.reduce"))
        events, seen = tracereduce.load_events(xplane)
        out.update(tracereduce.reduce_events(
            events, t1 - t0, host_spans=hostspans.load(xplane)))
        out.update(status0=status0, status1=status1, t0=t0, t1=t1,
                   start_trace_s=t0 - t_req, stop_trace_s=t_stopped - t1,
                   reduce_s=time.monotonic() - t_stopped, planes=seen,
                   events=len(events))
    except Exception as e:  # the parent reports it and fails the run
        out["error"] = f"{type(e).__name__}: {e}"
    write_json(os.path.join(trace_dir, "trace.json"), out)


def device_doc():
    import jax

    devices = jax.devices()
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": max(peaks)}


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--trace-dir")
    p.add_argument("--device-json", required=True)
    p.add_argument("--fault")
    args, server_argv = p.parse_known_args(argv)

    from foundationdb_tpu.tools import fdbserver

    if args.fault:
        import faults

        faults.FAULTS[args.fault]()
    if args.trace_dir:
        cf = server_argv[server_argv.index("--cluster-file") + 1]
        threading.Thread(target=tracer, args=(args.trace_dir, cf),
                         daemon=True, name="bench-tracer").start()
    rc = fdbserver.main(server_argv)
    write_json(args.device_json, device_doc())
    return rc


if __name__ == "__main__":
    sys.exit(main())
