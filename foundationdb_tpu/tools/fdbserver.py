"""fdbserver-shaped process entry: host a cluster (and a coordinator
replica) behind the RPC transport.

Ref parity: fdbserver/fdbserver.actor.cpp's worker process — started
with a listen address and a data directory, it serves the database to
any client holding the cluster file. Every process also hosts a
coordinator replica (ref: coordinators are fdbserver processes named in
the cluster file); ``--coordinators`` points recovery at a quorum of
peer processes, and ``--coordinator-only`` runs just the replica, so a
deployment looks like the reference's: N coordinator processes + a
transaction-system process, with recovery locking the generation
through a real network majority.

Usage::

    # three coordinators
    python -m foundationdb_tpu.tools.fdbserver --listen 127.0.0.1:4510 \
        --coordinator-only --dir /var/co1   (and 4511, 4512...)
    # the database server, recovering through that quorum
    python -m foundationdb_tpu.tools.fdbserver \
        --listen 127.0.0.1:4500 --dir /var/db --cluster-file fdb.cluster \
        --coordinators 127.0.0.1:4510,127.0.0.1:4511,127.0.0.1:4512

The cluster file is (re)written with this server's address on startup,
so `foundationdb_tpu.open(cluster_file=...)` finds it.
"""

import argparse
import os
import signal
import sys
import threading

from foundationdb_tpu.core.options import Knobs
from foundationdb_tpu.rpc.coordination import CoordinatorService, remote_quorum
from foundationdb_tpu.rpc.service import (
    ClusterService,
    write_cluster_file,
)
from foundationdb_tpu.rpc.transport import RpcServer
from foundationdb_tpu.utils.trace import TraceEvent


def build_cluster(args, coordination=None):
    from foundationdb_tpu.server.cluster import Cluster

    kw = {}
    if args.dir:
        os.makedirs(args.dir, exist_ok=True)
        kw["wal_path"] = os.path.join(args.dir, "tlog.wal")
        if coordination is None:
            kw["coordination_dir"] = os.path.join(args.dir, "coordination")
    engine = getattr(args, "storage_engine", None)
    if engine:
        from foundationdb_tpu.server.kvstore import open_engine

        if not args.dir and engine != "memory":
            raise SystemExit(f"--storage-engine {engine} requires --dir")
        base = os.path.join(args.dir, "store") if args.dir else None
        kw["storage_engines"] = [
            open_engine(engine, None if base is None else f"{base}.{i}")
            for i in range(args.storage)
        ]
    return Cluster(
        n_storage=args.storage,
        n_resolvers=args.resolvers,
        n_commit_proxies=args.commit_proxies,
        n_tlogs=args.tlogs,
        replication=args.replication,
        fsync=args.fsync,
        commit_pipeline=args.commit_pipeline,
        resolver_backend=args.resolver_backend,
        coordination=coordination,
        **kw,
    )


def main(argv=None):
    p = argparse.ArgumentParser(prog="fdbserver")
    p.add_argument("--listen", default="127.0.0.1:0",
                   help="host:port to listen on (port 0 = ephemeral)")
    p.add_argument("--cluster-file", default=None,
                   help="cluster file to write this server's address into")
    p.add_argument("--dir", default=None, help="data directory (WAL, paxos)")
    p.add_argument("--coordinators", default=None,
                   help="comma-separated coordinator addresses; recovery "
                        "locks its generation through this quorum")
    p.add_argument("--coordinator-only", action="store_true",
                   help="host only the coordinator replica (no database)")
    p.add_argument("--join", default=None, metavar="LEAD",
                   help="run as a storage-worker process: pull the "
                        "mutation stream from the lead server at this "
                        "address and serve versioned reads")
    p.add_argument("--tag", type=int, default=None,
                   help="with --join: subscribe to ONE storage tag's "
                        "log stream and serve only its owned ranges "
                        "(tag-partitioned log; default: full stream)")
    p.add_argument("--storage", type=int, default=1)
    p.add_argument("--storage-engine", default=None,
                   choices=["memory", "sqlite", "versioned", "redwood"],
                   help="persistent engine beneath each storage server "
                        "(ref: `configure ssd|memory`; redwood = the "
                        "disk-resident versioned engine; disk kinds "
                        "need --dir)")
    p.add_argument("--resolvers", type=int, default=1)
    p.add_argument("--commit-proxies", type=int, default=1,
                   help="commit-proxy fleet size (sequencer-chained "
                        "version grants; ref: the proxy count in "
                        "`configure`)")
    p.add_argument("--tlogs", type=int, default=1)
    p.add_argument("--replication", type=int, default=None)
    p.add_argument("--fsync", action="store_true")
    p.add_argument("--commit-pipeline", default="thread",
                   choices=["sync", "manual", "thread"],
                   help="thread = cross-client commit/GRV batching (default)")
    p.add_argument("--resolver-backend", default="cpu",
                   choices=["tpu", "cpu", "native"])
    p.add_argument("--monitor-interval", type=float, default=0.5,
                   help="failure-detection round interval, seconds")
    p.add_argument("--auth-secret", default=None,
                   help="shared secret for the transport handshake; every "
                        "process and client of the cluster must use the "
                        "same one (defaults to $FDB_TPU_AUTH_SECRET)")
    p.add_argument("--switch-interval", type=float, default=None,
                   metavar="SECONDS",
                   help="CPython thread switch interval for this server "
                        "process (default: the server_switch_interval_s "
                        "knob; 0 keeps the interpreter default)")
    args = p.parse_args(argv)
    secret = args.auth_secret or os.environ.get("FDB_TPU_AUTH_SECRET")

    # Read-RPC latency under commit load: CPython schedules a waiting
    # thread only every sys.getswitchinterval() (default 5ms), so a
    # read RPC landing while a commit batch holds this process's GIL
    # waits out the slice; what is left at a shorter interval is the
    # convoy on both ends of the synchronous read (what a read waits
    # for on the chip: PERF.md, rpc.read.queue_wait_ms).
    # Tunable as the server_switch_interval_s knob / --switch-interval.
    switch_s = args.switch_interval
    if switch_s is None:
        switch_s = Knobs().server_switch_interval_s
    if switch_s > 0:
        sys.setswitchinterval(switch_s)

    host, _, port = args.listen.rpartition(":")
    if secret is None and host not in ("", "127.0.0.1", "localhost",
                                       "::1", "[::1]"):
        print(
            "warning: --listen on a non-loopback interface without "
            "--auth-secret exposes unauthenticated read/write/management "
            "access to anyone who can reach the port",
            file=sys.stderr, flush=True,
        )

    if args.join:
        # storage-worker process: no coordinator, no local cluster —
        # a local store fed by pulling the lead's log (ref: a storage
        # process's update loop pulling its tag from the TLogs)
        from foundationdb_tpu.rpc.storageworker import StorageWorker

        worker = StorageWorker(args.join, secret=secret,
                               tag=args.tag).start()
        worker.wait_caught_up()
        server = worker.serve(host or "127.0.0.1", int(port))
        stop = threading.Event()
        signal.signal(signal.SIGTERM, lambda s, f: stop.set())
        signal.signal(signal.SIGINT, lambda s, f: stop.set())
        print(f"FDBD listening on {server.address} (storage-worker)",
              flush=True)
        TraceEvent("FdbServerUp").detail(
            address=server.address, role="storage-worker",
            pid=os.getpid()).log()
        stop.wait()
        server.close()
        worker.close()
        return 0

    # coordinator endpoints come up FIRST: peer recoveries must be able
    # to reach this replica before (and regardless of) any local cluster
    coord_path = None
    if args.dir:
        os.makedirs(args.dir, exist_ok=True)
        coord_path = os.path.join(args.dir, "coordinator.json")
    coord = CoordinatorService(coord_path)
    server = RpcServer(host or "127.0.0.1", int(port), coord.handlers(),
                       secret=secret)

    cluster = None
    if args.coordinator_only and args.cluster_file:
        print(
            "warning: --cluster-file is ignored with --coordinator-only "
            "(clients connect to a database server, not a coordinator)",
            file=sys.stderr, flush=True,
        )
    if not args.coordinator_only:
        coordination = None
        if args.coordinators:
            coordination = remote_quorum(
                [a.strip() for a in args.coordinators.split(",")],
                secret=secret,
            )
        from foundationdb_tpu.utils import deviceprofile

        deviceprofile.enter_process()  # compile cache + build counts
        cluster = build_cluster(args, coordination)
        service = ClusterService(cluster)
        service.rpc_server = server
        server.add_handlers(service.handlers(),
                            long_methods=service.LONG_METHODS,
                            inline_methods=service.inline_methods())
        # log-feed endpoints so --join storage-worker processes can pull
        from foundationdb_tpu.rpc.storageworker import LogFeed

        server.add_handlers(LogFeed(cluster).handlers(),
                            long_methods={"tlog_peek"})
        if args.cluster_file:
            write_cluster_file(args.cluster_file, [server.address])

    stop = threading.Event()

    def _shutdown(signum, frame):
        stop.set()

    signal.signal(signal.SIGTERM, _shutdown)
    signal.signal(signal.SIGINT, _shutdown)

    role = "coordinator" if args.coordinator_only else "fdbserver"
    print(f"FDBD listening on {server.address} ({role})", flush=True)
    TraceEvent("FdbServerUp").detail(
        address=server.address, role=role, pid=os.getpid()).log()
    # the operator loop the simulation normally pumps: failure detection
    # + recruitment (ref: ClusterController's failureDetectionServer)
    while not stop.wait(args.monitor_interval):
        if cluster is None:
            continue
        try:
            cluster.detect_and_recruit()
        except Exception as e:  # keep serving; log the monitor hiccup
            TraceEvent("FailureMonitorError", severity=30).detail(
                error=repr(e)).log()

    server.close()
    if cluster is not None:
        cluster.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
