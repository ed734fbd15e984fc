"""The server binary.

Equivalent of cmd/dgraph/main.go: flags (+ optional YAML config merge,
setupConfigOpts:85), storage bring-up, HTTP surface, health gating, and
a clean shutdown path.  The boot order mirrors main:675: open stores →
schema/posting init (implicit in DurableStore) → serving surface →
health OK.
"""

from __future__ import annotations

import argparse
import os
import signal
import sys

from dgraph_tpu.models.wal import DurableStore
from dgraph_tpu.serve.server import DgraphServer
from dgraph_tpu.utils.config import Options


def build_options(argv=None) -> Options:
    p = argparse.ArgumentParser(prog="dgraph-tpu", description=__doc__)
    # YAML is applied BEFORE flags (cmd/dgraph/main.go:164-168): config
    # values become the flag defaults, so explicit flags always win
    pre = argparse.ArgumentParser(add_help=False)
    pre.add_argument("--config", default="")
    pre_ns, _ = pre.parse_known_args(argv)
    d = Options()
    if pre_ns.config:
        d = d.merged_with_yaml(pre_ns.config)
    p.add_argument("--p", dest="postings_dir", default=d.postings_dir,
                   help="directory to store posting state + snapshots")
    p.add_argument("--w", dest="wal_dir", default=d.wal_dir,
                   help="(reserved) separate wal dir; DurableStore keeps wal beside postings")
    p.add_argument("--export", dest="export_path", default=d.export_path)
    p.add_argument("--port", type=int, default=d.port)
    p.add_argument("--grpc_port", type=int, default=d.grpc_port,
                   help="gRPC listener port (protos.Dgraph service); "
                        "0 = http port + 1000, -1 disables")
    p.add_argument("--dumpsg", default=d.dumpsg,
                   help="directory to dump each query's execution-shape "
                        "tree as JSON (offline plan inspection)")
    p.add_argument("--memory_mb", type=int, default=d.memory_mb,
                   help="HBM budget for device arenas in MB (0 = unlimited); "
                        "cold arenas LRU-evict to the host store")
    p.add_argument("--bind", default=d.bind)
    p.add_argument("--sync", dest="sync_writes", action="store_true",
                   default=d.sync_writes)
    p.add_argument("--snapshot_wal_mb", type=float,
                   default=d.snapshot_wal_mb,
                   help="seal+compact the WAL once it passes this many "
                        "MB (0 = env DGRAPH_TPU_SNAPSHOT_WAL_MB or 64)")
    p.add_argument("--snapshot_wal_records", type=int,
                   default=d.snapshot_wal_records,
                   help="seal+compact once this many records are "
                        "journaled (0 = env DGRAPH_TPU_SNAPSHOT_WAL_RECORDS "
                        "or 200000)")
    p.add_argument("--idx", dest="raft_id", type=int, default=d.raft_id)
    p.add_argument("--groups", dest="group_ids", default=d.group_ids)
    p.add_argument("--peer", default=d.peer)
    p.add_argument("--peer_groups", default=d.peer_groups,
                   help='per-peer group placement "1=0,1;2=0,2"; absent '
                        "peers serve every group")
    p.add_argument("--join", default=d.join,
                   help="address of a live cluster member; boot as a "
                        "joining node and acquire membership at runtime")
    p.add_argument("--my", dest="my_addr", default=d.my_addr)
    p.add_argument("--trace", dest="trace_ratio", type=float, default=d.trace_ratio)
    p.add_argument("--expose_trace", action="store_true", default=d.expose_trace)
    p.add_argument("--tls_cert", default=d.tls_cert)
    p.add_argument("--tls_key", default=d.tls_key)
    p.add_argument("--cluster_secret", default=d.cluster_secret,
                   help="shared secret required on intra-cluster endpoints "
                        "(/raft*, /assign-uids); empty disables the gate")
    p.add_argument("--peer_ca", default=d.peer_ca,
                   help="PEM CA bundle to verify peer TLS certs against "
                        "(CA pinning for the raft plane)")
    p.add_argument("--peer_tls_insecure", action="store_true",
                   default=d.peer_tls_insecure,
                   help="explicitly skip peer TLS verification "
                        "(throwaway self-signed clusters only)")
    p.add_argument("--raft_transport", default=d.raft_transport,
                   choices=("http", "grpc"),
                   help="raft frame carrier between servers; grpc uses "
                        "/protos.Worker/RaftMessage at peer http port+1000")
    p.add_argument("--workers", type=int, default=d.workers)
    p.add_argument("--num_pending", type=int, default=d.num_pending)
    p.add_argument("--max_edges", type=int, default=d.max_edges)
    p.add_argument("--config", default="", help="YAML config file (flat key: value)")
    p.add_argument("--cpu", dest="cpu_profile", default=d.cpu_profile,
                   help="write a CPU profile (pstats format) here on "
                        "shutdown (main.go:181 --cpu analog)")
    p.add_argument("--mem", dest="mem_profile", default=d.mem_profile,
                   help="write a memory allocation profile (tracemalloc "
                        "top-50 text) here on shutdown")
    p.add_argument("--compile_cache", default=d.compile_cache,
                   help="persistent XLA compilation cache dir; 'auto' = "
                        "<checkout>/.jax_cache, '' disables; "
                        "JAX_COMPILATION_CACHE_DIR, when set, wins over "
                        "both (repeat cold starts skip the first compile)")
    ns = p.parse_args(argv)
    # start from the YAML-merged defaults so Options fields without a flag
    # survive (previously YAML-only keys like workers were dropped)
    merged = {**d.__dict__, **{k: getattr(ns, k) for k in vars(ns) if k != "config"}}
    return Options(**merged)


def main(argv=None) -> int:
    opts = build_options(argv)
    # snapshot thresholds: explicit flags win over the env (the
    # Snapshotter reads the env at construction — models/durability.py)
    if opts.snapshot_wal_mb:
        os.environ["DGRAPH_TPU_SNAPSHOT_WAL_MB"] = str(opts.snapshot_wal_mb)
    if opts.snapshot_wal_records:
        os.environ["DGRAPH_TPU_SNAPSHOT_WAL_RECORDS"] = str(
            opts.snapshot_wal_records
        )
    # the gRPC listener port this process will bind (0 = http port + 1000)
    grpc_port = (
        -1
        if opts.grpc_port < 0
        else (opts.grpc_port or (opts.port + 1000 if opts.port else 0))
    )
    if opts.raft_transport == "grpc":
        # fail fast: a node whose raft plane is gRPC but that serves no
        # gRPC listener (or lacks grpcio) can neither send nor receive
        # frames — it would boot, never elect, and give no hint why
        if grpc_port <= 0 or opts.port <= 0:
            print(
                "--raft_transport grpc requires explicit --port and an "
                "enabled gRPC listener (--grpc_port >= 0); peers derive "
                "each other's raft targets as http port + the same offset",
                file=sys.stderr,
            )
            return 2
        try:
            import grpc  # noqa: F401
        except ImportError:
            print(
                "--raft_transport grpc requires grpcio, which is not "
                "importable in this environment",
                file=sys.stderr,
            )
            return 2
    # persistent XLA compilation cache: a restarted server re-uses every
    # compiled query shape instead of paying the seconds-long XLA/Mosaic
    # compile again (the reference has no compile step at all, so repeat
    # cold-start parity depends on this)
    from dgraph_tpu.utils import jaxcache

    try:
        jaxcache.configure(opts.compile_cache)
    except OSError as e:
        print(f"warning: compile cache disabled: {e}", file=sys.stderr)
    # profiling surface (setupProfiling, cmd/dgraph/main.go:181).  The
    # CPU profile covers QUERY EXECUTION (enabled per-request under the
    # engine lock — cProfile is per-thread, and a main-thread profiler
    # would only see the idle join loop); tracemalloc covers boot too.
    profiler = None
    if opts.cpu_profile:
        import cProfile

        profiler = cProfile.Profile()
    if opts.mem_profile:
        import tracemalloc

        tracemalloc.start(10)
    cluster = None
    if opts.join and not opts.peer:
        # runtime join: boot passive with only ourselves, then announce
        from dgraph_tpu.cluster.service import ClusterService

        scheme = "https" if opts.tls_cert else "http"
        my_addr = opts.my_addr or f"{scheme}://127.0.0.1:{opts.port}"
        cluster = ClusterService(
            node_id=str(opts.raft_id),
            my_addr=my_addr,
            peers={str(opts.raft_id): my_addr},
            group_ids=[int(g) for g in opts.group_ids.split(",") if g.strip()],
            directory=opts.postings_dir,
            sync_writes=opts.sync_writes,
            secret=opts.cluster_secret,
            peer_ca=opts.peer_ca,
            peer_tls_insecure=opts.peer_tls_insecure,
            raft_transport=opts.raft_transport,
            grpc_port_offset=max(0, grpc_port - opts.port),
            passive=True,
        )
        cluster.start()
        cluster.join_cluster(opts.join)
        store = cluster.store
    elif opts.peer:
        # clustered boot (StartRaftNodes analog): durability lives in the
        # raft logs + snapshots under the postings dir
        from dgraph_tpu.cluster.service import (
            ClusterService,
            parse_peer_groups,
            parse_peers,
        )

        scheme = "https" if opts.tls_cert else "http"
        my_addr = opts.my_addr or f"{scheme}://127.0.0.1:{opts.port}"
        cluster = ClusterService(
            node_id=str(opts.raft_id),
            my_addr=my_addr,
            peers=parse_peers(opts.peer, default_scheme=scheme),
            group_ids=[int(g) for g in opts.group_ids.split(",") if g.strip()],
            directory=opts.postings_dir,
            sync_writes=opts.sync_writes,
            secret=opts.cluster_secret,
            peer_ca=opts.peer_ca,
            peer_tls_insecure=opts.peer_tls_insecure,
            raft_transport=opts.raft_transport,
            grpc_port_offset=max(0, grpc_port - opts.port),
            peer_groups=parse_peer_groups(opts.peer_groups),
        )
        has_https_peer = any(
            a.startswith("https://") for a in cluster.peers.values()
        )
        if has_https_peer and not opts.peer_ca and not opts.peer_tls_insecure:
            print(
                "warning: TLS peers will be verified against the system "
                "trust store; for self-signed cluster certs pass --peer_ca "
                "(pin) or --peer_tls_insecure",
                file=sys.stderr,
            )
        cluster.start()
        store = cluster.store
    else:
        store = DurableStore(opts.postings_dir, sync_writes=opts.sync_writes)
    if opts.trace_ratio > 0 and not os.environ.get("DGRAPH_TPU_TRACE_RATIO"):
        # --trace drives the flight recorder's head sampler
        # (obs/spans.py: one sampler, one ring, /debug/traces) — the
        # env var wins when set explicitly
        from dgraph_tpu import obs

        obs.configure(ratio=opts.trace_ratio)
    srv = DgraphServer(
        store,
        port=opts.port,
        bind=opts.bind,
        export_path=opts.export_path,
        expose_trace=opts.expose_trace,
        tls_cert=opts.tls_cert,
        tls_key=opts.tls_key,
        cluster=cluster,
        profiler=profiler,
        arena_budget_mb=opts.memory_mb,
        dumpsg_path=opts.dumpsg,
    )
    srv.start()
    print(f"dgraph-tpu serving at {srv.addr}  (dashboard at /, queries at /query)")
    grpc_srv = None
    if grpc_port >= 0:
        try:
            from dgraph_tpu.serve.grpc_server import GrpcServer

            grpc_srv = GrpcServer(srv, bind=opts.bind, port=grpc_port)
            grpc_srv.start()
            print(f"gRPC (protos.Dgraph) at {opts.bind}:{grpc_srv.port}")
        except ImportError:
            print("grpcio unavailable; gRPC surface disabled", file=sys.stderr)
            grpc_srv = None

    stop = {"requested": False}

    def on_signal(signum, frame):
        # disarm: a second Ctrl+C must not re-enter stop() on the same
        # thread while the first holds the (non-reentrant) stop lock
        signal.signal(signal.SIGINT, signal.SIG_IGN)
        signal.signal(signal.SIGTERM, signal.SIG_IGN)
        stop["requested"] = True
        srv.stop()

    signal.signal(signal.SIGINT, on_signal)
    signal.signal(signal.SIGTERM, on_signal)
    def dump_profiles():
        if profiler is not None:
            profiler.dump_stats(opts.cpu_profile)
            print(f"cpu profile written to {opts.cpu_profile}")
        if opts.mem_profile:
            import tracemalloc

            snap = tracemalloc.take_snapshot()
            with open(opts.mem_profile, "w") as f:
                for stat in snap.statistics("lineno")[:50]:
                    f.write(str(stat) + "\n")
            print(f"memory profile written to {opts.mem_profile}")

    try:
        while srv._thread is not None and srv._thread.is_alive():
            srv._thread.join(timeout=0.5)
    except KeyboardInterrupt:
        pass
    # stop() is idempotent and holds its lock through teardown, so this
    # blocks until the store is durably closed even when shutdown was
    # initiated by /admin/shutdown on a daemon thread
    if grpc_srv is not None:
        grpc_srv.stop()
    srv.stop()
    dump_profiles()
    return 0


if __name__ == "__main__":
    sys.exit(main())
