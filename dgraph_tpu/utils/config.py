"""Server options: flags + optional YAML config file.

Equivalent of dgraph/config.go:82-104 + x.LoadConfigFromYAML
(cmd/dgraph/main.go:164-168): defaults, YAML merge, then explicit
overrides win."""

from __future__ import annotations

from dataclasses import dataclass, field, fields, replace
from typing import Optional


@dataclass
class Options:
    # storage
    postings_dir: str = "p"
    wal_dir: str = "w"
    export_path: str = "export"
    sync_writes: bool = False
    # background snapshot/compaction thresholds (models/durability.py
    # Snapshotter): seal+compact once the active WAL passes either
    # bound.  0 = keep the env/default (DGRAPH_TPU_SNAPSHOT_WAL_MB 64 /
    # DGRAPH_TPU_SNAPSHOT_WAL_RECORDS 200000); explicit flags win over
    # the env, like every other flag.
    snapshot_wal_mb: float = 0.0
    snapshot_wal_records: int = 0
    # serving
    port: int = 8080
    # gRPC listener (cmd/dgraph/main.go:602 grpcListener; the reference
    # serves gRPC on its own port next to HTTP).  0 = auto (http port +
    # 1000, the 8080/9080 convention); -1 disables the gRPC surface.
    grpc_port: int = 0
    bind: str = "127.0.0.1"
    tls_cert: str = ""   # PEM cert chain; empty = plain HTTP (x/tls_helper.go analog)
    tls_key: str = ""    # PEM key; empty = key inside tls_cert
    # cluster identity (mirrors --idx/--groups/--peer)
    raft_id: int = 1
    group_ids: str = "0"
    peer: str = ""
    # per-peer group placement: "1=0,1;2=0,2" — which groups each peer
    # serves; peers absent from the map serve every group (full
    # replication).  The server-side complement of the predicate→group
    # rules (group/conf.go), enabling disjoint data placement with
    # cross-server reads.
    peer_groups: str = ""
    my_addr: str = ""
    join: str = ""   # address of a live cluster member to join at boot
    workers: int = 4
    # cluster security: shared secret gating the raft/propose/assign
    # endpoints, and the trust model for intra-cluster TLS (pin a CA, or
    # explicitly opt out of verification for throwaway self-signed certs)
    cluster_secret: str = ""
    peer_ca: str = ""
    peer_tls_insecure: bool = False
    # raft plane carrier: "http" (binary frames over POST /raft/<g>) or
    # "grpc" (/protos.Worker/RaftMessage — the reference's native leg;
    # requires peers to serve gRPC at http port + 1000)
    raft_transport: str = "http"
    # observability
    trace_ratio: float = 0.0
    expose_trace: bool = False
    # profiling (cmd/dgraph/main.go:181 --cpu/--mem analog): output paths,
    # written at shutdown; empty = disabled
    cpu_profile: str = ""
    mem_profile: str = ""

    # engine
    num_pending: int = 1000
    max_edges: int = 1_000_000

    # HBM residency budget for device arenas, in MB; 0 = unlimited.  The
    # memory-watermark sizing of the reference's posting LRU
    # (posting/lists.go:191 --memory_mb, posting/lru.go:57).
    memory_mb: int = 0

    # persistent XLA compilation cache: first-compile of a query shape
    # costs seconds on TPU; caching across restarts makes repeat cold
    # starts warm.  "auto" = the fixed in-checkout directory of
    # utils/jaxcache.py (JAX_COMPILATION_CACHE_DIR wins when set), ""
    # disables.
    compile_cache: str = "auto"

    # directory for per-query execution-shape dumps (--dumpsg,
    # cmd/dgraph/main.go:347); empty = disabled
    dumpsg: str = ""

    def merged_with_yaml(self, path: str) -> "Options":
        """Overlay keys from a simple `key: value` YAML file onto self.
        Callers wanting flags-beat-YAML precedence (the reference applies
        YAML before flags) must merge BEFORE applying flag values — see
        cli/server.py build_options."""
        vals = _load_simple_yaml(path)
        known = {f.name: f.type for f in fields(self)}
        updates = {}
        for k, v in vals.items():
            k = k.replace("-", "_")
            if k in known:
                cur = getattr(self, k)
                updates[k] = _coerce(v, type(cur))
        return replace(self, **updates)


def _coerce(v: str, t):
    if t is bool:
        return str(v).strip().lower() in ("1", "true", "yes", "on")
    if t is int:
        return int(v)
    if t is float:
        return float(v)
    return str(v)


def _load_simple_yaml(path: str) -> dict:
    """Flat `key: value` YAML subset (the reference's config files are
    flat, cmd/dgraph/testrun/conf1.yaml)."""
    out = {}
    with open(path) as f:
        for line in f:
            line = line.split("#", 1)[0].strip()
            if not line or ":" not in line:
                continue
            k, v = line.split(":", 1)
            out[k.strip()] = v.strip().strip("'\"")
    return out
