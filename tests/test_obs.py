"""Flight-recorder tests (dgraph_tpu/obs/): span trees, W3C traceparent
propagation (HTTP header + gRPC metadata, across a 2-group cluster),
the zero-allocation overhead guard, slow-query tail sampling, exemplar
linkage, and the /debug/traces + /metrics serving surface.

The cluster tests boot real in-process servers (the test_cluster_http
pattern): both nodes share THIS process's recorder ring, so "spans on
both nodes" is asserted via each span's ``node`` attr under one
trace_id — no subprocess needed, which keeps the whole file tier-1.
"""

import json
import socket
import time
import urllib.error
import urllib.request

import pytest

from dgraph_tpu import obs
from dgraph_tpu.models import PostingStore
from dgraph_tpu.serve.server import DgraphServer
from dgraph_tpu.utils.metrics import SLOW_QUERIES, SPANS_RECORDED


@pytest.fixture(autouse=True)
def _recorder_reset():
    """Every test configures the process recorder explicitly; restore
    env-default behavior (ratio 0) afterwards so unrelated suites never
    see a leftover ratio-1.0 sampler."""
    yield
    obs.configure()


def _post(addr, path, body, headers=None):
    req = urllib.request.Request(
        addr + path, data=body.encode(), method="POST"
    )
    for k, v in (headers or {}).items():
        req.add_header(k, v)
    with urllib.request.urlopen(req, timeout=30) as r:
        return json.loads(r.read().decode())


def _get(addr, path, headers=None, raw=False):
    req = urllib.request.Request(addr + path)
    for k, v in (headers or {}).items():
        req.add_header(k, v)
    with urllib.request.urlopen(req, timeout=30) as r:
        data = r.read()
        ctype = r.headers.get("Content-Type", "")
    return (data, ctype) if raw else json.loads(data.decode())


def _tp(n: int, sampled: bool = True) -> str:
    """A deterministic traceparent for test n."""
    return f"00-{n:032x}-{n:016x}-{'01' if sampled else '00'}"


def _tid(n: int) -> str:
    return f"{n:032x}"


@pytest.fixture(scope="module")
def srv():
    server = DgraphServer(PostingStore())
    server.start()
    _post(server.addr, "/query", """
    mutation {
      schema { name: string @index(term) . follows: uid . }
      set {
        <0x1> <name> "Alice" .
        <0x2> <name> "Bob" .
        <0x3> <name> "Carol" .
        <0x1> <follows> <0x2> .
        <0x2> <follows> <0x3> .
      }
    }
    """)
    yield server
    server.stop()


# ------------------------------------------------------------- traceparent

def test_traceparent_parse_and_format_roundtrip():
    ctx = obs.parse_traceparent(_tp(0xABC))
    assert ctx is not None
    assert ctx.trace_id == _tid(0xABC)
    assert ctx.span_id == f"{0xABC:016x}"
    assert ctx.sampled is True
    assert obs.parse_traceparent(_tp(5, sampled=False)).sampled is False


@pytest.mark.parametrize("bad", [
    None,
    "",
    "garbage",
    "00-abc-def-01",                                    # wrong lengths
    "00-" + "0" * 32 + "-" + "1" * 16 + "-01",          # all-zero trace
    "00-" + "1" * 32 + "-" + "0" * 16 + "-01",          # all-zero span
    "ff-" + "1" * 32 + "-" + "2" * 16 + "-01",          # forbidden version
    "00-" + "G" * 32 + "-" + "2" * 16 + "-01",          # non-hex
    "00-" + "A" * 32 + "-" + "2" * 16 + "-01",          # uppercase hex
    "00-" + "1" * 32 + "-" + "2" * 16 + "-zz",          # bad flags
    "00-" + "1" * 32 + "-" + "2" * 16,                  # missing flags
])
def test_traceparent_malformed_is_none(bad):
    assert obs.parse_traceparent(bad) is None


def test_malformed_traceparent_never_500s(srv):
    obs.configure(ratio=0.0)
    out = _post(
        srv.addr, "/query", "{ q(func: uid(0x1)) { name } }",
        headers={"Traceparent": "not-a-trace-at-all"},
    )
    assert out["q"] == [{"name": "Alice"}]


# ----------------------------------------------------------------- sampler

def test_sampler_deterministic_under_pinned_seed():
    a = obs.Sampler(ratio=0.5, seed=42)
    b = obs.Sampler(ratio=0.5, seed=42)
    assert [a.decide() for _ in range(200)] == [
        b.decide() for _ in range(200)
    ]
    # the id stream is the same owned RNG
    a2 = obs.Sampler(ratio=0.5, seed=42)
    b2 = obs.Sampler(ratio=0.5, seed=42)
    assert a2.new_id(128) == b2.new_id(128)


# ---------------------------------------------------------- span mechanics

def test_span_tree_publishes_to_ring_with_consistent_nesting():
    rec = obs.configure(ratio=1.0, seed=3)
    root = obs.start_request("query")
    assert root is not None
    with root:
        with root.child("a") as a:
            with a.child("b"):
                time.sleep(0.001)
    t = rec.trace(root.trace_id)
    assert t is not None
    by_name = {s["name"]: s for s in t["spans"]}
    assert set(by_name) == {"query", "a", "b"}
    assert by_name["a"]["parent_id"] == by_name["query"]["span_id"]
    assert by_name["b"]["parent_id"] == by_name["a"]["span_id"]
    _assert_monotone_nesting(t["spans"])


def _assert_monotone_nesting(spans):
    """Every child interval nests inside its parent's [t0, t1]."""
    by_id = {s["span_id"]: s for s in spans}
    checked = 0
    for s in spans:
        p = by_id.get(s["parent_id"])
        if p is None:
            continue
        assert s["t0_ns"] >= p["t0_ns"], (s["name"], p["name"])
        assert s["t1_ns"] <= p["t1_ns"], (s["name"], p["name"])
        checked += 1
    return checked


def test_kill_switch_disables_roots_entirely():
    obs.configure(ratio=1.0, enabled=False)
    assert obs.start_request("query") is None
    # even a sampled upstream context is refused when the switch is off
    ctx = obs.parse_traceparent(_tp(9))
    assert obs.start_request("query", ctx) is None
    assert obs.server_span("peer.x", ctx) is obs.NOOP


# ----------------------------------------------- single-node serving trace

def test_single_node_trace_covers_scheduler_cache_engine(srv):
    # propagation-driven: the upstream sampled flag is honored only
    # while the local sampler is ARMED (ratio > 0) — a tiny ratio
    # keeps local head sampling effectively off
    obs.configure(ratio=1e-9)
    out = _post(
        srv.addr, "/query",
        "{ t1(func: uid(0x1)) { name follows { name } } }",
        headers={"Traceparent": _tp(1001)},
    )
    assert out["t1"][0]["follows"] == [{"name": "Bob"}]
    t = _get(srv.addr, f"/debug/traces/{_tid(1001)}")
    names = [s["name"] for s in t["spans"]]
    for want in (
        "query", "parsing", "processing", "cache.result", "sched.queue",
        "sched.flush", "engine", "hop", "cache.hop",
    ):
        assert want in names, f"missing span {want!r} in {names}"
    by_name = {s["name"]: s for s in t["spans"]}
    # root continues the CALLER's trace: parent is the header's span id
    assert by_name["query"]["parent_id"] == f"{1001:016x}"
    # hop spans carry the route + edge attribution
    hop = by_name["hop"]
    assert hop["attrs"]["pred"] == "follows"
    assert hop["attrs"]["edges"] == 1
    assert hop["attrs"]["route"] in ("host", "csr", "cache", "merged", "mesh")
    # the engine span links to the shared cohort-flush span
    eng = by_name["engine"]
    flush = by_name["sched.flush"]
    assert {"trace_id": flush["trace_id"], "span_id": flush["span_id"]} in (
        eng["links"]
    )
    # queue-wait is a real interval with an outcome
    assert by_name["sched.queue"]["attrs"]["outcome"] == "run"
    assert _assert_monotone_nesting(t["spans"]) >= 5


def test_repeat_query_trace_shows_result_cache_hit(srv):
    obs.configure(ratio=1e-9)  # armed: honor the header
    q = "{ t2(func: uid(0x2)) { name } }"
    _post(srv.addr, "/query", q, headers={"Traceparent": _tp(1002)})
    _post(srv.addr, "/query", q, headers={"Traceparent": _tp(1003)})
    t2 = _get(srv.addr, f"/debug/traces/{_tid(1003)}")
    by_name = {s["name"]: s for s in t2["spans"]}
    assert by_name["cache.result"]["attrs"]["outcome"] == "hit"
    assert by_name["cache.result"]["attrs"]["bytes"] > 0
    # a tier-2 hit returns before admission: no engine work in the trace
    assert "engine" not in by_name and "hop" not in by_name


def test_hop_cache_hit_routes_hop_span(srv):
    obs.configure(ratio=1e-9)  # armed: honor the header
    # different query texts (distinct tier-2 keys) sharing one hop
    _post(srv.addr, "/query",
          "{ a3(func: uid(0x2)) { follows { name } } }",
          headers={"Traceparent": _tp(1004)})
    _post(srv.addr, "/query",
          "{ b3(func: uid(0x2)) { follows { name } } }",
          headers={"Traceparent": _tp(1005)})
    t = _get(srv.addr, f"/debug/traces/{_tid(1005)}")
    hops = [s for s in t["spans"] if s["name"] == "hop"]
    assert hops and hops[0]["attrs"]["route"] == "cache"
    probes = [s for s in t["spans"] if s["name"] == "cache.hop"]
    assert probes[0]["attrs"]["outcome"] == "hit"
    assert probes[0]["attrs"]["bytes"] > 0


def test_debug_traces_listing_and_chrome_export(srv):
    obs.configure(ratio=1e-9)  # armed: honor the header
    _post(srv.addr, "/query", "{ t4(func: uid(0x1)) { name } }",
          headers={"Traceparent": _tp(1006)})
    listing = _get(srv.addr, "/debug/traces")
    assert any(e["trace_id"] == _tid(1006) for e in listing)
    entry = [e for e in listing if e["trace_id"] == _tid(1006)][0]
    assert entry["spans"] >= 3 and entry["duration_ms"] >= 0
    chrome = _get(srv.addr, f"/debug/traces/{_tid(1006)}?format=chrome")
    xs = [e for e in chrome["traceEvents"] if e["ph"] == "X"]
    assert xs and all("ts" in e and "dur" in e for e in xs)
    assert any(e["name"] == "query" for e in xs)
    with pytest.raises(urllib.error.HTTPError) as e:
        _get(srv.addr, "/debug/traces/" + "f" * 32)
    assert e.value.code == 404


# ----------------------------------------------------------- overhead guard

def test_unsampled_path_allocates_zero_spans(srv):
    obs.configure(ratio=0.0)
    q = "{ t5(func: uid(0x1)) { name follows { name } } }"
    _post(srv.addr, "/query", q)  # warm caches/compiles outside the window
    before = SPANS_RECORDED.value()
    for _ in range(5):
        out_on = _post(srv.addr, "/query", q)
    assert SPANS_RECORDED.value() == before, (
        "unsampled request allocated span objects"
    )
    # kill switch: same response, still zero spans
    obs.configure(enabled=False)
    out_off = _post(srv.addr, "/query", q)
    assert SPANS_RECORDED.value() == before
    out_on.pop("server_latency")
    out_off.pop("server_latency")  # timings differ run-to-run by nature
    assert out_on == out_off


def test_sampled_header_cannot_force_tracing_at_ratio_zero(srv):
    """An untrusted client's sampled traceparent must NOT defeat the
    ratio-0 zero-overhead promise on the public query surface (the
    authenticated peer plane still honors upstream unconditionally)."""
    obs.configure(ratio=0.0)
    before = SPANS_RECORDED.value()
    _post(srv.addr, "/query", "{ z(func: uid(0x1)) { name } }",
          headers={"Traceparent": _tp(1099)})
    assert SPANS_RECORDED.value() == before
    with pytest.raises(urllib.error.HTTPError) as e:
        _get(srv.addr, f"/debug/traces/{_tid(1099)}")
    assert e.value.code == 404


# ------------------------------------------------------ slow-query sampling

def test_slow_query_tail_sampled_at_ratio_zero(srv):
    from dgraph_tpu.utils.failpoints import fail

    rec = obs.configure(ratio=0.0, slow_ms=5.0)
    n0 = SLOW_QUERIES.value()
    fail.seed(0)
    fail.arm("sched.flush", "delay(ms=40,n=1)")
    try:
        out = _post(srv.addr, "/query", "{ t6(func: uid(0x3)) { name } }")
    finally:
        fail.disarm("sched.flush")
    assert out["t6"] == [{"name": "Carol"}]
    assert SLOW_QUERIES.value() == n0 + 1
    slow = rec.slow_queries()
    assert slow and slow[-1]["duration_ms"] >= 5.0
    assert "t6(func" in slow[-1]["query"]
    # tail sampling: the offender is findable in the ring even though
    # the head sampler never fired
    tid = slow[-1]["trace_id"]
    assert tid is not None
    t = _get(srv.addr, f"/debug/traces/{tid}")
    assert t["spans"][0]["attrs"].get("tail_sampled") is True
    # and the HTTP surface serves the log
    served = _get(srv.addr, "/debug/slow_queries")
    assert any(e["trace_id"] == tid for e in served)


# ---------------------------------------------------------------- exemplars

def test_latency_exemplars_resolve_to_ring(srv):
    obs.configure(ratio=1e-9)  # armed: honor the header
    _post(srv.addr, "/query", "{ t7(func: uid(0x1)) { name } }",
          headers={"Traceparent": _tp(1007)})
    body, ctype = _get(
        srv.addr, "/metrics",
        headers={"Accept": "application/openmetrics-text"}, raw=True,
    )
    assert ctype.startswith("application/openmetrics-text")
    text = body.decode()
    assert text.rstrip().endswith("# EOF")
    ex_lines = [
        l for l in text.splitlines()
        if l.startswith("dgraph_query_latency_seconds_bucket")
        and "# {trace_id=" in l
    ]
    assert ex_lines, "no exemplars on dgraph_query_latency_seconds"
    assert any(f'trace_id="{_tid(1007)}"' in l for l in ex_lines)
    # the exemplar resolves to a live ring entry
    t = _get(srv.addr, f"/debug/traces/{_tid(1007)}")
    assert t["trace_id"] == _tid(1007)


def test_metrics_alias_and_content_types(srv):
    body, ctype = _get(srv.addr, "/metrics", raw=True)
    assert ctype == "text/plain; version=0.0.4; charset=utf-8"
    assert b"dgraph_num_queries_total" in body
    # classic format must NOT carry exemplar syntax
    assert b"# {trace_id=" not in body
    legacy, _ = _get(srv.addr, "/debug/prometheus_metrics", raw=True)
    assert b"dgraph_num_queries_total" in legacy


# ------------------------------------------------------- WAL barrier spans

def test_wal_group_commit_barrier_span(tmp_path):
    from dgraph_tpu.models.wal import Wal

    rec = obs.configure(ratio=1.0, seed=11)
    wal = Wal(str(tmp_path / "w.wal"), sync=True)
    wal.group_commit = True
    root = obs.start_request("mutation")
    with root:
        wal.append(b"hello")
        wal.flush()
        wal.sync_upto()
    wal.close()
    t = rec.trace(root.trace_id)
    spans = {s["name"]: s for s in t["spans"]}
    assert "wal.group_commit" in spans
    assert spans["wal.group_commit"]["attrs"]["fsync"] is True
    assert spans["wal.group_commit"]["attrs"]["seq"] == 1


# --------------------------------------------------- gRPC metadata plumbing

def test_grpc_metadata_traceparent_joins_trace(srv):
    grpc = pytest.importorskip("grpc")
    from dgraph_tpu.serve.grpc_server import GrpcServer, encode_request

    obs.configure(ratio=1e-9)  # armed: honor the metadata header
    gsrv = GrpcServer(srv, port=0)
    gsrv.start()
    try:
        with grpc.insecure_channel(f"127.0.0.1:{gsrv.port}") as ch:
            run = ch.unary_unary("/protos.Dgraph/Run")
            run(
                encode_request("{ t8(func: uid(0x1)) { name } }"),
                metadata=(("traceparent", _tp(1008)),),
                timeout=30,
            )
            # malformed metadata must be ignored, not an error
            run(
                encode_request("{ t8b(func: uid(0x1)) { name } }"),
                metadata=(("traceparent", "junk"),),
                timeout=30,
            )
    finally:
        gsrv.stop()
    t = _get(srv.addr, f"/debug/traces/{_tid(1008)}")
    by_name = {s["name"]: s for s in t["spans"]}
    assert by_name["query"]["parent_id"] == f"{1008:016x}"
    assert "engine" in by_name or "cache.result" in by_name


# ----------------------------------------------- 2-group cluster, e2e trace

def _free_ports(n):
    socks, ports = [], []
    for _ in range(n):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        ports.append(s.getsockname()[1])
        socks.append(s)
    for s in socks:
        s.close()
    return ports


def _wait(cond, timeout=30.0, step=0.05):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(step)
    return False


def _post_retry(addr, path, body, headers=None, timeout=60.0):
    """Retry transient settling errors (leader election, forwarded
    proposals racing apply) — the test_cluster_http discipline."""
    deadline = time.monotonic() + timeout
    last = None
    while time.monotonic() < deadline:
        try:
            return _post(addr, path, body, headers=headers)
        except (urllib.error.HTTPError, OSError) as e:
            last = e
            time.sleep(0.2)
    raise AssertionError(f"cluster request never settled: {last}")


@pytest.fixture(scope="module")
def cluster2(tmp_path_factory):
    """Two nodes, two DATA groups, disjoint placement: node 1 serves
    group 1 (pred ``follows``), node 2 serves group 2 (pred ``name``) —
    so a 2-hop query on node 1 MUST read cross-group, and a ``name``
    mutation posted to node 1 MUST forward."""
    from dgraph_tpu.cluster.groups import GroupConfig
    from dgraph_tpu.cluster.service import ClusterService

    tmp = tmp_path_factory.mktemp("obs-cluster")
    ports = _free_ports(2)
    peers = {"1": f"http://127.0.0.1:{ports[0]}",
             "2": f"http://127.0.0.1:{ports[1]}"}
    conf = GroupConfig.parse(
        "1: follows\n2: name\ndefault: fp % 2 + 1"
    )
    groups_of = {"1": [0, 1], "2": [0, 2]}
    servers = []
    for nid in ("1", "2"):
        svc = ClusterService(
            node_id=nid,
            my_addr=peers[nid],
            peers=peers,
            group_ids=groups_of[nid],
            directory=str(tmp / f"n{nid}"),
            group_config=conf,
            peer_groups=groups_of,
        )
        svc.start()
        srv = DgraphServer(
            svc.store, port=ports[int(nid) - 1], cluster=svc
        )
        srv.start()
        servers.append(srv)
    assert _wait(lambda: all(s.cluster.has_leader() for s in servers)), (
        "no leader elected"
    )
    # seed the graph through node 1: name edges land on group 2 (node 2)
    _post_retry(servers[0].addr, "/query", """
    mutation { set {
      <0x1> <name> "Alice" .
      <0x2> <name> "Bob" .
      <0x3> <name> "Carol" .
      <0x1> <follows> <0x2> .
      <0x2> <follows> <0x3> .
    } }
    """)

    def visible():
        try:
            out = _post(
                servers[0].addr, "/query",
                "{ warm(func: uid(0x1)) { follows { follows { name } } } }",
            )
            w = out.get("warm", [{}])
            return bool(
                w and w[0].get("follows", [{}])[0].get("follows")
            )
        except (urllib.error.HTTPError, OSError, IndexError, KeyError):
            return False

    assert _wait(visible), "seed data never became readable on node 1"
    yield servers
    for s in servers:
        s.stop()


def test_cluster_two_hop_trace_covers_all_layers(cluster2):
    """The acceptance-criteria trace: ONE trace at /debug/traces/<id>
    covering server → scheduler (queue-wait + linked cohort flush) →
    cache probe → per-hop execution (edges + route attrs) → peer RPC
    attempts toward the remote node — with consistent parent links and
    monotone [t0, t1] nesting, asserted span by span.

    Deflaked (PR 11): spans land in the ring ASYNCHRONOUSLY — flush
    workers and peer-RPC legs may finish after the response returns, so
    on a busy host a trace snapshot taken immediately can be missing
    late spans (the known ~5/8 failure from the PR-10 notes).  The
    structural preconditions are therefore condition-POLLED with a
    bounded deadline (the PR-5 _post_retry discipline); the detailed
    assertions then run on a settled snapshot."""
    n1, _n2 = cluster2
    obs.configure(ratio=1e-9)  # armed: honor the header
    # bust the remote-snapshot TTL cache so the query truly crosses
    # groups inside THIS trace window
    n1.cluster.store._remote.clear()
    out = _post(
        n1.addr, "/query",
        "{ q(func: uid(0x1)) { follows { follows { name } } } }",
        headers={"Traceparent": _tp(2001)},
    )
    assert out["q"][0]["follows"][0]["follows"] == [{"name": "Carol"}]

    WANT = ("query", "processing", "sched.queue", "sched.flush",
            "engine", "hop", "cache.hop")

    def settled():
        t = _get(n1.addr, f"/debug/traces/{_tid(2001)}")
        spans = t["spans"]
        names = {s["name"] for s in spans}
        if any(w not in names for w in WANT):
            return None
        if not any(s["name"].startswith("rpc.") for s in spans):
            return None
        if not any(s["name"] == "peer.pred-snapshot" for s in spans):
            return None
        # every wanted span must have FINISHED (dur stamped): a span
        # mid-flight still shows up in the shared buffer only at close
        if any(
            s["dur_us"] is None for s in spans if s["name"] in WANT
        ):
            return None
        return spans

    deadline = time.monotonic() + 30.0
    spans = None
    while time.monotonic() < deadline:
        spans = settled()
        if spans is not None:
            break
        time.sleep(0.1)
    assert spans is not None, (
        "trace never settled with all layers present: "
        f"{[s['name'] for s in _get(n1.addr, f'/debug/traces/{_tid(2001)}')['spans']]}"
    )
    names = [s["name"] for s in spans]
    by_name = {s["name"]: s for s in spans}

    # server → scheduler → cache → engine
    for want in ("query", "processing", "sched.queue", "sched.flush",
                 "engine", "hop", "cache.hop"):
        assert want in names, f"missing {want!r} in {names}"
    # queue-wait + the flush LINK from the engine span
    flush = by_name["sched.flush"]
    assert {"trace_id": flush["trace_id"], "span_id": flush["span_id"]} in (
        by_name["engine"]["links"]
    )
    # per-hop device execution: two follows hops with edge counts
    hops = [s for s in spans if s["name"] == "hop"]
    assert len(hops) >= 2
    assert all(s["attrs"]["pred"] == "follows" for s in hops)
    assert sum(s["attrs"]["edges"] for s in hops) == 2
    assert all("route" in s["attrs"] for s in hops)
    # peer RPC attempts toward the remote name-owner
    rpcs = [s for s in spans if s["name"].startswith("rpc.")]
    assert rpcs, f"no peer RPC spans in {names}"
    assert any(s["attrs"].get("outcome") == "ok" for s in rpcs)
    assert all("attempt" in s["attrs"] for s in rpcs
               if s["attrs"].get("outcome") != "breaker_open")
    # the remote node recorded ITS leg under the SAME trace id
    remote = [s for s in spans if s["name"] == "peer.pred-snapshot"]
    assert remote and remote[0]["attrs"]["node"] == "2"
    assert remote[0]["attrs"]["pred"] == "name"

    # every parent link resolves or points at the remote caller span,
    # and REQUEST-THREAD child intervals nest inside their parents.
    # Two span classes are asynchronous to the request by design and
    # excluded from the nesting check (both traced to the 5/8 busy-host
    # failures): remote-side server spans (peer.*) — a timed-out first
    # RPC attempt gets retried, and the abandoned attempt's handler on
    # the other node finishes AFTER the local parent closed — and the
    # cohort-shared sched.flush span, which the flush WORKER closes
    # after dealing results, by which time the member's processing span
    # may already be done.  Out-living there is the machinery working,
    # not a trace bug.
    ids = {s["span_id"] for s in spans}
    roots = [s for s in spans if s["parent_id"] not in ids]
    for r in roots:
        # dangling parents are exactly: the inbound header's span (the
        # synthetic test caller) and the cross-thread rpc parents
        assert r["parent_id"] is None or len(r["parent_id"]) == 16
    sync_spans = [
        s for s in spans
        if not s["name"].startswith("peer.") and s["name"] != "sched.flush"
    ]
    assert _assert_monotone_nesting(sync_spans) >= 6


def test_cluster_forwarded_mutation_spans_on_both_nodes(cluster2):
    """Satellite: a forwarded mutation produces spans on BOTH nodes
    sharing one trace_id (node attr tells them apart — the two servers
    share this process's ring)."""
    n1, _n2 = cluster2
    obs.configure(ratio=1e-9)  # armed: honor the header
    # posting a *name* mutation to node 1 forces a cross-node forward:
    # group 2 lives only on node 2
    out = _post_retry(
        n1.addr, "/query",
        'mutation { set { <0x4> <name> "Dave" . } }',
        headers={"Traceparent": _tp(2002)},
    )
    assert out.get("code") == "Success"
    t = _get(n1.addr, f"/debug/traces/{_tid(2002)}")
    by_name = {}
    for s in t["spans"]:
        by_name.setdefault(s["name"], []).append(s)
    # node 1's half: the request root + the forward RPC attempt(s)
    assert by_name["query"][0]["attrs"]["node"] == "1"
    fwd = by_name.get("rpc.forward") or []
    assert fwd, f"no forward RPC span in {list(by_name)}"
    # node 2's half: the raft-propose server span, same trace
    props = by_name.get("peer.raft-propose") or []
    assert any(s["attrs"]["node"] == "2" for s in props)
    assert all(s["trace_id"] == _tid(2002) for s in t["spans"])


def test_cluster_cross_group_read_spans_on_both_nodes(cluster2):
    """Satellite twin: a cross-group READ records on both nodes under
    one trace_id (client span on node 1, server span on node 2)."""
    n1, _n2 = cluster2
    obs.configure(ratio=1e-9)  # armed: honor the header
    n1.cluster.store._remote.clear()
    _post(
        n1.addr, "/query", "{ r(func: uid(0x2)) { name } }",
        headers={"Traceparent": _tp(2003)},
    )
    t = _get(n1.addr, f"/debug/traces/{_tid(2003)}")
    nodes_seen = {
        s["attrs"]["node"]
        for s in t["spans"]
        if "node" in s.get("attrs", {})
    }
    assert {"1", "2"} <= nodes_seen, t["spans"]


def test_cluster_malformed_traceparent_ignored(cluster2):
    n1, _n2 = cluster2
    obs.configure(ratio=0.0)
    out = _post(
        n1.addr, "/query", "{ m(func: uid(0x1)) { follows { name } } }",
        headers={"Traceparent": "00-zzzz-yyyy-01"},
    )
    assert "m" in out


# ------------------------------------------------- stages on the profiler's clock

def _ring_engine():
    """An embedded engine over a 40-uid ring (each follows the next
    three), the fused chain route forced."""
    from dgraph_tpu.query.engine import QueryEngine

    store = PostingStore()
    eng = QueryEngine(store)
    eng.run(
        "mutation { schema { follows: uid . } set { %s } }" % "\n".join(
            f"<0x{u:x}> <follows> <0x{(u - 1 + d) % 40 + 1:x}> ."
            for u in range(1, 41) for d in (1, 2, 3)
        )
    )
    eng.chain_threshold = 0
    return eng


def test_stages_are_events_on_the_profilers_host_plane(tmp_path, monkeypatch):
    """obs.stage enters a ``dgraph.<stage>`` TraceAnnotation for the
    same interval it times: with a profiler session open round one chain
    query, ``dgraph.dispatch`` then ``dgraph.fetch`` sit on one host
    thread (the device guard's worker), inside the trace's extent — the
    same clock as the device's operations."""
    import glob

    import jax
    from jax.profiler import ProfileData

    monkeypatch.setenv("DGRAPH_TPU_SEGMENT", "0")
    eng = _ring_engine()
    q = "{ q(func: uid(0x1, 0x2, 0x3)) { follows { follows { uid } } } }"
    eng.run(q)  # compile outside the session
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        eng.run(q)
    finally:
        jax.profiler.stop_trace()
    assert eng.stats["chain_fused_levels"] == 2
    (pb,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
    lo, hi, lines = float("inf"), float("-inf"), []
    for plane in ProfileData.from_file(pb).planes:
        for ln in plane.lines:
            ev = [(e.name, e.start_ns, e.start_ns + e.duration_ns) for e in ln.events]
            if ev:
                lo = min(lo, min(s for _, s, _ in ev))
                hi = max(hi, max(t for _, _, t in ev))
            if plane.name.startswith("/host:"):
                lines.append([e for e in ev if e[0].startswith("dgraph.")])
    both = [
        ln for ln in lines
        if {"dgraph.dispatch", "dgraph.fetch"} <= {name for name, _, _ in ln}
    ]
    assert len(both) == 1, lines
    (dispatch,) = [e for e in both[0] if e[0] == "dgraph.dispatch"]
    (fetch,) = [e for e in both[0] if e[0] == "dgraph.fetch"]
    assert lo <= dispatch[1] < dispatch[2] <= fetch[1] < fetch[2] <= hi
    # and every stage of the engine's account is there by name
    seen = {name for ln in lines for name, _, _ in ln}
    assert {"dgraph.plan", "dgraph.h2d", "dgraph.convert", "dgraph.encode",
            "dgraph.chain"} <= seen, seen


def test_warm_requests_allocate_nothing_and_answers_are_unchanged(srv):
    """No profiler session, ratio 0: 50 warm requests construct no span
    and no ledger struct, and an answer that did not ask for the ledger
    is, outside its latency map, byte for byte what it was before the
    stages existed."""
    from dgraph_tpu.utils.metrics import LEDGERS_CREATED

    obs.configure(ratio=0.0)
    q = "{ q(func: uid(0x1)) { name follows { name follows { name } } } }"
    _post(srv.addr, "/query", q)
    spans, structs = SPANS_RECORDED.value(), LEDGERS_CREATED.value()
    for _ in range(50):
        out = _post(srv.addr, "/query", q)
    assert SPANS_RECORDED.value() == spans
    assert LEDGERS_CREATED.value() == structs
    assert set(out["server_latency"]) <= {"total", "parsing", "processing", "json"}
    del out["server_latency"]
    assert json.dumps(out) == (
        '{"q": [{"name": "Alice", "follows": [{"name": "Bob", '
        '"follows": [{"name": "Carol"}]}]}]}'
    )


@pytest.fixture(scope="module")
def boot_metrics():
    """/debug/prometheus_metrics of a server, in a process of its own,
    that has answered nothing."""
    import os
    import subprocess
    import sys

    code = (
        "import urllib.request\n"
        "from dgraph_tpu.models import PostingStore\n"
        "from dgraph_tpu.serve.server import DgraphServer\n"
        "s = DgraphServer(PostingStore()); s.start()\n"
        "print(urllib.request.urlopen(s.addr + '/debug/prometheus_metrics')"
        ".read().decode())\n"
        "s.stop()\n"
    )
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, PYTHONPATH=root, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True,
        text=True, timeout=120, check=True,
    ).stdout.splitlines()


@pytest.mark.parametrize("sample", [
    *(f'dgraph_ledger_stage_us_total{{stage="{s}"}} 0' for s in obs.ledger.STAGES),
    'dgraph_ledger_bytes_total{dir="h2d"} 0',
    'dgraph_ledger_bytes_total{dir="d2h"} 0',
    "dgraph_xla_cache_reads_total 0",
    "dgraph_xla_compiles_total 0",
])
def test_catalogue_is_exposed_at_zero_from_boot(boot_metrics, sample):
    """A scraper's first read of a window finds every label it will
    diff: a family absent until first incremented reads as no metric."""
    assert sample in boot_metrics


def test_cache_read_event_counts_apart_from_compiles():
    """JAX fires /jax/compilation_cache/cache_hits for a program read
    back from the persistent cache; it moves
    dgraph_xla_cache_reads_total and leaves dgraph_xla_compiles_total
    (every backend-compile bracket) alone."""
    import jax.monitoring

    from dgraph_tpu.obs import device
    from dgraph_tpu.utils.metrics import XLA_CACHE_READS, XLA_COMPILES

    device.install_compile_listener()
    reads, compiles = XLA_CACHE_READS.value(), XLA_COMPILES.value()
    jax.monitoring.record_event("/jax/compilation_cache/cache_hits")
    jax.monitoring.record_event("/jax/compilation_cache/compile_requests_use_cache")
    assert XLA_CACHE_READS.value() == reads + 1
    assert XLA_COMPILES.value() == compiles
