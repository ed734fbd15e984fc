"""Per-query resource ledger + device telemetry (obs/ledger.py,
obs/device.py): the SLO layer's accounting contracts.

The acceptance pins from ISSUE 13:

- the ledger's `dgraph_edges_traversed_total` per-tenant series
  reconciles EXACTLY with the engine's own stats on a pinned query;
- `DGRAPH_TPU_LEDGER=0` is byte-identical through the full serving
  path (scheduler + cache + planner + QoS armed);
- the unsampled path allocates zero ledger objects per request beyond
  the pooled struct (counter-asserted via
  `dgraph_ledger_structs_total`, the PR-7 discipline).
"""

import json
import time
import urllib.request

import pytest

from dgraph_tpu import obs
from dgraph_tpu.models import PostingStore
from dgraph_tpu.obs import ledger as ledgermod
from dgraph_tpu.serve.server import DgraphServer
from dgraph_tpu.utils.metrics import (
    EDGES_TRAVERSED,
    LEDGER_HOPS,
    LEDGERS_CREATED,
)

SEED = """
mutation {
  schema { name: string . follows: uid . }
  set {
    <0x1> <name> "Alice" .
    <0x2> <name> "Bob" .
    <0x3> <name> "Carol" .
    <0x1> <follows> <0x2> .
    <0x1> <follows> <0x3> .
    <0x2> <follows> <0x3> .
  }
}
"""


def _post(addr, path, body, headers=None):
    req = urllib.request.Request(
        addr + path, data=body.encode(), method="POST"
    )
    for k, v in (headers or {}).items():
        req.add_header(k, v)
    with urllib.request.urlopen(req, timeout=30) as r:
        return json.loads(r.read().decode())


def _get(addr, path):
    with urllib.request.urlopen(addr + path, timeout=30) as r:
        return json.loads(r.read().decode())


@pytest.fixture()
def srv(monkeypatch):
    """Full production regime, caches OFF so every query actually runs
    the engine (the reconcile tests need real traversal work)."""
    monkeypatch.setenv("DGRAPH_TPU_SCHED", "1")
    monkeypatch.setenv("DGRAPH_TPU_CACHE", "0")
    monkeypatch.setenv("DGRAPH_TPU_QOS", "1")
    server = DgraphServer(PostingStore())
    server.start()
    _post(server.addr, "/query", SEED)
    yield server
    server.stop()


# ------------------------------------------------------------- reconcile

def test_ledger_reconciles_with_engine_stats_exact(srv):
    """The pinned-query acceptance: ledger edges == the engine's own
    debug stats == the per-tenant Prometheus delta, as exact counts.
    0x1 has 2 `follows` edges; each target has its outgoing edges
    expanded at level 2 (0x2→0x3, 0x3→none) — 3 edges total."""
    before = EDGES_TRAVERSED.snapshot().get("default", 0)
    out = _post(
        srv.addr, "/query?ledger=true&debug=true",
        "{ q(func: uid(0x1)) { follows { follows { uid } } } }",
    )
    led = out["extensions"]["ledger"]
    eng = out["server_latency"]["engine"]
    assert led["edges"] == eng["edges"] == 3
    after = EDGES_TRAVERSED.snapshot().get("default", 0)
    assert after - before == 3
    # the hop account covers both levels, whatever route served them
    assert sum(led["hops"].values()) >= 2


def test_ledger_tenant_scoped_series(monkeypatch):
    monkeypatch.setenv("DGRAPH_TPU_SCHED", "1")
    monkeypatch.setenv("DGRAPH_TPU_CACHE", "0")
    monkeypatch.setenv("DGRAPH_TPU_QOS", "1")
    server = DgraphServer(PostingStore())
    server.start()
    try:
        _post(server.addr, "/query", SEED)
        before = EDGES_TRAVERSED.snapshot().get("acme", 0)
        _post(
            server.addr, "/query",
            "{ q(func: uid(0x1)) { follows { uid } } }",
            headers={"X-Dgraph-Tenant": "acme"},
        )
        assert EDGES_TRAVERSED.snapshot().get("acme", 0) - before == 2
    finally:
        server.stop()


# ------------------------------------------------------ zero-overhead guard

def test_warm_requests_allocate_zero_ledger_structs(srv):
    """The pooled-struct acceptance: after warmup the free list serves
    every request — N serial queries construct ZERO new Ledger objects
    (counter-asserted, not tracemalloc-suggested)."""
    q = "{ q(func: uid(0x1)) { follows { uid } } }"
    _post(srv.addr, "/query", q)  # warm the pool
    before = LEDGERS_CREATED.value()
    for _ in range(16):
        _post(srv.addr, "/query", q)
    assert LEDGERS_CREATED.value() == before, (
        "warm serial requests constructed new Ledger structs — the "
        "pool is not recycling"
    )


def test_ledger_off_is_byte_identical_and_allocation_free(monkeypatch):
    """DGRAPH_TPU_LEDGER=0 through the FULL armed serving path: same
    bytes (modulo the timing map), zero Ledger constructions, no
    extensions key even when ?ledger=true asks."""
    qs = [
        "{ q(func: uid(0x1)) { follows { name } } }",
        "{ q(func: has(follows)) { name } }",
        "{ q(func: uid(0x1)) { c: count(follows) } }",
    ]

    def serve(flag):
        monkeypatch.setenv("DGRAPH_TPU_LEDGER", flag)
        monkeypatch.setenv("DGRAPH_TPU_SCHED", "1")
        monkeypatch.setenv("DGRAPH_TPU_CACHE", "1")
        monkeypatch.setenv("DGRAPH_TPU_QOS", "1")
        monkeypatch.setenv("DGRAPH_TPU_PLANNER", "1")
        server = DgraphServer(PostingStore())
        server.start()
        try:
            _post(server.addr, "/query", SEED)
            out = []
            for q in qs:
                for _ in range(2):  # second pass exercises the caches
                    r = _post(server.addr, "/query", q)
                    r.pop("server_latency", None)
                out.append(r)
            return out
        finally:
            server.stop()

    on = serve("1")
    before = LEDGERS_CREATED.value()
    off = serve("0")
    assert off == on
    assert LEDGERS_CREATED.value() == before, (
        "DGRAPH_TPU_LEDGER=0 still constructed Ledger structs"
    )
    # and the opt-in surface stays silent under =0
    monkeypatch.setenv("DGRAPH_TPU_LEDGER", "0")
    server = DgraphServer(PostingStore())
    server.start()
    try:
        _post(server.addr, "/query", SEED)
        r = _post(
            server.addr, "/query?ledger=true",
            "{ q(func: uid(0x1)) { follows { uid } } }",
        )
        assert "extensions" not in r
    finally:
        server.stop()


def test_default_responses_carry_no_ledger_key(srv):
    r = _post(
        srv.addr, "/query", "{ q(func: uid(0x1)) { follows { uid } } }"
    )
    assert "extensions" not in r


# -------------------------------------------------------- route accounting

def test_cache_hit_accounting(monkeypatch):
    """With the caches ON, a repeat request's account reads 'served
    from cache': tier-2 hit recorded, zero engine edges."""
    monkeypatch.setenv("DGRAPH_TPU_SCHED", "1")
    monkeypatch.setenv("DGRAPH_TPU_CACHE", "1")
    server = DgraphServer(PostingStore())
    server.start()
    try:
        _post(server.addr, "/query", SEED)
        q = "{ q(func: uid(0x1)) { follows { uid } } }"
        first = _post(server.addr, "/query?ledger=true", q)
        led1 = first["extensions"]["ledger"]
        assert led1["edges"] > 0
        again = _post(server.addr, "/query?ledger=true", q)
        led2 = again["extensions"]["ledger"]
        assert led2["cache_hits"] >= 1
        assert led2["edges"] == 0  # no engine work — the truth
    finally:
        server.stop()


def test_hops_by_route_and_metric_family(srv):
    before = dict(LEDGER_HOPS.snapshot())
    _post(
        srv.addr, "/query",
        "{ q(func: uid(0x1)) { follows { follows { uid } } } }",
    )
    after = LEDGER_HOPS.snapshot()
    delta = {
        k: after.get(k, 0) - before.get(k, 0)
        for k in after
        if after.get(k, 0) != before.get(k, 0)
    }
    assert sum(delta.values()) >= 2, delta
    known = {
        "cache", "merged", "mesh", "host", "resident", "csr", "chain",
        "mxu", "empty",
    }
    assert set(delta) <= known, delta


def test_sampled_trace_carries_ledger_attr(srv):
    obs.configure(ratio=1.0, seed=7)
    try:
        _post(
            srv.addr, "/query",
            "{ q(func: uid(0x1)) { follows { uid } } }",
        )
        traces = _get(srv.addr, "/debug/traces")
        assert traces
        tid = traces[-1]["trace_id"]
        t = _get(srv.addr, f"/debug/traces/{tid}")
        roots = [s for s in t["spans"] if s["parent_id"] is None]
        assert roots and "ledger" in roots[0]["attrs"]
        assert roots[0]["attrs"]["ledger"]["edges"] == 2
    finally:
        obs.configure()


# --------------------------------------------------------- device telemetry

def test_debug_device_snapshot(srv):
    d = _get(srv.addr, "/debug/device")
    assert d["backend"]
    assert d["devices"] >= 1
    res = d["arenas"]
    assert res["resident_bytes"] >= 0
    assert set(res["program_caches"]) == {"tile_sets"}


def test_debug_bundle_is_one_consistent_postmortem(srv):
    _post(srv.addr, "/query", "{ q(func: uid(0x1)) { follows { uid } } }")
    b = _get(srv.addr, "/debug/bundle")
    for key in (
        "generated_unix", "traces", "slow_queries", "planner", "qos",
        "ivm", "qcache", "device", "ledger",
    ):
        assert key in b, key
    assert b["ledger"]["structs_created"] >= 1
    assert "edges_by_tenant" in b["ledger"]


def test_build_info_and_uptime_on_metrics(srv):
    with urllib.request.urlopen(srv.addr + "/metrics", timeout=30) as r:
        body = r.read().decode()
    assert 'dgraph_build_info{version="' in body
    assert 'backend="' in body
    up = [
        l for l in body.splitlines()
        if l.startswith("dgraph_uptime_seconds ")
    ]
    assert up and float(up[0].split()[1]) > 0


def test_ledger_pool_roundtrip_unit():
    """Module-level contract: start/finish recycles the struct and
    drains the aggregate exactly once."""
    led = ledgermod.start("t1")
    assert led is not None
    led.edges = 5
    led.note_hop("host", 5)
    before = EDGES_TRAVERSED.snapshot().get("t1", 0)
    summary = ledgermod.finish(led)
    assert summary["edges"] == 5
    assert summary["hop_edges"] == {"host": 5}
    assert EDGES_TRAVERSED.snapshot().get("t1", 0) - before == 5
    # the recycled struct carries nothing forward
    again = ledgermod.start("t2")
    assert again.edges == 0 and not again.hops and not again.hop_edges
    ledgermod.finish(again)


# ------------------------------------------------------- stage catalogue

CHAIN_Q = "{ q(func: uid(0x1, 0x2, 0x3)) { follows { follows { uid } } } }"
RING = 40  # uids 1..40, each follows the next three: every degree <= INLINE


def _ring_seed() -> str:
    quads = [
        f"<0x{u:x}> <follows> <0x{(u - 1 + d) % RING + 1:x}> ."
        for u in range(1, RING + 1) for d in (1, 2, 3)
    ]
    return (
        "mutation { schema { follows: uid . } set { %s } }" % "\n".join(quads)
    )


@pytest.fixture(scope="module")
def chain_answer():
    """One served chain-route query with ?ledger=true, its wall time on
    the client's clock, and the same text asked again (a result-cache
    hit).  Scheduler, QoS and result cache armed; the fused chain route
    forced (threshold 0) and monolithic (no segments), so the 2-level
    query is ONE program with ONE packed buffer."""
    import time

    mp = pytest.MonkeyPatch()
    mp.setenv("DGRAPH_TPU_SCHED", "1")
    mp.setenv("DGRAPH_TPU_CACHE", "1")
    mp.setenv("DGRAPH_TPU_QOS", "1")
    mp.setenv("DGRAPH_TPU_SEGMENT", "0")
    server = DgraphServer(PostingStore())
    server.engine.chain_threshold = 0
    server.start()
    try:
        _post(server.addr, "/query", _ring_seed())
        _post(server.addr, "/query", CHAIN_Q.replace("q(", "warm("))
        t0 = time.monotonic()
        first = _post(server.addr, "/query?ledger=true&debug=true", CHAIN_Q)
        wall_ms = (time.monotonic() - t0) * 1e3
        again = _post(server.addr, "/query?ledger=true&debug=true", CHAIN_Q)
        yield first, wall_ms, again
    finally:
        server.stop()
        mp.undo()


@pytest.mark.parametrize("stage", [
    "parse", "queue", "plan", "h2d", "dispatch", "fetch", "convert",
    "assemble", "encode",
])
def test_chain_route_request_carries_every_stage(chain_answer, stage):
    """The account of a request's time: a served chain-route query
    passes through each boundary of the catalogue, and says how long."""
    first, _wall, _again = chain_answer
    assert first["server_latency"]["engine"]["chain_fused_levels"] == 2
    stages = first["extensions"]["ledger"]["stages"]
    assert stages.get(stage, 0.0) > 0.0, stages


def test_stages_sum_to_no_more_than_the_wall_time(chain_answer):
    """Catalogue brackets never nest, so they add up — to at most what
    the client waited."""
    first, wall_ms, _again = chain_answer
    stages = first["extensions"]["ledger"]["stages"]
    assert set(stages) <= set(ledgermod.STAGES)
    assert 0.0 < sum(stages.values()) <= wall_ms, (stages, wall_ms)


def test_result_cache_hit_carries_parse_and_probe_alone(chain_answer):
    """A tier-2 hit returns before admission: it was parsed and probed,
    and nothing else happened to it."""
    _first, _wall, again = chain_answer
    led = again["extensions"]["ledger"]
    assert led["cache_hits"] == 1 and led["edges"] == 0
    assert set(led["stages"]) == {"parse", "result_cache"}, led["stages"]


def test_http_write_grows_with_every_answer_written(srv):
    from dgraph_tpu.utils.metrics import LEDGER_STAGE_US

    def written():
        return LEDGER_STAGE_US.snapshot()["http_write"]

    q = "{ q(func: uid(0x1)) { follows { uid } } }"
    seen = [written()]
    for _ in range(3):
        _post(srv.addr, "/query", q)
        # the handler books the stage after the socket write, so the
        # client can hold its answer first: give the handler a moment
        deadline = time.monotonic() + 5.0
        while written() <= seen[-1] and time.monotonic() < deadline:
            time.sleep(0.005)
        seen.append(written())
    assert all(b > a for a, b in zip(seen, seen[1:])), seen


def test_chain_route_books_the_bytes_it_moved(chain_answer):
    """bytes_d2h is the packed buffer, to the byte, computed here from
    the chain's caps (every degree <= INLINE, so no overflow chunks; a
    full-mode undecorated level packs inline | ovflat | ovseg | next
    frontier | total); bytes_h2d holds at least the root vector."""
    from dgraph_tpu import ops

    first, _wall, _again = chain_answer
    led = first["extensions"]["ledger"]
    capc = ops.bucket(1)
    elems, B = 0, ops.bucket(3)
    root_bytes = B * 4
    for _level in range(2):
        slots = B * ops.INLINE + capc * ops.CHUNK
        cap_u = min(ops.bucket(min(slots, RING)), slots)
        elems += slots + capc + cap_u + 1
        B = cap_u
    assert led["hops"] == {"chain": 2}
    assert led["bytes_d2h"] == elems * 4, (led["bytes_d2h"], elems)
    assert led["bytes_h2d"] >= root_bytes
