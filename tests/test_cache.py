"""Two-tier snapshot-versioned query cache (dgraph_tpu/cache/):
correctness across mutations, arena evictions and concurrency, the
LFU-with-aging admission policy, parity with the cache-off path, and
the Prometheus exposition of the new series.

The load-bearing invariant everywhere: a mutation bumps
``store.version`` and NO cached entry recorded under an older version
is ever served — stale entries die logically at the bump and are
reclaimed by the incremental sweep, never handed out.
"""

import json
import threading
import urllib.request

import numpy as np
import pytest

from dgraph_tpu.cache import (
    Answer,
    HopCache,
    ResultCache,
    VersionedLFUCache,
    cacheable,
)
from dgraph_tpu.models import PostingStore
from dgraph_tpu.query.engine import QueryEngine
from dgraph_tpu.serve.server import DgraphServer
from dgraph_tpu.utils.metrics import (
    QCACHE_HOP_EVENTS,
    QCACHE_RESULT_EVENTS,
)


def _parse(text):
    from dgraph_tpu import gql

    return gql.parse(text, None)


def _post(addr, body, timeout=30):
    req = urllib.request.Request(
        addr + "/query", data=body.encode(), method="POST"
    )
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.loads(r.read().decode())


def _seed_store():
    store = PostingStore()
    store.apply_schema("name: string @index(exact) .\nfriend: uid @reverse .")
    store.set_value("name", 1, _tv("Ann"))
    store.set_value("name", 2, _tv("Ben"))
    store.set_value("name", 3, _tv("Cara"))
    store.set_edge("friend", 1, 2)
    store.set_edge("friend", 1, 3)
    store.set_edge("friend", 2, 3)
    return store


def _tv(s):
    from dgraph_tpu.models.types import TypeID, TypedValue

    return TypedValue(TypeID.STRING, s)


# ------------------------------------------------------------- core policy


def test_core_hit_miss_stale():
    c = VersionedLFUCache(budget_bytes=1 << 20)
    assert c.get("k", 1) is None                      # miss
    assert c.put("k", 1, "v", 100)
    assert c.get("k", 1)[0] == "v"                    # live hit
    assert c.get("k", 2) is None                      # older version = stale
    assert c.get("k", 2) is None                      # reclaimed, plain miss
    assert len(c) == 0 and c.occupancy_bytes == 0


def test_core_megaquery_refused_admission():
    """One giant entry can't evict the hot head: entries over the
    per-entry cap are refused outright."""
    c = VersionedLFUCache(budget_bytes=1000, max_entry_frac=0.125)
    assert c.put("hot", 1, "v", 100)
    assert not c.put("mega", 1, "V", 500)             # > 125-byte cap
    assert c.get("hot", 1) is not None                # untouched
    assert c.get("mega", 1) is None


def test_core_lfu_evicts_cold_not_hot():
    c = VersionedLFUCache(budget_bytes=1000, max_entry_frac=0.5)
    c.put("hot", 1, "v", 400)
    for _ in range(5):
        assert c.get("hot", 1) is not None            # heat it up
    c.put("cold", 1, "v", 400)
    c.put("new", 1, "v", 400)                         # over budget: evict one
    assert c.get("hot", 1) is not None                # LFU kept the hot key
    assert c.get("cold", 1) is None                   # coldest evicted


def test_core_generation_sweep_reclaims_stale_bytes():
    """Dead-version entries are reclaimed incrementally by puts — no
    global flush, but the budget comes back."""
    c = VersionedLFUCache(budget_bytes=1 << 20, sweep_limit=64)
    for i in range(50):
        c.put(("old", i), 1, "v", 100)
    assert len(c) == 50
    # a new-version put sweeps the dead generation (all 50 fit inside
    # one sweep_limit=64 batch), so only the live entries remain
    c.put("fresh", 2, "v", 100)
    c.put("fresh2", 2, "v", 100)
    assert len(c) == 2
    assert c.occupancy_bytes == 200


def test_core_aging_lets_new_heat_win():
    """Frequencies halve every age_interval puts, so yesterday's hot key
    cannot squat forever against a currently-hot one."""
    c = VersionedLFUCache(
        budget_bytes=800, max_entry_frac=0.5, age_interval=4
    )
    c.put("old", 1, "v", 400)
    for _ in range(64):
        c.get("old", 1)                               # huge historic heat
    # aging decay across puts, while the new key keeps getting touched
    for i in range(12):
        c.put("new", 1, "v", 400)                     # re-puts keep it warm
        c.get("new", 1)
    c.get("new", 1)
    c.put("now", 1, "v", 400)                         # forces an eviction
    assert c.get("new", 1) is not None or c.get("now", 1) is not None
    # the historically-hot-but-idle key is the one that lost its slot
    assert c.get("old", 1) is None


# ------------------------------------------------------------ tier 1 (hop)


def test_hop_cache_hits_and_mutation_invalidation():
    """Repeat expansions hit; a mutation bumps the version and the next
    read recomputes against fresh arenas — never a stale expansion."""
    store = _seed_store()
    eng = QueryEngine(store)
    assert eng.arenas.hop_cache is not None
    q = "{ q(func: uid(0x1)) { friend { name } } }"
    before = QCACHE_HOP_EVENTS.snapshot()
    out1 = eng.run(q)
    out2 = eng.run(q)
    after = QCACHE_HOP_EVENTS.snapshot()
    assert out1 == out2
    assert after.get("hit", 0) - before.get("hit", 0) >= 1
    # mutation-then-read: fresh data, not the memoized expansion
    store.set_edge("friend", 1, 4)
    store.set_value("name", 4, _tv("Dee"))
    out3 = eng.run(q)
    names = sorted(f["name"] for f in out3["q"][0]["friend"])
    assert names == ["Ben", "Cara", "Dee"]


def test_hop_cache_dropped_on_arena_eviction():
    """Evicting an arena under the HBM budget drops its tier-1 entries
    (id-keyed entries must never outlive the arena object)."""
    store = _seed_store()
    eng = QueryEngine(store, arena_budget_bytes=1)  # evict on every build
    hc = eng.arenas.hop_cache
    arena = eng.arenas.data("friend")
    eng.expander.expand(arena, np.array([1, 2]), attr="friend")
    assert len(hc) == 1
    # building ANOTHER arena under the 1-byte budget evicts 'friend'
    eng.arenas.reverse("friend")
    assert len(hc) == 0


def test_hop_cache_disabled_by_gate(monkeypatch):
    monkeypatch.setenv("DGRAPH_TPU_CACHE", "0")
    eng = QueryEngine(_seed_store())
    assert eng.arenas.hop_cache is None
    out = eng.run("{ q(func: uid(0x1)) { friend { name } } }")
    assert sorted(f["name"] for f in out["q"][0]["friend"]) == ["Ben", "Cara"]


def test_hop_cache_distinguishes_frontier_order():
    """Expansion output depends on row order — permuted frontiers must
    not collide on one entry."""
    store = _seed_store()
    eng = QueryEngine(store)
    arena = eng.arenas.data("friend")
    a = eng.expander.expand(arena, np.array([1, 2]), attr="friend")
    b = eng.expander.expand(arena, np.array([2, 1]), attr="friend")
    assert not np.array_equal(a[0], b[0])
    # each is its own entry; repeats of each hit exactly
    a2 = eng.expander.expand(arena, np.array([1, 2]), attr="friend")
    assert np.array_equal(a[0], a2[0]) and np.array_equal(a[1], a2[1])


# --------------------------------------------------------- tier 2 (result)


@pytest.fixture()
def srv():
    server = DgraphServer(_seed_store())
    server.start()
    yield server
    server.stop()


def test_result_cache_hit_skips_execution(srv, monkeypatch):
    """A repeat request over an unchanged snapshot returns from tier 2
    without touching the engine at all."""
    runs = []
    orig = QueryEngine.run_parsed

    def counting(self, parsed):
        runs.append(1)
        return orig(self, parsed)

    monkeypatch.setattr(QueryEngine, "run_parsed", counting)
    q = "{ q(func: uid(0x1)) { name friend { name } } }"
    out1 = _post(srv.addr, q)
    n1 = len(runs)
    out2 = _post(srv.addr, q)
    out1.pop("server_latency"), out2.pop("server_latency")
    assert out1 == out2
    assert len(runs) == n1  # second request executed NOTHING


def test_result_cache_mutation_then_read_is_fresh(srv):
    q = "{ q(func: uid(0x1)) { friend { name } } }"
    out1 = _post(srv.addr, q)
    _post(srv.addr, q)  # warm hit
    _post(
        srv.addr,
        'mutation { set { <0x1> <friend> <0x4> . <0x4> <name> "Dee" . } }',
    )
    out2 = _post(srv.addr, q)
    names = sorted(f["name"] for f in out2["q"][0]["friend"])
    assert names == ["Ben", "Cara", "Dee"]
    assert out1 != out2


def test_result_cache_keys_on_variables_and_debug(srv):
    """vars and the debug flag are part of the request key — a cached
    plain response must not answer a ?debug=true request or different
    variable bindings."""
    q = (
        "query q($n: string) "
        '{ q(func: eq(name, $n)) { name friend { name } } }'
    )

    def run(vars_, debug=False):
        req = urllib.request.Request(
            srv.addr + "/query" + ("?debug=true" if debug else ""),
            data=q.encode(), method="POST",
            headers={"X-Dgraph-Vars": json.dumps(vars_)},
        )
        with urllib.request.urlopen(req, timeout=30) as r:
            return json.loads(r.read().decode())

    ann = run({"$n": "Ann"})
    ann2 = run({"$n": "Ann"})
    ben = run({"$n": "Ben"})
    assert ann["q"][0]["name"] == "Ann" == ann2["q"][0]["name"]
    assert ben["q"][0]["name"] == "Ben"
    dbg = run({"$n": "Ann"}, debug=True)
    assert "_uid_" in dbg["q"][0]  # debug encoding, not the cached plain one


def test_cacheable_excludes_wall_clock_math():
    ok = _parse("{ q(func: uid(0x1)) { name } }")
    assert cacheable(ok)
    clock = _parse(
        "{ q(func: uid(0x1)) { d as dob x: math(since(d)) } }"
    )
    assert not cacheable(clock)


def test_result_cache_gate_off_is_cacheless(monkeypatch):
    monkeypatch.setenv("DGRAPH_TPU_CACHE", "0")
    server = DgraphServer(_seed_store())
    server.start()
    try:
        assert server.scheduler is not None
        assert server.scheduler.result_cache is None
        assert server.engine.arenas.hop_cache is None
        q = "{ q(func: uid(0x1)) { name } }"
        before = QCACHE_RESULT_EVENTS.snapshot()
        _post(server.addr, q)
        _post(server.addr, q)
        assert QCACHE_RESULT_EVENTS.snapshot() == before  # zero cache traffic
    finally:
        server.stop()


# ------------------------------------- tier 2: the unit is the encoded body


def _post_raw(addr, body, path="/query", headers=None, timeout=30):
    req = urllib.request.Request(
        addr + path, data=body.encode(), method="POST", headers=headers or {}
    )
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.read()


def _cut_tail(raw: bytes) -> bytes:
    """The answer's blocks: all before the per-request tail."""
    at = raw.rfind(b'"server_latency"')
    assert at > 0, raw[:200]
    return raw[:at]


def _hits() -> int:
    return QCACHE_RESULT_EVENTS.snapshot().get("hit", 0)


@pytest.mark.parametrize("query", ["", "?ledger=true", "?debug=true"])
def test_hit_body_equals_miss_body_byte_for_byte(srv, query):
    """A hit splices the tail round the STORED bytes; the client must not
    be able to tell it from the miss but for the tail's values — and the
    whole is ``json.dumps`` of the response dict, as it always was."""
    _post(srv.addr, 'mutation { set { <0x2> <name> "Zo\u00eb \\"B\\"" . } }')
    q = "{ q(func: uid(0x1)) { name friend { name } } r(func: uid(0x2)) { name } }"
    h0 = _hits()
    miss = _post_raw(srv.addr, q, "/query" + query)
    hit = _post_raw(srv.addr, q, "/query" + query)
    assert _hits() == h0 + 1
    assert _cut_tail(miss) == _cut_tail(hit)
    for raw in (miss, hit):
        d = json.loads(raw)
        assert json.dumps(d).encode() == raw  # same separators, same escapes
        assert list(d)[:2] == ["q", "r"] and d["r"][0]["name"] == 'Zo\u00eb "B"'
        assert ("extensions" in d) == (query == "?ledger=true")
        assert ("engine" in d["server_latency"]) == (query == "?debug=true")
    assert set(json.loads(hit).get("extensions", {}).get("ledger", {"stages": {}})["stages"]) <= {
        "parse", "result_cache"
    }


@pytest.mark.parametrize("tree,tail", [
    ({}, {"server_latency": {"total": "1ms"}}),
    ({"q": []}, {"server_latency": {"total": "1ms"}}),
    ({"q": [{"name": "Zo\u00eb \"q\"", "n": 1.5, "ok": True, "none": None}]},
     {"server_latency": {"total": "1ms"}, "extensions": {"ledger": {"edges": 3}}}),
    # a block NAMED like a tail key: the tail's value takes its place
    ({"server_latency": [{"name": "x"}], "q": [1]}, {"server_latency": {"total": "1ms"}}),
    ({"q": [1], "extensions": [2]},
     {"server_latency": {}, "extensions": {"ledger": {}}}),
    ({"q": [1]}, {}),
])
def test_answer_reply_is_json_dumps_of_the_merged_dict(tree, tail):
    want = json.dumps({**tree, **tail}).encode()
    assert Answer(tree).reply(tail) == want
    if tail.keys().isdisjoint(tree):  # what the cache may hold: bytes alone
        assert Answer(body=json.dumps(tree).encode()).reply(tail) == want


def test_response_is_sent_frozen_and_thaws_into_the_dict():
    """``run_query(encoded=True)`` hands the handler a frozen Response:
    sent by splice while untouched; read or changed as a dict, it IS the
    dict ``run_query`` returns otherwise, and what is left is sent."""
    import copy

    from dgraph_tpu.serve.server import Response

    body = b'{"q": [{"name": "Ann"}]}'
    tail = {"server_latency": {"total": "1ms"}}
    want = {"q": [{"name": "Ann"}], "server_latency": {"total": "1ms"}}
    frozen = Response(Answer(body=body), tail)
    assert frozen.encode() == json.dumps(want).encode()
    assert frozen._dict is None                     # no tree was built
    r = Response(Answer(body=body), tail)
    assert r == want and dict(r) == want and list(r) == list(want)
    assert r["q"][0]["name"] == "Ann" and "q" in r and len(r) == 2
    clone = copy.deepcopy(Response(Answer(body=body), tail))
    assert type(clone) is dict and clone == want    # a hook's deepcopy
    r["q"] = []
    del r["server_latency"]
    assert r.encode() == b'{"q": []}'


def test_a_wrapper_round_run_query_decides_what_is_sent(srv, monkeypatch):
    """The benchmark plants its fault by wrapping ``run_query`` and
    altering a deep copy of what it returns: hit or miss, the handler
    sends the altered dict."""
    import copy

    plain = DgraphServer.run_query

    def wrapped(self, text, *a, **kw):
        out = plain(self, text, *a, **kw)
        if "friend" in text:
            out = copy.deepcopy(out)
            out["q"][0]["friend"].pop()
        return out

    q = "{ q(func: uid(0x1)) { name friend { name } } }"
    whole = _post(srv.addr, q)
    monkeypatch.setattr(DgraphServer, "run_query", wrapped)
    h0 = _hits()
    cut = _post(srv.addr, q)
    assert _hits() == h0 + 1                        # a hit, thawed by the hook
    assert len(cut["q"][0]["friend"]) == len(whole["q"][0]["friend"]) - 1
    assert "server_latency" in cut


def test_reserved_block_names_are_never_cached(srv):
    assert not cacheable(_parse("{ server_latency(func: uid(0x1)) { name } }"))
    assert not cacheable(_parse("{ extensions(func: uid(0x1)) { name } }"))
    q = "{ extensions(func: uid(0x1)) { name } }"
    a = json.loads(_post_raw(srv.addr, q, "/query?ledger=true"))
    assert "ledger" in a["extensions"] and "q" not in a


def test_large_answer_admitted_at_len_of_body():
    """48,000 objects — the head of the benchmark's Zipf law — are 1.2 MB
    of JSON: under the default 32 MiB budget (4 MiB an entry) that is an
    entry of exactly its length, not a refused 8.5 MB walk."""
    body = json.dumps(
        {"q": [{"name": "Actor %d" % n} for n in range(48_000)]}
    ).encode()
    assert 1_000_000 < len(body) < 1_300_000
    rc = ResultCache()
    assert rc._c.budget_bytes == 32 << 20 and rc._c.max_entry_bytes == 4 << 20
    before = QCACHE_RESULT_EVENTS.snapshot()
    key = ("{ big }", "", False)
    rc.put(key, 7, body)
    assert rc.occupancy_bytes == len(body) and len(rc) == 1
    got, stats = rc.get(key, 7)
    assert got is body and stats == {}
    after = QCACHE_RESULT_EVENTS.snapshot()
    assert after.get("rejected", 0) == before.get("rejected", 0)
    # a debug entry keeps the engine's stats, encoded and counted too
    dkey = ("{ big }", "", True)
    rc.put(dkey, 7, body, {"edges": 5, "chain_ms": 1.25})
    assert rc.get(dkey, 7)[1] == {"edges": 5, "chain_ms": 1.25}
    assert rc.occupancy_bytes == 2 * len(body) + len(
        json.dumps({"edges": 5, "chain_ms": 1.25})
    )


def test_no_recursive_footprint_walk_left():
    import inspect

    from dgraph_tpu.cache import result

    assert not hasattr(result, "_approx_bytes")
    assert "isinstance" not in inspect.getsource(result)


def test_acknowledged_mutation_makes_the_twin_request_execute(srv, monkeypatch):
    """Nothing is weakened: between two identical requests, an
    acknowledged write to a predicate the request READS makes the second
    execute; a write to one it does not read leaves it a hit."""
    runs = []
    orig = QueryEngine.run_parsed

    def counting(self, parsed):
        runs.append(1)
        return orig(self, parsed)

    q = "{ q(func: uid(0x1)) { friend { name } } }"
    first = _post_raw(srv.addr, q)
    monkeypatch.setattr(QueryEngine, "run_parsed", counting)
    assert _cut_tail(_post_raw(srv.addr, q)) == _cut_tail(first)
    assert runs == []                                   # a hit
    _post(srv.addr, 'mutation { set { <0x9> <hobby> "chess" . } }')
    n = len(runs)                                       # the mutation ran
    assert _cut_tail(_post_raw(srv.addr, q)) == _cut_tail(first)
    assert len(runs) == n                               # unread predicate: hit
    _post(srv.addr, 'mutation { set { <0x3> <name> "Cleo" . } }')
    n = len(runs)
    fresh = json.loads(_post_raw(srv.addr, q))
    assert len(runs) == n + 1                           # read predicate: executed
    assert sorted(f["name"] for f in fresh["q"][0]["friend"]) == ["Ben", "Cleo"]


def test_debug_and_variables_keep_separate_entries(srv):
    q = "query q($n: string) { q(func: eq(name, $n)) { name friend { name } } }"
    rc = srv.scheduler.result_cache

    def run(vars_, debug=False):
        return _post_raw(
            srv.addr, q, "/query" + ("?debug=true" if debug else ""),
            {"X-Dgraph-Vars": json.dumps(vars_)},
        )

    n0, h0 = len(rc), _hits()
    ann = run({"$n": "Ann"})
    ben = run({"$n": "Ben"})
    dbg = run({"$n": "Ann"}, debug=True)
    assert len(rc) == n0 + 3 and _hits() == h0          # three entries, no hit
    assert _cut_tail(run({"$n": "Ann"})) == _cut_tail(ann)
    assert _cut_tail(run({"$n": "Ben"})) == _cut_tail(ben)
    dbg2 = run({"$n": "Ann"}, debug=True)
    assert _cut_tail(dbg2) == _cut_tail(dbg) != _cut_tail(ann)
    assert len(rc) == n0 + 3 and _hits() == h0 + 3
    # the debug hit still carries the execution's engine breakdown
    assert json.loads(dbg2)["server_latency"]["engine"]["edges"] == \
        json.loads(dbg)["server_latency"]["engine"]["edges"]


def test_singleflight_twins_get_one_encoding(srv, monkeypatch):
    """K identical requests in flight together: one execution, ONE
    serialisation of its blocks (each handler used to dump the shared
    dict), and every twin is sent the same bytes."""
    import time as _time

    from dgraph_tpu.cache import result as result_mod

    runs, encodes = [], []
    orig_run = QueryEngine.run_parsed

    def slow(self, parsed):
        runs.append(1)
        _time.sleep(0.4)  # twins attach while this executes
        return orig_run(self, parsed)

    class CountingJson:
        loads = staticmethod(json.loads)

        @staticmethod
        def dumps(obj, **kw):
            if isinstance(obj, dict) and "twin" in obj:
                encodes.append(1)
            return json.dumps(obj, **kw)

    monkeypatch.setattr(QueryEngine, "run_parsed", slow)
    monkeypatch.setattr(result_mod, "json", CountingJson)
    q = "{ twin(func: uid(0x1)) { name friend { name } } }"
    got = [None] * 6

    def call(i):
        got[i] = _post_raw(srv.addr, q)

    ts = [threading.Thread(target=call, args=(i,)) for i in range(6)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=30)
    assert all(g is not None for g in got)
    assert len(runs) < 6                      # they did coalesce
    assert len(encodes) == len(runs)          # one encoding an execution
    assert len({_cut_tail(g) for g in got}) == 1
    assert len(srv.scheduler.result_cache) >= 1   # and the cache got the bytes


def _via_protobuf(srv, q):
    from dgraph_tpu.serve.proto import decode_response

    return decode_response(_post_raw(
        srv.addr, q, headers={"Accept": "application/protobuf"}
    ))


def _via_grpc(srv, q):
    grpc = pytest.importorskip("grpc")
    from dgraph_tpu.serve.grpc_server import GrpcServer, encode_request
    from dgraph_tpu.serve.proto import decode_response

    gsrv = GrpcServer(srv, port=0)
    gsrv.start()
    try:
        with grpc.insecure_channel(f"127.0.0.1:{gsrv.port}") as ch:
            return decode_response(
                ch.unary_unary("/protos.Dgraph/Run")(encode_request(q))
            )
    finally:
        gsrv.stop()


def _via_subscription(srv, q):
    sub = srv.subs.register(q)
    try:
        ev = sub.next_event(timeout=10)
        assert ev["kind"] == "snapshot"
        return ev["data"]
    finally:
        srv.subs.cancel(sub.id)


@pytest.mark.parametrize(
    "surface", [_via_protobuf, _via_grpc, _via_subscription],
    ids=["protobuf", "grpc", "subscription"],
)
def test_tree_surfaces_answer_alike_on_hit_and_miss(srv, surface):
    """The surfaces that need the tree DECODE the stored body on a hit
    (and store an encoding of their own miss): equal answers either way,
    and equal to what the JSON surface says."""
    q = (
        "{ %s(func: uid(0x1)) { name friend { name friend { name } } } }"
        % surface.__name__
    )
    n0, h0 = len(srv.scheduler.result_cache), _hits()
    miss = surface(srv, q)
    assert len(srv.scheduler.result_cache) == n0 + 1 and _hits() == h0
    hit = surface(srv, q)
    assert _hits() == h0 + 1
    miss.pop("server_latency", None), hit.pop("server_latency", None)
    assert miss == hit and surface.__name__ in miss
    # the entry a tree surface stored serves the JSON surface, and back
    over_json = _post(srv.addr, q)
    assert _hits() == h0 + 2
    over_json.pop("server_latency")
    if surface is not _via_protobuf:  # (its decoder renders uids its own way)
        assert miss == over_json


def test_scheduler_run_returns_tree_run_answer_the_body(srv):
    """``run`` (tree surfaces) and ``run_answer`` (the socket) over one
    entry: the same blocks in both forms, whichever stored them."""
    q = "{ both(func: uid(0x1)) { name friend { name } } }"
    key = (q, "", False)
    tree, _ = srv.scheduler.run(_parse(q), key=key)             # miss, stores
    answer, stats = srv.scheduler.run_answer(_parse(q), key=key)  # hit
    assert stats == {}
    assert answer.body() == json.dumps(tree).encode()
    assert answer.tree() == tree
    again, _ = srv.scheduler.run(_parse(q), key=key)            # hit, decoded
    assert again == tree and again is not tree


# ------------------------------------------------- concurrency correctness


def test_no_stale_hit_across_version_bump(srv):
    """Concurrent readers racing a stream of mutations: per reader, the
    observed value index must be MONOTONIC — a cached response from an
    older snapshot served after the bump would show up as a regression."""
    q = "{ q(func: uid(0x1)) { name } }"
    n_writes = 12
    stop = threading.Event()
    regressions = []
    errors = []

    def reader():
        last = -1
        try:
            while not stop.is_set():
                out = _post(srv.addr, q)
                name = out["q"][0]["name"]
                k = 0 if name == "Ann" else int(name[1:])
                if k < last:
                    regressions.append((last, k))
                    return
                last = k
        except Exception as e:  # pragma: no cover
            errors.append(e)

    readers = [threading.Thread(target=reader) for _ in range(4)]
    for t in readers:
        t.start()
    try:
        for k in range(1, n_writes + 1):
            _post(srv.addr, 'mutation { set { <0x1> <name> "v%d" . } }' % k)
    finally:
        stop.set()
    for t in readers:
        t.join(timeout=30)
    assert not errors, errors[:2]
    assert not regressions, regressions
    # and the final read is the final write, not any cached ancestor
    assert _post(srv.addr, q)["q"][0]["name"] == "v%d" % n_writes


def test_cache_on_off_parity_under_8_threads(monkeypatch):
    """The 8-thread parity harness (tests/test_sched.py): responses with
    the cache on are byte-identical to a DGRAPH_TPU_CACHE=0 server over
    an identical store."""
    workload = [
        "{ q(func: uid(0x1)) { name friend { name } } }",
        "{ q(func: uid(0x2)) { name friend { name } } }",
        '{ q(func: eq(name, "Ann")) { name friend { name } } }',
        "{ q(func: uid(0x1)) { c: count(friend) } }",
        "{ q(func: uid(0x3)) { name ~friend { name } } }",
    ]
    monkeypatch.setenv("DGRAPH_TPU_CACHE", "0")
    plain = DgraphServer(_seed_store())
    plain.start()
    try:
        want = {}
        for q in workload:
            out = _post(plain.addr, q)
            out.pop("server_latency", None)
            want[q] = out
    finally:
        plain.stop()

    monkeypatch.setenv("DGRAPH_TPU_CACHE", "1")
    cached = DgraphServer(_seed_store())
    cached.start()
    results, errs = [], []
    try:
        def client(seed):
            rng = np.random.default_rng(seed)
            try:
                for _ in range(8):
                    q = workload[int(rng.integers(len(workload)))]
                    out = _post(cached.addr, q)
                    out.pop("server_latency", None)
                    results.append((q, out))
            except Exception as e:  # pragma: no cover
                errs.append(e)

        ts = [threading.Thread(target=client, args=(s,)) for s in range(8)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=60)
    finally:
        cached.stop()
    assert not errs, errs[:3]
    assert len(results) == 64
    for q, out in results:
        assert out == want[q], q


# ------------------------------------------------------- metrics / tooling


def test_qcache_prometheus_series_render(srv):
    """CI guard: the new per-tier series render in the /debug metrics
    exposition after real traffic."""
    q = "{ q(func: uid(0x1)) { friend { name } } }"
    _post(srv.addr, q)
    _post(srv.addr, q)  # guarantees at least one tier-2 hit (hit-age too)
    with urllib.request.urlopen(
        srv.addr + "/debug/prometheus_metrics", timeout=10
    ) as r:
        text = r.read().decode()
    assert 'dgraph_qcache_result_events_total{event="hit"}' in text
    assert 'dgraph_qcache_result_events_total{event="miss"}' in text
    assert "dgraph_qcache_hop_events_total" in text
    assert "dgraph_qcache_hop_bytes" in text
    assert "dgraph_qcache_result_bytes" in text
    assert "dgraph_qcache_hit_age_seconds_bucket" in text
    # occupancy also shows at-a-glance on /debug/store
    with urllib.request.urlopen(srv.addr + "/debug/store", timeout=10) as r:
        st = json.loads(r.read().decode())
    assert st["qcache"]["result"]["entries"] >= 1


def test_hop_cache_drop_arena_is_selective():
    hc = HopCache(budget_bytes=1 << 20)
    a1, a2 = object(), object()
    src = np.array([1, 2, 3], dtype=np.int64)
    out = np.array([7], dtype=np.int64)
    seg = np.array([0, 1, 1, 1], dtype=np.int64)
    hc.put(a1, "p", False, src, 5, out, seg)
    hc.put(a2, "p", False, src, 5, out, seg)
    assert len(hc) == 2
    assert hc.drop_arena(id(a1)) == 1
    assert len(hc) == 1
    assert hc.get(a2, "p", False, src, 5) is not None
    assert hc.get(a1, "p", False, src, 5) is None


def test_result_cache_zero_budget_disables():
    rc = ResultCache(budget_bytes=0)
    rc.put(("q", "", False), 1, b'{"q": []}')
    assert rc.get(("q", "", False), 1) is None


def test_tier2_never_caches_non_strict_version_stores(srv, monkeypatch):
    """Stores whose version is not strict (ClusterStore: remote-TTL
    reads refresh WITHOUT a bump, and only during execution) must never
    tier-2 cache — a warm hit would starve the freshness probe and
    serve the stale remote copy forever (the test_placement regression
    this guard exists for)."""
    monkeypatch.setattr(
        type(srv.store), "strict_snapshot_versions", False, raising=False
    )
    q = "{ q(func: uid(0x2)) { name } }"
    before = QCACHE_RESULT_EVENTS.snapshot()
    _post(srv.addr, q)
    _post(srv.addr, q)
    after = QCACHE_RESULT_EVENTS.snapshot()
    assert after == before  # zero tier-2 traffic, every request executes
