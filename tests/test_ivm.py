"""Incremental view maintenance (dgraph_tpu/ivm/): predicate-scoped
cache invalidation, delta repair of derived views, the mutation delta
stream, and live-query subscriptions.

The load-bearing invariants:

- an entry is invalidated IFF a predicate in its footprint mutated
  (schema changes and snapshot restores invalidate everything via the
  floor) — never served stale, never killed by an unrelated write;
- a repaired view is BYTE-IDENTICAL to a rebuilt one (hop entries,
  tile blocks, degree histogram) — pinned by randomized property
  tests;
- a registered live query is pushed exactly when an affecting mutation
  changed its result, trace-linked, quota-bounded, cancellable;
- ``DGRAPH_TPU_IVM=0`` restores the global store.version keying
  byte-identically.
"""

import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from dgraph_tpu import ivm
from dgraph_tpu.ivm.deltas import DeltaStream
from dgraph_tpu.ivm.repair import repair_hop_entry
from dgraph_tpu.models import PostingStore
from dgraph_tpu.models.arena import ArenaManager, csr_from_edges
from dgraph_tpu.models.types import TypeID, TypedValue
from dgraph_tpu.query.engine import QueryEngine
from dgraph_tpu.serve.server import DgraphServer
from dgraph_tpu.utils.metrics import (
    IVM_REPAIRS,
    QCACHE_HOP_EVENTS,
    QCACHE_RESULT_EVENTS,
    SUBS_EVENTS,
)


def _post(addr, body, headers=None, timeout=30):
    req = urllib.request.Request(
        addr + "/query", data=body.encode(), headers=headers or {}
    )
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.loads(r.read().decode())


def _seed_store():
    st = PostingStore()
    st.apply_schema("friend: [uid] @reverse .\nname: string @index(exact) .")
    names = ["Ann", "Bob", "Cat", "Dan", "Eve"]
    for i, nm in enumerate(names, start=1):
        st.set_value("name", i, TypedValue(TypeID.STRING, nm))
    for s, d in [(1, 2), (1, 3), (2, 3), (3, 4), (4, 5), (2, 5)]:
        st.set_edge("friend", s, d)
    return st


# ------------------------------------------------------- store versions


def test_pred_versions_track_mutations():
    st = PostingStore()
    st.set_edge("e", 1, 2)
    v1 = st.version
    assert st.pred_versions["e"] == v1
    st.set_value("name", 1, TypedValue(TypeID.STRING, "x"))
    assert st.pred_versions["name"] == st.version
    assert st.pred_versions["e"] == v1  # untouched predicate keeps its mark
    st.bulk_set_uid_edges("bulk", np.array([1]), np.array([2]))
    assert st.pred_versions["bulk"] == st.version
    st.delete_predicate("e")
    assert st.pred_versions["e"] == st.version
    floor_before = st.pred_floor
    st.apply_schema("name: string .")
    assert st.pred_floor == st.version > floor_before


def test_version_for_scoping(monkeypatch):
    st = PostingStore()
    st.set_edge("a", 1, 2)
    va = st.version
    st.set_edge("b", 1, 2)
    vb = st.version
    assert ivm.version_for(st, {"a"}) == va
    assert ivm.version_for(st, {"b"}) == vb
    assert ivm.version_for(st, {"a", "b"}) == vb
    assert ivm.version_for(st, {"zzz"}) == 0       # never-mutated pred
    assert ivm.version_for(st, None) == st.version  # unknowable footprint
    assert ivm.hop_version(st, "a") == va
    # the floor dominates every footprint after a schema change
    st.apply_schema("a: [uid] .")
    assert ivm.version_for(st, {"zzz"}) == st.pred_floor == st.version
    assert ivm.version_for(st, {"a"}) == st.version
    # kill switch: bare global version for everything
    monkeypatch.setenv("DGRAPH_TPU_IVM", "0")
    st.set_edge("a", 5, 6)
    assert ivm.version_for(st, {"zzz"}) == st.version
    # version-less duck stores never cache
    class Duck:
        pass
    assert ivm.version_for(Duck(), {"a"}) is None


def test_result_version_footprints():
    from dgraph_tpu import gql

    st = PostingStore()
    st.set_edge("friend", 1, 2)
    vf = st.version
    st.set_value("name", 1, TypedValue(TypeID.STRING, "x"))
    p = gql.parse("{ q(func: uid(0x1)) { friend { uid } } }", None)
    assert ivm.result_version(st, p) == vf
    # expand() makes the footprint unknowable: global version
    p2 = gql.parse("{ q(func: uid(0x1)) { expand(_all_) } }", None)
    assert ivm.result_version(st, p2) == st.version


def test_delta_base_window_and_refresh_consumption():
    st = _seed_store()
    am = ArenaManager(st)
    am.data("friend")  # drains the seed journal
    assert "friend" not in st.delta
    base_expected = st.pred_versions["friend"]
    st.set_edge("friend", 1, 5)
    st.set_edge("friend", 2, 4)
    assert st.delta_base["friend"] == base_expected  # window-open version
    am.refresh()
    assert "friend" not in st.delta_base  # consumed with the journal


# ------------------------------------------------------- delta stream


def test_delta_stream_events_cursor_and_overflow():
    ds = DeltaStream(cap=16)
    ds.publish_edge("p", 1, 2, +1, version=5)
    ds.publish_pred("q", version=6)
    ds.publish_epoch(version=7)
    evs, cur, lost = ds.read_since(0)
    assert not lost
    assert [(e[2], e[3], e[6]) for e in evs] == [
        ("p", "edge", 1), ("q", "pred", 0), ("", "epoch", 0)
    ]
    assert cur == 3
    # overflow: the oldest events fall off and a stale cursor is told so
    for i in range(40):
        ds.publish_edge("p", i, i + 1, +1, version=10 + i)
    evs, cur2, lost = ds.read_since(cur)
    assert lost and ds.dropped > 0
    assert len(evs) == 16  # the ring's worth
    # a current cursor reads clean again
    _evs, cur3, lost = ds.read_since(cur2)
    assert not lost and cur3 == cur2


def test_attach_stream_idempotent_and_store_publishes():
    st = PostingStore()
    ds = ivm.attach_stream(st)
    assert ivm.attach_stream(st) is ds
    st.set_edge("e", 1, 2)                  # edge event
    st.del_edge("e", 1, 2)                  # edge event (sign -1)
    st.set_value("v", 1, TypedValue(TypeID.STRING, "x"))  # pred event
    st.apply_schema("v: string .")          # epoch event
    evs, _cur, _lost = ds.read_since(0)
    kinds = [(e[2], e[3], e[6]) for e in evs]
    assert kinds == [
        ("e", "edge", 1), ("e", "edge", -1), ("v", "pred", 0),
        ("", "epoch", 0),
    ]


# ------------------------------------------- predicate-scoped caching


def test_hop_cache_survives_unrelated_write_and_repairs_own():
    st = _seed_store()
    eng = QueryEngine(st)
    q = "{ q(func: uid(0x1)) { name friend { name } } }"
    r1 = eng.run(q)
    h0 = QCACHE_HOP_EVENTS.snapshot()
    eng.run(q)
    h1 = QCACHE_HOP_EVENTS.snapshot()
    assert h1.get("hit", 0) > h0.get("hit", 0)
    # unrelated predicate: the hop entry stays a hit
    st.set_edge("unrelated", 9, 10)
    assert eng.run(q) == r1
    h2 = QCACHE_HOP_EVENTS.snapshot()
    assert h2.get("hit", 0) > h1.get("hit", 0)
    assert h2.get("miss", 0) == h1.get("miss", 0)
    assert h2.get("stale", 0) == h1.get("stale", 0)
    # own predicate, small delta: REPAIRED in place — still a hit, and
    # byte-identical to a fresh engine over the post-write store
    rep0 = IVM_REPAIRS.snapshot()
    st.set_edge("friend", 1, 5)
    r2 = eng.run(q)
    rep1 = IVM_REPAIRS.snapshot()
    assert rep1.get(("hop", "repaired"), 0) > rep0.get(("hop", "repaired"), 0)
    h3 = QCACHE_HOP_EVENTS.snapshot()
    assert h3.get("hit", 0) > h2.get("hit", 0)
    assert r2 == QueryEngine(st).run(q)
    assert any(f.get("name") == "Eve" for f in r2["q"][0]["friend"])


def test_reverse_arena_entries_repair_too():
    st = _seed_store()
    eng = QueryEngine(st)
    q = "{ q(func: uid(0x3)) { name ~friend { name } } }"
    eng.run(q)
    eng.arenas.reverse("friend")  # ensure the reverse arena is cached
    r1 = eng.run(q)
    st.set_edge("friend", 5, 3)  # a new in-edge of 0x3
    r2 = eng.run(q)
    assert r2 == QueryEngine(st).run(q)
    assert r2 != r1
    assert any(f.get("name") == "Eve" for f in r2["q"][0]["~friend"])


def test_result_cache_scoped_invalidation_server(monkeypatch):
    monkeypatch.setenv("DGRAPH_TPU_CACHE", "1")
    monkeypatch.setenv("DGRAPH_TPU_IVM", "1")
    srv = DgraphServer(_seed_store())
    srv.start()
    try:
        q = "{ q(func: uid(0x1)) { name friend { name } } }"
        want = _post(srv.addr, q)
        want.pop("server_latency", None)
        t0 = QCACHE_RESULT_EVENTS.snapshot()
        _post(srv.addr, q)
        t1 = QCACHE_RESULT_EVENTS.snapshot()
        assert t1.get("hit", 0) > t0.get("hit", 0)
        # unrelated-predicate write: the memoized response stays a hit
        _post(srv.addr, 'mutation { set { <0x9> <hobby> "chess" . } }')
        out = _post(srv.addr, q)
        out.pop("server_latency", None)
        t2 = QCACHE_RESULT_EVENTS.snapshot()
        assert out == want
        assert t2.get("hit", 0) > t1.get("hit", 0)
        assert t2.get("miss", 0) == t1.get("miss", 0)
        # footprint write: fresh result, never stale
        _post(srv.addr, "mutation { set { <0x1> <friend> <0x5> . } }")
        out2 = _post(srv.addr, q)
        assert any(
            f.get("name") == "Eve" for f in out2["q"][0]["friend"]
        ), out2
    finally:
        srv.stop()


def test_ivm_off_restores_global_keys(monkeypatch):
    monkeypatch.setenv("DGRAPH_TPU_CACHE", "1")
    monkeypatch.setenv("DGRAPH_TPU_IVM", "0")
    srv = DgraphServer(_seed_store())
    srv.start()
    try:
        q = "{ q(func: uid(0x1)) { name friend { name } } }"
        _post(srv.addr, q)
        _post(srv.addr, q)
        t1 = QCACHE_RESULT_EVENTS.snapshot()
        # ANY write invalidates EVERY entry under the legacy keying
        _post(srv.addr, 'mutation { set { <0x9> <hobby> "chess" . } }')
        _post(srv.addr, q)
        t2 = QCACHE_RESULT_EVENTS.snapshot()
        assert t2.get("hit", 0) == t1.get("hit", 0)
        assert (
            t2.get("stale", 0) + t2.get("miss", 0)
            > t1.get("stale", 0) + t1.get("miss", 0)
        )
    finally:
        srv.stop()


def test_schema_mutation_invalidates_everything(monkeypatch):
    monkeypatch.setenv("DGRAPH_TPU_CACHE", "1")
    srv = DgraphServer(_seed_store())
    srv.start()
    try:
        q = "{ q(func: uid(0x1)) { name } }"
        _post(srv.addr, q)
        _post(srv.addr, q)
        t1 = QCACHE_RESULT_EVENTS.snapshot()
        _post(srv.addr, "mutation { schema { hobby: string . } }")
        _post(srv.addr, q)
        t2 = QCACHE_RESULT_EVENTS.snapshot()
        assert t2.get("hit", 0) == t1.get("hit", 0)  # the floor killed it
    finally:
        srv.stop()


# --------------------------------------------- repair == rebuild (hop)


def _rand_graph(rng, n_uids=60, n_edges=220):
    src = rng.integers(1, n_uids, size=n_edges).astype(np.int64)
    dst = rng.integers(1, n_uids, size=n_edges).astype(np.int64)
    keep = src != dst
    return src[keep], dst[keep]


def _rand_delta(rng, arena, n_uids=60, k_add=6, k_del=6):
    """(adds, dels): adds absent from the arena, dels present."""
    have = set()
    h_dst = arena.host_dst()
    for i, u in enumerate(arena.h_src):
        for d in h_dst[arena.h_offsets[i]:arena.h_offsets[i + 1]]:
            have.add((int(u), int(d)))
    adds = set()
    while len(adds) < k_add:
        s, d = int(rng.integers(1, n_uids + 8)), int(rng.integers(1, n_uids + 8))
        if s != d and (s, d) not in have:
            adds.add((s, d))
    dels = set(
        list(have)[i] for i in rng.choice(
            len(have), size=min(k_del, len(have)), replace=False
        )
    )
    to_arr = lambda s: np.array(sorted(s), dtype=np.int64).reshape(-1, 2)  # noqa: E731
    return to_arr(adds), to_arr(dels)


def test_repair_hop_entry_equals_rebuild_property():
    for seed in range(8):
        rng = np.random.default_rng(seed)
        src, dst = _rand_graph(rng)
        a = csr_from_edges(src, dst)
        # frontier: arbitrary order, duplicates legal, rowless uids too
        frontier = rng.integers(1, 70, size=12).astype(np.int64)
        out, seg = a.expand_host(a.rows_for_uids_host(frontier))
        adds, dels = _rand_delta(rng, a)
        a.apply_delta(adds, dels)
        fixed = repair_hop_entry(out, seg, frontier, adds, dels)
        assert fixed is not None
        want_out, want_seg = a.expand_host(a.rows_for_uids_host(frontier))
        np.testing.assert_array_equal(fixed[0], want_out)
        np.testing.assert_array_equal(fixed[1], want_seg)


def test_repair_hop_entry_inconsistent_delete_returns_none():
    rng = np.random.default_rng(3)
    src, dst = _rand_graph(rng)
    a = csr_from_edges(src, dst)
    frontier = a.h_src[:4].astype(np.int64)
    out, seg = a.expand_host(a.rows_for_uids_host(frontier))
    bogus = np.array([[int(frontier[0]), 10_000]], dtype=np.int64)
    assert repair_hop_entry(
        out, seg, frontier, np.zeros((0, 2), np.int64), bogus
    ) is None


def test_repair_zero_delta_rekeys_entries_on_facet_touch():
    """A facet-only touch bumps the pred version but leaves (out,
    seg_ptr) exact: the entry must survive as a re-keyed hit."""
    st = _seed_store()
    eng = QueryEngine(st)
    q = "{ q(func: uid(0x1)) { friend { name } } }"
    eng.run(q)
    eng.run(q)
    h1 = QCACHE_HOP_EVENTS.snapshot()
    # facet write on an EXISTING edge: journal records an empty touch
    st.set_edge("friend", 1, 2, facets={"since": TypedValue(TypeID.INT, 7)})
    eng.run(q)
    h2 = QCACHE_HOP_EVENTS.snapshot()
    assert h2.get("hit", 0) > h1.get("hit", 0)
    assert h2.get("miss", 0) == h1.get("miss", 0)


# ------------------------------------------- repair == rebuild (tiles)


def _dense(pt):
    m = np.zeros((pt.nb * pt.t, pt.nb * pt.t), np.float32)
    tl = np.asarray(pt.tiles)
    bi = np.asarray(pt.bi)
    bj = np.asarray(pt.bj)
    for k in range(pt.n_tiles):
        m[bi[k] * pt.t:(bi[k] + 1) * pt.t,
          bj[k] * pt.t:(bj[k] + 1) * pt.t] += tl[k]
    return m


def test_tile_repair_equals_rebuild_property(monkeypatch):
    from dgraph_tpu.ops import spgemm

    monkeypatch.setenv("DGRAPH_TPU_TILE", "8")
    for seed in range(4):
        rng = np.random.default_rng(100 + seed)
        src, dst = _rand_graph(rng, n_uids=48, n_edges=400)
        a = csr_from_edges(src, dst)
        pt = a.tiles()
        assert pt is not None
        # delta constrained to STORED blocks (repairable by contract)
        hbi = np.asarray(pt.bi)[: pt.n_tiles]
        hbj = np.asarray(pt.bj)[: pt.n_tiles]
        blocks = set(zip(hbi.tolist(), hbj.tolist()))
        adds, dels = _rand_delta(rng, a, n_uids=48)
        adds = np.array(
            [e for e in adds
             if (e[0] // 8, e[1] // 8) in blocks
             and e[0] < pt.nb * 8 and e[1] < pt.nb * 8],
            dtype=np.int64,
        ).reshape(-1, 2)
        a.apply_delta(adds, dels)
        pt2 = a._tiles
        assert pt2 is not None, "in-grid delta must repair, not drop"
        fresh = spgemm.build_tiles(a.h_src, a.h_offsets, a.host_dst(), t=8)
        # block lists may differ by emptied blocks; the densified
        # adjacency and the degree vector must match exactly
        got, want = _dense(pt2), _dense(fresh)
        n = max(got.shape[0], want.shape[0])
        got = np.pad(got, ((0, n - got.shape[0]),) * 2)
        want = np.pad(want, ((0, n - want.shape[0]),) * 2)
        np.testing.assert_array_equal(got, want)
        nd = max(pt2.degs.shape[0], fresh.degs.shape[0])
        np.testing.assert_array_equal(
            np.pad(np.asarray(pt2.degs), (0, nd - pt2.degs.shape[0])),
            np.pad(np.asarray(fresh.degs), (0, nd - fresh.degs.shape[0])),
        )


def test_tile_repair_new_block_falls_back(monkeypatch):
    monkeypatch.setenv("DGRAPH_TPU_TILE", "8")
    rng = np.random.default_rng(7)
    # two tight communities: block (0,*) and far block — plenty of
    # UN-materialized blocks between them
    src = rng.integers(1, 8, size=60).astype(np.int64)
    dst = rng.integers(1, 8, size=60).astype(np.int64)
    src2 = rng.integers(40, 47, size=60).astype(np.int64)
    dst2 = rng.integers(40, 47, size=60).astype(np.int64)
    a = csr_from_edges(
        np.concatenate([src, src2]), np.concatenate([dst, dst2])
    )
    pt = a.tiles()
    assert pt is not None
    # an edge bridging the communities lands in a block never stored
    a.apply_delta(np.array([[2, 42]], dtype=np.int64),
                  np.empty((0, 2), np.int64))
    assert a._tiles is None  # repair refused: rebuild on next use
    assert a.tiles() is not None  # and the rebuild includes the bridge


def test_degree_histogram_incremental_equals_recompute():
    for seed in range(6):
        rng = np.random.default_rng(200 + seed)
        src, dst = _rand_graph(rng)
        a = csr_from_edges(src, dst)
        a.degree_histogram()  # materialize so the incremental path runs
        adds, dels = _rand_delta(rng, a, k_add=8, k_del=8)
        a.apply_delta(adds, dels)
        got = a._deg_hist.copy()
        del a._deg_hist
        want = a.degree_histogram()
        n = max(len(got), len(want))
        np.testing.assert_array_equal(
            np.pad(got, (0, n - len(got))), np.pad(want, (0, n - len(want)))
        )


# --------------------------------------------------- planner repair gate


def test_repair_route_modes(monkeypatch):
    from dgraph_tpu.query import planner

    # force: always (cap still bounds)
    monkeypatch.setenv("DGRAPH_TPU_IVM_REPAIR", "force")
    assert planner.repair_route(4, 100.0) == (True, None)
    assert planner.repair_route(10_000, 100.0) == (False, None)
    # off: never
    monkeypatch.setenv("DGRAPH_TPU_IVM_REPAIR", "0")
    assert planner.repair_route(1, 100.0) == (False, None)
    # planner off: the static cap IS the decision
    monkeypatch.setenv("DGRAPH_TPU_IVM_REPAIR", "1")
    monkeypatch.setenv("DGRAPH_TPU_PLANNER", "0")
    assert planner.repair_route(4, 100.0) == (True, None)
    assert planner.repair_route(9_999, 100.0) == (False, None)
    # planner on: recorded decision with both estimates; a tiny delta
    # against a warm entry repairs, a delta rivaling the entry rebuilds
    monkeypatch.delenv("DGRAPH_TPU_PLANNER", raising=False)
    # priced at the priors: the rates that other tests of this process
    # refined online (process state) are no part of what this checks
    planner._reset_for_tests()
    ok, dec = planner.repair_route(2, 5_000.0)
    assert ok and dec is not None and dec["route"] == "repair"
    assert dec["est_chosen_us"] > 0 and dec["est_other_us"] > 0
    ok, dec = planner.repair_route(500, 1.0)
    assert not ok and dec is not None and dec["route"] == "rebuild"


def test_repair_gate_cap_drops_instead(monkeypatch):
    """Over the delta cap the entries are dropped (stale), never
    half-repaired — and results stay correct."""
    monkeypatch.setenv("DGRAPH_TPU_IVM_REPAIR_MAX_DELTA", "1")
    st = _seed_store()
    eng = QueryEngine(st)
    q = "{ q(func: uid(0x1)) { friend { name } } }"
    eng.run(q)
    rep0 = IVM_REPAIRS.snapshot()
    st.set_edge("friend", 1, 4)
    st.set_edge("friend", 2, 1)
    st.set_edge("friend", 3, 5)  # 3 deltas > cap 1
    r = eng.run(q)
    rep1 = IVM_REPAIRS.snapshot()
    assert rep1.get(("hop", "repaired"), 0) == rep0.get(("hop", "repaired"), 0)
    assert r == QueryEngine(st).run(q)


# ------------------------------------- mutation-interleaved cache parity


def test_mutation_interleaved_cache_parity_concurrent_readers(monkeypatch):
    """Satellite: cache-on with predicate-scoped invalidation must stay
    byte-identical to a DGRAPH_TPU_CACHE=0 server across an interleaved
    write schedule, with concurrent readers hammering the cached server
    between writes."""
    workload = [
        "{ q(func: uid(0x1)) { name friend { name } } }",
        "{ q(func: uid(0x2)) { c: count(friend) } }",
        '{ q(func: eq(name, "Ann")) { friend { name } } }',
        "{ q(func: uid(0x3)) { name ~friend { name } } }",
    ]
    writes = [
        "mutation { set { <0x1> <friend> <0x4> . } }",
        'mutation { set { <0x6> <name> "Fay" . } }',
        "mutation { delete { <0x1> <friend> <0x2> . } }",
        'mutation { set { <0x9> <unrelated> "x" . } }',
        "mutation { set { <0x2> <friend> <0x1> . } }",
    ]
    monkeypatch.setenv("DGRAPH_TPU_CACHE", "0")
    plain = DgraphServer(_seed_store())
    plain.start()
    monkeypatch.setenv("DGRAPH_TPU_CACHE", "1")
    monkeypatch.setenv("DGRAPH_TPU_IVM", "1")
    cached = DgraphServer(_seed_store())
    cached.start()
    errs = []
    try:
        for step, w in enumerate(writes):
            stop = time.monotonic() + 0.15

            def reader(seed):
                rng = np.random.default_rng(seed)
                try:
                    while time.monotonic() < stop:
                        _post(cached.addr,
                              workload[int(rng.integers(len(workload)))])
                except Exception as e:  # pragma: no cover
                    errs.append(e)

            ts = [
                threading.Thread(target=reader, args=(step * 10 + s,))
                for s in range(6)
            ]
            for t in ts:
                t.start()
            # the write lands on BOTH servers while readers run
            _post(plain.addr, w)
            _post(cached.addr, w)
            for t in ts:
                t.join(timeout=30)
            assert not errs, errs[:2]
            # quiesced checkpoint: identical responses, byte for byte
            for q in workload:
                a = _post(plain.addr, q)
                b = _post(cached.addr, q)
                a.pop("server_latency", None)
                b.pop("server_latency", None)
                assert a == b, (step, q)
    finally:
        plain.stop()
        cached.stop()


# --------------------------------------------------------- subscriptions


@pytest.fixture
def sub_srv(monkeypatch):
    from dgraph_tpu import obs

    monkeypatch.setenv("DGRAPH_TPU_CACHE", "1")
    monkeypatch.setenv("DGRAPH_TPU_IVM", "1")
    monkeypatch.setenv("DGRAPH_TPU_SUBS_DEBOUNCE_MS", "5")
    rec = obs.configure(ratio=1.0, seed=13)
    srv = DgraphServer(_seed_store())
    srv.start()
    yield srv, rec
    srv.stop()
    obs.configure(ratio=0.0)


def test_subscribe_push_on_affecting_write_only(sub_srv):
    srv, rec = sub_srv
    reg = json.load(urllib.request.urlopen(urllib.request.Request(
        srv.addr + "/subscribe",
        data=b"{ s(func: uid(0x1)) { name friend { name } } }",
    ), timeout=30))
    assert sorted(reg["preds"]) == ["friend", "name"]
    sub = srv.subs.get(reg["sub_id"])
    snap = sub.next_event(timeout=10)
    assert snap["kind"] == "snapshot" and snap["seq"] == 1
    assert snap["data"]["s"][0]["name"] == "Ann"
    # unrelated predicate: silence
    _post(srv.addr, 'mutation { set { <0x9> <hobby> "chess" . } }')
    assert sub.next_event(timeout=0.6) is None
    # affecting predicate: exactly one push, trace-linked
    _post(srv.addr, "mutation { set { <0x1> <friend> <0x5> . } }")
    ev = sub.next_event(timeout=10)
    assert ev is not None and ev["kind"] == "update", ev
    assert any(f.get("name") == "Eve" for f in ev["data"]["s"][0]["friend"])
    assert ev["preds"] and "friend" in ev["preds"]
    assert ev["trace_id"]
    tr = rec.trace(ev["trace_id"])
    assert tr is not None
    assert any(s["name"] == "subs.eval" for s in tr["spans"])
    # cancel: terminal event, table drained
    out = json.load(urllib.request.urlopen(urllib.request.Request(
        srv.addr + "/subscribe/cancel?id=" + reg["sub_id"], data=b""
    ), timeout=10))
    assert out["code"] == "Success"
    assert sub.next_event(timeout=5)["kind"] == "cancelled"
    assert srv.subs.get(reg["sub_id"]) is None


def test_subscribe_sse_stream_inline(sub_srv):
    srv, _rec = sub_srv
    frames = []
    done = threading.Event()

    def consume():
        req = urllib.request.Request(
            srv.addr + "/subscribe?stream=1",
            data=b"{ s(func: uid(0x2)) { c: count(friend) } }",
        )
        with urllib.request.urlopen(req, timeout=30) as resp:
            assert resp.headers["Content-Type"] == "text/event-stream"
            buf = b""
            for line in resp:
                if line.strip() == b"" and buf:
                    for ln in buf.split(b"\n"):
                        if ln.startswith(b"data: "):
                            frames.append(json.loads(ln[6:]))
                    buf = b""
                    if frames and frames[-1].get("kind") == "cancelled":
                        done.set()
                        return
                elif not line.startswith(b":"):
                    buf += line.strip() + b"\n"

    t = threading.Thread(target=consume, daemon=True)
    t.start()
    deadline = time.monotonic() + 10
    while not frames and time.monotonic() < deadline:
        time.sleep(0.02)
    assert frames and frames[0]["kind"] == "snapshot"
    _post(srv.addr, "mutation { set { <0x2> <friend> <0x4> . } }")
    deadline = time.monotonic() + 10
    while len(frames) < 2 and time.monotonic() < deadline:
        time.sleep(0.02)
    assert len(frames) >= 2 and frames[1]["kind"] == "update"
    assert frames[1]["data"]["s"][0]["c"] == 3
    sid = frames[0]["sub_id"]
    urllib.request.urlopen(urllib.request.Request(
        srv.addr + "/subscribe/cancel?id=" + sid, data=b""
    ), timeout=10)
    assert done.wait(timeout=10)
    t.join(timeout=10)


def test_subscribe_unchanged_result_skips(sub_srv):
    srv, _rec = sub_srv
    reg = json.load(urllib.request.urlopen(urllib.request.Request(
        srv.addr + "/subscribe", data=b"{ s(func: uid(0x1)) { name } }",
    ), timeout=30))
    sub = srv.subs.get(reg["sub_id"])
    assert sub.next_event(timeout=10)["kind"] == "snapshot"
    s0 = SUBS_EVENTS.snapshot()
    # footprint predicate (name) mutates on ANOTHER node: re-evaluated,
    # result unchanged, no push
    _post(srv.addr, 'mutation { set { <0x5> <name> "Eve2" . } }')
    assert sub.next_event(timeout=1.0) is None
    s1 = SUBS_EVENTS.snapshot()
    assert s1.get("skip", 0) > s0.get("skip", 0)
    srv.subs.cancel(reg["sub_id"])


def test_subscribe_quota_and_caps(sub_srv, monkeypatch):
    srv, _rec = sub_srv
    srv.subs.per_tenant_default = 1
    body = b"{ s(func: uid(0x1)) { name } }"

    def register(tenant):
        return urllib.request.urlopen(urllib.request.Request(
            srv.addr + "/subscribe", data=body,
            headers={"X-Dgraph-Tenant": tenant},
        ), timeout=30)

    ok = json.load(register("alpha"))
    with pytest.raises(urllib.error.HTTPError) as ei:
        register("alpha")
    assert ei.value.code == 429
    assert int(ei.value.headers["Retry-After"]) >= 1
    # the quota is tenant-scoped: another tenant still registers
    ok2 = json.load(register("beta"))
    # parse errors and mutations are client errors
    with pytest.raises(urllib.error.HTTPError) as ei:
        urllib.request.urlopen(urllib.request.Request(
            srv.addr + "/subscribe",
            data=b"mutation { set { <0x1> <name> \"x\" . } }",
        ), timeout=30)
    assert ei.value.code == 400
    srv.subs.cancel(ok["sub_id"])
    srv.subs.cancel(ok2["sub_id"])


def test_subscribe_debounce_coalesces_burst(monkeypatch):
    monkeypatch.setenv("DGRAPH_TPU_SUBS_DEBOUNCE_MS", "400")
    monkeypatch.setenv("DGRAPH_TPU_CACHE", "1")
    srv = DgraphServer(_seed_store())
    srv.start()
    try:
        sub = srv.subs.register("{ s(func: uid(0x1)) { friend { uid } } }")
        assert sub.next_event(timeout=10)["kind"] == "snapshot"
        for d in (5, 6, 7, 8):
            _post(srv.addr, "mutation { set { <0x1> <friend> <0x%x> . } }" % d)
        ev = sub.next_event(timeout=10)
        assert ev is not None and ev["kind"] == "update"
        # the burst coalesced into ONE push carrying the final state
        assert len(ev["data"]["s"][0]["friend"]) == 6
        assert sub.next_event(timeout=0.7) is None
    finally:
        srv.stop()


def test_subscribe_grpc_server_stream(sub_srv):
    grpc = pytest.importorskip("grpc")
    from dgraph_tpu.serve import proto as _p
    from dgraph_tpu.serve.grpc_server import GrpcServer, encode_request

    srv, _rec = sub_srv
    gs = GrpcServer(srv)
    gs.start()
    try:
        ch = grpc.insecure_channel(f"127.0.0.1:{gs.port}")
        call = ch.unary_stream("/protos.Dgraph/Subscribe")(
            encode_request("{ s(func: uid(0x1)) { c: count(friend) } }"),
            timeout=30,
        )
        got = []

        def consume():
            try:
                for m in call:
                    got.append(_p.decode_response(m))
            except grpc.RpcError:
                pass  # the test cancels the call when done

        t = threading.Thread(target=consume, daemon=True)
        t.start()
        deadline = time.monotonic() + 10
        while not got and time.monotonic() < deadline:
            time.sleep(0.02)
        assert got, "no snapshot frame"
        assert got[0]["s"][0]["c"] == 2
        meta = got[0]["_subscription_"][0]
        assert meta["kind"] == "snapshot" and meta["sub_id"]
        _post(srv.addr, "mutation { set { <0x1> <friend> <0x4> . } }")
        deadline = time.monotonic() + 10
        while len(got) < 2 and time.monotonic() < deadline:
            time.sleep(0.02)
        assert len(got) >= 2 and got[1]["s"][0]["c"] == 3
        assert got[1]["_subscription_"][0]["kind"] == "update"
        call.cancel()
        t.join(timeout=10)
        ch.close()
    finally:
        gs.stop()


def test_unknowable_footprint_sub_idles_quietly(monkeypatch):
    """Regression (review): a footprint-None subscription (expand())
    must NOT re-evaluate on the notifier's idle timeout ticks — only
    when mutations actually arrive."""
    monkeypatch.setenv("DGRAPH_TPU_CACHE", "1")
    srv = DgraphServer(_seed_store())
    srv.start()
    try:
        sub = srv.subs.register("{ s(func: uid(0x1)) { expand(_all_) } }")
        assert sub.footprint is None
        assert sub.next_event(timeout=10)["kind"] == "snapshot"
        evals0 = sub.evals
        time.sleep(2.3)  # two idle wait_for timeouts, zero mutations
        assert sub.evals == evals0, "idle ticks re-evaluated the sub"
        # a real mutation still reaches it (any predicate affects it)
        _post(srv.addr, 'mutation { set { <0x7> <whatever> "x" . } }')
        deadline = time.monotonic() + 5
        while sub.evals == evals0 and time.monotonic() < deadline:
            time.sleep(0.02)
        assert sub.evals > evals0
    finally:
        srv.stop()


def test_scheduler_shed_defers_instead_of_cancelling(monkeypatch):
    """Regression (review): retryable 429-class backpressure from the
    scheduler must leave the subscription REGISTERED (triggers
    restored), never tear it down."""
    monkeypatch.setenv("DGRAPH_TPU_CACHE", "1")
    srv = DgraphServer(_seed_store())
    srv.start()
    try:
        sub = srv.subs.register("{ s(func: uid(0x1)) { friend { uid } } }")
        assert sub.next_event(timeout=10)["kind"] == "snapshot"
        s0 = SUBS_EVENTS.snapshot()
        # choke admission: every eval sheds SchedOverloadError
        srv.scheduler.queue_cap = 0
        _post_err = None
        try:
            _post(srv.addr, "mutation { set { <0x1> <friend> <0x5> . } }")
        except urllib.error.HTTPError as e:  # pragma: no cover — host-dependent
            _post_err = e  # mutations bypass the scheduler; shouldn't 429
        assert _post_err is None
        deadline = time.monotonic() + 5
        while (
            SUBS_EVENTS.snapshot().get("deferred", 0)
            <= s0.get("deferred", 0)
            and time.monotonic() < deadline
        ):
            time.sleep(0.02)
        assert (
            SUBS_EVENTS.snapshot().get("deferred", 0) > s0.get("deferred", 0)
        )
        assert not sub.token.cancelled
        assert srv.subs.get(sub.id) is not None
        assert sub.pending, "triggers must be restored for the retry"
        # admission reopens: the retry delivers the push
        srv.scheduler.queue_cap = 256
        ev = sub.next_event(timeout=10)
        assert ev is not None and ev["kind"] == "update", ev
    finally:
        srv.stop()


def test_server_stop_cancels_subscriptions(monkeypatch):
    monkeypatch.setenv("DGRAPH_TPU_CACHE", "1")
    srv = DgraphServer(_seed_store())
    srv.start()
    sub = srv.subs.register("{ s(func: uid(0x1)) { name } }")
    assert sub.next_event(timeout=10)["kind"] == "snapshot"
    srv.stop()
    assert sub.token.cancelled
    assert sub.next_event(timeout=5)["kind"] == "cancelled"


def test_debug_store_ivm_section_and_series(sub_srv):
    srv, _rec = sub_srv
    reg = json.load(urllib.request.urlopen(urllib.request.Request(
        srv.addr + "/subscribe", data=b"{ s(func: uid(0x1)) { name } }",
    ), timeout=30))
    with urllib.request.urlopen(srv.addr + "/debug/store", timeout=10) as r:
        st = json.loads(r.read().decode())
    assert st["ivm"]["tracked_preds"] >= 2
    assert st["ivm"]["stream"]["seq"] >= 0
    assert st["ivm"]["subs"]["active"] == 1
    assert st["ivm"]["subs"]["subs"][0]["id"] == reg["sub_id"]
    with urllib.request.urlopen(
        srv.addr + "/debug/prometheus_metrics", timeout=10
    ) as r:
        text = r.read().decode()
    assert "dgraph_subscription_active" in text
    assert "dgraph_subscription_evals_total" in text
    assert "dgraph_ivm_deltas_total" in text
    srv.subs.cancel(reg["sub_id"])


# --------------------------------------------- QoS priority satellite


def test_priority_folds_into_effective_weight():
    from dgraph_tpu.sched.qos import TenantConfig

    assert TenantConfig("a", weight=1.0).effective_weight == 1.0
    assert TenantConfig("a", weight=1.0, priority="high").effective_weight == 2.0
    assert TenantConfig("a", weight=2.0, priority="critical").effective_weight == 8.0
    assert TenantConfig("a", weight=2.0, priority="low").effective_weight == 1.0
    # unknown class degrades to standard, never starves
    assert TenantConfig("a", weight=3.0, priority="wat").effective_weight == 3.0


def test_priority_drives_cohort_pick(monkeypatch):
    """The same-weight tenants split flush slots by PRIORITY class now:
    critical (×4) wins 4 of every 5 picks against standard."""
    from dgraph_tpu import gql
    from dgraph_tpu.sched import Cohort, SchedRequest
    from dgraph_tpu.sched.scheduler import CohortScheduler

    monkeypatch.setenv("DGRAPH_TPU_QOS_TENANTS", json.dumps({
        "vip": {"weight": 1, "priority": "critical"},
        "std": {"weight": 1},
    }))
    monkeypatch.setattr(CohortScheduler, "_worker_loop", lambda self: None)
    srv = DgraphServer(_seed_store())  # not started: data structure host
    sched = CohortScheduler(srv, max_batch=1, flush_ms=60_000, queue_cap=999)
    try:
        parsed = gql.parse("{ q(func: uid(0x1)) { name } }", None)
        for tenant in ("vip", "std"):
            for i in range(40):
                c = Cohort(("s", tenant, i), tenant=tenant)
                c.reqs = [SchedRequest(parsed, tenant=tenant)]
                sched._queues[(tenant, ("s", tenant, i))] = c
        picks = []
        with sched._cond:
            for _ in range(50):
                key, reason = sched._due_cohort(time.monotonic())
                assert reason == "full"
                picks.append(key[0])
                sched._queues.pop(key)
        assert picks.count("vip") == 40
        assert picks.count("std") == 10
    finally:
        sched.stop()


# ------------------------------------------------------- lint rule


def test_naked_version_key_rule_golden_and_counterexamples():
    from dgraph_tpu.analysis.framework import check_source
    from dgraph_tpu.analysis.rules import NakedVersionKey

    bad = (
        "def probe(self, key):\n"
        "    v = self.engine.store.version\n"
        "    w = getattr(self._server.store, \"version\", None)\n"
        "    return self.cache.get(key, v or w)\n"
    )
    found = check_source(
        bad, [NakedVersionKey()], path="dgraph_tpu/cache/newtier.py"
    )
    assert len(found) == 2
    assert all(f.rule == "naked-version-key" for f in found)
    # out of scope: ivm/ (the sanctioned home) and non-keying layers
    assert check_source(
        bad, [NakedVersionKey()], path="dgraph_tpu/ivm/versions.py"
    ) == []
    assert check_source(
        bad, [NakedVersionKey()], path="dgraph_tpu/models/store.py"
    ) == []
    # non-store .version attributes don't trip it
    ok = (
        "def f(self):\n"
        "    return self.calibration.version + entry.version\n"
    )
    assert check_source(
        ok, [NakedVersionKey()], path="dgraph_tpu/cache/core.py"
    ) == []
    # pragma'd non-key reads pass
    pragma = (
        "def sig(self):\n"
        "    # graftlint: ignore[naked-version-key]\n"
        "    return getattr(self._server.store, \"version\", None)\n"
    )
    assert check_source(
        pragma, [NakedVersionKey()], path="dgraph_tpu/sched/x.py"
    ) == []


def test_tree_ships_clean_for_naked_version_key():
    import pathlib

    from dgraph_tpu.analysis.framework import run_rules
    from dgraph_tpu.analysis.rules import NakedVersionKey

    root = pathlib.Path(__file__).resolve().parents[1] / "dgraph_tpu"
    findings = run_rules([str(root)], [NakedVersionKey()])
    assert findings == [], [f"{f.path}:{f.line}" for f in findings]
