"""shortest(from:, to:, numpaths: k) on the device route (ops/kbfs.py via
query/shortest.py) against ``benchmark/reference_kpaths.py`` — uid for uid,
path for path, in the rule's order — and against the host's Dijkstra, on
seeded graphs of several predicates walked both ways; the work booked (the
ledger's ``edges``, the ``dgraph_kpath_*`` counters) by its definition; a
device fault handed to the Dijkstra and counted; and the
one-path search's programs, which this route shares a layout and helpers
with, lowered to the text they had before it.
"""

import collections
import hashlib
import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dgraph_tpu.models import PostingStore
from dgraph_tpu.ops import bfs, kbfs
from dgraph_tpu.query import planner
from dgraph_tpu.query.engine import QueryEngine
from dgraph_tpu.utils.metrics import (
    KPATH_ARRIVALS, KPATH_CANDIDATES, KPATH_LEVELS, KPATH_PATHS, KPATH_SEARCHES, PATH_SEARCHES)

from test_path_search import LISTED, PREDS, load, make_graph, on_host

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark")
sys.path.insert(0, BENCH)

import reference_kpaths  # noqa: E402


def ref_of(g, k, listed=LISTED):
    edges = {p: (np.array([u for u, _ in sorted(g[p])], np.int64),
                 np.array([v for _, v in sorted(g[p])], np.int64)) for p in g}
    return reference_kpaths.KPathReference(edges, listed.split(), k)


def paths_of(answer):
    """[[uid, ...], ...] of the ``_path_`` list, and [[hop predicate, ...], ...]."""
    out, keys = [], []
    for node in answer.get("_path_", []):
        uids, ks = [], []
        while True:
            uids.append(int(node["_uid_"], 16))
            nxt = [x for x in node if x not in ("_uid_", "@facets")]
            if not nxt:
                break
            assert len(nxt) == 1 and len(node[nxt[0]]) == 1
            ks.append(nxt[0])
            node = node[nxt[0]][0]
        out.append(uids)
        keys.append(ks)
    return out, keys


def counters():
    return {"levels": KPATH_LEVELS.value(), "paths": KPATH_PATHS.value(),
            **KPATH_ARRIVALS.snapshot(), **KPATH_CANDIDATES.snapshot(),
            **{"route_" + r: n for r, n in KPATH_SEARCHES.snapshot().items()}}


def grown(before):
    return {x: n - before[x] for x, n in counters().items()}


def text(src, dst, k, listed=LISTED):
    return "{ shortest(from: 0x%x, to: 0x%x, numpaths: %d) { %s } }" % (src, dst, k, listed)


CASES = [
    # id, seed, n, edges a predicate, hub, chain, k
    pytest.param(1, 60, 50, 0, 0, 2, id="sparse-k2"),
    pytest.param(2, 60, 50, 0, 0, 3, id="sparse-k3"),
    pytest.param(3, 60, 120, 0, 0, 3, id="dense-k3"),
    pytest.param(4, 200, 150, 0, 0, 5, id="wide-k5"),
    pytest.param(5, 200, 150, 150, 0, 3, id="hub-k3"),
    pytest.param(7, 40, 30, 0, 10, 3, id="chain-k3"),
    pytest.param(9, 500, 300, 0, 0, 2, id="large-k2"),
    pytest.param(11, 120, 200, 0, 0, 5, id="dense-k5"),
]


def pairs_for(g, seed, n, top, chain):
    rng = np.random.default_rng(200 + seed)
    pairs = [tuple(p) for p in rng.integers(1, n + 1, (8, 2)).tolist()]
    pairs += [(1, int(rng.integers(2, n + 1))), (top - 3, top - 1), (top - 1, 1), (1, top), (5, 5)]
    pairs.append(sorted(g["a"])[0])
    if chain:
        pairs += [(n, n + chain), (1, n + chain)]
    return pairs


@pytest.mark.parametrize("route", ["device", "host"])
@pytest.mark.parametrize("seed,n,per_pred,hub,chain,k", CASES)
def test_both_routes_agree_with_the_reference_uid_for_uid(
        monkeypatch, route, seed, n, per_pred, hub, chain, k):
    """Every path, in the rule's order, and the hop predicates; on the device
    the ledger's edges and every counter by the reference's count."""
    g, top = make_graph(seed, n, per_pred, hub, chain)
    e = load(g)
    ref = ref_of(g, k)
    if route == "host":
        on_host(monkeypatch)
    seen = {"found": 0, "fewer": 0, "again": 0, "loop": 0}
    for src, dst in pairs_for(g, seed, n, top, chain):
        want = ref.search(src, dst, with_paths=True)
        before = counters()
        got = e.run(text(src, dst, k))
        paths, keys = paths_of(got)
        assert paths == want["paths"], (src, dst)
        assert [len(p) - 1 for p in paths] == want["lengths"]
        for p, ks in zip(paths, keys):
            assert len(set(p)) == len(p)
            for u, v, key in zip(p, p[1:], ks):
                assert key == ref.holds(u, v)[0], (u, v)     # the first listed that holds it
        assert len({tuple(p) for p in paths}) == len(paths) <= k
        seen["found"] += bool(paths)
        seen["fewer"] += 0 < len(paths) < k
        seen["again"] += want["again"] > 0
        seen["loop"] += want["loop"] > 0
        grew = grown(before)
        if route == "host":
            assert grew["route_host"] == 1 and grew["route_device"] == 0
            continue
        assert grew["route_device"] == 1 and grew["route_fallback"] == 0
        assert e.stats["edges"] == want["edges"], (src, dst)
        assert {x: grew[x] for x in ("levels", "first", "again", "kept", "full", "loop")} == {
            x: want[x] for x in ("levels", "first", "again", "kept", "full", "loop")}, (src, dst)
        assert grew["first"] + grew["again"] == grew["kept"]
        assert grew["paths"] == len(paths) <= k
    assert seen["found"], seen
    if route == "device" and per_pred >= 120:
        assert seen["again"] and seen["loop"], seen


def film_graph(films):
    """A film-shaped store: each film a cast of actors, each role a
    performance node between them (uids: films 1..F, performances next,
    then actors: a uid space the listed arenas cover, so the planner gives
    the search to the device).  Returns the store and the uid of actor 0."""
    g = {"starring": set(), "performance.actor": set()}
    actor = len(films) + sum(map(len, films)) + 1
    perf = len(films) + 1
    for f, cast in enumerate(films, start=1):
        for a in cast:
            g["starring"].add((f, perf))
            g["performance.actor"].add((perf, actor + a))
            perf += 1
    return g, actor


FILM_LISTED = "~performance.actor ~starring starring performance.actor"


def load_film(g):
    e = QueryEngine(PostingStore())
    lines = [f"<0x{u:x}> <{p}> <0x{v:x}> ." for p in g for u, v in sorted(g[p])]
    e.run("mutation { schema { starring: uid . performance.actor: uid @reverse . } "
          "set { %s } }" % "\n".join(lines))
    return e


SHAPES = [
    # costars of one film (d = 4), who meet again past it only through three
    # other films (d = 12) and a third route (d = 16): the re-arrivals
    pytest.param([[1, 2, 3], [1, 4], [4, 5], [5, 2], [2, 6], [6, 7], [7, 8], [8, 1]],
                 1, 2, 3, [4, 12, 16], id="costar"),
    pytest.param([[1, 2], [2, 3]], 1, 3, 3, [8], id="fewer-than-k"),
    pytest.param([[1, 2], [3, 4]], 1, 4, 3, [], id="unreachable"),
    pytest.param([[1, 2], [2, 3]], 2, 2, 3, [0], id="from-is-to"),
    pytest.param([[1, 2, 3, 4], [1, 2, 3, 4], [1, 2, 3, 4]], 1, 4, 5, [4, 4, 4, 8, 8],
                 id="five-of-one-cast"),
]


@pytest.mark.parametrize("route", ["device", "host"])
@pytest.mark.parametrize("films,a,b,k,lengths", SHAPES)
def test_film_shaped_searches(monkeypatch, route, films, a, b, k, lengths):
    g, actor = film_graph(films)
    e = load_film(g)
    ref = ref_of(g, k, FILM_LISTED)
    src, dst = actor + a, actor + b
    if route == "host":
        on_host(monkeypatch)
    want = ref.search(src, dst, with_paths=True)
    assert want["lengths"] == lengths
    before = counters()
    paths, _ = paths_of(e.run(text(src, dst, k, FILM_LISTED)))
    assert paths == want["paths"]
    assert grown(before)["route_" + route] == 1


def test_a_pair_joined_by_two_predicates_is_one_hop(monkeypatch):
    """The Dijkstra pushed a path once a predicate that holds its hop and
    returned the same path twice (two of k = 2 here, the other route lost);
    both routes now return the two distinct paths the reference does."""
    g = {p: set() for p in PREDS}
    g["a"] |= {(1, 2), (2, 3), (1, 4)}
    g["b"] |= {(1, 2), (4, 3)}
    e = load(g)
    ref = ref_of(g, 2, "a b")
    want = ref.search(1, 3, with_paths=True)["paths"]
    assert want == [[1, 2, 3], [1, 4, 3]]
    assert paths_of(e.run(text(1, 3, 2, "a b")))[0] == want
    with monkeypatch.context() as m:
        on_host(m)
        assert paths_of(e.run(text(1, 3, 2, "a b")))[0] == want


def _kstate(e, listed, src, k):
    lay = e.arenas.path_layout(tuple((t.lstrip("~"), t.startswith("~")) for t in listed.split()))
    kt = lay.kpath_tables()
    cap, chunk = kbfs.capacities(kt.targets, int(kt.dst.shape[0]), kt.max_degree, k)
    return kt.arrays(), chunk, kbfs.start(kt.arrays(), jnp.int32(src), cap, chunk, k)


def test_levels_whose_candidates_outgrow_the_chunk():
    """Layered: 1 -> 40 uids -> 12 each; the second level's 480 candidates
    are several chunks of the layout's 64; a level a call, against the
    reference's counts."""
    g = {p: set() for p in PREDS}
    mids = list(range(2, 42))
    for i, u in enumerate(mids):
        g["a"].add((1, u))
        for j in range(12):
            g["b"].add((u, 100 + (i * 7 + j) % 60))
    for v in range(100, 160):
        g["a"].add((v, 500))
    e = load(g)
    k = 3
    kp, chunk, st = _kstate(e, "a b", 1, k)
    widest = 0
    while True:
        widest = max(widest, int(st["m"]))
        st = kbfs.run_levels(kp, st, jnp.int32(500), jnp.int32(1), chunk, k)
        if not bool((st["found"] < k) & (st["m"] > 0)):
            break
    assert widest == 480 > chunk
    want = ref_of(g, k, "a b").search(1, 500, with_paths=True)
    head = np.asarray(kbfs.finish(st, jnp.int32(500), k))
    assert head[0] == len(want["paths"]) == 3 and head[3] == want["edges"]
    assert list(head[4:9]) == [want[x] for x in ("first", "again", "kept", "full", "loop")]


def _device_answer(kp, st, to, chunk, k):
    """(the nine-word header, the paths as uid lists) of the search ``st``
    starts, through ``kbfs`` alone, all its levels in one call."""
    st = kbfs.run_levels(kp, st, jnp.int32(to), jnp.int32(1 << 30), chunk, k)
    got = np.asarray(kbfs.finish(st, jnp.int32(to), k))
    walks = got[kbfs.KHEAD:].reshape(k, bfs.PATH_CAP)
    paths = [[int(a) >> kbfs.shift(k) for a in reversed(w) if a != bfs.SENT]
             for w in walks[:got[0]]]
    return [int(x) for x in got[:kbfs.KHEAD]], paths


def _header_of(want) -> list:
    return [len(want["paths"])] + [want[x] for x in (
        "levels", "rows", "edges", "first", "again", "kept", "full", "loop")]


@pytest.mark.parametrize("k", [3, 4])
def test_one_chunk_splits_a_targets_candidates_every_way(k):
    """Level 1 is one chunk in which each target has more than k candidates:
    six back to the source (loops), six to a uid with edges (k listed, the
    rest full), six to a sink (k kept but not listed, the rest full) and two
    to ``to``; level 2 re-arrives at a uid of level 1, a loop through it and
    kept through the others, and fills ``to``.  The answers and all nine
    words of the header against the reference."""
    g = {p: set() for p in PREDS}
    mids = range(10, 16)
    g["a"] |= {(1, m) for m in mids} | {(m, t) for m in mids for t in (1, 50, 60)}
    g["a"] |= {(10, 99), (11, 99), (50, 10), (50, 99)}
    g["a"] |= {(200, 300 + j) for j in range(40)}   # a wide uid: a chunk of 64
    e = load(g)
    kp, chunk, st = _kstate(e, "a", 1, k)
    assert chunk >= 20                              # level 1 fits one chunk
    want = ref_of(g, k, "a").search(1, 99, with_paths=True)
    assert want["paths"][:3] == [[1, 10, 99], [1, 11, 99], [1, 10, 50, 99]]
    assert want["full"] > 0 and want["loop"] > 6 and want["again"] > 0
    head, paths = _device_answer(kp, st, 99, chunk, k)
    assert paths == want["paths"]
    assert head == _header_of(want)
    assert paths_of(e.run(text(1, 99, k, "a")))[0] == want["paths"]


def test_a_chunk_of_131072_slots_whose_last_slot_loops():
    """The widest slot the loop bit is packed beside: one level-1 chunk of
    C = 131,072 slots, each uid's pairs (``to``, the source), so that every
    second slot loops back to the source, the last (slot C - 1) too, and
    ``to`` takes k of 65,536 candidates."""
    M, C, k = 65536, 131072, 3
    T, S = M, M + 1
    ub = -(-(M + 2) // 128) * 128
    kdst = np.concatenate([np.tile([T, S], M), np.arange(M)]).astype(np.int32)
    deg = np.zeros(ub, np.int32)
    deg[:M], deg[S] = 2, M
    first = np.full(ub, 3 * M, np.int32)
    first[:M], first[S] = 2 * np.arange(M), 2 * M
    kp = tuple(jnp.asarray(x) for x in (kdst, first, deg, deg))
    cap, _ = kbfs.capacities(M + 2, 3 * M, M, k)
    head, paths = _device_answer(kp, kbfs.start(kp, jnp.int32(S), cap, C, k), T, C, k)
    g = {"a": {(m, t) for m in range(M) for t in (T, S)} | {(S, m) for m in range(M)}}
    want = ref_of(g, k, "a").search(S, T, with_paths=True)
    assert want["paths"] == [[S, 0, T], [S, 1, T], [S, 2, T]]
    assert want["loop"] == M and want["full"] == M - k
    assert paths == want["paths"]
    assert head == _header_of(want)


@pytest.mark.parametrize("route", ["device", "host"])
def test_a_level_of_uids_without_edges(monkeypatch, route):
    """A level that takes far more uids than hold an edge (299 sinks beside
    a chain of three): the level's kept arrivals are all listed for their
    parents, so the lists are sized by the uids a search can take."""
    g = {p: set() for p in PREDS}
    g["a"] |= {(1, u) for u in range(2, 301)} | {(1, 301), (301, 302), (302, 303), (1, 302)}
    e = load(g)
    if route == "host":
        on_host(monkeypatch)
    want = ref_of(g, 3, "a").search(1, 303, with_paths=True)
    assert want["paths"] == [[1, 302, 303], [1, 301, 302, 303]] and want["kept"] > 300
    before = counters()
    assert paths_of(e.run(text(1, 303, 3, "a")))[0] == want["paths"]
    assert grown(before)["route_" + route] == 1
    if route == "device":
        assert grown(before)["kept"] == want["kept"]


@pytest.mark.parametrize("films,a,b,chain,walks", [
    # a path of 149 hops beside one of 2: the long one takes two walks past
    # ``finish``'s (64 uids each), the short one none
    pytest.param(None, 1, 150, 149, 2, id="past-one-walk"),
    # paths of 4, 12 and 16 hops in 16 levels: each came back whole
    pytest.param(SHAPES[0].values[0], 1, 2, 0, 0, id="shorter-than-the-levels"),
])
def test_a_path_is_walked_on_only_where_its_walk_came_back_full(
        monkeypatch, films, a, b, chain, walks):
    if films is None:
        g = {p: set() for p in PREDS}
        g["a"] |= {(u, u + 1) for u in range(1, chain + 1)}
        g["b"] |= {(1, chain + 2), (chain + 2, chain + 1)}
        e, listed, src, dst = load(g), "a b", a, b
    else:
        g, actor = film_graph(films)
        e, listed, src, dst = load_film(g), FILM_LISTED, actor + a, actor + b
    want = ref_of(g, 2 if films is None else 3, listed).search(src, dst, with_paths=True)
    calls = []
    walk = kbfs.walk_back

    def counted(apar, at):
        if not isinstance(at, jax.core.Tracer):
            calls.append(at)
        return walk(apar, at)

    monkeypatch.setattr(kbfs, "walk_back", counted)
    before = counters()
    got, _ = paths_of(e.run(text(src, dst, len(want["paths"]), listed)))
    assert got == want["paths"] and len(calls) == walks
    assert grown(before)["route_device"] == 1
    if films is None:
        assert [len(p) - 1 for p in got] == [2, chain]


def test_the_one_path_rule_at_k_1():
    """The k-path programs at k = 1 take the rule's one path (least uid
    sequence from ``from``), as the reference; the one-path route (ops/bfs.py)
    keeps its own tie rule and agrees on the length."""
    g, _ = make_graph(3, 60, 120)
    e = load(g)
    ref = ref_of(g, 1)
    rng = np.random.default_rng(5)
    for src, dst in rng.integers(1, 61, (12, 2)).tolist():
        if src == dst:
            continue
        want = ref.search(src, dst, with_paths=True)
        kp, chunk, st = _kstate(e, LISTED, src, 1)
        st = kbfs.run_levels(kp, st, jnp.int32(dst), jnp.int32(1 << 30), chunk, 1)
        got = np.asarray(kbfs.finish(st, jnp.int32(dst), 1))
        ids = [int(a) for a in got[kbfs.KHEAD:] if a != bfs.SENT]
        assert ids[::-1] == (want["paths"][0] if want["paths"] else []), (src, dst)
        one, _ = paths_of(e.run("{ shortest(from: 0x%x, to: 0x%x) { %s } }" % (src, dst, LISTED)))
        assert [len(p) - 1 for p in one] == want["lengths"]


def test_a_device_fault_is_the_dijkstras_and_counted():
    from dgraph_tpu.utils.failpoints import fail

    g, _ = make_graph(4, 200, 150)
    e = load(g)
    ref = ref_of(g, 3)
    fail.arm("device.path", "error(n=1)")
    try:
        before = counters()
        assert paths_of(e.run(text(1, 9, 3)))[0] == ref.search(1, 9, with_paths=True)["paths"]
        grew = grown(before)
        assert grew["route_fallback"] == 1 and grew["route_device"] == 0 and grew["kept"] == 0
    finally:
        fail.disarm("device.path")


@pytest.mark.parametrize("text_,route", [
    pytest.param("{ shortest(from: 0x1, to: 0x9, numpaths: 3) { a ~a b } }", "device",
                 id="plain"),
    pytest.param("{ shortest(from: 0x1, to: 0x9, numpaths: 2) { w b } }", "host",
                 id="weight-facet"),
    pytest.param("{ shortest(from: 0x1, to: 0x9, numpaths: 2) { a @filter(uid(0x2)) b } }",
                 "host", id="filtered-child"),
])
def test_the_k_path_route_is_read_from_the_block_and_the_store(text_, route):
    g, _ = make_graph(1, 30, 40)
    e = load(g)
    e.run("mutation { set { <0x1> <w> <0x2> (weight=0.5) . } }")
    before = (counters(), PATH_SEARCHES.snapshot())
    e.run(text_)
    grew = grown(before[0])
    assert grew["route_" + route] == 1 and sum(
        n for x, n in grew.items() if x.startswith("route_")) == 1
    assert PATH_SEARCHES.snapshot()[route] == before[1][route] + 1
    (dec,) = [d for d in e.stats["planner"] if d["kind"] == "path"]
    assert dec["route"] == route
    if route == "device":
        assert dec["reason"] == "k paths at unit cost over plain predicates"


def test_fits_the_arrival_ids():
    assert kbfs.fits(3, 9_437_184) and kbfs.fits(255, 1000)
    assert not kbfs.fits(256, 1000) and not kbfs.fits(1, 1000)
    assert not kbfs.fits(3, 1 << 29)
    ok, dec = planner.path_route(300, False, False, False, 100, 1000)
    assert not ok and "arrival ids" in dec["reason"]


# -- the one-path programs, at the paths cell's shapes -------------------------------------

# film21m-q4-paths' merged layout (5,250,000 quads: benchmark/filmgen.py, the four
# listed predicates): 9,437,184 uids, 8,388,608 edge slots, the widest uid 97,734
UB, SLOTS, WIDEST = 9_437_184, 8_388_608, 97_734
LOWERED = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures",
                       "bfs_lowered_sha256.json")


def _lowered_one_path_programs() -> dict:
    cap, chunk = bfs.capacities(SLOTS, WIDEST)
    i32 = jnp.int32
    off = jax.ShapeDtypeStruct((UB, 2), i32)
    dst = jax.ShapeDtypeStruct((SLOTS,), i32)
    one = jax.ShapeDtypeStruct((), i32)
    st = jax.eval_shape(lambda o, s: bfs.start(o, s, cap, chunk), off, one)
    return {
        "start": bfs.start.lower(off, one, cap, chunk).as_text(),
        "run_levels": bfs.run_levels.lower(off, dst, dst, st, one, one, chunk).as_text(),
        "finish": bfs.finish.lower(st, one).as_text(),
        "walk_back": bfs.walk_back.lower(jax.ShapeDtypeStruct((UB,), i32), one).as_text(),
    }


def test_the_one_path_programs_lower_to_the_text_they_had():
    """``start``, ``run_levels``, ``finish`` and ``walk_back`` at the paths
    cell's shapes, hashed: the copy in tests/fixtures was taken from the
    tree before ops/kbfs.py existed."""
    with open(LOWERED) as f:
        saved = json.load(f)
    got = {name: hashlib.sha256(t.encode()).hexdigest()
           for name, t in _lowered_one_path_programs().items()}
    assert got == saved


# -- the k-path chunk, at the k-path cell's shapes -----------------------------------------

# film21m-q4-kpaths' pairs: the paths cell's layout above, whose 2,234,858 uids are
# a pair's target
KTARGETS = 2_234_858


def test_a_k_path_chunk_gathers_back_nothing_its_sorts_carry():
    """``run_levels`` at the cell's shapes: a chunk of 131,072 pair slots
    reads seven tables a slot by ``_take``'s row gather — the pairs' targets,
    ``info``, the frontier list, the walk's hits, and the pair count, first
    pair slot and out-degree of the target — and nothing its two sorts
    carry (a slot's parent, its ``info`` word, its loop bit, its kept
    arrival's word and pair count).  The 8,192-slot chunk of a tiny level
    the same; the prefix walk's pieces of a chunk's eighth read four."""
    k = 3
    cap, chunk = kbfs.capacities(KTARGETS, SLOTS, WIDEST, k)
    assert chunk == 131_072
    i32 = jnp.int32
    one = jax.ShapeDtypeStruct((), i32)
    kp = tuple(jax.ShapeDtypeStruct((n,), i32) for n in (SLOTS, UB, UB, UB))
    st = jax.eval_shape(lambda kp, s: kbfs.start(kp, s, cap, chunk, k), kp, one)
    t = kbfs.run_levels.lower(kp, st, one, one, chunk, k).as_text()
    rows = collections.Counter(re.findall(r"stablehlo\.gather.*-> tensor<(\d+)x128xi32>", t))
    small = bfs.small_chunk(chunk)
    assert {n: rows[str(n)] for n in (chunk, small, chunk // 8, small // 8)} == {
        chunk: 7, small: 7, chunk // 8: 4, small // 8: 4}
