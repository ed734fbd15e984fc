"""shortest(from:, to:) on the device route (ops/bfs.py via query/shortest.py)
against the host's Dijkstra and against a numpy BFS, on seeded random
graphs of several predicates walked both ways: length, validity hop by hop,
the tie rule (walking back from ``to``, the least predecessor), the ledger's
``edges`` / ``rows`` by their definition, the route choice, ``uid(var)``
endpoints and cancellation at a level boundary.
"""

import json
import urllib.error
import urllib.request

import numpy as np
import pytest

from dgraph_tpu.models import PostingStore
from dgraph_tpu.ops import bfs
from dgraph_tpu.query import planner
from dgraph_tpu.query.engine import QueryEngine
from dgraph_tpu.sched import CancelToken, QueryCancelledError
from dgraph_tpu.utils.metrics import (
    PATH_FRONTIER_ROWS, PATH_LEVEL_WAYS, PATH_LEVELS, PATH_SEARCHES)

PREDS = ("a", "b", "c")
LISTED = "a ~a b ~c"          # c is walked backwards only


def make_graph(seed: int, n: int, per_pred: int, hub: int = 0, chain: int = 0):
    """{pred: set of (src, dst)} over uids 1..n: random edges, optionally a
    hub (one uid with ``hub`` out-edges under ``a``: the level that holds it
    outgrows the gather's list) and a chain of ``chain`` uids beyond n (long
    distances).  Uids n+chain+1 .. n+chain+4 form an island of their own."""
    rng = np.random.default_rng(seed)
    g = {p: set() for p in PREDS}
    for p in PREDS:
        for u, v in rng.integers(1, n + 1, (per_pred, 2)).tolist():
            if u != v:
                g[p].add((u, v))
    for v in rng.choice(np.arange(2, n + 1), size=min(hub, n - 1), replace=False).tolist():
        g["a"].add((1, int(v)))
    for i in range(chain):
        g["b"].add((n + i, n + i + 1))
    base = n + chain
    g["a"] |= {(base + 1, base + 2), (base + 2, base + 3)}
    g["c"].add((base + 4, base + 3))
    return g, base + 4


def load(g) -> QueryEngine:
    e = QueryEngine(PostingStore())
    lines = [f"<0x{u:x}> <{p}> <0x{v:x}> ." for p in PREDS for u, v in sorted(g[p])]
    e.run("mutation { schema { a: uid @reverse . b: uid . c: uid . } set { %s } }"
          % "\n".join(lines))
    return e


def neighbours(g):
    """uid -> [(neighbour, listed predicate)] under ``LISTED``, listed order."""
    out = {}
    for tok in LISTED.split():
        rev, p = tok.startswith("~"), tok.lstrip("~")
        for u, v in g[p]:
            s, d = (v, u) if rev else (u, v)
            out.setdefault(s, []).append((d, p))
    return out


def numpy_bfs(g, src, dst):
    """(path by the tie rule or None, edges, rows, levels) by definition."""
    nb = neighbours(g)
    level = {src: 0}
    frontier, edges, rows, levels = [src], 0, 0, 0
    while frontier and dst not in level:
        rows += len(frontier)
        levels += 1
        nxt = set()
        for u in frontier:
            edges += len(nb.get(u, ()))
            for v, _ in nb.get(u, ()):
                if v not in level:
                    nxt.add(v)
        for v in nxt:
            level[v] = levels
        frontier = sorted(nxt)
    if dst not in level:
        return None, edges, rows, levels
    path = [dst]
    while path[-1] != src:
        v = path[-1]
        path.append(min(u for u in level if level[u] == level[v] - 1
                        and any(w == v for w, _ in nb.get(u, ()))))
    return path[::-1], edges, rows, levels


def path_of(answer):
    """[(uid, predicate of the hop out of it)] of the one ``_path_``."""
    node, out = answer["_path_"][0], []
    while True:
        keys = [k for k in node if k not in ("_uid_", "@facets")]
        out.append((int(node["_uid_"], 16), keys[0] if keys else None))
        if not keys:
            return out
        assert len(keys) == 1 and len(node[keys[0]]) == 1
        node = node[keys[0]][0]


def on_host(monkeypatch):
    monkeypatch.setattr(
        planner, "path_route",
        lambda k, *a: (False, {"kind": "path", "route": "host", "units": k, "reason": "test"}))


CASES = [
    # seed, n, edges a predicate, hub, chain
    pytest.param(1, 60, 50, 0, 0, id="sparse"),
    pytest.param(2, 60, 50, 0, 0, id="sparse-2"),
    pytest.param(3, 60, 120, 0, 0, id="dense"),
    pytest.param(4, 200, 150, 0, 0, id="wide"),
    pytest.param(5, 200, 150, 150, 0, id="hub"),
    pytest.param(6, 200, 400, 190, 0, id="hub-dense"),
    pytest.param(7, 40, 30, 0, 10, id="chain"),
    pytest.param(8, 40, 30, 30, 9, id="hub-chain"),
    pytest.param(9, 500, 300, 0, 0, id="large"),
    pytest.param(10, 500, 400, 450, 0, id="large-hub"),
]


@pytest.mark.parametrize("seed,n,per_pred,hub,chain", CASES)
def test_device_route_agrees_with_the_dijkstra_and_the_definition(
        monkeypatch, seed, n, per_pred, hub, chain):
    g, top = make_graph(seed, n, per_pred, hub, chain)
    e = load(g)
    rng = np.random.default_rng(100 + seed)
    pairs = [tuple(p) for p in rng.integers(1, n + 1, (10, 2)).tolist()]
    pairs += [(1, int(rng.integers(2, n + 1))), (top - 3, top - 1), (top - 3, top),
              (top - 1, 1), (1, top)]
    pairs.append(sorted(g["a"])[0])                  # one hop
    if chain:
        pairs += [(n, n + chain), (1, n + chain), (n + 2, n + chain - 1)]
    nb = neighbours(g)
    seen_d, sweeps, mid_level = set(), 0, 0
    for src, dst in pairs:
        text = "{ shortest(from: 0x%x, to: 0x%x) { %s } }" % (src, dst, LISTED)
        want, edges, rows, levels = numpy_bfs(g, src, dst)
        before = (PATH_SEARCHES.snapshot()["device"], PATH_LEVELS.value(),
                  PATH_FRONTIER_ROWS.value())
        got = e.run(text)
        assert PATH_SEARCHES.snapshot()["device"] == before[0] + 1, (src, dst)
        if src == dst:
            assert [u for u, _ in path_of(got)] == [src]
            continue
        # the ledger's account, by the definition
        assert e.stats["edges"] == edges, (src, dst)
        assert PATH_LEVELS.value() - before[1] == levels
        assert PATH_FRONTIER_ROWS.value() - before[2] == rows
        sweeps += e.stats.get("path_sweeps", 0)
        if want is None:
            assert got.get("_path_", []) == [], (src, dst)
        else:
            hops = path_of(got)
            uids = [u for u, _ in hops]
            assert uids == want, (src, dst)          # length AND the tie rule
            assert len(set(uids)) == len(uids)
            for (u, p), v in zip(hops, uids[1:]):    # each hop a stored edge,
                first = next(q for w, q in nb[u] if w == v)
                assert p == first                    # under the first listed predicate
            seen_d.add(len(uids) - 1)
            level_of_dst = numpy_level(g, src, len(uids) - 1)
            mid_level += 0 < level_of_dst.index(dst) < len(level_of_dst) - 1
        # the Dijkstra says the same, byte for byte
        with monkeypatch.context() as m:
            on_host(m)
            assert e.run(text) == got, (src, dst)
    assert seen_d, "no pair was reachable"
    assert 1 in seen_d
    if chain:
        assert max(seen_d) >= chain
    if hub >= 150:
        assert sweeps > 0, "no level went to the sweep"
    assert mid_level > 0, "`to` was never met in the middle of a level"


def numpy_level(g, src, d):
    """The uids of level ``d`` from ``src``, ascending."""
    nb = neighbours(g)
    seen, frontier = {src}, [src]
    for _ in range(d):
        nxt = {v for u in frontier for v, _ in nb.get(u, ())} - seen
        seen |= nxt
        frontier = sorted(nxt)
    return frontier


def test_both_ways_of_doing_a_level_run_and_agree():
    """A hub's level goes to the sweep, its neighbours' to the gather; the
    distances from uid 1 are those of the numpy BFS for every target."""
    g, _ = make_graph(5, 200, 150, 150, 0)
    e = load(g)
    gathers = sweeps = 0
    for dst in range(2, 60):
        want, edges, _, levels = numpy_bfs(g, 1, dst)
        ways = PATH_LEVEL_WAYS.snapshot()
        got = e.run("{ shortest(from: 0x1, to: 0x%x) { %s } }" % (dst, LISTED))
        assert (len(path_of(got)) - 1 if want else None) == (len(want) - 1 if want else None)
        assert e.stats["edges"] == edges
        swept = e.stats.get("path_sweeps", 0)
        grown = {w: n - ways[w] for w, n in PATH_LEVEL_WAYS.snapshot().items()}
        assert grown == {"gather": levels - swept, "sweep": swept}
        sweeps += swept
        gathers += levels - swept
    assert sweeps > 0 and gathers > 0


def test_a_path_longer_than_one_walk_back():
    """The device hands back ``bfs.PATH_CAP`` uids a walk; a longer path is
    walked on from where the last walk ended."""
    n = 2 * bfs.PATH_CAP + 7
    e = QueryEngine(PostingStore())
    e.run("mutation { schema { a: uid . } set { %s } }"
          % "\n".join(f"<0x{u:x}> <a> <0x{u + 1:x}> ." for u in range(1, n)))
    got = e.run("{ shortest(from: 0x1, to: 0x%x) { a } }" % n)
    assert [u for u, _ in path_of(got)] == list(range(1, n + 1))
    assert e.stats["edges"] == n - 1


@pytest.mark.parametrize("slots,widest", [
    pytest.param(8 * 1024 * 1024, 97_734, id="film-q4"),
    pytest.param(3 * 1024 * 1024, 12, id="narrow"),
    pytest.param(1024, 200, id="one-wide-uid"),
])
def test_capacities_follow_the_layouts_size(slots, widest):
    cap, chunk = bfs.capacities(slots, widest)
    break_even = slots * bfs._ACCESS_PER_EDGE / bfs._ACCESS_PER_SLOT
    # the list ends where the sweep gets cheaper, and nothing is floored away
    assert break_even / 2 < cap <= break_even
    assert widest <= chunk <= cap             # the list holds the widest uid
    assert chunk & (chunk - 1) == 0


def test_a_uid_wider_than_the_break_even_still_fits_the_chunk():
    cap, chunk = bfs.capacities(16, 40)
    assert chunk >= 40 and cap >= chunk


def _accesses(jaxpr, into=None, scattered=None):
    """{index rows: count} of the ``gather`` / ``scatter*`` equations of a
    jaxpr, those of its sub-jaxprs (loops, branches, calls) included;
    ``scattered`` collects the size of every scatter's operand."""
    into = {} if into is None else into
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "gather" or name.startswith("scatter"):
            rows = int(np.prod(eqn.invars[1].aval.shape[:-1]))
            into[rows] = into.get(rows, 0) + 1
            if scattered is not None and name != "gather":
                scattered.append(int(np.prod(eqn.invars[0].aval.shape)))
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _accesses(sub, into, scattered)
    return into


def test_the_access_counts_are_what_the_code_does():
    """``_ACCESS_PER_SLOT`` / ``_ACCESS_PER_EDGE`` price a level's two ways
    (``capacities``, ``run_levels``): they have to be the random accesses the
    code makes — per slot of a chunk, per row (half as many), per uid found
    (one a slot at most), per edge of the layout.  And what a gathered level
    writes over the uid space is ONE table, once a uid found: no scatter of
    ``_gather_chunk`` has an operand of the uid space's size, ``_enlist``'s
    one has (``par``), and the level as a whole has no other."""
    import jax
    import jax.numpy as jnp

    C, E, ub = 64, 4096, 1024        # sizes no two of which coincide
    off = jnp.zeros((ub, 2), jnp.int32)
    dst = jnp.zeros((E,), jnp.int32)
    i32 = lambda n: jnp.zeros((n,), jnp.int32)  # noqa: E731
    into = []
    chunk = _accesses(jax.make_jaxpr(
        lambda par, uids, starts, cum: bfs._gather_chunk(dst, par, uids, starts, cum, 3, C)
    )(i32(ub), i32(C // 2), i32(C // 2), i32(C // 2)).jaxpr, scattered=into)
    assert chunk == {C: 2, C // 2: 1}, chunk    # dst and par read; the rows' one scatter
    assert into == [2 * C], into                # and that one not over the uid space
    st = bfs.start(off, jnp.int32(1), 8 * C, C)
    n = st["fl"].shape[0]
    assert "lvl" not in st and st["mark"].dtype == bool
    into = []
    found = _accesses(jax.make_jaxpr(
        lambda st: bfs._enlist(off, C, st, jnp.int32(5), parents=st["via"]))(st).jaxpr,
        scattered=into)
    assert found == {C: 2} and into == [ub], (found, into)   # off read, par written
    assert bfs._ACCESS_PER_SLOT == chunk[C] + chunk[C // 2] / 2 + found[C] == 4.5
    into = []
    _accesses(jax.make_jaxpr(
        lambda st: bfs._gather_level(off, dst, C, n, st))(st).jaxpr, scattered=into)
    assert into.count(ub) == 1, into
    # a sweep: two an edge; after a listed level it first marks the list,
    # once a list slot (``_ACCESS_PER_EDGE``'s comment: not an edge's cost);
    # a list made again reads its uids' offsets and writes no table
    into = []
    sweep = _accesses(jax.make_jaxpr(
        lambda st: bfs._sweep_level(off, dst, dst, C, st))(st).jaxpr, scattered=into)
    assert sweep == {E: 2, n: 1, C: 1}, sweep
    assert bfs._ACCESS_PER_EDGE == sweep[E]
    assert sorted(into) == [ub, ub], into       # the mark and the candidates


def test_the_film_layout_lists_what_the_count_allows():
    # 8,388,608 edge slots: twice the slots over 4.5 accesses a slot, floored
    assert bfs.capacities(8 * 1024 * 1024, 97_734) == (3_670_016, 131_072)


def _csr(adj, ub):
    """(off int32[ub + 1], dst) of ``adj`` over uids 0..ub-1, rows in order."""
    off = np.zeros(ub + 1, np.int32)
    np.cumsum([len(adj.get(u, ())) for u in range(ub)], out=off[1:])
    return off, np.concatenate([adj.get(u, []) for u in range(ub)]).astype(np.int32)


def hand_layout(order):
    """A layout by hand, three levels below uid 1, each uid of degree 4 so a
    chunk of 8 slots holds two: level 1 = 10..15 (three chunks), level 2 =
    twelve uids of 30..52 (six chunks) which level 1 finds in ``order`` —
    "asc": the first chunk finds the least, "desc": the last does — and
    two of which every neighbouring pair of level 1 finds twice; level 3 =
    100 (reached from level 2's least AND greatest uid, the first and the
    last chunk), 101 (from the greatest and a middle one), 102."""
    adj = {1: [15, 12, 10, 13, 11, 14, 1, 1]}          # any order within a row
    two = [30 + 2 * k for k in range(12)]
    two = two if order == "asc" else two[::-1]
    for i, u in enumerate(range(10, 16)):
        shared = two[2 * ((i + 1) % 6)]                # the next uid's first find
        adj[u] = [two[2 * i + 1], 1, shared, two[2 * i]]
    lo, mid, hi = 30, 40, 52
    for v in range(30, 54, 2):
        adj[v] = [10, 1, v, 102]
    adj[lo], adj[hi], adj[mid] = [100, 10, 1, 102], [11, 100, 101, 1], [101, 1, 12, 102]
    return (adj, *_csr(adj, 128))


@pytest.mark.parametrize("order", ["asc", "desc"])
def test_a_gathered_level_of_several_chunks_keeps_the_least_parent(order):
    """``_gather_level`` at a chunk of 8 slots over levels of one, three and
    six chunks: the parent of a uid two chunks reach is the least uid of the
    level, whichever chunk found that parent; the next list is ascending,
    duplicate-free, with its offsets and exact degree sums."""
    import jax.numpy as jnp

    adj, off, dst = hand_layout(order)
    chunk, cap = 8, 56
    d_off = jnp.asarray(np.stack([off[:-1], off[1:]], axis=1))
    d_dst = jnp.asarray(np.concatenate([dst, np.full(128 - len(dst), bfs.SENT, np.int32)]))
    st = bfs.start(d_off, jnp.int32(1), cap, chunk)
    parent, level, par = {1: 1}, [1], np.asarray(st["par"])
    for cur in range(3):
        st = dict(bfs._gather_level(d_off, d_dst, chunk, cap + chunk, st), cur=jnp.int32(cur + 1))
        nxt = sorted({v for u in level for v in adj[u]} - set(parent))
        for v in nxt:
            parent[v] = min(u for u in level if v in adj[u])
        f = int(st["f"])
        assert np.asarray(st["fl"])[:f].tolist() == nxt, cur      # ascending, once each
        assert np.asarray(st["fo"])[:f].tolist() == [int(off[v]) for v in nxt]
        degs = [len(adj.get(v, ())) for v in nxt]
        assert np.asarray(st["cd"])[:f].tolist() == np.cumsum(degs).tolist()
        assert int(st["m"]) == sum(degs)
        # the parents of exactly the uids found, and no other entry moved
        before, par = par, np.asarray(st["par"])
        assert np.flatnonzero(par != before).tolist() == nxt, cur
        assert {v: int(par[v]) for v in np.flatnonzero(par != bfs.SENT).tolist()} == parent
        level = nxt
    assert [len(level), parent[100], parent[101]] == [3, 30, 40]


def layered(seed, widths, degs):
    """A layout by layers: layer i holds ``widths[i]`` uids of ``degs[i]``
    edges each.  A layer's first edges reach every uid of the next (so the
    levels are the layers, level i of ``widths[i] * degs[i]`` edges); of the
    others seven in ten go to the next layer and the rest anywhere at or
    above their own (back edges, loops and repeats: a merged layout has
    them).  The uids are dealt at random, so a layer's order is not its
    uids'.  Returns (adj, off, dst, the source)."""
    rng = np.random.default_rng(seed)
    uids = (1 + rng.permutation(sum(widths))).tolist()
    layers, at = [], 0
    for w in widths:
        layers.append(uids[at:at + w])
        at += w
    adj = {}
    for i, (layer, d) in enumerate(zip(layers, degs)):
        ahead = layers[min(i + 1, len(layers) - 1)]
        behind = [u for lay in layers[:i + 1] for u in lay]
        assert len(layer) * d >= len(ahead) or i + 1 == len(layers)
        for k, u in enumerate(layer):
            adj[u] = [ahead[e] if e < len(ahead)
                      else int(rng.choice(ahead if rng.random() < 0.7 else behind))
                      for e in range(k * d, k * d + d)]
            rng.shuffle(adj[u])
    return (adj, *_csr(adj, 128 * -(-(len(uids) + 1) // 128)), layers[0][0])


WAYS = [
    # layout, chunk, list capacity, the run of ways that has to occur
    pytest.param(lambda: (*hand_layout("asc"), 1), 8, 16, "gssg", id="hand-asc"),
    pytest.param(lambda: (*hand_layout("desc"), 1), 8, 16, "gssg", id="hand-desc"),
    pytest.param(lambda: layered(1, [1, 3, 10, 20, 20], [3, 30, 2, 2, 1]), 32, 64, "gsg",
                 id="layers-gather-sweep-gather"),
    pytest.param(lambda: layered(2, [1, 4, 60, 10, 15, 5], [4, 20, 3, 2, 1, 0]), 32, 64, "gssg",
                 id="layers-sweep-sweep-gather"),
    pytest.param(lambda: layered(3, [1, 6, 50, 70, 12, 9], [6, 16, 4, 1, 2, 1]), 16, 48, "gsssg",
                 id="layers-three-sweeps"),
    pytest.param(lambda: layered(4, [1, 5, 8, 6, 10, 6], [5, 12, 2, 14, 1, 1]), 16, 32, "gsgsg",
                 id="layers-in-and-out"),
]


@pytest.mark.parametrize("layout,chunk,cap,ways", WAYS)
def test_levels_swept_between_gathered_ones_keep_distance_parent_and_list(
        layout, chunk, cap, ways):
    """A list of a few chunks, so that levels go gather -> sweep -> gather
    and sweep -> sweep -> gather: after EVERY level the parent table is the
    reference's (the least uid of the level before, nothing else touched),
    the level's size and degree sum are exact and, where the level left a
    list, so are its uids, offsets and running degrees.  What a sweep reads
    — a mark written from the list, or from the sweep before — and what a
    gather reads after a sweep have no other judge."""
    import jax.numpy as jnp

    adj, off, dst, src = layout()
    ub, e = len(off) - 1, 128 * -(-len(dst) // 128)
    deg = np.diff(off)
    assert deg.max() <= chunk <= cap
    d_off = jnp.asarray(np.stack([off[:-1], off[1:]], axis=1))
    d_dst = jnp.asarray(np.concatenate([dst, np.full(e - len(dst), bfs.SENT, np.int32)]))
    d_esrc = jnp.asarray(np.concatenate(
        [np.repeat(np.arange(ub), deg), np.zeros(e - len(dst), np.int64)]).astype(np.int32))
    st = bfs.start(d_off, jnp.int32(src), cap, chunk)
    parent, level, went, par = {src: src}, [src], "", np.asarray(st["par"])
    rows = edges = 0
    while level:
        rows, edges = rows + len(level), edges + int(deg[level].sum())
        swept = int(st["sweeps"])
        st = bfs.run_levels(d_off, d_dst, d_esrc, st, jnp.int32(0), jnp.int32(1), chunk)
        went += "s" if int(st["sweeps"]) > swept else "g"
        nxt = sorted({v for u in level for v in adj.get(u, ())} - set(parent))
        for v in nxt:
            parent[v] = min(u for u in level if v in adj[u])
        # distance: the uids that got a parent at this level are this level's
        before, par = par, np.asarray(st["par"])
        assert np.flatnonzero(par != before).tolist() == nxt, went
        assert {v: int(par[v]) for v in np.flatnonzero(par != bfs.SENT).tolist()} == parent, went
        assert (int(st["f"]), int(st["m"])) == (len(nxt), int(deg[nxt].sum())), went
        assert (int(st["rows"]), int(st["edges"]), int(st["cur"])) == (rows, edges, len(went))
        if bool(st["listed"]):
            f = len(nxt)
            assert np.asarray(st["fl"])[:f].tolist() == nxt, went
            assert np.asarray(st["fo"])[:f].tolist() == off[nxt].tolist(), went
            assert np.asarray(st["cd"])[:f].tolist() == np.cumsum(deg[nxt]).tolist(), went
        else:
            assert len(nxt) > cap or deg[nxt].sum() > cap, went
        level = nxt
    assert ways in went, went


ROUTES = [
    pytest.param("{ shortest(from: 0x1, to: 0x9) { a ~a b } }", "device", id="plain"),
    pytest.param("{ shortest(from: 0x1, to: 0x9, numpaths: 2) { a ~a b } }", "host",
                 id="numpaths-2"),
    pytest.param("{ shortest(from: 0x1, to: 0x9) { a @filter(uid(0x2, 0x3, 0x9)) b } }",
                 "host", id="filtered-child"),
    pytest.param("{ shortest(from: 0x1, to: 0x9) { a (first: 2) b } }", "host",
                 id="paginated-child"),
    pytest.param("{ shortest(from: 0x1, to: 0x9) { w b } }", "host", id="weight-facet"),
]


@pytest.fixture(scope="module")
def small():
    g, _ = make_graph(1, 30, 40)
    e = load(g)
    e.run("mutation { set { <0x1> <w> <0x2> (weight=0.5) . <0x2> <w> <0x9> (weight=0.25) . } }")
    return e


@pytest.mark.parametrize("text,route", ROUTES)
def test_route_choice_is_read_from_the_block_and_the_store(small, text, route):
    before = PATH_SEARCHES.snapshot()
    small.run(text)
    after = PATH_SEARCHES.snapshot()
    other = "host" if route == "device" else "device"
    assert after[route] == before[route] + 1 and after[other] == before[other]
    assert [d["route"] for d in small.stats["planner"] if d["kind"] == "path"] == [route]


def test_a_uid_space_wider_than_the_arenas_is_searched_on_the_host():
    """One explicit uid near 2^30 in a store of five edges: the BFS's tables
    are dense over the uid space (gigabytes here, for every search in
    flight), so the planner, which sees the largest uid beside what the
    arenas hold, leaves the block to the Dijkstra — and no layout is built."""
    far = (1 << 30) + 5
    e = QueryEngine(PostingStore())
    hops = [(1, 2), (2, 3), (3, far), (far, 4), (1, 7)]
    e.run("mutation { schema { a: uid @reverse . } set { %s } }"
          % "\n".join(f"<0x{u:x}> <a> <0x{v:x}> ." for u, v in hops))
    before = PATH_SEARCHES.snapshot()
    got = e.run("{ shortest(from: 0x1, to: 0x4) { a ~a } }")
    after = PATH_SEARCHES.snapshot()
    assert [u for u, _ in path_of(got)] == [1, 2, 3, far, 4]
    assert after["host"] == before["host"] + 1 and after["device"] == before["device"]
    (dec,) = [d for d in e.stats["planner"] if d["kind"] == "path"]
    assert dec["route"] == "host" and "uid space" in dec["reason"]
    assert not e.arenas._path_layouts
    assert e.arenas.path_extent((("a", False), ("a", True))) == (far, (4 + 5) + (5 + 5))   # rows + edges, forward and reverse


def test_weighted_search_answers_as_before(small):
    got = small.run("{ shortest(from: 0x1, to: 0x9) { w } }")
    hops = path_of(got)
    assert [u for u, _ in hops] == [1, 2, 9]
    assert got["_path_"][0]["w"][0]["@facets"]["_"]["weight"] == 0.5


@pytest.fixture(scope="module")
def srv():
    from dgraph_tpu.serve.server import DgraphServer

    server = DgraphServer(PostingStore())
    server.start()
    names = "\n".join(f'<0x{u:x}> <name> "N{u % 7}" .' for u in range(1, 15))
    edges = "\n".join(f"<0x{u:x}> <a> <0x{u + 1:x}> ." for u in range(1, 14))
    post(server.addr, "/query",
         "mutation { schema { name: string @index(exact) . a: uid @reverse . } "
         "set { %s %s <0x1> <name> \"Only\" . <0x9> <name> \"Nine\" . } }" % (names, edges))
    yield server
    server.stop()


def post(addr, path, body):
    req = urllib.request.Request(addr + path, data=body.encode(), method="POST")
    with urllib.request.urlopen(req, timeout=60) as r:
        return json.loads(r.read().decode())


BY_NAME = """{
  A as var(func: eq(name, "%s")) { name }
  B as var(func: eq(name, "%s")) { name }
  path as shortest(from: uid(A), to: uid(B)) { a ~a }
  hops(func: uid(path)) { name }
}"""


def test_endpoints_by_uid_variable_over_http_with_a_ledger(srv):
    before = PATH_SEARCHES.snapshot()["device"]
    out = post(srv.addr, "/query?ledger=true", BY_NAME % ("Only", "Nine"))
    assert [u for u, _ in path_of(out)] == list(range(1, 10))
    assert len(out["hops"]) == 9
    assert PATH_SEARCHES.snapshot()["device"] == before + 1
    led = out["extensions"]["ledger"]
    # levels 0..7 of a chain walked both ways: 1 + 2 * 7 edges, 8 rows
    assert led["edges"] == 15 and led["hop_edges"] == {"path": 15} and led["hops"]["path"] == 1
    assert led["bytes_d2h"] > 0
    for stage in ("plan", "dispatch", "fetch"):
        assert led["stages"].get(stage, 0) > 0, stage


@pytest.mark.parametrize("a,b,n", [
    pytest.param("Nobody", "Nine", 0, id="from-binds-0"),
    pytest.param("Only", "N3", 2, id="to-binds-2"),
])
def test_an_endpoint_variable_has_to_bind_one_uid(srv, a, b, n):
    with pytest.raises(urllib.error.HTTPError) as err:
        post(srv.addr, "/query", BY_NAME % (a, b))
    assert err.value.code == 400
    assert f"binds {n} uids" in err.value.read().decode()


def test_endpoints_by_uid_variable_over_grpc(srv):
    pytest.importorskip("grpc")
    from dgraph_tpu.client import GrpcTransport
    from dgraph_tpu.serve.grpc_server import GrpcServer

    gsrv = GrpcServer(srv, port=0)
    gsrv.start()
    try:
        before = PATH_SEARCHES.snapshot()["device"]
        out = GrpcTransport(f"127.0.0.1:{gsrv.port}").run(BY_NAME % ("Nine", "Only"))
        assert PATH_SEARCHES.snapshot()["device"] == before + 1
        assert [u for u, _ in path_of(out)] == list(range(9, 0, -1))
    finally:
        gsrv.stop()


def test_literal_endpoints_and_missing_ones_as_before(small):
    assert "_path_" in small.run("{ shortest(from: 1, to: 0x9) { a ~a b } }")
    for text in ("{ shortest(to: 0x2) { a } }", "{ shortest(from: 0x1) { a } }"):
        with pytest.raises(ValueError, match="from: and to:"):
            small.run(text)


def test_cancellation_lands_at_a_level_boundary(monkeypatch):
    """One level a dispatch: a token flipped while the first level runs is
    seen before the second is dispatched."""
    from dgraph_tpu.utils.failpoints import fail

    monkeypatch.setenv("DGRAPH_TPU_SEGMENT", "force")
    monkeypatch.setenv("DGRAPH_TPU_SEGMENT_K", "1")
    g, _ = make_graph(7, 40, 30, 0, 9)
    e = load(g)
    text = "{ shortest(from: 0x%x, to: 0x%x) { %s } }" % (40, 49, LISTED)
    assert len(path_of(e.run(text))) == 10           # 9 levels, uncancelled
    e.cancel = tok = CancelToken()
    real = e.checkpoint
    fail.arm("device.path", "delay(ms=1)")           # armed: the site counts its hits
    h0 = fail.hits("device.path")

    def checkpoint():
        if fail.hits("device.path") - h0 >= 2:       # start + the first level
            tok.cancel("admin")
        real()

    monkeypatch.setattr(e, "checkpoint", checkpoint)
    try:
        with pytest.raises(QueryCancelledError):
            e.run(text)
        assert fail.hits("device.path") - h0 == 2    # no second level ran
    finally:
        fail.disarm("device.path")
