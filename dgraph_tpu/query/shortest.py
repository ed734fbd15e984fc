"""shortest(from:, to:, numpaths:) — uniform-cost / k-shortest paths.

Two routes, chosen by ``planner.path_route`` from what the block and the
store show (no knob):

- **device**: no facet on any listed predicate, children that are plain
  predicates or ``~predicate``.  ``numpaths`` absent or 1
  (``_device_search``, ops/bfs.py): a level-synchronous BFS over the listed
  predicates' merged layout; levels, visited set and parents stay on the
  device, ONE fetch brings back the distance and the path.  ``numpaths``
  k > 1 (``_device_kpaths``, ops/kbfs.py): the same layout searched level
  by level over ARRIVALS — up to k a uid, each with its parent arrival —
  and ONE fetch brings back the k paths, walked back on the device.  Its
  lists hold k arrivals of every uid a search can take, so no level
  outgrows them.  A device fault hands the block to the Dijkstra
  (``dgraph_kpath_searches_total{route="fallback"}``).
- **host** (``_dijkstra``): everything else.  Equivalent of
  query/shortest.go: Dijkstra over an adjacency cache built by lazy
  frontier expansion (expandOut:134) — each expansion hop is one batched
  gather per predicate; edge costs come from a "weight" facet when present
  else 1 (getCost:102); k-shortest keeps per-path copies
  (KShortestPath:274).  Two listed predicates that join the same pair are
  ONE hop (the cheaper, else the first listed).  Caps mirror
  shortest.go:214 (10M edges).  It is also the device routes' reference in
  the tests.

**Which of several equal paths** (one rule for both routes).  ``numpaths``
1: walking back from ``to``, each uid's predecessor is the LEAST uid among
those one step nearer ``from`` that hold an edge to it under a listed
predicate.  ``numpaths`` k > 1, the rule the Dijkstra's heap key (cost,
uid, path) has always given: a path never visits a uid twice; a uid is
taken at most k times, in order of cost and, between equal costs, least
uid sequence from ``from`` first; the first k arrivals at ``to`` are the
answer, and an arrival at ``to`` is not expanded.  Where fewer than k
shortest paths exist this is not Yen's k shortest simple paths: a uid's k
taken prefixes may all lead into a dead end that another, longer prefix of
it would have passed.  Either way a hop is rendered under the first listed
predicate that holds it.

The ledger's ``edges`` of a one-path device search is the sum, over the
levels expanded and the listed predicates, of the out-degree of every uid
of the level — the work asked for, whatever did a level; ``rows`` the sum
of the level sizes (``dgraph_path_frontier_rows_total``).  Of a k-path
search: the out-degree of every expanded ARRIVAL (levels 0..L-1, those at
``to`` not expanded), and ``rows`` the number of those arrivals.  Both
book under the ``path`` route.
"""

from __future__ import annotations

import heapq
from typing import Dict, List, Optional, Tuple

import numpy as np

from dgraph_tpu import obs
from dgraph_tpu.models.types import TypedValue, numeric
from dgraph_tpu.obs import ledger as _ledger
from dgraph_tpu.ops.sets import SENT
from dgraph_tpu.query.functions import QueryError
from dgraph_tpu.query.subgraph import SubGraph
from dgraph_tpu.utils.metrics import (
    KPATH_ARRIVALS, KPATH_CANDIDATES, KPATH_LEVELS, KPATH_PATHS, KPATH_SEARCHES, PATH_CHUNKS,
    PATH_FRONTIER_ROWS, PATH_LEVEL_WAYS, PATH_LEVELS, PATH_SEARCHES)

MAX_EDGES = 10_000_000


def shortest_path(engine, sg: SubGraph, resolver):
    src = _endpoint(sg.params.path_from, sg.params.path_from_var, "from", resolver)
    dst = _endpoint(sg.params.path_to, sg.params.path_to_var, "to", resolver)
    k = max(1, sg.params.num_paths)
    if not src or not dst:
        raise ValueError("shortest needs from: and to:")
    preds = [c for c in sg.children if c.attr not in ("_uid_", "uid")]
    if not preds:
        raise ValueError("shortest needs at least one predicate child")
    from dgraph_tpu.query import planner

    with obs.stage(engine.stats, "plan_ms"):
        on_device, dec = planner.path_route(
            k, *_what_the_block_shows(engine, preds))
        planner.record(engine.stats, dec)
    if k == 1:
        if on_device and _device_search(engine, sg, preds, src, dst):
            PATH_SEARCHES.add("device")
            return
    elif on_device:
        if _device_kpaths(engine, sg, preds, src, dst, k):
            PATH_SEARCHES.add("device")
            KPATH_SEARCHES.add("device")
            return
        KPATH_SEARCHES.add("fallback")
    else:
        KPATH_SEARCHES.add("host")
    PATH_SEARCHES.add("host")
    _dijkstra(engine, sg, resolver, preds, src, dst, k)


def _endpoint(literal: int, var: str, which: str, resolver) -> int:
    """``from:`` / ``to:`` as a uid: the literal, or the ONE uid that
    ``uid(var)`` binds — none or several is the client's error."""
    if not var:
        return literal
    bound = np.asarray(resolver.uid_vars.get(var, ()))
    if len(bound) != 1:
        raise QueryError(
            f"shortest {which}: uid({var}) binds {len(bound)} uids, "
            "a path's endpoint has to be exactly one")
    return int(bound[0])


def _what_the_block_shows(engine, preds) -> Tuple[bool, bool, bool, int, int]:
    """(a child is decorated, a listed predicate carries facets, the device
    may not be used, the largest uid the listed arenas hold, their rows and
    edges) — what ``planner.path_route`` decides from."""
    from dgraph_tpu.utils import devguard

    decorated = faceted = False
    for c in preds:
        p = c.params
        decorated |= bool(
            c.filter is not None or c.func is not None or c.children
            or c.langs or p.do_count or p.is_groupby or p.expand
            or p.facets is not None or p.facets_filter is not None
            or p.order_attr or p.first or p.offset or p.after or p.var
        )
        pd = engine.store.peek(c.attr)
        # a facet is a weight or is rendered on the hop: the Dijkstra's
        faceted |= pd is not None and bool(pd.edge_facets)
    unusable = not devguard.get().allowed() or any(
        # an arena sharded over the mesh is walked by the mesh's programs
        engine.arenas.use_mesh_for(
            engine.arenas.reverse(c.attr) if c.reverse else engine.arenas.data(c.attr))
        for c in preds
    )
    universe, held = engine.arenas.path_extent(
        tuple((c.attr, bool(c.reverse)) for c in preds))
    return decorated, faceted, unusable, universe, held


def _device_search(engine, sg: SubGraph, preds, src: int, dst: int) -> bool:
    """The search as ops/bfs.py runs it; False where the device gave up
    (a device fault: the Dijkstra answers instead)."""
    import jax.numpy as jnp

    from dgraph_tpu.ops import bfs
    from dgraph_tpu.sched import segments
    from dgraph_tpu.utils import devguard
    from dgraph_tpu.utils.failpoints import fail

    st = engine.stats
    if src == dst:
        _set_paths(engine, sg, preds, [[src]])
        return True
    # the listed predicates' merged layout: built and put on the device on
    # first use, under its own h2d bracket (models/arena.py)
    lay = engine.arenas.path_layout(
        tuple((c.attr, bool(c.reverse)) for c in preds))
    if max(src, dst) >= lay.ub:
        # an endpoint no edge knows of: level 0 alone
        _book(engine, 1, 1, 0, 0)
        _set_paths(engine, sg, preds, [])
        return True
    with obs.stage(st, "plan_ms"):
        cap, chunk = bfs.capacities(int(lay.dst.shape[0]), lay.max_degree)
        # a search expands an edge at most once, over about log2(uids)
        # levels: what a level holds on average prices the segments
        n_est = max(2, int(lay.ub).bit_length())
        k = segments.plan(n_est, max(1, lay.n_edges // n_est), "path")
        steps = k if k > 0 else 1 << 30

    def _start():
        fail.point("device.path")
        with obs.stage(st, "h2d_ms"):
            return bfs.start(lay.off, jnp.int32(src), cap, chunk)

    def _levels(state):
        # (the state after up to ``steps`` levels, whether levels are left);
        # a segment's end is read inside the guard's watchdog bracket
        fail.point("device.path")
        with obs.stage(st, "dispatch_ms"):
            state = bfs.run_levels(
                lay.off, lay.dst, lay.esrc, state, jnp.int32(dst),
                jnp.int32(steps), chunk)
        if k <= 0:
            return state, False
        with obs.stage(st, "fetch_ms"):
            return state, bool(~state["found"] & (state["f"] > 0))

    def _fetch(state):
        with obs.stage(st, "dispatch_ms"):
            dev = bfs.finish(state, jnp.int32(dst))
        with obs.stage(st, "fetch_ms"):
            return np.asarray(dev)

    guard = devguard.get()
    try:
        state, got = _dispatch(engine, guard, _start, _levels, _fetch)
        d2h = int(got.nbytes)
        found, levels, rows, edges, sweeps, chunks, scatters = (
            int(x) for x in got[:bfs.HEAD])
        path: Optional[List[int]] = None
        if found:
            with obs.stage(st, "convert_ms"):
                path = [int(u) for u in got[bfs.HEAD:] if u != bfs.SENT]
            d2h += _walk_on(guard, lambda u: bfs.walk_back(state["par"], jnp.int32(u)),
                            path, levels)
            path.reverse()
    except devguard.DeviceFaultError:
        return False
    led = _ledger.current()
    if led is not None:
        led.bytes_h2d += 8          # the two endpoints
        led.bytes_d2h += d2h
    _book(engine, levels, rows, edges, sweeps, chunks, scatters)
    with obs.stage(st, "convert_ms"):
        _set_paths(engine, sg, preds, [path] if path is not None else [])
    return True


def _device_kpaths(engine, sg: SubGraph, preds, src: int, dst: int, k: int) -> bool:
    """The k-path search as ops/kbfs.py runs it; False where the device gave
    up (a device fault: the Dijkstra answers instead, and the caller counts
    the fallback)."""
    import jax.numpy as jnp

    from dgraph_tpu.ops import kbfs
    from dgraph_tpu.sched import segments
    from dgraph_tpu.utils import devguard
    from dgraph_tpu.utils.failpoints import fail

    st = engine.stats
    if src == dst:
        _kbook(engine, [1] + [0] * (kbfs.KHEAD - 1))
        _set_paths(engine, sg, preds, [[src]])
        return True
    lay = engine.arenas.path_layout(
        tuple((c.attr, bool(c.reverse)) for c in preds))
    if max(src, dst) > lay.universe:
        # an endpoint no edge knows of: nothing is expanded
        _kbook(engine, [0] * kbfs.KHEAD)
        _set_paths(engine, sg, preds, [])
        return True
    with obs.stage(st, "h2d_ms"):
        kt = lay.kpath_tables()         # built on the layout's first k-path search
    kp = kt.arrays()
    with obs.stage(st, "plan_ms"):
        cap, chunk = kbfs.capacities(kt.targets, int(kt.dst.shape[0]), kt.max_degree, k)
        n_est = max(2, int(lay.ub).bit_length())
        seg = segments.plan(n_est, max(1, k * lay.n_edges // n_est), "path")
        steps = seg if seg > 0 else 1 << 30
    sh = kbfs.shift(k)

    def _start():
        fail.point("device.path")
        with obs.stage(st, "h2d_ms"):
            return kbfs.start(kp, jnp.int32(src), cap, chunk, k)

    def _levels(state):
        fail.point("device.path")
        with obs.stage(st, "dispatch_ms"):
            state = kbfs.run_levels(kp, state, jnp.int32(dst), jnp.int32(steps), chunk, k)
        if seg <= 0:
            return state, False
        with obs.stage(st, "fetch_ms"):
            return state, bool((state["found"] < k) & (state["m"] > 0))

    def _fetch(state):
        with obs.stage(st, "dispatch_ms"):
            dev = kbfs.finish(state, jnp.int32(dst), k)
        with obs.stage(st, "fetch_ms"):
            return np.asarray(dev)

    guard = devguard.get()
    try:
        state, got = _dispatch(engine, guard, _start, _levels, _fetch)
        d2h = int(got.nbytes)
        head = [int(x) for x in got[:kbfs.KHEAD]]
        paths: List[List[int]] = []
        with obs.stage(st, "convert_ms"):
            walks = got[kbfs.KHEAD:].reshape(k, kbfs.PATH_CAP)
            for s in range(head[0]):
                ids = [int(a) for a in walks[s] if a != kbfs.SENT]
                if len(ids) == kbfs.PATH_CAP:
                    # a walk that came back full: the path goes on past it (one
                    # that reached the source is whole, however many levels ran)
                    d2h += _walk_on(
                        guard, lambda a: kbfs.walk_back(state["apar"], jnp.int32(a)),
                        ids, head[1])
                paths.append([a >> sh for a in reversed(ids)])
    except devguard.DeviceFaultError:
        return False
    led = _ledger.current()
    if led is not None:
        led.bytes_h2d += 8              # the two endpoints
        led.bytes_d2h += d2h
    _kbook(engine, head)
    with obs.stage(st, "convert_ms"):
        _set_paths(engine, sg, preds, paths)
    return True


def _dispatch(engine, guard, start, levels, fetch):
    """A device search's dispatches, each inside the guard's watchdog
    bracket: ``start``, then ``levels`` — (the state after a segment of
    levels, whether levels are left) — with a level boundary before each,
    where the request's cancellation is seen and a queued cohort of higher
    priority may take the seam, then ``fetch``.  Returns (state, fetched)."""
    from dgraph_tpu.sched import segments

    state = guard.run("device.path", start)
    while True:
        engine.checkpoint()
        state, more = guard.run("device.path", lambda s=state: levels(s))
        if not more:
            break
        segments.seam("path")
    return state, guard.run("device.path", lambda: fetch(state))


def _walk_on(guard, walk, path: List[int], levels: int) -> int:
    """Extends ``path`` (walked back from its target, PATH_CAP a walk) by
    further walks from its last entry while it is shorter than ``levels``
    hops allow; returns the bytes fetched."""
    d2h = 0
    while len(path) <= levels:
        more = guard.run("device.path", lambda a=path[-1]: np.asarray(walk(a)))
        d2h += int(more.nbytes)
        path += [int(u) for u in more[1:] if u != SENT]
        if more[1] == SENT:
            break                       # the source has no parent: the walk is over
    return d2h


def _kbook(engine, head) -> None:
    """A device k-path search's header (ops/kbfs.py ``finish``) to the
    request's stats (hence the ledger's ``edges``), the ``path`` route and
    the k-path counters — the one place they are booked."""
    paths, levels, rows, edges, first, again, kept, full, loop = head[:9]
    st = engine.stats
    st["edges"] = st.get("edges", 0) + edges
    KPATH_LEVELS.add(levels)
    KPATH_ARRIVALS.add("first", first)
    KPATH_ARRIVALS.add("again", again)
    KPATH_CANDIDATES.add("kept", kept)
    KPATH_CANDIDATES.add("full", full)
    KPATH_CANDIDATES.add("loop", loop)
    KPATH_PATHS.add(paths)
    led = _ledger.current()
    if led is not None:
        led.note_hop("path", edges)


def _book(engine, levels: int, rows: int, edges: int, sweeps: int,
          chunks: int = 0, scatters: int = 0) -> None:
    """A device search's work, by the module's definition, to the request's
    stats (hence the ledger's ``edges``), the ``path`` route and the path
    counters; ``sweeps`` of its levels were swept, the others gathered, in
    ``chunks`` chunks of which ``scatters`` mapped their slots by the
    scatter and the rest by their windows (ops/bfs.py ``_gather_chunk``)."""
    st = engine.stats
    st["edges"] = st.get("edges", 0) + edges
    st["path_sweeps"] = st.get("path_sweeps", 0) + sweeps
    PATH_LEVELS.add(levels)
    PATH_FRONTIER_ROWS.add(rows)
    PATH_LEVEL_WAYS.add("gather", levels - sweeps)
    PATH_LEVEL_WAYS.add("sweep", sweeps)
    PATH_CHUNKS.add("window", chunks - scatters)
    PATH_CHUNKS.add("scatter", scatters)
    led = _ledger.current()
    if led is not None:
        led.note_hop("path", edges)


def _set_paths(engine, sg: SubGraph, preds, paths: List[List[int]]) -> None:
    """``sg.paths`` / ``sg.dest_uids`` as the Dijkstra leaves them: the
    paths in the order given, each hop under the first listed predicate
    that holds it; the destination uids the union of the paths'."""
    sg.paths = []
    for path in paths:
        elems = []
        for i, u in enumerate(path):
            attr_out = ""
            if i + 1 < len(path):
                v = path[i + 1]
                for c in preds:
                    pd = engine.store.peek(c.attr)
                    a, b = (v, u) if c.reverse else (u, v)
                    if pd is not None and b in pd.edges.get(a, ()):
                        attr_out = c.attr
                        break
            elems.append({"uid": u, "facets": {}, "attr_out": attr_out or "path"})
        sg.paths.append(elems)
    sg.dest_uids = np.array(sorted({u for p in paths for u in p}), dtype=np.int64)


def _dijkstra(engine, sg: SubGraph, resolver, preds, src: int, dst: int, k: int):
    # adjacency cache: uid -> list of (neighbor, cost, facets, attr)
    adj: Dict[int, List[Tuple[int, float, dict, str]]] = {}
    hop: Dict[Tuple[int, int], int] = {}    # (u, v) -> its entry in adj[u]
    expanded: set = set()
    edges = 0

    def expand(frontier: np.ndarray):
        nonlocal edges
        todo = np.array([u for u in frontier.tolist() if u not in expanded], dtype=np.int64)
        if not len(todo):
            return
        for u in todo.tolist():
            adj.setdefault(int(u), [])
            expanded.add(int(u))
        for tmpl in preds:
            # cancellation checkpoint per predicate expansion: Dijkstra
            # over a big fan-out must stop at the next hop, not at the
            # end of the search
            engine.checkpoint()
            child = SubGraph(attr=tmpl.attr, params=tmpl.params, filter=tmpl.filter,
                             reverse=tmpl.reverse)
            engine._exec_child(child, np.sort(todo), resolver, {}, {})
            pd = engine.store.peek(tmpl.attr)
            counts = np.diff(child.seg_ptr)
            owner = np.repeat(np.arange(len(counts)), counts)
            for j, d in enumerate(child.out_flat.tolist()):
                s = int(child.src_uids[owner[j]])
                facets = {}
                if pd is not None:
                    facets = pd.edge_facets.get((s, int(d)), {})
                cost = 1.0
                w = facets.get("weight")
                if w is not None:
                    x = numeric(w)
                    if x is not None:
                        cost = x
                edges += 1
                # two listed predicates joining one pair are ONE hop: the
                # cheaper, else the first listed (each pushed, a path
                # came back once a predicate)
                at = hop.get((s, int(d)))
                if at is None:
                    hop[(s, int(d))] = len(adj[s])
                    adj[s].append((int(d), cost, facets, tmpl.attr))
                elif cost < adj[s][at][1]:
                    adj[s][at] = (int(d), cost, facets, tmpl.attr)

    # uniform-cost search, expanding lazily per frontier ring.  The heap
    # orders equal costs by (uid, path): with numpaths 1 a path is kept
    # TARGET-first, so the first pop of a uid is the one with the least
    # predecessor (the module's rule, which the device route follows);
    # numpaths > 1 keeps source-first, the order it always had
    back = k == 1
    found: List[Tuple[float, List[int]]] = []
    heap: List[Tuple[float, int, List[int]]] = [(0.0, src, [src])]
    best_count: Dict[int, int] = {}
    while heap and len(found) < k and edges < MAX_EDGES:
        engine.checkpoint()
        cost, u, path = heapq.heappop(heap)
        if best_count.get(u, 0) >= k:
            continue
        best_count[u] = best_count.get(u, 0) + 1
        if u == dst:
            found.append((cost, path[::-1] if back else path))
            continue
        if u not in expanded:
            expand(np.array([u], dtype=np.int64))
        for (v, c, _f, _a) in adj.get(u, ()):
            if v in path:  # simple paths only (matches reference)
                continue
            heapq.heappush(heap, (cost + c, v, [v] + path if back else path + [v]))

    sg.paths = []
    for cost, path in found:
        elems = []
        for i, u in enumerate(path):
            facets = {}
            attr_out = ""
            if i + 1 < len(path):
                # predicate of the outgoing hop keys the nested object
                # (createPathSubgraph keys hops by traversed attr)
                for (v, _c, _f, a) in adj.get(u, ()):
                    if v == path[i + 1]:
                        attr_out = a
                        break
            if i > 0:
                # facets of the edge that led here
                for (v, _c, f, _a) in adj.get(path[i - 1], ()):
                    if v == u:
                        facets = f
                        break
            elems.append({"uid": u, "facets": facets, "attr_out": attr_out or "path"})
        sg.paths.append(elems)

    # dest_uids = the union of path nodes (for the attribute block render)
    uids = sorted({u for _c, p in found for u in p})
    sg.dest_uids = np.array(uids, dtype=np.int64)
