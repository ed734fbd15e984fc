"""@recurse execution: level-synchronous frontier expansion.

Equivalent of query/recurse.go (expandRecurse:31, Recurse:164): the same
child template re-expands level by level; traversed (attr, src, dst)
edges are deduplicated and the walk stops at ``depth`` levels or when a
level adds nothing new.  The reference's per-edge reachMap
(recurse.go:110-145) becomes sorted visited-uid sets per predicate —
frontier dedup is a device sort_unique/difference, the TPU shape of BFS.
Caps mirror recurse.go:148 (1M edges).
"""

from __future__ import annotations

import copy
from typing import Dict, List

import jax.numpy as jnp
import numpy as np

from dgraph_tpu import ops
from dgraph_tpu.ops.sets import SENT
from dgraph_tpu.query.subgraph import SubGraph

MAX_EDGES = 1_000_000


def recurse(engine, sg: SubGraph, resolver):
    depth = sg.params.depth or (1 << 30)
    # children split: value leaves re-evaluated per level; uid templates drive
    uid_templates = [c for c in sg.children if _is_uid_child(engine, c)]
    if not uid_templates:
        raise ValueError("recurse query needs at least one uid predicate child")

    if _try_fused_recurse(engine, sg, uid_templates):
        return

    frontier = sg.dest_uids
    visited = frontier.copy()
    # per-level realized children attach under the previous level's nodes
    cur_parents: List[SubGraph] = [sg]
    edges = 0
    level = 0
    while level < depth and len(frontier) and edges < MAX_EDGES:
        next_frontier_parts = []
        new_parents: List[SubGraph] = []
        for parent in cur_parents:
            src = parent.dest_uids
            if not len(src):
                continue
            for tmpl in uid_templates:
                # cancellation checkpoint per realized level-template:
                # a cancelled @recurse stops before its next expansion
                engine.checkpoint()
                child = SubGraph(
                    attr=tmpl.attr,
                    alias=tmpl.alias,
                    langs=list(tmpl.langs),
                    params=copy.deepcopy(tmpl.params),
                    func=tmpl.func,
                    filter=tmpl.filter,
                    reverse=tmpl.reverse,
                )
                # value leaves of the template are re-instantiated each level
                child.children = [
                    copy.deepcopy(c) for c in sg.children if not _is_uid_child(engine, c)
                ]
                engine._exec_child(child, src, resolver, {}, {})
                # drop already-visited targets (reachMap dedup)
                keep = np.setdiff1d(child.dest_uids, visited)
                engine._mask_matrix(child, keep)
                child.dest_uids = np.unique(child.out_flat)
                # re-fetch value leaves for the new frontier
                for vc in child.children:
                    engine.checkpoint()
                    engine._exec_child(vc, child.dest_uids, resolver, {}, {})
                edges += len(child.out_flat)
                parent.children = parent.children + [child]
                new_parents.append(child)
                if len(child.dest_uids):
                    next_frontier_parts.append(child.dest_uids)
        if not next_frontier_parts:
            break
        frontier = np.unique(np.concatenate(next_frontier_parts))
        frontier = np.setdiff1d(frontier, visited)
        visited = np.union1d(visited, frontier)
        cur_parents = new_parents
        level += 1

    # the templates themselves are replaced by realized levels
    sg.children = [c for c in sg.children if c not in uid_templates]
    # root-level value leaves for the root frontier
    for vc in sg.children:
        engine.checkpoint()
        if not _is_uid_child(engine, vc) and not vc.values:
            engine._exec_child(vc, sg.dest_uids, resolver, {}, {})


def _try_fused_recurse(engine, sg: SubGraph, uid_templates) -> bool:
    """Internal (var-block) recursion over ONE plain uid template runs as
    the lax.scan BFS driver (ops.multi_hop, track_visited): one device
    program for the whole walk, frontier + visited set device-resident
    with donated carry buffers, instead of one expansion dispatch (plus
    host setdiff/union) per level.  Var blocks encode nothing, so the
    realized levels carry dest frontiers only — the same light contract
    the fused chain's var-block mode established (query/chain.py).

    Strictly gated: any decoration (filters, ordering, value leaves,
    @cascade, mesh arenas, unbounded depth) falls back to the general
    level-by-level loop, which remains the correctness reference."""
    import numpy as np

    p = sg.params
    if not p.is_internal or p.cascade or len(uid_templates) != 1:
        return False
    if any(not _is_uid_child(engine, c) for c in sg.children):
        return False  # value leaves re-evaluate per level: loop path
    tmpl = uid_templates[0]
    tp = tmpl.params
    if tmpl.filter is not None or tmpl.func is not None or tmpl.children:
        return False
    if (
        tp.do_count or tp.is_groupby or tp.expand
        or tp.facets is not None or tp.facets_filter is not None
        or tp.order_attr or tp.first or tp.offset or tp.after
    ):
        return False
    depth = p.depth or 0
    if not 0 < depth <= 64:  # scan length must be static and sane
        return False
    frontier = np.asarray(sg.dest_uids)
    if not len(frontier):
        sg.children = [c for c in sg.children if c is not tmpl]
        return True
    if not np.all(frontier[1:] > frontier[:-1]):
        # an ordered root permutes dest_uids; expand_ascending's slot
        # telescoping and the visited-set member_mask both require a
        # sorted-unique frontier (same guard as try_run_chain)
        return False
    arena = (
        engine.arenas.reverse(tmpl.attr)
        if tmpl.reverse
        else engine.arenas.data(tmpl.attr)
    )
    if arena.n_edges == 0 or engine.arenas.use_mesh_for(arena):
        return False
    # overflow-free planning: worst-case edges per hop via the top-m
    # degree cumsum; abandon (before compile) when the uniform scan
    # capacity would exceed the recursion edge budget
    from dgraph_tpu.query.chain import _topm_deg_sum

    nd = max(1, arena.n_distinct_dst())
    bounds = []
    m = len(frontier)
    total_bound = 0
    for _ in range(depth):
        e = _topm_deg_sum(arena, min(m, arena.n_rows))
        bounds.append(e)
        total_bound += e
        m = min(e, nd)
    if total_bound > MAX_EDGES:
        return False
    cap = ops.bucket(max(max(bounds), len(frontier) + nd, 1))
    from dgraph_tpu.utils import devguard

    try:
        arena.ensure_device()
        universe = int(arena.h_src[-1]) if arena.n_rows else 0
        lut = arena.lut(universe)
        f = jnp.asarray(ops.pad_to(frontier.astype(np.int64), cap))
        vis = jnp.asarray(ops.pad_to(frontier.astype(np.int64), cap))
        # guard-bracketed inside ops.multi_hop: a wedged/sick/OOM scan
        # surfaces here as DeviceFaultError and the general level-by-
        # level loop (whose expansions hot-fail to host) takes over
        fs, totals, _vis = ops.multi_hop(
            arena.offsets, arena.dst, f, vis, depth, cap,
            track_visited=True, lut=lut,
        )
        fs = np.asarray(fs)
    except devguard.DeviceFaultError:
        return False
    engine.stats["edges"] += int(np.asarray(totals).astype(np.int64).sum())
    parent = sg
    prev = sg.dest_uids
    for i in range(depth):
        dest = fs[i][fs[i] != SENT].astype(np.int64)
        if not len(dest):
            break
        child = SubGraph(
            attr=tmpl.attr,
            alias=tmpl.alias,
            langs=list(tmpl.langs),
            params=copy.deepcopy(tp),
            reverse=tmpl.reverse,
        )
        child.src_uids = prev
        child.out_flat = np.empty(0, dtype=np.int64)
        child.seg_ptr = np.zeros(len(prev) + 1, dtype=np.int64)
        child.dest_uids = dest
        parent.children = parent.children + [child]
        parent = child
        prev = dest
    sg.children = [c for c in sg.children if c is not tmpl]
    return True


def _is_uid_child(engine, c: SubGraph) -> bool:
    from dgraph_tpu.models.types import TypeID

    if c.attr in ("_uid_", "uid", "val", "math", "", "_predicate_"):
        return False
    if c.params.do_count:
        return False
    tid = engine.store.schema.type_of(c.attr)
    if tid == TypeID.UID:
        return True
    pd = engine.store.peek(c.attr)
    return pd is not None and bool(pd.edges)
