"""The plain reference of the result encoder: the depth-first walk that
``dgraph_tpu/query/outputnode.py`` held until PR 27, moved here verbatim
(``_src_index``, ``encode_node``, ``_normalize_flatten``, ``encode_block``).
One recursive call per emitted object, one scalar ``np.searchsorted`` per
(uid child, uid).  ``tests/test_encode_parity.py`` holds the level encoder
to it, ``json.dumps`` byte for byte.  Nothing in the package imports this.
"""

from __future__ import annotations

from typing import List, Optional

import numpy as np

from dgraph_tpu.models.store import PostingStore
from dgraph_tpu.query.outputnode import (
    DEBUG_UIDS,
    _display_key,
    _facets_json,
    _uid_hex,
    json_value,
)
from dgraph_tpu.query.subgraph import SubGraph


def _src_index(sg: SubGraph, uid: int) -> int:
    i = int(np.searchsorted(sg.src_uids, uid))
    if i < len(sg.src_uids) and sg.src_uids[i] == uid:
        return i
    return -1


def encode_node(
    store: PostingStore,
    sg: SubGraph,
    uid: int,
    path: frozenset = frozenset(),
    ignore_reflex: bool = False,
) -> Optional[dict]:
    """One result object for ``uid`` at node ``sg`` (preTraverse analog).

    ``path``/``ignore_reflex``: @ignorereflex drops targets already on the
    ancestor path (parentIds stack, query/query.go:365-375)."""
    path = path | {uid}
    obj: dict = {}
    cascade_fail = False
    for child in sg.children:
        if child.params.is_internal and not child.params.var:
            continue
        if child.params.is_internal and child.attr not in ("val", "math") :
            continue
        key = _display_key(child)
        attr = child.attr
        if attr in ("_uid_", "uid"):
            obj[child.alias or "_uid_"] = _uid_hex(uid)
            continue
        if child.params.do_count and attr == "":
            continue  # bare count() handled at list level
        if child.params.do_count:
            i = _src_index(child, uid)
            n = int(child.counts[i]) if (child.counts is not None and i >= 0) else 0
            obj[child.alias or f"count({'~' if child.reverse else ''}{attr})"] = n
            continue
        if attr == "val":
            v = child.values.get(uid)
            var = child.needs_var[0] if child.needs_var else ""
            if child.params.agg_func:
                if v is not None:
                    obj[child.alias or f"{child.params.agg_func}(val({var}))"] = json_value(v)
            elif v is not None:
                obj[child.alias or f"val({var})"] = json_value(v)
            elif sg.params.cascade:
                cascade_fail = True
            continue
        if attr == "math":
            if child.params.is_internal:
                continue
            v = child.values.get(uid)
            if v is not None:
                obj[child.alias or "math"] = json_value(v)
            continue
        if attr == "_predicate_":
            v = child.values.get(uid)
            if v is not None:
                obj[child.alias or "_predicate_"] = v.value
            continue
        if child.params.is_groupby:
            if child.groups is not None:
                obj[key] = [{"@groupby": child.groups}]
            continue
        if child.func is not None and child.func.name == "checkpwd":
            v = child.values.get(uid)
            if v is not None:
                # reference shape: "pwd": [{"checkpwd": true}]
                obj[child.alias or attr] = [{"checkpwd": bool(v.value)}]
            continue
        if child.is_value_node() or (not len(child.out_flat) and child.values):
            v = child.values.get(uid)
            if v is not None:
                obj[key] = json_value(v)
                f = child.value_facets.get(uid)
                if f and child.params.facets:
                    fj = _facets_json(f, child.params.facets)
                    if fj:
                        obj.setdefault("@facets", {})[key] = fj
            elif sg.params.cascade:
                cascade_fail = True
            continue
        if len(child.seg_ptr) > 1 or len(child.out_flat):
            # uid child
            i = _src_index(child, uid)
            items: List[dict] = []
            if i >= 0:
                for dst in child.row_targets(i).tolist():
                    if ignore_reflex and int(dst) in path:
                        continue
                    sub = encode_node(store, child, int(dst), path, ignore_reflex)
                    if sub is None:
                        continue
                    f = child.edge_facets.get((uid, int(dst)))
                    if f and child.params.facets is not None:
                        fj = _facets_json(f, child.params.facets)
                        if fj:
                            sub = {**sub, "@facets": {"_": fj}}
                    if sub:
                        items.append(sub)
                for gc in child.children:
                    if gc.params.do_count and gc.attr == "":
                        items.append({"count": len(child.row_targets(i))})
                        break
            if items:
                obj[key] = items
            elif sg.params.cascade or child.params.cascade:
                cascade_fail = True
            continue
        # empty expansion (no data): under cascade this kills the node
        if child.values:
            v = child.values.get(uid)
            if v is not None:
                obj[key] = json_value(v)
                continue
        if sg.params.cascade:
            cascade_fail = True
    if cascade_fail:
        return None
    if DEBUG_UIDS.get() and obj:
        obj.setdefault("_uid_", _uid_hex(uid))
    return obj


def _normalize_flatten(store, sg: SubGraph, uid: int) -> Optional[List[dict]]:
    """@normalize: one flat object per DFS path, aliased leaves only."""
    base: dict = {}
    for child in sg.children:
        if child.alias and (child.is_value_node() or child.values):
            v = child.values.get(uid)
            if v is not None:
                base[child.alias] = json_value(v)
        elif child.alias and child.params.do_count:
            i = _src_index(child, uid)
            if child.counts is not None and i >= 0:
                base[child.alias] = int(child.counts[i])
        elif child.alias and child.attr in ("_uid_", "uid"):
            base[child.alias] = _uid_hex(uid)
    branch_lists: List[List[dict]] = []
    for child in sg.children:
        if (len(child.seg_ptr) > 1 or len(child.out_flat)) and child.children:
            i = _src_index(child, uid)
            if i < 0:
                continue
            subs: List[dict] = []
            for dst in child.row_targets(i).tolist():
                got = _normalize_flatten(store, child, int(dst))
                if got:
                    subs.extend(got)
            if subs:
                branch_lists.append(subs)
    if not branch_lists:
        return [base] if base else []
    out = [base]
    for subs in branch_lists:
        out = [{**o, **s} for o in out for s in subs]
    return out


def encode_block(store: PostingStore, sg: SubGraph) -> List[dict]:
    if sg.params.is_groupby and sg.groups is not None:
        return [{"@groupby": sg.groups}]  # root-level @groupby (GroupByRoot)
    out: List[dict] = []
    bare_count = any(
        c.params.do_count and c.attr == "" for c in sg.children
    )
    if bare_count:
        out.append({"count": int(len(sg.dest_uids))})
    if not len(sg.dest_uids) and sg.func is None:
        # aggregation-only block (`total() { sum(val(c)) ... }`): values
        # live under the synthetic uid 0
        obj = encode_node(store, sg, 0)
        return [obj] if obj else []
    for uid in sg.dest_uids.tolist():
        if sg.params.normalize:
            got = _normalize_flatten(store, sg, int(uid))
            if got:
                out.extend(got)
            continue
        obj = encode_node(
            store, sg, int(uid), ignore_reflex=sg.params.ignore_reflex
        )
        if obj:
            out.append(obj)
    return out
