"""Result encoding: SubGraph tree → JSON-able dicts.

Equivalent of the reference's query/outputnode.go fastJsonNode encoder
driven by the preTraverse DFS (query/query.go:375-551).  Key shapes match
the reference's goldens (query_test.go):

- uids as hex strings under "_uid_"
- counts as "count(attr)" (or alias), bare count() as its own {"count":N}
- value variables as "val(x)", aggregates like "min(val(x))"
- edge facets on the child object under "@facets":{"_":{k:v}}; value
  facets on the parent under "@facets":{attr:{k:v}}
- @normalize flattens aliased leaves into one object per DFS path
- @groupby results under "@groupby"

A block is encoded a LEVEL at a time (``_level``): what each child of a
SubGraph node lays on an object is decided once per node (``_plan``), a
child's rows are found with one vectorised lookup per level, and a uid
child's objects are built once per DISTINCT target and dealt to every
edge that reaches it.  Two directives make an object depend on the path
that reached it and keep a depth-first walk: @ignorereflex (``_level``
one uid at a time, with the ancestor path) and @normalize
(``_normalize_flatten``).  Which one runs is read off the block's params.

**A response is read-only after ``QueryEngine.execute``**: two edges to
one target hold the SAME dict (an edge with facets holds a copy with
"@facets" laid on it).  The scheduler already deals one result to
coalesced twins; ``cache/result.py``'s footprint walk, ``json.dumps`` and
``serve/proto.py`` only read.
"""

from __future__ import annotations

import contextvars
import datetime as _dt
from itertools import pairwise, repeat
from operator import attrgetter
from typing import Any, Dict, List, Optional

import numpy as np

from dgraph_tpu.models.store import PostingStore
from dgraph_tpu.models.types import TypeID, TypedValue
from dgraph_tpu.query.subgraph import SubGraph
from dgraph_tpu.utils.metrics import ENCODE_OBJECTS

# ?debug=true attaches "_uid_" to every emitted node, as the reference's
# queryHandler debug context does (cmd/dgraph/main.go:226)
DEBUG_UIDS: "contextvars.ContextVar[bool]" = contextvars.ContextVar(
    "debug_uids", default=False
)

# both paths are there at zero from boot (obs/ledger.py does the same for
# its stages): a scraper diffs the family over a window
for _p in ("level", "walk"):
    ENCODE_OBJECTS.add(_p, 0)

_NA = object()  # a column's "this uid has nothing under this key"
# the tids json_value renders; every other value goes out as it is
_JSON_TYPED = frozenset(
    (TypeID.DATETIME, TypeID.DATE, TypeID.GEO, TypeID.BINARY)
)
_UID0 = np.zeros(1, dtype=np.int64)  # aggregation-only blocks: uid 0


def _uid_hex(u: int) -> str:
    return hex(int(u))


def json_value(v: TypedValue) -> Any:
    if v.tid in (TypeID.DATETIME, TypeID.DATE):
        d = v.value
        if isinstance(d, _dt.datetime) and d.tzinfo is None:
            return d.isoformat() + "Z"
        return d.isoformat() if hasattr(d, "isoformat") else str(d)
    if v.tid == TypeID.GEO:
        return v.value.to_geojson()
    if v.tid == TypeID.BINARY:
        import base64

        return base64.b64encode(bytes(v.value)).decode()
    return v.value


def _facets_json(f: Dict[str, TypedValue], spec=None) -> Dict[str, Any]:
    """Facet map → JSON, restricted to the requested keys when @facets
    named specific ones (query/outputnode.go facet selection)."""
    if spec is not None and spec.keys and not spec.all_keys:
        return {k: json_value(v) for k, v in f.items() if k in spec.keys}
    return {k: json_value(v) for k, v in f.items()}


def _display_key(sg: SubGraph) -> str:
    if sg.alias:
        return sg.alias
    key = sg.attr
    if sg.reverse:
        key = "~" + key
    if sg.langs:
        key += "@" + ":".join(sg.langs)
    return key


def _src_index(sg: SubGraph, uid: int) -> int:
    i = int(np.searchsorted(sg.src_uids, uid))
    if i < len(sg.src_uids) and sg.src_uids[i] == uid:
        return i
    return -1


class _Block:
    """One block's encode: the debug flag read once, the rows laid into
    lists counted for ONE counter increment."""

    __slots__ = ("debug", "rows")

    def __init__(self):
        self.debug = DEBUG_UIDS.get()
        self.rows = 0


def _plan(sg: SubGraph) -> list:
    """What each emitted child of ``sg`` lays on an object, decided once
    per node: ``(kind, child, key, strict, render)``.  ``strict``: an
    object without this key is dropped (@cascade).  Kinds: ``hex`` the
    uid itself, ``count``, ``value`` (val / aggregate / math /
    _predicate_ / checkpwd, ``render`` says how), ``leaf`` (a value
    predicate; may carry value facets), ``groups``, ``rows`` (a uid
    child), ``none`` (an expansion that found nothing, under @cascade)."""
    cascade = sg.params.cascade
    plan = []
    for child in sg.children:
        p, attr = child.params, child.attr
        if p.is_internal and (not p.var or attr not in ("val", "math")):
            continue
        if attr in ("_uid_", "uid"):
            plan.append(("hex", child, child.alias or "_uid_", False, None))
        elif p.do_count:
            if attr:  # bare count() is a row of the list above, not a key
                key = f"count({'~' if child.reverse else ''}{attr})"
                plan.append(("count", child, child.alias or key, False, None))
        elif attr == "val":
            var = child.needs_var[0] if child.needs_var else ""
            if p.agg_func:
                key, strict = f"{p.agg_func}(val({var}))", False
            else:
                key, strict = f"val({var})", cascade
            plan.append(("value", child, child.alias or key, strict, None))
        elif attr == "math":
            if not p.is_internal:
                plan.append(("value", child, child.alias or "math", False, None))
        elif attr == "_predicate_":
            plan.append(
                ("value", child, child.alias or attr, False, attrgetter("value"))
            )
        elif p.is_groupby:
            if child.groups is not None:
                plan.append(("groups", child, _display_key(child), False, None))
        elif child.func is not None and child.func.name == "checkpwd":
            # reference shape: "pwd": [{"checkpwd": true}]
            plan.append(("value", child, child.alias or attr, False, _checkpwd))
        elif child.is_value_node():
            plan.append(("leaf", child, _display_key(child), cascade, None))
        elif len(child.seg_ptr) > 1 or len(child.out_flat):
            strict = cascade or p.cascade
            plan.append(("rows", child, _display_key(child), strict, None))
        elif cascade:
            # empty expansion (no data): under cascade this kills the node
            plan.append(("none", child, "", True, None))
    return plan


def _checkpwd(v: TypedValue) -> List[dict]:
    return [{"checkpwd": bool(v.value)}]


def _values(child: SubGraph, uids: List[int], render=None) -> list:
    """The column of a value child: one entry per uid, rendered by tid
    (strings, numbers and bools pass through as they are)."""
    got = map(child.values.get, uids)
    if render is not None:
        return [_NA if v is None else render(v) for v in got]
    return [
        _NA if v is None
        else v.value if v.tid not in _JSON_TYPED
        else json_value(v)
        for v in got
    ]


def _rows_of(src: np.ndarray, arr: np.ndarray):
    """Where each uid of ``arr`` sits in the non-empty ``src`` and whether
    it is there at all, in ONE lookup; ``(None, None)`` where ``arr`` is
    ``src`` itself, row for row — the usual case: a child's sources are its
    parent's uids — and nothing needs looking up.  ``src`` is ascending
    unless its block's root is ordered (display order); then the lookup
    goes through a sorter."""
    n = len(src)
    if n == len(arr) and (src == arr).all():
        return None, None
    order = None if (src[1:] >= src[:-1]).all() else src.argsort(kind="stable")
    pos = np.searchsorted(src, arr, sorter=order)
    pos[pos == n] = 0
    if order is not None:
        pos = order[pos]
    return pos, src[pos] == arr


def _counts(child: SubGraph, arr: np.ndarray) -> list:
    if child.counts is None or not len(child.src_uids):
        return [0] * len(arr)
    pos, hit = _rows_of(child.src_uids, arr)
    if pos is None:
        return child.counts.tolist()
    return (child.counts[pos] * hit).tolist()


def _with_edge_facets(sub: dict, f, spec) -> dict:
    fj = _facets_json(f, spec) if f else None
    return {**sub, "@facets": {"_": fj}} if fj else sub


def _has_bare_count(sg: SubGraph) -> bool:
    return any(c.params.do_count and c.attr == "" for c in sg.children)


def _rows(blk: _Block, child: SubGraph, arr: np.ndarray, uids: List[int]) -> list:
    """The column of a uid child: per uid of the level, the list of the
    objects under it, ``_NA`` where that list is empty.  The child's own
    level is built once, over the distinct targets of all the level's
    edges, and dealt out edge by edge."""
    src, seg, out = child.src_uids, child.seg_ptr, child.out_flat
    if not len(src) or not uids:
        return [_NA] * len(uids)
    pos, hit = _rows_of(src, arr)
    if pos is None:
        targets = out[seg[0]:seg[-1]]
        bounds = (seg - seg[0]).tolist()
        hits = repeat(True)
    else:
        lo = seg[pos]
        lens = (seg[pos + 1] - lo) * hit
        ends = np.cumsum(lens)
        targets = out[np.repeat(lo - ends + lens, lens) + np.arange(ends[-1])]
        bounds = [0] + ends.tolist()
        hits = hit.tolist()
    if (targets[1:] > targets[:-1]).all():  # one sorted row: all distinct
        subs = per_edge = _level(blk, child, targets)
    else:
        uniq, inv = np.unique(targets, return_inverse=True)
        subs = _level(blk, child, uniq)
        per_edge = [subs[i] for i in inv.tolist()]
    spec = child.params.facets
    facets = child.edge_facets if spec is not None else None
    dsts = targets.tolist() if facets else None
    whole = not facets and all(subs)  # no copy to make, nothing to drop
    tally = _has_bare_count(child)
    col, rows = [], 0
    for u, (a, b), found in zip(uids, pairwise(bounds), hits):
        if whole:
            items = per_edge[a:b]
        elif not facets:
            items = [sub for sub in per_edge[a:b] if sub]
        else:
            items = []
            for e in range(a, b):
                sub = per_edge[e]
                if sub is not None:
                    sub = _with_edge_facets(sub, facets.get((u, dsts[e])), spec)
                    if sub:
                        items.append(sub)
        if tally and found:
            items.append({"count": b - a})
        rows += len(items)
        col.append(items or _NA)
    blk.rows += rows
    return col


def _reflex_rows(blk: _Block, child: SubGraph, uid: int, path: frozenset):
    """@ignorereflex: the same column for ONE uid, depth first, dropping
    targets already on the ancestor path (parentIds stack,
    query/query.go:365-375)."""
    i = _src_index(child, uid)
    if i < 0:
        return _NA
    row = child.row_targets(i).tolist()
    spec = child.params.facets
    items = []
    for dst in row:
        if dst in path:
            continue
        sub = _level(blk, child, np.array([dst], dtype=np.int64), path)[0]
        if sub is not None:
            if spec is not None:
                sub = _with_edge_facets(sub, child.edge_facets.get((uid, dst)), spec)
            if sub:
                items.append(sub)
    if _has_bare_count(child):
        items.append({"count": len(row)})
    blk.rows += len(items)
    return items or _NA


def _level(
    blk: _Block, sg: SubGraph, arr: np.ndarray, path: Optional[frozenset] = None
) -> List[Optional[dict]]:
    """The objects of node ``sg`` for the uids ``arr``, one per uid and
    in their order (preTraverse analog, a level at a time); ``None``
    where @cascade drops it.  With a ``path`` (@ignorereflex) ``arr`` is
    one uid and its uid children are walked under ``path`` + that uid."""
    uids = arr.tolist()
    if path is not None:
        path = path | {uids[0]}
    objs: List[Optional[dict]] = [{} for _ in uids]
    dead: List[int] = []
    for kind, child, key, strict, render in _plan(sg):
        if kind == "rows":
            if path is None:
                col = _rows(blk, child, arr, uids)
            else:
                col = [_reflex_rows(blk, child, uids[0], path)]
        elif kind == "hex":
            col = map(hex, uids)
        elif kind == "count":
            col = _counts(child, arr)
        elif kind == "groups":
            col = ([{"@groupby": child.groups}] for _ in uids)
        elif kind == "none":
            col = repeat(_NA, len(uids))
        else:
            col = _values(child, uids, render)
        if strict:
            for i, v in enumerate(col):
                if v is _NA:
                    dead.append(i)
                else:
                    objs[i][key] = v
        else:
            for obj, v in zip(objs, col):
                if v is not _NA:
                    obj[key] = v
        if kind == "leaf" and child.value_facets and child.params.facets:
            for obj, u, v in zip(objs, uids, col):
                f = child.value_facets.get(u) if v is not _NA else None
                if f:
                    fj = _facets_json(f, child.params.facets)
                    if fj:
                        obj.setdefault("@facets", {})[key] = fj
    for i in dead:
        objs[i] = None
    if blk.debug:
        for obj, u in zip(objs, uids):
            if obj:
                obj.setdefault("_uid_", hex(u))
    return objs


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
    p = sg.params
    blk, path = _Block(), "level"
    if p.is_groupby and sg.groups is not None:
        out = [{"@groupby": sg.groups}]  # root-level @groupby (GroupByRoot)
    elif not len(sg.dest_uids) and sg.func is None:
        # aggregation-only block (`total() { sum(val(c)) ... }`): values
        # live under the synthetic uid 0
        out = [obj for obj in _level(blk, sg, _UID0) if obj]
    else:
        out = [{"count": len(sg.dest_uids)}] if _has_bare_count(sg) else []
        if p.normalize:
            path = "walk"
            for uid in sg.dest_uids.tolist():
                out += _normalize_flatten(store, sg, uid) or ()
        elif p.ignore_reflex:
            path = "walk"
            for uid in sg.dest_uids:
                out += [obj for obj in _level(blk, sg, uid[None], frozenset()) if obj]
        else:
            out += [obj for obj in _level(blk, sg, sg.dest_uids) if obj]
    ENCODE_OBJECTS.add(path, blk.rows + len(out))
    return out


def encode_path(store: PostingStore, sg: SubGraph, out: dict):
    """shortest blocks render under "_path_" (query/shortest.go
    createPathSubgraph:598) plus a regular block for requested attrs."""
    paths = getattr(sg, "paths", None) or []
    objs = []
    for path in paths:
        node: Optional[dict] = None
        for elem in reversed(path):
            cur = {"_uid_": _uid_hex(elem["uid"])}
            if elem.get("facets"):
                cur["@facets"] = {"_": _facets_json(elem["facets"])}
            if node is not None:
                cur[elem["attr_out"]] = [node]
            node = cur
        if node:
            objs.append(node)
    out.setdefault("_path_", []).extend(objs)
    if sg.children:
        out.setdefault(sg.params.alias or "_path_", [])
