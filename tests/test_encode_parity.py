"""The level encoder (``dgraph_tpu/query/outputnode.py``) against the plain
reference, the depth-first walk it replaced (``tests/_encode_oracle.py``):
``json.dumps`` byte for byte — key order, list order, which empties are
dropped, hex uids, where facets land — over

(a) one case per directive and kind, run through ``QueryEngine`` on the
    goldens' fixture graph (both encoders read the SAME SubGraph);
(b) 200 seeded random SubGraph trees (depth <= 4, repeated targets, rows
    with no data, sources missing from ``src_uids``, every kind of child);
(c) the benchmark cell's three texts on ``benchmark/filmgen.py`` at 200k quads.

And the structure that makes it fast, held by call counts, not by a clock:
no per-object walk, at most one ``np.searchsorted`` per SubGraph node, a
one-object answer makes no more numpy calls than it has nodes, and
``dgraph_encode_objects_total{path}`` grows by the objects emitted.
"""

import datetime as dt
import json
import os
import sys

import numpy as np
import pytest

import _encode_oracle as oracle
from test_goldens import RDF, SCHEMA

from dgraph_tpu.gql.ast import FacetsSpec, Function
from dgraph_tpu.models import PostingStore
from dgraph_tpu.models.types import TypeID, TypedValue
from dgraph_tpu.query import QueryEngine, outputnode
from dgraph_tpu.query.subgraph import SubGraph
from dgraph_tpu.utils.metrics import ENCODE_OBJECTS

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark")

# value facets, which the goldens' fixture lacks
EXTRA = r"""
    <0x1> <nick> "Annie" (origin="school", since=2001-09-01) .
    <0x2> <nick> "Benji" (origin="home") .
    <0x3> <nick> "Cee" .
"""


@pytest.fixture(scope="module")
def eng():
    e = QueryEngine(PostingStore())
    e.run("mutation { schema { %s nick: string . } set { %s %s } }" % (SCHEMA, RDF, EXTRA))
    e.run('mutation { set { <0x4> <pwd> "hunter2" . } }')
    return e


def both(eng, text, monkeypatch, debug=False):
    """Run ``text`` once; every block's SubGraph is encoded by the level
    encoder (what ``execute`` returns) and by the oracle.  Returns the two
    ``json.dumps`` of the blocks, in the order they were encoded."""
    level = outputnode.encode_block
    seen = []

    def spy(store, sg):
        want = oracle.encode_block(store, sg)
        got = level(store, sg)
        seen.append((got, want))
        return got

    monkeypatch.setattr(outputnode, "encode_block", spy)
    token = outputnode.DEBUG_UIDS.set(debug)
    try:
        out = eng.run(text)
    finally:
        outputnode.DEBUG_UIDS.reset(token)
    assert seen, "no block was encoded"
    return (json.dumps([g for g, _ in seen]), json.dumps([w for _, w in seen]), out)


CASES = {
    "alias": "{ me(func: uid(0x1, 0x2)) { n: name a: age kept: cares_for { pn: name } } }",
    "langs": "{ me(func: uid(0x1, 0x2, 0x3)) { name@ru name@hu:ru name@. friend { name@ru } } }",
    "uid": "{ me(func: uid(0x1, 0x3)) { _uid_ name friend { u: _uid_ } } }",
    "count_pred": "{ me(func: has(dob)) { name count(cares_for) c: count(friend) count(~friend) } }",
    "count_bare_root": "{ me(func: has(age)) { count() } }",
    "count_bare_child": "{ me(func: uid(0x1, 0x2, 0x4)) { name cares_for { count() } } }",
    "count_bare_child_and_rows": "{ me(func: uid(0x1, 0x4)) { cares_for { name count() } } }",
    "val": """{ var(func: has(dob)) { a as age }
                me(func: uid(0x1, 0x2, 0xa)) { name val(a) v: val(a) } }""",
    "aggregates": """{ var(func: uid(0x1, 0x2)) { cares_for { a as age } m as min(val(a)) }
                       me(func: uid(0x1, 0x2, 0x3)) { name min(val(a)) val(m) } }""",
    "math": """{ var(func: has(dob)) { a as age w as weight }
                 me(func: has(dob)) { name math(a + 1) m: math(w / (a / 10.0)) } }""",
    "predicate": "{ me(func: uid(0x1, 0x4, 0xe, 0x77)) { _predicate_ } }",
    "value_facets": "{ me(func: uid(0x1, 0x2, 0x3, 0x4)) { name nick @facets } }",
    "value_facets_keys": "{ me(func: uid(0x1, 0x2, 0x3)) { nick @facets(origin) name } }",
    "edge_facets": "{ me(func: uid(0x1, 0x2, 0x3)) { name cares_for @facets { name } } }",
    "edge_facets_keys": "{ me(func: uid(0x1, 0x2)) { cares_for @facets(level) { name age } } }",
    "edge_facets_on_empty_objects": "{ me(func: uid(0x1, 0x2)) { cares_for @facets(since) { weight } } }",
    "edge_facets_reverse": "{ me(func: uid(0xa, 0xd)) { ~cares_for @facets(level) { name } } }",
    "cascade_root": "{ me(func: uid(0x1, 0x2, 0x3, 0x4)) @cascade { name cares_for { name age } } }",
    "cascade_child": "{ me(func: uid(0x1, 0x2, 0x3)) { name cares_for @cascade { name age } } }",
    "cascade_empty_expansion": "{ me(func: uid(0x1, 0x4)) @cascade { name pet { name } } }",
    "cascade_val": """{ var(func: uid(0x1, 0x2)) { a as age }
                        me(func: uid(0x1, 0x2, 0x3)) @cascade { name val(a) } }""",
    "groupby_root": "{ me(func: uid(0xa, 0xb, 0xc, 0xd)) @groupby(age) { count(_uid_) } }",
    "groupby_nested": "{ me(func: uid(0x1, 0x2)) { name cares_for @groupby(age) { count(_uid_) } } }",
    "checkpwd": '{ me(func: uid(0x4, 0x1)) { name checkpwd(pwd, "hunter2") } }',
    "normalize": """{ me(func: uid(0x1, 0x2)) @normalize {
                        n: name cares_for { pn: name pa: age } friend { fn: name } } }""",
    "normalize_cascade": "{ me(func: uid(0x2)) @cascade @normalize { n: name cares_for { pn: name pa: age } } }",
    "ignorereflex": "{ me(func: uid(0x1, 0x3)) @ignorereflex { name friend { name friend { name friend { name } } } } }",
    "ignorereflex_facets": "{ me(func: uid(0x1)) @ignorereflex { cares_for @facets(level) { name ~cares_for { name count(cares_for) } } } }",
    "reflex_kept": "{ me(func: uid(0x1)) { name friend { name friend { name friend { name } } } } }",
    "recurse": "{ me(func: uid(0x1)) @recurse(depth: 3) { name friend } }",
    "recurse_two_preds": "{ recurse(func: uid(0x2), depth: 2) { name cares_for pet } }",
    "aggregation_only": """{ var(func: has(dob)) { a as age }
                             stats() { mn: min(val(a)) mx: max(val(a)) sum(val(a)) avg(val(a)) } }""",
    "empty_block": '{ me(func: eq(name, "Nobody")) { name friend { name } } }',
    "empty_objects_dropped": "{ me(func: uid(0x1, 0x2, 0xe)) { weight friend { weight } } }",
    "datetime_and_bool": "{ me(func: uid(0x1, 0x2, 0xa)) { dob wild weight age } }",
    "shared_targets": "{ me(func: has(cares_for)) { name cares_for { name ~cares_for { name cares_for { name } } } } }",
    "filter_order_paginate": """{ me(func: uid(0x1, 0x2)) { name
                                    cares_for (orderdesc: age, first: 2) @filter(has(age)) { name age } } }""",
    "var_block_then_uid": """{ var(func: uid(0x1)) { friend { F as friend } }
                               leaf(func: uid(F)) { name } }""",
    "expand_all": "{ me(func: uid(0x2)) { expand(_all_) { name } } }",
    "two_blocks": "{ a(func: uid(0x1)) { name } b(func: uid(0x2)) { name cares_for { name } } }",
}


@pytest.mark.parametrize("debug", [False, True], ids=["plain", "debug"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_same_bytes_as_the_walk(eng, monkeypatch, case, debug):
    got, want, _ = both(eng, CASES[case], monkeypatch, debug=debug)
    assert got == want


def test_an_ordered_root_keeps_its_rows(eng, monkeypatch):
    """Where the two MUST differ: a root in display order hands its
    children sources that are not ascending, and the walk's scalar binary
    search then misses rows that are there (Cara Lee's and Ann's pets and
    counts).  The level encoder takes the child's rows as they lie."""
    got, want, out = both(
        eng, "{ me(func: has(dob), orderdesc: age) { name n: count(cares_for) cares_for { name } } }",
        monkeypatch)
    assert out["me"] == [
        {"name": "Cara Lee", "n": 1, "cares_for": [{"name": "Asha"}]},
        {"name": "Ann", "n": 3, "cares_for": [{"name": "Asha"}, {"name": "Bo"}, {"name": "Cleo"}]},
        {"name": "Ben", "n": 2, "cares_for": [{"name": "Dodo"}, {"name": "Ember"}]},
    ]
    assert got != want, "the walk found every row: drop this test's reason"
    # ... and under @cascade the root's uids are a subset of those sources
    got, want, out = both(
        eng, "{ me(func: has(age), orderdesc: age) @cascade { name cares_for { name } } }", monkeypatch)
    assert [o["name"] for o in out["me"]] == ["Cara Lee", "Ann", "Ben"]
    assert [len(o["cares_for"]) for o in out["me"]] == [1, 3, 2]


# ------------------------------------------------------------ (b) random trees

VALUE_POOL = [
    lambda r, u: TypedValue(TypeID.STRING, f"s{u}"),
    lambda r, u: TypedValue(TypeID.INT, int(r.integers(-5, 50))),
    lambda r, u: TypedValue(TypeID.FLOAT, float(r.integers(0, 9)) / 4),
    lambda r, u: TypedValue(TypeID.BOOL, bool(r.integers(0, 2))),
    lambda r, u: TypedValue(TypeID.DATETIME, dt.datetime(1990 + int(u) % 30, 1 + int(u) % 12, 3)),
    lambda r, u: TypedValue(TypeID.DATE, dt.date(2000 + int(u) % 20, 5, 1 + int(u) % 28)),
    lambda r, u: TypedValue(TypeID.BINARY, bytes([int(u) % 256, 7])),
]


def _facet(r):
    f = {}
    if r.random() < 0.8:
        f["w"] = TypedValue(TypeID.INT, int(r.integers(0, 9)))
    if r.random() < 0.5:
        f["since"] = TypedValue(TypeID.DATETIME, dt.datetime(2020, 1 + int(r.integers(0, 12)), 1))
    return f


def _facets_spec(r):
    return r.choice([FacetsSpec(all_keys=True), FacetsSpec(keys=["w"]), FacetsSpec(keys=["nope"]), FacetsSpec()])


def _sources(r, uids):
    """A child's ``src_uids``: the level's uids, some missing, some extra."""
    src = uids[r.random(len(uids)) < r.choice([1.0, 1.0, 0.7])]
    if r.random() < 0.3:
        src = np.union1d(src, r.integers(1, 400, int(r.integers(1, 4))))
    return np.asarray(src, dtype=np.int64)


def _value_child(r, uids, i):
    c = SubGraph(attr=f"v{i}", alias=str(r.choice(["", "", f"al{i}"])))
    c.src_uids = _sources(r, uids)
    if r.random() < 0.3:
        c.langs = ["en", "ru"][: int(r.integers(1, 3))]
    make = VALUE_POOL[int(r.integers(0, len(VALUE_POOL)))]
    c.values = {int(u): make(r, u) for u in c.src_uids.tolist() if r.random() < 0.8}
    if r.random() < 0.3:
        c.params.facets = _facets_spec(r)
        c.value_facets = {u: _facet(r) for u in c.values if r.random() < 0.6}
    return c


def _special_child(r, uids, i):
    kind = r.choice(["uid", "count", "val", "agg", "math", "math_internal", "predicate",
                     "groupby", "checkpwd", "bare_count", "internal", "nothing"])
    c = SubGraph(attr=f"x{i}", alias=str(r.choice(["", f"sp{i}"])))
    c.src_uids = _sources(r, uids)
    some = {int(u): TypedValue(TypeID.INT, int(u) % 7) for u in c.src_uids.tolist() if r.random() < 0.7}
    if kind == "uid":
        c.attr = str(r.choice(["_uid_", "uid"]))
    elif kind == "count":
        c.params.do_count = True
        c.reverse = bool(r.integers(0, 2))
        c.counts = None if r.random() < 0.1 else r.integers(0, 6, len(c.src_uids)).astype(np.int64)
    elif kind in ("val", "agg"):
        c.attr, c.needs_var, c.values = "val", ["x"], some
        c.params.agg_func = "min" if kind == "agg" else ""
    elif kind in ("math", "math_internal"):
        c.attr, c.values = "math", some
        c.params.is_internal = kind == "math_internal"
        c.params.var = "m" if c.params.is_internal else ""
    elif kind == "predicate":
        c.attr = "_predicate_"
        c.values = {u: TypedValue(TypeID.STRING, ["a", "b"][: 1 + u % 2]) for u in some}
    elif kind == "groupby":
        c.params.is_groupby = True
        c.groups = None if r.random() < 0.3 else [{"age": 2, "count": 3}]
    elif kind == "checkpwd":
        c.func = Function(name="checkpwd")
        c.values = {u: TypedValue(TypeID.BOOL, bool(u % 2)) for u in some}
    elif kind == "bare_count":
        c.attr, c.params.do_count = "", True
    elif kind == "internal":
        c.params.is_internal, c.values = True, some
    return c          # "nothing": an expansion that found no data


def _uid_child(r, uids, i, depth, cascade):
    c = SubGraph(attr=f"e{i}", alias=str(r.choice(["", "", f"edge{i}"])), reverse=bool(r.random() < 0.2))
    c.src_uids = _sources(r, uids)
    degs = r.integers(0, 5, len(c.src_uids)) * (r.random(len(c.src_uids)) < 0.8)
    c.seg_ptr = np.concatenate([[0], np.cumsum(degs)]).astype(np.int64)
    # a small universe: targets repeat within a row's neighbours and across rows
    c.out_flat = r.integers(1, int(r.choice([12, 60, 400])), int(c.seg_ptr[-1])).astype(np.int64)
    if r.random() < 0.5:     # posting lists are sorted sets, ordered children are not
        for a, b in zip(c.seg_ptr[:-1], c.seg_ptr[1:]):
            c.out_flat[a:b] = np.sort(c.out_flat[a:b])
    c.dest_uids = np.unique(c.out_flat)
    c.params.cascade = cascade or bool(r.random() < 0.15)
    if r.random() < 0.3:
        c.params.facets = _facets_spec(r)
        pairs = zip(np.repeat(c.src_uids, np.diff(c.seg_ptr)).tolist(), c.out_flat.tolist())
        c.edge_facets = {p: _facet(r) for p in pairs if r.random() < 0.5}
    c.children = _children(r, c.dest_uids, depth - 1, c.params.cascade)
    return c


def _children(r, uids, depth, cascade):
    kids = []
    for i in range(int(r.integers(0, 5))):
        roll = r.random()
        if roll < 0.4:
            kids.append(_value_child(r, uids, i))
        elif roll < 0.75 and depth > 0:
            kids.append(_uid_child(r, uids, i, depth, cascade))
        else:
            kids.append(_special_child(r, uids, i))
    return kids


def random_block(seed):
    r = np.random.default_rng(seed)
    sg = SubGraph(func=Function(name="uid"))
    sg.params.alias = "q"
    n = int(r.choice([0, 1, 3, 20, 60]))
    sg.dest_uids = np.sort(r.choice(np.arange(1, 400), n, replace=False)).astype(np.int64)
    if r.random() < 0.2:
        sg.dest_uids = r.permutation(sg.dest_uids)    # an ordered root whose children...
    sg.params.cascade = bool(r.random() < 0.2)
    sg.params.normalize = bool(r.random() < 0.1)
    sg.params.ignore_reflex = bool(r.random() < 0.15)
    if r.random() < 0.05:
        sg.func, sg.dest_uids = None, np.empty(0, np.int64)     # aggregation-only
    sg.children = _children(r, np.sort(sg.dest_uids) if len(sg.dest_uids) else np.zeros(1, np.int64),
                            int(r.integers(1, 5)), sg.params.cascade)
    return sg, bool(r.random() < 0.25)


@pytest.mark.parametrize("seed", range(200))
def test_random_tree_same_bytes_as_the_walk(seed):
    sg, debug = random_block(seed)
    token = outputnode.DEBUG_UIDS.set(debug)
    try:
        want = json.dumps(oracle.encode_block(None, sg))
        got = json.dumps(outputnode.encode_block(None, sg))
    finally:
        outputnode.DEBUG_UIDS.reset(token)
    assert got == want


def test_the_random_trees_reach_every_kind():
    """The generator is worth its 200 cases only if it reaches the kinds
    and the depths: count what the first 200 seeds emit."""
    kinds, depth, objects = set(), 0, 0

    def walk(sg, d):
        nonlocal depth
        depth = max(depth, d)
        for c in sg.children:
            kinds.update(kind for kind, *_ in outputnode._plan(sg))
            walk(c, d + 1)

    for seed in range(200):
        sg, _ = random_block(seed)
        walk(sg, 0)
        objects += len(oracle.encode_block(None, sg))
    assert kinds == {"hex", "count", "value", "leaf", "groups", "rows", "none"}
    assert depth >= 4 and objects > 1000


# ------------------------------------------------------------ (c) the cell's texts

@pytest.fixture(scope="module")
def film():
    sys.path.insert(0, BENCH)
    try:
        import filmgen
        import trafficgen
        from run import World
    finally:
        sys.path.remove(BENCH)
    from dgraph_tpu.serve.bulk import fast_apply_set

    g = filmgen.generate(200_000, 7)
    e = QueryEngine(PostingStore())
    cfg = trafficgen.load_json("configs", "film21m-q4.json")
    e.run("mutation { schema { %s } }" % "\n".join(cfg["schema"]))
    body = "\n".join(filmgen.nquad_lines(g, 0, len(g.director)))
    if fast_apply_set(e.store, body, {}) is None:      # no native scanner here
        e.run("mutation { set { %s } }" % body)
    mix = trafficgen.load_json("traffic", "traverse.json")
    return e, trafficgen.load_classes(mix, World(g))


@pytest.mark.parametrize("cls", ["hot_actor4", "two_hop", "coactor3"])
def test_the_cells_texts_same_bytes_as_the_walk(film, monkeypatch, cls):
    e, classes = film
    kind = classes[cls]
    for root in kind.pool()[:2]:
        got, want, out = both(e, kind.text(root), monkeypatch)
        assert got == want
        assert len(got) > 1000, "a root with an answer worth comparing"
        monkeypatch.undo()


# ------------------------------------------------------------ structure, by call counts

class CountingNumpy:
    """``outputnode.np`` with every function call through it counted."""

    def __init__(self):
        self.calls = {}

    def __getattr__(self, name):
        real = getattr(np, name)
        if not callable(real) or isinstance(real, type):
            return real

        def counted(*a, **kw):
            self.calls[name] = self.calls.get(name, 0) + 1
            return real(*a, **kw)

        return counted


def _leaf(src, attr="name"):
    c = SubGraph(attr=attr)
    c.src_uids = src
    c.values = {u: TypedValue(TypeID.STRING, f"N{u}") for u in src.tolist()}
    return c


def _edge(attr, src, degree, universe, r):
    c = SubGraph(attr=attr)
    c.src_uids = src
    c.seg_ptr = np.arange(0, degree * len(src) + 1, degree, dtype=np.int64)
    c.out_flat = r.integers(1, universe, degree * len(src)).astype(np.int64)
    c.dest_uids = np.unique(c.out_flat)
    return c


def leaf_block(n):
    sg = SubGraph(func=Function(name="uid"))
    sg.dest_uids = np.arange(5, 5 + 3 * n, 3, dtype=np.int64)
    sg.children = [_leaf(sg.dest_uids)]
    return sg, 2            # nodes


def nested_block(n_root, r):
    """root -> e1 (4 a row) -> e2 (3 a row) -> name, with names at every level."""
    sg = SubGraph(func=Function(name="uid"))
    sg.dest_uids = np.arange(1, 1 + n_root, dtype=np.int64)
    e1 = _edge("e1", sg.dest_uids, 4, 5_000, r)
    e2 = _edge("e2", e1.dest_uids, 3, 2_000, r)
    e2.children = [_leaf(e2.dest_uids)]
    e1.children = [_leaf(e1.dest_uids), e2]
    sg.children = [_leaf(sg.dest_uids), e1]
    return sg, 6            # root, name, e1, e1.name, e2, e2.name


@pytest.fixture
def counted(monkeypatch):
    """``calls``: numpy functions the encoder called, by name; ``levels``:
    one entry a call of ``_level``.  Any per-object walk fails the test."""
    cn, levels, real = CountingNumpy(), [], outputnode._level
    monkeypatch.setattr(outputnode, "np", cn)
    monkeypatch.setattr(outputnode, "_level", lambda *a, **kw: levels.append(1) or real(*a, **kw))
    for walk in ("_src_index", "_reflex_rows", "_normalize_flatten"):
        monkeypatch.setattr(outputnode, walk, lambda *a: pytest.fail("the per-object walk ran"))
    return cn.calls, levels


def _emitted(rows, seen=None):
    """Result objects the encoder laid into an answer's lists: the rows of
    every DISTINCT list (an object that several edges reach is built once,
    and what hangs under it is laid once)."""
    seen = set() if seen is None else seen
    if id(rows) in seen:
        return 0
    seen.add(id(rows))
    n = len(rows)
    for obj in rows:
        for v in obj.values():
            if isinstance(v, list) and v and isinstance(v[0], dict):
                n += _emitted(v, seen)
    return n


def test_a_50k_leaf_block_is_one_pass(counted):
    calls, levels = counted
    sg, nodes = leaf_block(50_000)
    before = ENCODE_OBJECTS.snapshot()
    out = outputnode.encode_block(None, sg)
    assert len(out) == 50_000 and out[7] == {"name": "N26"}
    assert len(levels) == 1, "one call for the level, not one an object"
    assert calls.get("searchsorted", 0) <= nodes and sum(calls.values()) <= nodes
    after = ENCODE_OBJECTS.snapshot()
    assert after["level"] - before["level"] == 50_000 and after["walk"] == before["walk"]


def test_a_nested_block_looks_rows_up_once_a_node(counted):
    calls, levels = counted
    sg, nodes = nested_block(3_000, np.random.default_rng(3))
    before = ENCODE_OBJECTS.snapshot()
    out = outputnode.encode_block(None, sg)
    assert json.dumps(out) == json.dumps(oracle.encode_block(None, sg))
    assert len(levels) == 3, "one call a uid level: root, e1's targets, e2's targets"
    assert calls.get("searchsorted", 0) <= nodes
    grown = ENCODE_OBJECTS.snapshot()["level"] - before["level"]
    distinct_e1 = len(sg.children[1].dest_uids)
    assert grown == _emitted(out) == 3_000 + 12_000 + 3 * distinct_e1


def test_a_one_object_answer_makes_no_more_numpy_calls_than_nodes(counted):
    calls, _ = counted
    sg, nodes = nested_block(1, np.random.default_rng(4))
    # a posting list is a sorted set
    for c in (sg.children[1], sg.children[1].children[1]):
        for a, b in zip(c.seg_ptr[:-1], c.seg_ptr[1:]):
            c.out_flat[a:b] = np.sort(c.out_flat[a:b])
    out = outputnode.encode_block(None, sg)
    assert _emitted(out) == 1 + 4 + 12
    assert sum(calls.values()) <= nodes, calls
    calls.clear()
    sg, nodes = leaf_block(1)
    assert outputnode.encode_block(None, sg) == [{"name": "N5"}]
    assert sum(calls.values()) <= nodes, calls


@pytest.mark.parametrize("directive, path", [("", "level"), ("@cascade", "level"),
                                             ("@normalize", "walk"), ("@ignorereflex", "walk")])
def test_the_counter_grows_by_the_objects_emitted(eng, directive, path):
    text = "{ me(func: uid(0x1, 0x2, 0x3)) %s { n: name friend { f: name friend { g: name } } } }" % directive
    before = ENCODE_OBJECTS.snapshot()
    out = eng.run(text)["me"]
    after = ENCODE_OBJECTS.snapshot()
    other = "walk" if path == "level" else "level"
    assert after[path] - before[path] == _emitted(out) > 0
    assert after[other] == before[other]


def test_both_paths_are_there_from_boot():
    from dgraph_tpu.utils.metrics import metrics

    assert set(ENCODE_OBJECTS.snapshot()) >= {"level", "walk"}
    text = metrics.prometheus_text()
    assert 'dgraph_encode_objects_total{path="level"}' in text
    assert 'dgraph_encode_objects_total{path="walk"}' in text


def test_repeated_targets_share_one_object(eng):
    """The read-only rule the module states: two edges to one target hold
    the same dict, an edge with facets a copy of its own."""
    out = eng.run("{ me(func: uid(0x1, 0x3)) { cares_for { name } } }")["me"]
    ann, cara = out
    assert ann["cares_for"][0] is cara["cares_for"][0]              # Asha, twice
    out = eng.run("{ me(func: uid(0x1, 0x3)) { cares_for @facets(level) { name } } }")["me"]
    ann, cara = out
    assert ann["cares_for"][0] == {"name": "Asha", "@facets": {"_": {"level": 3}}}
    assert cara["cares_for"][0] == {"name": "Asha"}
