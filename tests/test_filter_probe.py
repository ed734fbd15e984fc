"""A filter leaf probed by its candidates (``FuncResolver.probe``): where the
candidates are fewer than a ``uid(var)`` leaf's bound arrays or a sortable
index inequality's rows, each candidate is looked up — in the arrays, or in
the predicate's numeric value column — and the leaf's set is never built.
Every case gives the uids the whole set bounded by the candidates gives
(the route before the probe, kept here as the reference), sorted-unique
int64; the probe books itself as a k-way of route ``probe``, and a level
larger than its leaves books none.
"""

import datetime as dt

import numpy as np
import pytest

from dgraph_tpu.gql.ast import Function, VarRef
from dgraph_tpu.models import PostingStore
from dgraph_tpu.models.types import TypedValue, TypeID
from dgraph_tpu.query import QueryEngine
from dgraph_tpu.query import joinplan
from dgraph_tpu.query.functions import FuncResolver
from dgraph_tpu.utils.metrics import INDEX_RECHECK_UIDS, KWAY_ELEMENTS, KWAY_INTERSECTS

N = 300            # uids 0x1-0x12c; 251-300 hold no value of d, n or w
HUB, SMALL_HUB = 1000, 1001
SMALL = [2, 3, 17, 40, 41, 99, 150, 201, 260, 290]   # the small hub's level


def _dates(rng):
    out = {1: "1975-03-14", 2: "1975-01-01", 3: "1976-01-01", 6: "1975-03-14T00:00:00.000001"}
    for u in range(1, 251):
        if u not in out:
            t = dt.datetime(int(rng.integers(1968, 1980)), int(rng.integers(1, 13)),
                            int(rng.integers(1, 29)), int(rng.integers(0, 24)))
            out[u] = t.isoformat()
    return out


@pytest.fixture(scope="module")
def world():
    rng = np.random.default_rng(45)
    d = _dates(rng)
    n = {u: int(rng.integers(-50, 51)) for u in range(1, 251)}
    n[4], n[5] = 2 ** 60, -(2 ** 60)           # past 2**53: compared one by one
    w = {u: float(rng.integers(-20, 21)) / 4 for u in range(1, 251)}
    wi = {u: float(rng.integers(-20, 21)) / 4 for u in range(1, 251)}
    wi[7], wi[8] = "inf", "-inf"               # no float token holds them
    od = {u: f"1975-12-31T{int(rng.integers(0, 24)):02d}:00:00+05:00" for u in range(1, 120)}
    od.update({u: f"1976-01-01T{int(rng.integers(0, 24)):02d}:00:00Z" for u in range(120, 200)})
    quads = [f'<0x{u:x}> <d> "{v}" .' for u, v in d.items()]
    quads += [f'<0x{u:x}> <n> "{v}" .' for u, v in n.items()]
    quads += [f'<0x{u:x}> <w> "{v}" .' for u, v in w.items()]
    quads += [f'<0x{u:x}> <wi> "{v}" .' for u, v in wi.items()]
    quads += [f'<0x{u:x}> <od> "{v}" .' for u, v in od.items()]
    quads += [f'<0x{u:x}> <ld> "1975-0{u % 9 + 1}-10"@en .' for u in range(1, 60)]
    quads += [f'<0x{u:x}> <ld> "1976-0{u % 9 + 1}-10" .' for u in range(30, 90)]
    quads += [f"<0x{HUB:x}> <link> <0x{u:x}> ." for u in range(1, N + 1)]
    quads += [f"<0x{SMALL_HUB:x}> <link> <0x{u:x}> ." for u in SMALL]
    e = QueryEngine(PostingStore())
    e.run("mutation { schema { d: datetime @index(year) . n: int @index(int) . "
          "w: float @index(float) . wi: float @index(float) . od: datetime @index(year) . ld: datetime @index(year) . "
          "link: uid . } set { %s } }" % "\n".join(quads))
    return e, d


def _resolver(e, rng):
    f = np.sort(rng.choice(np.arange(1, N + 1), 120, replace=False)).astype(np.int64)
    g = np.sort(rng.choice(np.arange(1, N + 1), 80, replace=False)).astype(np.int64)
    unsorted = rng.integers(1, N + 1, 100).astype(np.int64)       # duplicates, no order
    vv = {int(u): TypedValue(TypeID.INT, 1) for u in rng.choice(np.arange(1, N + 1), 90, replace=False)}
    return FuncResolver(e.store, e.arenas, {"F": f, "G": g, "U": unsorted}, {"V": vv}, stats={})


def _booked():
    return KWAY_INTERSECTS.snapshot()["probe"], KWAY_ELEMENTS.snapshot()["probe"]


def _check(res, fn, cands):
    """The probe route against the whole set bounded by the candidates, and
    whether the probe was taken and booked where the candidates are fewer."""
    want = np.intersect1d(res.resolve(fn, None), cands)
    plan = res.probe_plan(fn)
    c0, e0 = _booked()
    got = res.resolve(fn, cands)
    c1, e1 = _booked()
    assert got.dtype == np.int64 and np.array_equal(got, want), (fn, got, want)
    return plan, c1 - c0, e1 - e0


def _var(*names, typ="uid"):
    return [VarRef(n, typ) for n in names]


UID_LEAVES = {
    "one var": Function(name="uid", needs_vars=_var("F")),
    "two vars": Function(name="uid", needs_vars=_var("F", "G")),
    "literal uids and a var": Function(name="uid", uid_args=[299, 3, 7, 3], needs_vars=_var("G")),
    "an unsorted var": Function(name="uid", needs_vars=_var("U")),
    "a value var": Function(name="uid", needs_vars=_var("V", typ="value")),
    "an unbound var": Function(name="uid", needs_vars=_var("nothing", "F")),
    "literal uids alone": Function(name="uid", uid_args=[5, 1, 9, 200]),
}


@pytest.mark.parametrize("unsorted", [False, True])
@pytest.mark.parametrize("leaf", sorted(UID_LEAVES))
def test_a_uid_leaf_probed_keeps_what_its_whole_set_keeps(world, leaf, unsorted):
    e, _ = world
    rng = np.random.default_rng(7)
    res = _resolver(e, rng)
    cands = np.unique(np.concatenate([rng.choice(np.arange(1, 321), 20, replace=False),
                                      [1, 3, 5, 7, 299]])).astype(np.int64)   # the literals
    n = len(cands)
    if unsorted:                                  # a root's uids may come in any order
        cands = np.concatenate([cands[::-1], cands[:5]])
    plan, count, elements = _check(res, UID_LEAVES[leaf], cands)
    taken = len(cands) < plan.size
    assert taken == (leaf != "literal uids alone")
    assert (count, elements) == ((1, n) if taken else (0, 0))


INEQ_BOUNDS = {
    "d": ["1975-03-14", "1975-01-01", "1976-01-01", "1975-03-14T00:00:00.000001",
          "1980-05-05", "1967-06-01"],
    "n": ["0", "-50", "17", "1152921504606846976", "51"],
    "w": ["1.5", "-0.25", "0.0", "4.75", "-5.0"],
    "wi": ["1.5", "-0.25", "4.75", "-5.0"],
}


@pytest.mark.parametrize("op", ["ge", "gt", "le", "lt", "eq"])
@pytest.mark.parametrize("pred,bound", [(p, b) for p, bs in INEQ_BOUNDS.items() for b in bs])
def test_an_inequality_probed_keeps_what_its_index_keeps(world, pred, bound, op):
    """Bounds inside a year, on a year's first day, a microsecond past a
    value, outside every year; ints and floats, bucket edges, ints past
    2**53; candidates with no value among them.  A float predicate that
    holds ±inf (``wi``) keeps the index: no token holds them, so the index
    never gives them, and its value column is no stand-in for it."""
    e, d = world
    rng = np.random.default_rng(hash((pred, bound, op)) % 2 ** 32)
    on = [u for u, v in d.items() if v == bound]
    cands = np.unique(np.concatenate([rng.choice(np.arange(1, N + 1), 16, replace=False),
                                      [4, 5, 6, 7, 8, 255, 300], on])).astype(np.int64)
    res = FuncResolver(e.store, e.arenas, {}, {}, stats={})
    fn = Function(name=op, attr=pred, args=[bound])
    plan, count, elements = _check(res, fn, cands)
    taken = plan is not None and len(cands) < plan.size and pred != "wi"
    assert (count, elements) == ((1, len(cands)) if taken else (0, 0))
    assert (plan is None) == (bound == "1152921504606846976")   # no float64 holds it


@pytest.mark.parametrize("fn", [
    Function(name="ge", attr="od", args=["1975-12-31T21:00:00Z"]),           # values with offsets
    Function(name="ge", attr="d", args=["1975-03-14T03:00:00+05:00"]),       # a bound with one
    Function(name="ge", attr="ld", args=["1975-05-01"]),                     # a predicate with tags
    Function(name="le", attr="ld", lang="en", args=["1975-05-01"]),          # a tagged function
], ids=["offset values", "offset bound", "tagged predicate", "tagged function"])
def test_what_the_value_column_cannot_stand_for_keeps_the_index(world, fn):
    e, _ = world
    res = FuncResolver(e.store, e.arenas, {}, {}, stats={})
    cands = np.arange(1, 40, 3, dtype=np.int64)
    plan, count, _ = _check(res, fn, cands)
    assert count == 0
    if fn.attr != "od":
        assert plan is None


def test_the_recheck_counts_each_probed_value():
    """A value compare of the probe is a ``value`` recheck, once a uid held."""
    e = QueryEngine(PostingStore())
    e.run('mutation { schema { d: datetime @index(year) . } set {'
          ' <0x1> <d> "1975-03-14" . <0x2> <d> "1976-01-01" . <0x3> <d> "1977-01-01" .'
          ' <0x4> <d> "1978-01-01" . <0x5> <d> "1979-05-05" . } }')
    res = FuncResolver(e.store, e.arenas, {}, {}, stats={})
    v0 = INDEX_RECHECK_UIDS.snapshot()["value"]
    got = res.resolve(Function(name="ge", attr="d", args=["1976-01-01"]), np.array([2, 9]))
    assert got.tolist() == [2]
    assert INDEX_RECHECK_UIDS.snapshot()["value"] - v0 == 1
    assert res.stats["recheck_ms"] > 0 and "resolver_expand_ms" not in res.stats


@pytest.mark.parametrize("size", [0, 3, 90, 300])
def test_an_and_probes_its_larger_leaves_and_folds_the_rest(world, size):
    """``narrow`` then the k-way against the k-way of the whole sets: empty
    candidates, fewer than every leaf, between the leaves (the first probe
    narrows them below the second), more than all."""
    e, d = world
    rng = np.random.default_rng(size)
    res = _resolver(e, rng)
    fns = [Function(name="uid", needs_vars=_var("G")),
           Function(name="ge", attr="d", args=["1975-03-14"]),
           Function(name="has", attr="n")]
    both = np.intersect1d(res.uid_vars["G"], res.resolve(fns[1], None))
    cands = (both[:3] if size == 3 else
             np.sort(rng.choice(np.arange(1, N + 1), size, replace=False)).astype(np.int64))
    want = joinplan.kway_intersect([cands] + [res.resolve(f, None) for f in fns])
    c0, _ = _booked()
    out, left = res.narrow(fns, cands)
    got = joinplan.kway_intersect([out] + [res.resolve(f, None) for f in left])
    assert got.dtype == np.int64 and np.array_equal(got, want)
    assert fns[2] in left                         # has() is no probe's
    probed = len(fns) - len(left)
    assert probed == {0: 2, 3: 2, 90: 2, 300: 0}[size]
    assert _booked()[0] - c0 == {0: 0, 3: 2, 90: 2, 300: 0}[size]


FILTERED = ('{ var(func: uid(0x%x)) { F as link @filter(lt(n, 10)) } '
            'q(func: uid(0x%x)) { link @filter(uid(F) and ge(d, "1975-03-14")) { _uid_ } } }')


def test_a_level_books_its_probes_and_a_larger_level_none(world):
    """The small hub's level (10 uids) is probed by both leaves, the smaller
    first; the large hub's (300) outnumbers both, which are built and folded
    as before.  The answers are the level's uids that pass both."""
    e, d = world
    n = {u: e.store.value("n", u) for u in range(1, N + 1)}
    in_f = {u for u in range(1, N + 1) if n[u] is not None and n[u].value < 10}
    on_d = {u for u, v in d.items() if (v if "T" in v else v + "T00:00:00") >= "1975-03-14T00:00:00"}
    d_rows = sum(1 for v in d.values() if v[:4] >= "1975")
    first = in_f if len(in_f) <= d_rows else on_d
    for hub, level, probes in ((SMALL_HUB, SMALL, 2), (HUB, range(1, N + 1), 0)):
        c0, e0 = _booked()
        out = e.run(FILTERED % (HUB, hub))
        got = sorted(int(o["_uid_"], 16) for o in out["q"][0].get("link", []))
        assert got == [u for u in level if u in in_f and u in on_d], hub
        c1, e1 = _booked()
        assert c1 - c0 == probes and e.stats["kway_probe"] == probes
        if probes:
            assert e1 - e0 == len(SMALL) + len(first.intersection(SMALL))
            assert e.stats["kway_host"] + e.stats["kway_device"] == 0
        else:
            assert e1 == e0 and e.stats["kway_host"] + e.stats["kway_device"] == 1
