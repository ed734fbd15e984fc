"""Filtered traversal (``film21m-q4-filter``): a level narrowed by
``@filter(uid(F) and ge(initial_release_date, D))``, served through
``/query`` — scheduler, planner, engine — on a seeded film graph, against
``benchmark/reference_filter.py``, under each k-way route; the lossy year
index's recheck (``query/functions.py`` ``_ineq_one``) at bounds inside a
year and on a year's first day, for every inequality, with the counters it
books; and the benchmark's own pieces of the cell: the control that has to
come out not correct, the draws, the readers.
"""

import datetime as dt
import json
import os
import sys
import urllib.request

import numpy as np
import pytest

from dgraph_tpu.models import PostingStore
from dgraph_tpu.query import QueryEngine
from dgraph_tpu.query.functions import FuncResolver
from dgraph_tpu.serve.server import DgraphServer
from dgraph_tpu.utils.metrics import (
    FILTER_STAGE_US, INDEX_RECHECK_UIDS, KWAY_ELEMENTS, metrics)

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark")
sys.path.insert(0, BENCH)

import compare  # noqa: E402
import filmgen  # noqa: E402
import run  # noqa: E402
import trafficgen  # noqa: E402

QUADS = 30_000
SEED = 3_300_000_523        # above 2**31: a seed past 32 signed bits


def _post(addr, body):
    req = urllib.request.Request(addr + "/query?ledger=true", data=body.encode(), method="POST")
    with urllib.request.urlopen(req, timeout=60) as r:
        return r.read()


@pytest.fixture(scope="module")
def world():
    return run.World(filmgen.generate(QUADS, SEED))


@pytest.fixture(scope="module")
def classes(world):
    return trafficgen.load_classes(trafficgen.load_json("traffic", "filtered.json"), world)


@pytest.fixture(scope="module")
def srv(world, tmp_path_factory):
    server = DgraphServer(PostingStore(), export_path=str(tmp_path_factory.mktemp("export")))
    server.start()
    _post(server.addr, "mutation { schema { %s } }" % filmgen.SCHEMA)
    lines = filmgen.nquad_lines(world.g, 0, len(world.g.director))
    for lo in range(0, len(lines), 20_000):
        _post(server.addr, "mutation { set { %s } }" % "\n".join(lines[lo:lo + 20_000]))
    yield server
    server.stop()


def _cases(world, kind):
    """(root, genre uid, date) to ask: drawn ids across the pool's sizes,
    and by hand a bound on a year's first day, the first day after the last
    film (a genre-date pair with no film) and the generator's earliest day.
    An actor's class adds the two most-cast actors under the smallest of the
    mix's genres: the first with a date in the last year, a level that
    outnumbers both leaves, so that neither is probed and the whole k-way
    runs on the route the knob pins; the second with a date its level falls
    short of, so that the date is probed and F folds with what is left."""
    ids = np.linspace(0, len(kind.pool()) - 1, 12).astype(int)
    out = [kind.request(int(i)) for i in ids]
    root = out[len(out) // 2][0]
    out += [(root, 2, "1975-01-01"), (root, 9, "2020-01-01"), (root, 3, "1960-01-10")]
    ref = kind.ref
    assert ref.on_or_after("2020-01-01") == 0
    assert any(ref.keeps(np.arange(len(world.g.film)), 2, "1975-01-01")
               & (world.g.film_date[:, 0] == 1975))
    if kind.shape == "costars":
        years = world.g.film_date[:, 0]
        genre = int(kind.spec["root"]["genre"]["last"]) + 1
        for rank, year in ((0, int(years.max())), (1, 1990)):
            actor = int(world.actors_by_cast[rank])
            level = len(np.unique(world.g.perf_film[ref.roles(actor)]))
            rows = int((years >= year).sum())     # the year index's rows from the bound's year
            assert (level > max(ref.genre_films[genre], rows) if rank == 0
                    else ref.genre_films[genre] < level < rows), (level, rows)
            out.append((actor, genre, f"{year}-03-15"))
    return out


@pytest.mark.parametrize("route", ["planner", "device", "host"])
def test_both_classes_answer_as_the_reference(srv, world, classes, monkeypatch, route):
    """Every answer as the reference's, its ledger's edges by the reference's
    count, and the k-way elements booked to the route the knob pins, the
    levels that outnumber their leaves; the others' to the probe, which no
    knob pins."""
    if route != "planner":
        monkeypatch.setenv("DGRAPH_TPU_KWAY_DEVICE_MIN", "0" if route == "device" else str(1 << 40))
    before = KWAY_ELEMENTS.snapshot()
    asked = 0
    for name, kind in classes.items():
        for n, (root, genre, date) in enumerate(_cases(world, kind)):
            body = _post(srv.addr, kind.text_of(root, genre, date, tag=f"{route}{n}"))
            exp = kind.expect_of(root, genre, date)
            out = json.loads(body)
            assert kind.check(out, exp, tag=f"{route}{n}") is None, (name, root, genre, date)
            led = out["extensions"]["ledger"]
            if compare.executed(led):
                assert led["edges"] == exp["edges"], (name, root, genre, date)
            asked += 1
    grown = {r: KWAY_ELEMENTS.snapshot()[r] - before[r] for r in ("host", "device", "probe")}
    assert grown["probe"] > 0
    if route == "planner":
        assert grown["host"] + grown["device"] > 0
    else:
        assert grown[route] > 0 and grown[{"device": "host", "host": "device"}[route]] == 0
    assert asked == 15 + 17


def test_an_empty_level_renders_as_the_reference(srv, world, classes):
    """A genre-date pair with no film: the co-star block and the director's
    films are empty, and the rendered reference says the same."""
    for kind in classes.values():
        root = kind.request(0)[0]
        out = json.loads(_post(srv.addr, kind.text_of(root, 9, "2020-01-01", tag="none")))
        exp = kind.expect_of(root, 9, "2020-01-01")
        assert len(exp["want"]) == 0
        assert kind.check(out, exp, tag="none") is None
        assert kind.check(kind.render_of(root, 9, "2020-01-01"), exp) is None


# -- the recheck of a lossy index --------------------------------------------------

DATES = ["1974-12-31", "1975-01-01", "1975-03-13", "1975-03-14", "1975-03-14",
         "1975-03-15", "1975-12-31", "1976-01-01", "1976-06-30", "1973-07-04"]


@pytest.fixture(scope="module")
def dated():
    e = QueryEngine(PostingStore())
    quads = "\n".join(f'<0x{i + 1:x}> <d> "{d}" .' for i, d in enumerate(DATES))
    e.run("mutation { schema { d: datetime @index(year) . } set { %s } }" % quads)
    return e


def _truth(op, bound):
    cmp = {"ge": str.__ge__, "gt": str.__gt__, "le": str.__le__, "lt": str.__lt__,
           "eq": str.__eq__}[op]
    return sorted(i + 1 for i, d in enumerate(DATES) if cmp(d, bound))


def _recheck_counts():
    s = INDEX_RECHECK_UIDS.snapshot()
    return s["token"], s["value"]


@pytest.mark.parametrize("bound", ["1975-03-14", "1975-01-01", "1976-01-01", "1980-05-05"])
@pytest.mark.parametrize("op", ["ge", "gt", "le", "lt", "eq"])
def test_ineq_one_takes_inner_years_whole_and_compares_the_bounds_year(dated, op, bound):
    """Exact for every inequality, whether the bound falls inside a year or
    on its first day; only the bound's own year is compared against values."""
    year = bound[:4]
    t0, v0 = _recheck_counts()
    out = dated.run('{ q(func: %s(d, "%s")) { _uid_ } }' % (op, bound))
    got = sorted(int(o["_uid_"], 16) for o in out.get("q", []))
    assert got == _truth(op, bound)
    t1, v1 = _recheck_counts()
    same_year = sum(d[:4] == year for d in DATES)
    assert v1 - v0 == same_year
    if op == "eq":
        assert t1 - t0 == 0
    else:
        inner = {"ge": str.__gt__, "gt": str.__gt__, "le": str.__lt__, "lt": str.__lt__}[op]
        assert t1 - t0 == sum(inner(d[:4], year) for d in DATES)


def test_the_recheck_runs_no_store_probe_a_uid(dated, monkeypatch):
    """A langless datetime predicate's candidates are compared as vectors:
    the per-uid store probe is never called."""
    def probe(*a, **k):
        raise AssertionError("a per-uid probe on the vector path")
    monkeypatch.setattr(dated.store, "value", probe)
    monkeypatch.setattr(dated.store, "any_value", probe)
    out = dated.run('{ q(func: ge(d, "1975-03-14")) { _uid_ } }')
    assert sorted(int(o["_uid_"], 16) for o in out["q"]) == _truth("ge", "1975-03-14")
    assert dated.stats["recheck_ms"] > 0


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_split_recheck_agrees_with_the_per_uid_loop(seed):
    """Random datetimes to the microsecond, bounds inside and between them:
    the split vector recheck keeps what the per-uid loop over every candidate
    of the range keeps (the recheck before the split, kept as the reference)."""
    rng = np.random.default_rng(seed)
    secs = rng.integers(-2 * 10**9, 2 * 10**9, 300)
    micros = rng.integers(0, 10**6, 300) * (rng.random(300) < 0.5)
    base = dt.datetime(1970, 1, 1)
    vals = [base + dt.timedelta(seconds=int(s), microseconds=int(m)) for s, m in zip(secs, micros)]
    quads = " ".join(f'<0x{i + 1:x}> <d> "{v.isoformat()}" .' for i, v in enumerate(vals))
    e = QueryEngine(PostingStore())
    e.run("mutation { schema { d: datetime @index(year) . } set { %s } }" % quads)
    res = FuncResolver(e.store, e.arenas, {}, {})
    idx = e.arenas.index("d", "year")
    everyone = res._expand_rows(idx.csr, np.arange(len(idx.tokens)))
    bounds = [vals[int(i)].isoformat() for i in rng.integers(0, 300, 4)] + ["1975-03-14T12:30:00.000001"]
    for raw in bounds:
        val = res._typed_value("d", raw)
        for op in ("ge", "gt", "le", "lt", "eq"):
            fn = type("F", (), {"attr": "d", "name": op, "lang": ""})()
            assert np.array_equal(res._ineq_one(fn, raw),
                                  res._host_recheck("d", everyone, op, val)), (op, raw)


def test_a_filter_books_its_parts(dated):
    """The engine's filter stats reach ``dgraph_filter_stage_us_total`` once a
    request through the ledger."""
    from dgraph_tpu.obs import ledger

    led = ledger.Ledger()
    before = FILTER_STAGE_US.snapshot()
    dated.run('{ q(func: has(d)) @filter(ge(d, "1975-03-14") and le(d, "1976-01-01")) { _uid_ } }')
    led.merge_engine_stats(dated.stats)
    grown = {p: FILTER_STAGE_US.snapshot()[p] - before[p] for p in before}
    assert grown["recheck"] > 0 and grown["resolver_expand"] > 0
    assert led.host_ms >= dated.stats["recheck_ms"]


def test_a_lang_tagged_predicate_keeps_the_whole_recheck():
    """Values under language tags: every candidate of the range is compared
    (the tagged value a uid is chosen along the lang chain), none taken on
    its token; and the answers follow the tag."""
    e = QueryEngine(PostingStore())
    e.run('mutation { schema { d: datetime @index(year) . } set {'
          ' <0x1> <d> "1975-03-14"@en . <0x1> <d> "1975-01-02" .'
          ' <0x2> <d> "1975-05-01"@en . <0x3> <d> "1977-01-01"@en .'
          ' <0x4> <d> "1976-02-02" . } }')
    t0, v0 = _recheck_counts()
    out = e.run('{ q(func: ge(d@en, "1975-03-14")) { _uid_ } }')
    assert sorted(int(o["_uid_"], 16) for o in out["q"]) == [1, 2, 3]
    t1, v1 = _recheck_counts()
    assert (t1 - t0, v1 - v0) == (0, 4)          # uids 1-4, each compared once
    out = e.run('{ q(func: ge(d, "1975-03-14")) { _uid_ } }')
    assert sorted(int(o["_uid_"], 16) for o in out["q"]) == [4]


def test_offset_datetimes_compare_every_candidate_exactly():
    """Where a value's wall clock is not its UTC instant the year tokens do
    not order as the instants: every candidate is compared, by its instant."""
    e = QueryEngine(PostingStore())
    e.run('mutation { schema { d: datetime @index(year) . } set {'
          ' <0x1> <d> "1976-01-01T01:00:00+05:00" . <0x2> <d> "1975-12-31T22:00:00Z" .'
          ' <0x3> <d> "1975-06-01T00:00:00Z" . <0x4> <d> "1976-01-01T06:00:00Z" . } }')
    t0, v0 = _recheck_counts()
    out = e.run('{ q(func: ge(d, "1975-12-31T21:00:00Z")) { _uid_ } }')
    assert sorted(int(o["_uid_"], 16) for o in out["q"]) == [2, 4]
    t1, v1 = _recheck_counts()
    assert t1 == t0 and v1 - v0 == 4


def test_a_lossy_float_index_splits_at_its_bucket():
    e = QueryEngine(PostingStore())
    vals = [0.5, 1.0, 1.25, 1.75, 2.0, 2.5, -0.5, 3.0]
    quads = " ".join(f'<0x{i + 1:x}> <w> "{v}" .' for i, v in enumerate(vals))
    e.run("mutation { schema { w: float @index(float) . } set { %s } }" % quads)
    for op, bound in (("ge", 1.5), ("gt", 1.25), ("lt", 2.5), ("le", 0.5)):
        out = e.run('{ q(func: %s(w, "%s")) { _uid_ } }' % (op, bound))
        want = [i + 1 for i, v in enumerate(vals)
                if {"ge": v >= bound, "gt": v > bound, "lt": v < bound, "le": v <= bound}[op]]
        assert sorted(int(o["_uid_"], 16) for o in out.get("q", [])) == want, (op, bound)


def test_the_new_families_are_there_at_zero_from_boot():
    text = metrics.prometheus_text()
    for family, labels in (("dgraph_index_recheck_uids_total", ("token", "value")),
                           ("dgraph_kway_intersect_total", ("host", "device", "probe")),
                           ("dgraph_kway_intersect_elements_total", ("host", "device", "probe")),
                           ("dgraph_filter_stage_us_total", ("resolver_expand", "recheck", "kway"))):
        for label in labels:
            assert f'{family}{{' in text and f'"{label}"' in text, (family, label)


# -- the benchmark's pieces of the cell ------------------------------------------------

def _records(kind, name, ids, answer):
    return [(0, name, int(i), 0.0, 0.001, 200, answer(int(i))) for i in ids]


def test_the_whole_years_control_is_not_correct(world, classes):
    """The reference that keeps the bound's whole year is called wrong; the
    reference's own answers, rendered the same way, are called right."""
    broken = trafficgen.load_module("controls", "whole_years").walker(world)
    for name, kind in classes.items():
        ids = kind.pool()[::max(1, len(kind.pool()) // 400)]
        ok = compare.compare(_records(kind, name, ids, lambda i: json.dumps(
            {**kind.render(i), "server_latency": {}}).encode()), classes)
        assert compare.verdict(ok["numbers"])[0], ok["first_words"]
        if name == "filter_costars":
            bad = compare.compare(_records(kind, name, ids, lambda i: json.dumps(
                {**kind.render(i, broken), "server_latency": {}}).encode()), classes)
            assert not compare.verdict(bad["numbers"])[0]
            assert bad["numbers"]["wrong"] > 0


def test_the_draws_are_the_same_for_the_same_seed(world, classes):
    again = run.World(filmgen.generate(QUADS, SEED))
    other = trafficgen.load_classes(trafficgen.load_json("traffic", "filtered.json"), again)
    for name, kind in classes.items():
        d = kind.draws()
        assert np.array_equal(d, other[name].draws())
        assert len(d) == kind.spec["root"]["draws"]
        root, genre, date = kind.request(0)
        assert kind.text(0) == other[name].text(0)
        assert 2 <= genre <= 9 and f'"Genre {genre - 1}"' in kind.text(0)
        years = d[:, 2] // 10000
        days = d[:, 2] % 100
        assert years.min() >= 1960 and years.max() <= 1999 and days.min() >= 10 and days.max() <= 18
    plan = trafficgen.deal(trafficgen.load_json("traffic", "filtered.json"), classes, SEED)
    assert plan == trafficgen.deal(trafficgen.load_json("traffic", "filtered.json"), other, SEED)


class _Obs:
    def __init__(self, grown, n=10):
        self.grown = {**grown, "dgraph_num_queries_total": {"": n}}

    def delta(self, family):
        return self.grown.get(family, {})


@pytest.mark.parametrize("reader,grown,want", [
    ("kway_device_share", {"dgraph_kway_intersect_elements_total": {"host": 100, "device": 300}}, 75.0),
    ("kway_device_share", {}, None),
    ("kway_elements_per_query", {"dgraph_kway_intersect_elements_total": {"host": 100, "device": 300}}, 40.0),
    ("kway_elements_per_query", {}, None),
    ("kway_probe_share", {"dgraph_kway_intersect_total": {"host": 1, "device": 0, "probe": 3}}, 75.0),
    ("kway_probe_share", {"dgraph_kway_intersect_total": {"host": 4, "device": 0}}, None),
    ("kway_probe_share", {"dgraph_kway_intersect_total": {"host": 0, "device": 0, "probe": 0}}, None),
    ("kway_probe_share", {}, None),
    ("recheck_share", {"dgraph_index_recheck_uids_total": {"token": 96, "value": 4}}, 4.0),
    ("recheck_share", {}, None),
    ("filter_ms", {"dgraph_filter_stage_us_total": {"resolver_expand": 5000, "recheck": 1000,
                                                    "kway": 4000}}, 1.0),
    ("filter_ms", {}, None),
])
def test_the_cells_readers_read_a_window(reader, grown, want):
    assert trafficgen.load_module("metrics", reader).read(_Obs(grown)) == want
