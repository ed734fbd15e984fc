"""Path search on a graph that is being written (CPU; small graphs): the
merged layout of a search's listed predicates (``models/arena.py``
``PathLayout``) under writes, against a build from the arenas, and the served
path against the benchmark's plain reference (``benchmark/reference_paths.py``
for the generated graph, ``benchmark/reference_paths_rw.py`` for the written
films, through ``run.World``).

- after random runs of film-shaped writes a cached layout is array for array
  what ``PathLayout(arenas)`` builds (cast sizes 1-8), every one a counted
  ``delta``;
- a write that gives an EXISTING uid a new listed edge, a delete, and a write
  that outgrows the uid bucket or the edge bucket are each a counted
  ``rebuild`` — by the refresh, not by the next search — and the device route
  still says what the Dijkstra says;
- fifty films through a server compile nothing after the first: ``ops/bfs.py``'s
  programs, the delta's scatters and ``dgraph_xla_compiles_total`` stand still;
- a path-back after the ack is whole through the device route, and the control
  ``lost_path_write`` is not what the system says;
- under eight threads of searches, writes and probes every answer is the
  reference's and no search sees half a film;
- ``planner.path_route`` keeps a written store on the device.
"""

import os
import sys
import threading

import numpy as np
import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark")
sys.path.insert(0, BENCH)

import filmgen  # noqa: E402
import reference_paths_rw  # noqa: E402
import run  # noqa: E402
import trafficgen  # noqa: E402

from dgraph_tpu.models import PostingStore  # noqa: E402
from dgraph_tpu.models import arena as A  # noqa: E402
from dgraph_tpu.ops import bfs  # noqa: E402
from dgraph_tpu.query import QueryEngine, planner  # noqa: E402
from dgraph_tpu.query.functions import QueryError  # noqa: E402
from dgraph_tpu.serve.server import DgraphServer  # noqa: E402
from dgraph_tpu.utils.metrics import (  # noqa: E402
    PATH_LAYOUT_H2D_BYTES,
    PATH_LAYOUT_UPDATES,
    PATH_SEARCHES,
    XLA_COMPILES,
)

QUADS, SEED = 6000, 11
SEARCHES = ("path_to_star", "path_pair", "path_costar")
LISTED = "~performance.actor ~starring starring performance.actor"
PREDS = tuple((t.lstrip("~"), t.startswith("~")) for t in LISTED.split())


def _updates():
    s = PATH_LAYOUT_UPDATES.snapshot()
    return s["delta"], s["rebuild"]


def _arenas(mgr):
    return [mgr.reverse(p) if rev else mgr.data(p) for p, rev in PREDS]


def _canon(off):
    """``off`` with the rows of uids that hold no edge zeroed: which two equal
    slots such a uid reads is not said (``PathLayout``'s docstring)."""
    off = np.asarray(off).copy()
    off[off[:, 0] == off[:, 1]] = 0
    return off


def _assert_layout_is_a_fresh_build(mgr):
    """The cached layout IS current (no search has to build it) and reads as
    a build from the arenas does."""
    arenas = _arenas(mgr)
    lay = mgr._path_layouts[PREDS]
    assert lay.key == tuple((id(a), a.epoch) for a in arenas)
    bytes_before = PATH_LAYOUT_H2D_BYTES.value()
    assert mgr.path_layout(PREDS) is lay
    assert PATH_LAYOUT_H2D_BYTES.value() == bytes_before
    fresh = A.PathLayout(arenas)
    for field in ("n_edges", "top", "universe", "ub", "max_degree"):
        assert getattr(lay, field) == getattr(fresh, field), field
    assert lay.off.shape == fresh.off.shape and lay.dst.shape == fresh.dst.shape
    np.testing.assert_array_equal(_canon(lay.off), _canon(fresh.off))
    np.testing.assert_array_equal(np.asarray(lay.dst), np.asarray(fresh.dst))
    np.testing.assert_array_equal(np.asarray(lay.esrc), np.asarray(fresh.esrc))
    return lay


# -- a bare manager: film-shaped writes are deltas, array for array a fresh build ------------


def _film_store(seed, hub=12, n_perf=300):
    """Films 1-100, performances from 200 on, actors 600-1030 (one with ``hub``
    roles, so that no written film is the widest uid): 1,200 edge slots of
    2,048 and a uid space of 1,032 in a bucket of 1,152."""
    rng = np.random.default_rng(seed)
    store = PostingStore()
    perf = 200 + np.arange(n_perf)
    actor = rng.integers(600, 1031, len(perf))
    actor[:hub], actor[-1] = 600, 1030
    store.bulk_set_uid_edges("starring", rng.integers(1, 101, len(perf)), perf)
    store.bulk_set_uid_edges("performance.actor", perf, actor)
    return store, 1030


def _write_film(store, top, c):
    """One film of cast ``c`` at the uids past ``top``, as ``add_film`` lays it
    out: the film, its performances, its newcomers.  Returns the new top."""
    f, perf, new = top + 1, top + 2 + np.arange(c), top + 2 + c + np.arange(c)
    store.bulk_set_uid_edges("starring", np.full(c, f), perf)
    store.bulk_set_uid_edges("performance.actor", perf, new)
    return top + 1 + 2 * c


@pytest.mark.parametrize("c", range(1, 9))
def test_film_shaped_writes_leave_the_layout_a_fresh_build(c):
    store, top = _film_store(c)
    mgr = A.ArenaManager(store)
    first = mgr.path_layout(PREDS)
    shapes = (first.off.shape, first.dst.shape, first.esrc.shape)
    rng = np.random.default_rng(100 + c)
    d0, r0 = _updates()
    for n in range(5):
        films = int(rng.integers(1, 3)) if c <= 4 else 1     # a refresh may hold two films
        for _ in range(films):
            top = _write_film(store, top, c)
        mgr.refresh()
        lay = _assert_layout_is_a_fresh_build(mgr)
        assert (lay.off.shape, lay.dst.shape, lay.esrc.shape) == shapes
        assert _updates() == (d0 + n + 1, r0)
    assert lay is not first and first.n_edges == 1200     # the old layout is a whole snapshot still
    assert int(np.asarray(first.off)[top, 1]) == int(np.asarray(first.off)[top, 0])


def test_a_delta_puts_index_vectors_on_the_device_not_tables():
    store, top = _film_store(0)
    mgr = A.ArenaManager(store)
    b0 = PATH_LAYOUT_H2D_BYTES.value()
    lay = mgr.path_layout(PREDS)
    built = PATH_LAYOUT_H2D_BYTES.value() - b0
    assert built == lay.off.nbytes + lay.dst.nbytes + lay.esrc.nbytes
    _write_film(store, top, 8)
    mgr.refresh()
    # three scatters of 64 padded entries: uid + two offsets, slot + target, slot + source
    assert PATH_LAYOUT_H2D_BYTES.value() - b0 - built == 64 * (4 + 8) + 2 * 64 * (4 + 4)


# -- what rebuilds it: counted, by the refresh, and still right ---------------------------------


def _engine(store):
    e = QueryEngine(store)
    e.run("mutation { schema { starring: uid . performance.actor: uid @reverse . } }")
    return e


def _search(e, src, dst):
    return e.run("{ shortest(from: 0x%x, to: 0x%x) { %s } }" % (src, dst, LISTED))


def _hops(answer):
    node, n = answer["_path_"][0], 0
    while True:
        keys = [k for k in node if k != "_uid_"]
        if not keys:
            return n
        node, n = node[keys[0]][0], n + 1


REBUILDS = ("an_existing_uid_gets_an_edge", "a_delete", "the_uid_bucket_outgrown",
            "the_edge_bucket_outgrown", "a_wider_uid_than_any")


@pytest.mark.parametrize("kind", REBUILDS)
def test_what_cannot_be_taken_in_place_is_a_counted_rebuild_and_still_right(monkeypatch, kind):
    # 250 performances are 1,000 slots of 1,024: the bucket a film of eight outgrows
    store, top = _film_store(7, n_perf=250 if kind == "the_edge_bucket_outgrown" else 300)
    e = _engine(store)
    mgr = e.arenas
    assert "_path_" in _search(e, 600, 1030)         # the layout is built and cached
    top = _write_film(store, top, 3)                 # a film first: a delta
    d0, r0 = _updates()
    mgr.refresh()
    assert _updates() == (d0 + 1, r0)
    film, last = top - 6, top
    if kind == "an_existing_uid_gets_an_edge":       # a new role for an old actor
        e.run("mutation { set { <0x%x> <starring> <0x%x> . <0x%x> <performance.actor> <0x%x> . } }"
              % (film, top + 1, top + 1, 601))
        probe, want, top = (last, 601), 4, top + 1
    elif kind == "a_delete":
        e.run("mutation { delete { <0x%x> <performance.actor> <0x%x> . } }" % (top - 3, last))
        probe, want = (last, film), None
    elif kind == "the_uid_bucket_outgrown":          # 1,152 uids: a film past them
        top = _write_film(store, 1200, 2)
        probe, want = (top, top - 1), 4
    elif kind == "the_edge_bucket_outgrown":         # 1,012 slots of 1,024, and 32 more
        top = _write_film(store, top, 8)
        probe, want = (top, top - 1), 4
    else:                                            # a film of thirteen: wider than the hub's twelve
        top = _write_film(store, top, 13)
        probe, want = (top, top - 1), 4
    d1, r1 = _updates()
    mgr.refresh()                                    # the refresher pays, whoever it is
    assert _updates() == (d1, r1 + 1)
    lay = _assert_layout_is_a_fresh_build(mgr)
    got = _search(e, *probe)
    assert _updates() == (d1, r1 + 1) and mgr._path_layouts[PREDS] is lay
    assert (_hops(got) if got.get("_path_") else None) == want
    with monkeypatch.context() as m:                 # the Dijkstra says the same, byte for byte
        m.setattr(planner, "path_route",
                  lambda k, *a: (False, {"kind": "path", "route": "host", "units": k,
                                         "reason": "test"}))
        assert _search(e, *probe) == got
    _write_film(store, top, 2)                       # and the rebuilt layout takes the next film in place
    mgr.refresh()
    assert _updates() == (d1 + 1, r1 + 1)
    _assert_layout_is_a_fresh_build(mgr)


def test_a_layout_whose_arena_left_the_cache_goes_with_it_counted():
    store, top = _film_store(3)
    mgr = A.ArenaManager(store)
    mgr.path_layout(PREDS)
    # a journal window the data arena cannot take in place: the predicate's arenas are dropped
    store.bulk_set_uid_edges("starring", np.full(store.BULK_JOURNAL_MAX + 1, 50),
                             2000 + np.arange(store.BULK_JOURNAL_MAX + 1))
    d0, r0 = _updates()
    mgr.refresh()
    assert _updates() == (d0, r0 + 1) and PREDS not in mgr._path_layouts
    lay = mgr.path_layout(PREDS)                     # the next search builds arena and layout
    assert lay.n_edges == 1200 + 2 * (store.BULK_JOURNAL_MAX + 1) and _updates() == (d0, r0 + 1)


# -- a server: films ingested, searched back -----------------------------------------------------


def _boot(quads=QUADS):
    g = filmgen.generate(quads, SEED)
    srv = DgraphServer(PostingStore())
    # the suite's eight virtual devices are not this deployment's one chip
    srv.engine.arenas.shard_threshold = 1 << 62
    srv.run_query("mutation { schema { %s } }" % filmgen.SCHEMA)
    lines = filmgen.nquad_lines(g, 0, len(g.director))
    for lo in range(0, len(lines), 2000):
        srv.run_query("mutation { set {\n%s\n} }" % "\n".join(lines[lo:lo + 2000]))
    world = run.World(g)
    mix = trafficgen.load_json("traffic", "searchwrite.json")
    return srv, world, trafficgen.load_classes(mix, world)


def _strip(out):
    return {k: v for k, v in dict(out).items() if k not in ("server_latency", "extensions")}


def _ask(srv, kind, root, tag):
    out = _strip(srv.run_query(kind.text(root, tag)))
    return kind.check(out, kind.expect(root), tag)


def _roots(kind, n):
    pool = kind.pool()
    return [int(pool[i]) for i in np.linspace(0, len(pool) - 1, n).astype(int)]


@pytest.fixture(scope="module")
def written():
    srv, world, classes = _boot()
    films = _roots(classes["add_film"], 12)
    before = {c: [_ask(srv, classes[c], r, "s") for r in _roots(classes[c], 3)] for c in SEARCHES}
    searches0, updates0 = PATH_SEARCHES.snapshot(), _updates()
    acks = [_ask(srv, classes["add_film"], k, "s") for k in films]
    yield srv, world, classes, films, before, acks, searches0, updates0
    srv.stop()


def test_every_film_is_a_delta_to_the_layout_the_searches_built(written):
    srv, world, classes, films, before, acks, _, (d0, r0) = written
    assert acks == [None] * len(films) and all(v == [None] * 3 for v in before.values())
    assert _updates()[1] == r0 and _updates()[0] >= d0 + len(films)
    assert not srv.store.dirty                       # the writer consumed its journal
    _assert_layout_is_a_fresh_build(srv.engine.arenas)
    w = classes["add_film"].written
    assert reference_paths_rw.closed(world.g, w, films, LISTED.split())


@pytest.mark.parametrize("i", range(12))
def test_a_path_back_after_the_ack_is_whole_through_the_device_route(written, i):
    srv, _, classes, films, *_ = written
    kind, k = classes["path_back"], films[i]
    on_device = PATH_SEARCHES.snapshot()["device"]
    assert _ask(srv, kind, k, "s") is None
    assert PATH_SEARCHES.snapshot()["device"] == on_device + 1
    c = classes["add_film"].written.cast_size(k)
    assert kind.expect(k)["edges"] == (3 * c + 1 if c > 1 else 3)
    assert kind.expect(k)["rows"] == (c + 2 if c > 1 else 2)


@pytest.mark.parametrize("cls", SEARCHES)
def test_the_searches_say_what_the_reference_says_after_the_writes(written, cls):
    srv, _, classes, *_ = written
    for r in _roots(classes[cls], 3):
        assert _ask(srv, classes[cls], r, "t") is None      # a new alias: executed again


def test_the_lost_path_write_control_is_not_what_the_system_says(written):
    srv, world, classes, films, *_ = written
    broken = trafficgen.load_module("controls", "lost_path_write").walker(world)
    kind = classes["path_back"]
    for k in films:
        # a control is rendered and judged under the empty alias (run.py)
        assert "0 paths" in kind.check(kind.render(k, broken), kind.expect(k))
        assert kind.check(kind.render(k, world.walker), kind.expect(k)) is None
    for cls in SEARCHES + ("add_film",):             # everything else is the true reference's
        r = _roots(classes[cls], 2)[1]
        assert classes[cls].check(classes[cls].render(r, broken), classes[cls].expect(r)) is None


def test_path_route_keeps_a_written_store_on_the_device(written):
    srv, _, classes, films, _, _, searches0, _ = written
    assert PATH_SEARCHES.snapshot()["host"] == searches0["host"]
    universe, held = srv.engine.arenas.path_extent(PREDS)
    assert universe <= held
    # a film brings 1 + 2c uids against 4c edges and 1 + 3c rows (c >= 1): held grows faster
    w = classes["add_film"].written
    for k in films:
        c = w.cast_size(k)
        assert 1 + 2 * c < 4 * c + 1 + 3 * c
    ok, dec = planner.path_route(1, False, False, False, universe, held)
    assert ok and dec["route"] == "device"


# -- eight threads: searches, writes with their path-backs, and probes ------------------------------


@pytest.fixture(scope="module")
def under_search():
    srv, world, classes = _boot()
    stop = threading.Event()
    names = SEARCHES + ("path_back", "add_film", "probe")
    problems, counts = {c: [] for c in names}, dict.fromkeys(names, 0)
    lock = threading.Lock()
    todo = _roots(classes["add_film"], 30)
    for cls in SEARCHES:            # a kind draws its pairs on first use, and not under a lock
        classes[cls].pairs()

    def note(cls, problem):
        with lock:
            counts[cls] += 1
            if problem is not None:
                problems[cls].append(problem)

    def searcher(i):
        cls = SEARCHES[i % len(SEARCHES)]
        roots = _roots(classes[cls], 5)
        n = 0
        while not stop.is_set():
            note(cls, _ask(srv, classes[cls], roots[n % len(roots)], f"x{i}n{n}"))
            n += 1

    def writer(i):
        for k in todo[i::2]:
            problem = _ask(srv, classes["add_film"], k, "u")
            note("add_film", problem)
            if problem is None:     # the follower: by the writer, after the ack
                note("path_back", _ask(srv, classes["path_back"], k, "u"))

    def prober():
        # the path-back of films the writers are about to write, or have: either
        # nobody by that name yet, or the whole path — never half a film
        kind, n = classes["path_back"], 0
        while not stop.is_set():
            k = todo[n % len(todo)]
            text = kind.text(k, "u").replace("hopsu(", f"hopsp{n}(")
            try:
                out = _strip(srv.run_query(text))
            except (QueryError, ValueError) as e:
                note("probe", None if "binds 0 uids" in str(e) else f"film {k}: {e}")
            else:
                out["hopsu"] = out.pop(f"hopsp{n}", None)
                note("probe", kind.check(out, kind.expect(k), "u"))
            n += 1

    threads = [threading.Thread(target=searcher, args=(i,)) for i in range(5)]
    threads.append(threading.Thread(target=prober))
    writers = [threading.Thread(target=writer, args=(i,)) for i in range(2)]
    for t in threads + writers:
        t.start()
    for t in writers:
        t.join(timeout=600)
    stop.set()
    for t in threads:
        t.join(timeout=120)
    yield problems, counts, srv
    srv.stop()


@pytest.mark.parametrize("cls", SEARCHES + ("path_back", "add_film", "probe"))
def test_under_eight_threads_every_answer_is_the_references(under_search, cls):
    problems, counts, _ = under_search
    assert problems[cls] == []
    assert counts[cls] >= (30 if cls in ("path_back", "add_film") else 1)


def test_under_eight_threads_no_write_rebuilt_the_layout(under_search):
    _, _, srv = under_search
    _assert_layout_is_a_fresh_build(srv.engine.arenas)


# -- fifty films compile nothing after the first ------------------------------------------------------


def test_fifty_writes_compile_nothing_after_the_first():
    # at 60,000 quads fifty films (867 uids, 1,000 slots at most) cross neither the
    # uid bucket (96,464 of 98,304) nor the edge bucket
    srv, world, classes = _boot(60_000)
    try:
        w = classes["add_film"].written
        films = [int(k) for k in w.by_cast[::-1][:51]]      # the largest casts
        root = _roots(classes["path_costar"], 3)[1]

        def round_(k, tag):
            assert _ask(srv, classes["add_film"], k, "o") is None
            assert _ask(srv, classes["path_back"], k, "o") is None
            assert _ask(srv, classes["path_costar"], root, tag) is None

        assert _ask(srv, classes["path_costar"], root, "o") is None   # builds the layout
        round_(films[0], "o0")       # the first write and the searches after it compile
        lay = srv.engine.arenas._path_layouts[PREDS]
        shapes = (lay.off.shape, lay.dst.shape, lay.ub, bfs.capacities(lay.dst.shape[0],
                                                                      lay.max_degree))
        programs = (bfs.run_levels._cache_size(), bfs.start._cache_size(),
                    A._scatter_rows._cache_size())
        xla, (d0, r0) = XLA_COMPILES.value(), _updates()
        for n, k in enumerate(films[1:]):
            round_(k, f"o{n + 1}")
        assert (bfs.run_levels._cache_size(), bfs.start._cache_size(),
                A._scatter_rows._cache_size()) == programs
        assert XLA_COMPILES.value() == xla
        assert _updates() == (d0 + 50, r0)
        lay = _assert_layout_is_a_fresh_build(srv.engine.arenas)
        assert (lay.off.shape, lay.dst.shape, lay.ub,
                bfs.capacities(lay.dst.shape[0], lay.max_degree)) == shapes
    finally:
        srv.stop()
