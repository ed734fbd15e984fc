"""Writes while the film graph is read (CPU; a film graph of a few thousand
quads from the benchmark's own generator, seeded): the system against the
benchmark's plain reference (``benchmark/reference_rw.py`` for the written
films, ``benchmark/reference.py`` for the generated graph, through ``run.World``).

- N films ingested through ``DgraphServer.run_query``: every read-back and the
  traverse mix's three classes at fixed roots say what the reference says;
- the same with readers on eight threads while the writes go in: a read-back
  sent after its ack is whole, the traverse answers never change;
- fifty writes that add overflow chunks compile nothing after the first:
  ``_run_fused``'s cache and ``dgraph_xla_compiles_total`` stand still;
- the device ``_inline`` / ``_lut`` after deltas are what a build from the host
  mirror gives (the arenas of a served graph, and random deltas on a bare one);
- a cached answer whose footprint a write touched is never served again;
- the host mirrors have room at their end: a delta that lies past all the arena
  holds is written there (``dgraph_arena_mirror_updates_total{append}``), views
  taken before it stay what they were, anything else is copied once and leaves
  room; either way the mirrors are what a build from the edges gives;
- the parts: a merged delta is what a build from the edges gives, the top-m chunk sums are repaired
  exactly, an index arena and the value mirror take a new value in place, the
  store's journals overflow where they must.
"""

import json
import os
import sys
import threading

import numpy as np
import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark")
sys.path.insert(0, BENCH)

import filmgen  # noqa: E402
import reference_rw  # noqa: E402
import run  # noqa: E402
import trafficgen  # noqa: E402

from dgraph_tpu import ops  # noqa: E402
from dgraph_tpu.models import PostingStore  # noqa: E402
from dgraph_tpu.models import arena as A  # noqa: E402
from dgraph_tpu.models.types import TypeID, TypedValue  # noqa: E402
from dgraph_tpu.query import chain  # noqa: E402
from dgraph_tpu.serve.server import DgraphServer  # noqa: E402
from dgraph_tpu.utils.metrics import (  # noqa: E402
    ARENA_LAYOUT_UPDATES,
    ARENA_MIRROR_UPDATES,
    WRITES,
    WRITE_QUADS,
    XLA_COMPILES,
)

QUADS, SEED = 6000, 11
READS = ("hot_actor4", "two_hop", "coactor3")
NONE = np.zeros((0, 2), dtype=np.int64)



def _boot(quads=QUADS):
    """A server that holds the generated graph, every chain on the fused
    device route (a toy graph never clears the default thresholds)."""
    g = filmgen.generate(quads, SEED)
    srv = DgraphServer(PostingStore())
    srv.engine.chain_threshold = 0
    # the suite's eight virtual devices are not this deployment's one chip
    srv.engine.arenas.shard_threshold = 1 << 62
    srv.run_query("mutation { schema { %s } }" % filmgen.SCHEMA)
    lines = filmgen.nquad_lines(g, 0, len(g.director))
    for lo in range(0, len(lines), 2000):
        srv.run_query("mutation { set {\n%s\n} }" % "\n".join(lines[lo:lo + 2000]))
    world = run.World(g)
    mix = trafficgen.load_json("traffic", "readwrite.json")
    return srv, world, trafficgen.load_classes(mix, world)


def _strip(out):
    return {k: v for k, v in dict(out).items() if k not in ("server_latency", "extensions")}


def _ask(srv, kind, root, tag):
    out = _strip(srv.run_query(kind.text(root, tag)))
    return kind.check(out, kind.expect(root), tag)


def _roots(kind, n):
    pool = kind.pool()
    return [int(pool[i]) for i in np.linspace(0, len(pool) - 1, n).astype(int)]


# -- N films, then every read ---------------------------------------------------------


@pytest.fixture(scope="module")
def written():
    srv, world, classes = _boot()
    films = _roots(classes["add_film"], 12)
    before = {c: [_strip(srv.run_query(classes[c].text(r, "s"))) for r in _roots(classes[c], 4)]
              for c in READS}
    w0, q0 = WRITES.snapshot().get("ok", 0), WRITE_QUADS.value()
    acks = [_ask(srv, classes["add_film"], k, "s") for k in films]
    wrote = (WRITES.snapshot().get("ok", 0) - w0, WRITE_QUADS.value() - q0)
    yield srv, world, classes, films, before, acks, wrote
    srv.stop()


def test_every_write_is_acknowledged_with_its_uids(written):
    srv, world, classes, films, _, acks, wrote = written
    assert acks == [None] * len(films)
    w = classes["add_film"].written
    assert wrote == (len(films), sum(4 + 3 * w.cast_size(k) for k in films))
    assert reference_rw.isolated(world.g, w, films)


@pytest.mark.parametrize("i", range(12))
def test_a_read_back_after_the_ack_is_whole(written, i):
    srv, _, classes, films, _, _, _ = written
    assert _ask(srv, classes["read_back"], films[i], "s") is None


@pytest.mark.parametrize("cls", READS)
def test_the_traverse_classes_say_what_the_reference_says_after_the_writes(written, cls):
    srv, _, classes, _, before, _, _ = written
    for r, was in zip(_roots(classes[cls], 4), before[cls]):
        assert _ask(srv, classes[cls], r, "s") is None
        # a second alias: executed again, not read from the result cache
        now = _strip(srv.run_query(classes[cls].text(r, "t")))
        assert json.dumps(now).replace('"leaft"', '"leafs"').replace('"met"', '"mes"') \
            .replace('"qt"', '"qs"') == json.dumps(was)


def test_the_lost_write_control_is_not_what_the_system_says(written):
    srv, world, classes, films, _, _, _ = written
    broken = trafficgen.load_module("controls", "lost_write").walker(world)
    kind = classes["read_back"]
    for k in films:
        # a control is rendered and judged under the empty alias (run.py)
        assert kind.check(kind.render(k, broken), kind.expect(k)) is not None
        assert kind.check(kind.render(k, world.walker), kind.expect(k)) is None


# -- readers on eight threads while the writes go in --------------------------------------


@pytest.fixture(scope="module")
def under_read():
    srv, world, classes = _boot()
    stop = threading.Event()
    problems = {c: [] for c in READS + ("read_back", "add_film")}
    counts = dict.fromkeys(problems, 0)
    lock = threading.Lock()

    def note(cls, problem):
        with lock:
            counts[cls] += 1
            if problem is not None:
                problems[cls].append(problem)

    def reader(i):
        cls = READS[i % len(READS)]
        roots = _roots(classes[cls], 5)
        n = 0
        while not stop.is_set():
            # a tag of its own every time: the result cache answers none of them
            note(cls, _ask(srv, classes[cls], roots[n % len(roots)], f"x{i}n{n}"))
            n += 1

    def writer(i):
        for k in _roots(classes["add_film"], 40)[i::2]:
            problem = _ask(srv, classes["add_film"], k, "u")
            note("add_film", problem)
            if problem is None:     # the follower: by the writer, after the ack
                note("read_back", _ask(srv, classes["read_back"], k, "u"))

    readers = [threading.Thread(target=reader, args=(i,)) for i in range(6)]
    writers = [threading.Thread(target=writer, args=(i,)) for i in range(2)]
    for t in readers + writers:
        t.start()
    for t in writers:
        t.join(timeout=600)
    stop.set()
    for t in readers:
        t.join(timeout=120)
    yield problems, counts
    srv.stop()


@pytest.mark.parametrize("cls", READS + ("read_back", "add_film"))
def test_under_eight_threads_every_answer_is_the_references(under_read, cls):
    problems, counts = under_read
    assert problems[cls] == []
    assert counts[cls] >= (40 if cls in ("read_back", "add_film") else 1)


# -- fifty writes that add overflow chunks compile nothing -----------------------------------


def test_fifty_writes_with_overflow_chunks_compile_nothing_after_the_first():
    # at 60,000 quads no bucketed size of the graph crosses a power of two
    # within fifty films (actors with a role 1,149 of 2,048, chunked films 837
    # of 1,024, the uid space 96,464 of 131,072): a program compiled anew
    # would be the layout's doing, not the graph's growth
    srv, world, classes = _boot(60_000)
    try:
        w = classes["add_film"].written
        big = [int(k) for k in w.by_cast[::-1] if w.cast_size(k) > ops.INLINE][:51]
        assert len(big) == 51
        reads = [(c, r) for c in READS for r in _roots(classes[c], 3)]

        def round_(k, tag):
            assert _ask(srv, classes["add_film"], k, "o") is None
            assert _ask(srv, classes["read_back"], k, "o") is None
            for c, r in reads:
                assert _ask(srv, classes[c], r, tag) is None

        round_(big[0], "o0")       # the first write and the reads after it compile
        st = srv.engine.arenas.data("starring")
        shapes = tuple(t.shape for t in st._inline) + (st._lut.shape,)
        used = int(st._ov_coff[-1])
        fused, xla = chain._run_fused._cache_size(), XLA_COMPILES.value()
        rebuilt = ARENA_LAYOUT_UPDATES.snapshot()["rebuild"]
        for n, k in enumerate(big[1:]):
            round_(k, f"o{n + 1}")
        assert chain._run_fused._cache_size() == fused
        assert XLA_COMPILES.value() == xla
        assert ARENA_LAYOUT_UPDATES.snapshot()["rebuild"] == rebuilt
        st = srv.engine.arenas.data("starring")
        assert tuple(t.shape for t in st._inline) + (st._lut.shape,) == shapes
        assert int(st._ov_coff[-1]) == used + 50          # a chunk a film: the table grew inside its capacity
    finally:
        srv.stop()


# -- the device layouts are what the host mirror says ------------------------------------------


def _fresh(a):
    return A._csr_from_arrays(a.h_src.copy(), a.h_offsets.copy(), a.host_dst().copy())


def _assert_layout_is_a_fresh_build(a):
    f = _fresh(a)
    f.inline_layout()
    (m1, o1), (m2, o2) = a._inline, f._inline
    assert m1.shape == m2.shape
    np.testing.assert_array_equal(np.asarray(m1), np.asarray(m2))
    n = min(o1.shape[0], o2.shape[0])
    np.testing.assert_array_equal(np.asarray(o1)[:n], np.asarray(o2)[:n])
    assert (np.asarray(o1)[n:] == ops.sets.SENT).all() and (np.asarray(o2)[n:] == ops.sets.SENT).all()
    np.testing.assert_array_equal(a._ov_coff, f._ov_coff)
    if a._lut is not None:
        f._build_lut(int(a._lut.shape[0]))
        np.testing.assert_array_equal(np.asarray(a._lut), np.asarray(f._lut))
    for m in (1, 3, 10, 1000):
        assert chain._topm_ov_chunk_sum(a, m) == chain._topm_ov_chunk_sum(f, m)
    assert a.n_distinct_dst() >= f.n_distinct_dst() and a.max_uid() >= f.max_uid()


@pytest.mark.parametrize("pred, reverse", [("starring", False), ("starring", True),
                                           ("performance.actor", False),
                                           ("performance.actor", True)])
def test_a_served_arenas_layout_after_the_writes_is_a_fresh_build(written, pred, reverse):
    srv = written[0]
    a = srv.engine.arenas.reverse(pred) if reverse else srv.engine.arenas.data(pred)
    assert a._inline is not None and a._lut is not None    # the chains built them
    assert not srv.store.dirty                             # the writer consumed its journal
    _assert_layout_is_a_fresh_build(a)
    truth = srv.store.peek(pred).edges
    src = np.repeat(a.h_src, np.diff(a.h_offsets)).tolist()
    pairs = set(zip(a.host_dst().tolist(), src) if reverse else zip(src, a.host_dst().tolist()))
    assert pairs == {(s, d) for s, ds in truth.items() for d in ds}


KINDS = ("new_film_at_the_end", "adds_in_the_middle", "deletes", "the_last_row_grows")


@pytest.mark.parametrize("kind", KINDS)
def test_random_deltas_leave_the_layout_a_fresh_build(kind):
    rng = np.random.default_rng(KINDS.index(kind))
    for trial in range(6):
        n = int(rng.integers(20, 400))
        a = A.csr_from_edges(rng.integers(1, 200, n), rng.integers(1, 500, n))
        a.inline_layout()
        a.lut(4000)
        chain._topm_ov_chunk_sum(a, 5)
        a.n_distinct_dst()
        a.max_uid()
        have = set(zip(np.repeat(a.h_src, np.diff(a.h_offsets)).tolist(), a.host_dst().tolist()))
        top = 600
        for _ in range(8):
            adds, dels = set(), set()
            if kind == "new_film_at_the_end":
                adds = {(top, top + 1 + j) for j in range(int(rng.integers(1, 12)))}
                top += 20
            elif kind == "adds_in_the_middle":
                adds = {(int(rng.integers(1, 200)), int(rng.integers(1, 900)))
                        for _ in range(int(rng.integers(1, 6)))} - have
            elif kind == "deletes":
                hl = sorted(have)
                dels = {hl[int(i)] for i in rng.integers(0, len(hl), int(rng.integers(1, 5)))}
            else:
                adds = {(int(a.h_src[-1]), int(rng.integers(1, 2000)))
                        for _ in range(int(rng.integers(1, 10)))} - have
            have = (have | adds) - dels
            a.apply_delta(np.array(sorted(adds), dtype=np.int64).reshape(-1, 2),
                          np.array(sorted(dels), dtype=np.int64).reshape(-1, 2))
            got = set(zip(np.repeat(a.h_src, np.diff(a.h_offsets)).tolist(), a.host_dst().tolist()))
            assert got == have
            _assert_layout_is_a_fresh_build(a)


def test_rows_at_the_end_are_taken_in_place_and_a_bucket_outgrown_is_rebuilt():
    a = A.csr_from_edges(np.arange(1, 9), np.arange(11, 19))       # 8 rows: metap's bucket is full
    a.inline_layout()
    a.lut(100)
    before = ARENA_LAYOUT_UPDATES.snapshot()
    a.apply_delta(np.array([[8, 30], [8, 31]], dtype=np.int64), NONE)      # a row that is there
    mid = ARENA_LAYOUT_UPDATES.snapshot()
    assert (mid["delta"] - before["delta"], mid["rebuild"] - before["rebuild"]) == (1, 0)
    a.apply_delta(np.array([[50, 60]], dtype=np.int64), NONE)              # a ninth row: 8 -> 16
    after = ARENA_LAYOUT_UPDATES.snapshot()
    assert after["rebuild"] - mid["rebuild"] == 1
    assert a._inline[0].shape[0] == 16
    _assert_layout_is_a_fresh_build(a)
    a.apply_delta(np.array([[5000, 1]], dtype=np.int64), NONE)             # past the LUT: dropped, the caller's size
    assert a._lut is None
    assert int(np.asarray(a.lut(5000))[5000]) == a.n_rows - 1


# -- the host mirrors have room at their end ------------------------------------------------------


MIRRORS = ("h_src", "h_offsets", "_h_dst")


def _pairs(edges):
    return np.array(sorted(edges), dtype=np.int64).reshape(-1, 2)


def _edges_of(a):
    return set(zip(np.repeat(a.h_src, np.diff(a.h_offsets)).tolist(), a.host_dst().tolist()))


def _assert_mirrors_are_a_build_from(a, have):
    """``a``'s mirrors against ``csr_from_edges`` of the edge set ``have``
    (a row that deletes emptied stays, with no edge), its chunk offsets
    against a layout built from that build."""
    now = _pairs(have)
    want = A.csr_from_edges(now[:, 0], now[:, 1])
    rows = np.diff(a.h_offsets) > 0
    np.testing.assert_array_equal(a.h_src[rows], want.h_src)
    np.testing.assert_array_equal(a.h_offsets[np.concatenate([[True], rows])], want.h_offsets)
    np.testing.assert_array_equal(a.host_dst(), want.host_dst())
    assert (a.n_rows, a.n_edges) == (len(a.h_src), len(a.host_dst()))
    assert (a.h_src.dtype, a.h_offsets.dtype, a.host_dst().dtype) == (np.int64, np.int64, np.int32)
    if a._ov_coff is not None and rows.all():
        want.inline_layout()
        np.testing.assert_array_equal(a._ov_coff, want._ov_coff)


def _film(top, cast):
    """A new film's edges as one arena sees them: ``cast`` rows past ``top``."""
    return {(top + 1, top + 2 + j) for j in range(cast)} | \
           {(top + 2 + j, top + 40 + j) for j in range(cast - 1)}


def _grown():
    return ARENA_MIRROR_UPDATES.snapshot()


def _since(was):
    now = _grown()
    return {h: now[h] - was[h] for h in ("append", "grow", "copy") if now[h] != was[h]}


MIXES = ("tail", "tail_and_middle", "one_delta_holds_both")


@pytest.mark.parametrize("mix", MIXES)
def test_a_sequence_of_deltas_leaves_mirrors_a_build_from_the_edges(mix):
    rng = np.random.default_rng(MIXES.index(mix))
    for trial in range(4):
        n = int(rng.integers(20, 400))
        a = A.csr_from_edges(rng.integers(1, 200, n), rng.integers(1, 500, n))
        if trial % 2:
            a.inline_layout()
            a.lut(8000)
        have, top, was = _edges_of(a), 600, _grown()
        for step in range(12):
            adds, dels = set(), set()
            if mix == "tail" or step % 3 != 2 or mix == "one_delta_holds_both":
                adds |= _film(top, int(rng.integers(2, 12)))
                top += 100
            if mix != "tail" and (step % 3 == 2 or mix == "one_delta_holds_both"):
                # under the last row: a row that is there, or a new one in the middle
                adds |= {(int(rng.integers(1, int(a.h_src[-1]))), int(rng.integers(1, 900)))
                         for _ in range(int(rng.integers(1, 5)))} - have
                hl = sorted(have)
                dels = {hl[int(i)] for i in rng.integers(0, len(hl), int(rng.integers(0, 3)))}
            have = (have | adds) - dels
            a.apply_delta(_pairs(adds), _pairs(dels))
            # deletes may leave empty rows: compare the mirrors' own invariants then
            assert _edges_of(a) == have
            assert (np.diff(a.h_src) > 0).all() and a.h_offsets[0] == 0 and a.h_offsets[-1] == a.n_edges
            if not (np.diff(a.h_offsets) == 0).any():
                _assert_mirrors_are_a_build_from(a, have)
            if a._inline is not None:
                _assert_layout_is_a_fresh_build(a)
        took = _since(was)
        assert sum(took.values()) == 12
        if mix == "tail":
            assert set(took) <= {"append", "grow"} and took["append"] >= 10
        else:
            assert took["copy"] == (4 if mix == "tail_and_middle" else 12)


@pytest.mark.parametrize("pred, reverse", [("starring", False), ("starring", True),
                                           ("performance.actor", False),
                                           ("performance.actor", True)])
def test_a_served_arenas_mirrors_after_the_writes_are_a_build_from_the_edges(written, pred, reverse):
    srv = written[0]
    a = srv.engine.arenas.reverse(pred) if reverse else srv.engine.arenas.data(pred)
    truth = {(s, d) for s, ds in srv.store.peek(pred).edges.items() for d in ds}
    _assert_mirrors_are_a_build_from(a, {(d, s) for s, d in truth} if reverse else truth)
    # twelve films, each past all that was there: the mirrors are the start of buffers with room
    for name in MIRRORS + ("_ov_coff",):
        view, buf = getattr(a, name), a._bufs[name]
        assert view.base is buf and len(buf) > len(view) and np.shares_memory(view, buf)


@pytest.mark.parametrize("layouts", [False, True])
def test_views_taken_before_a_tail_delta_are_what_they_were_after_it(layouts):
    rng = np.random.default_rng(7)
    a = A.csr_from_edges(rng.integers(1, 300, 900), rng.integers(1, 700, 900))
    if layouts:
        a.inline_layout()
    a.apply_delta(_pairs(_film(1000, 5)), NONE)            # the buffers are made
    for step in range(6):
        held = (a.h_src, a.h_offsets, a.host_dst(), a._ov_coff)
        copies = [None if v is None else v.copy() for v in held]
        edges = _edges_of(a)
        a.apply_delta(_pairs(_film(2000 + 100 * step, 3 + step)), NONE)
        for v, c in zip(held, copies):
            if v is not None:
                np.testing.assert_array_equal(v, c)
        # together they still describe the arena as it was
        assert held[1][-1] == len(held[2]) and len(held[1]) == len(held[0]) + 1
        assert set(zip(np.repeat(held[0], np.diff(held[1])).tolist(), held[2].tolist())) == edges
        assert len(a.h_src) > len(held[0]) and np.shares_memory(a.h_src, held[0])


def test_two_hundred_films_reallocate_each_mirror_a_few_times():
    rng = np.random.default_rng(3)
    a = A.csr_from_edges(rng.integers(1, 3000, 9000), rng.integers(1, 7000, 9000))
    a.inline_layout()
    have, was = _edges_of(a), _grown()
    made = dict.fromkeys(MIRRORS + ("_ov_coff",), 0)
    for k in range(200):
        found = dict(a._bufs)
        film = _film(10_000 + 100 * k, 8)
        have |= film
        a.apply_delta(_pairs(film), NONE)
        for name in made:
            if a._bufs.get(name) is not found.get(name):
                made[name] += 1         # (a layout built anew, its bucket outgrown, owns its offsets)
            elif name in found:         # written into the room of the buffer it found
                assert np.shares_memory(getattr(a, name), found[name])
    took = _since(was)
    # 1,600 rows and 3,000 edges in rooms of 1,024 at least: a mirror's buffer is made
    # once and outgrown twice or thrice, never once a film
    assert took.get("copy", 0) == 0 and took["append"] + took["grow"] == 200
    assert 1 <= took["grow"] <= 8 and max(made.values()) <= 4 and min(made.values()) >= 1
    _assert_mirrors_are_a_build_from(a, have)
    _assert_layout_is_a_fresh_build(a)


@pytest.mark.parametrize("what", ["a_delete", "an_edge_of_a_middle_row", "an_edge_of_the_last_row",
                                  "a_row_in_the_middle"])
def test_anything_but_a_tail_delta_is_copied_once_and_leaves_room(what):
    a = A.csr_from_edges(np.repeat(np.arange(10, 400, 10), 3), np.arange(117) + 1000)
    have = _edges_of(a)
    old = (a.h_src, a.h_offsets, a.host_dst())
    olds = [v.copy() for v in old]
    adds, dels = set(), set()
    if what == "a_delete":
        dels = {sorted(have)[40]}
    elif what == "an_edge_of_a_middle_row":
        adds = {(200, 5)}
    elif what == "an_edge_of_the_last_row":
        adds = {(390, 5000)}
    else:
        adds = {(205, 77), (205, 78)}
    was = _grown()
    a.apply_delta(_pairs(adds), _pairs(dels))
    assert _since(was) == {"copy": 1}
    have = (have | adds) - dels
    assert _edges_of(a) == have
    for v, c in zip(old, olds):                            # the published arrays were not written
        np.testing.assert_array_equal(v, c)
    for name in ("h_offsets", "_h_dst"):                   # what was copied has room
        view, buf = getattr(a, name), a._bufs[name]
        assert view.base is buf and len(buf) >= len(view) + 1024
    was = _grown()
    film = _film(5000, 4)
    a.apply_delta(_pairs(film), NONE)                      # h_src may still be the build's own
    assert set(_since(was)) <= {"append", "grow"}
    was, bufs = _grown(), dict(a._bufs)
    a.apply_delta(_pairs(_film(6000, 4)), NONE)
    assert _since(was) == {"append": 1} and all(a._bufs[k] is bufs[k] for k in MIRRORS)
    if what != "a_delete":
        _assert_mirrors_are_a_build_from(a, have | film | _film(6000, 4))


def test_an_arena_that_took_no_delta_owns_arrays_of_exactly_its_size():
    rng = np.random.default_rng(5)
    a = A.csr_from_edges(rng.integers(1, 300, 900), rng.integers(1, 700, 900))
    a.inline_layout()
    a.lut(1000)
    assert a._bufs == {}
    for v, n in ((a.h_src, a.n_rows), (a.h_offsets, a.n_rows + 1), (a._ov_coff, a.n_rows + 1)):
        assert v.shape == (n,) and (v.base is None or v.base.shape == (n,))
    assert a.host_dst().shape == (a.n_edges,)
    a.apply_delta(NONE, NONE)                              # an empty delta: nothing is made, nothing counted
    assert a._bufs == {}


# -- a cached answer whose footprint a write touched ------------------------------------------


def test_a_cached_answer_a_write_touched_is_never_served_again():
    srv, world, classes = _boot()
    try:
        from dgraph_tpu.utils.metrics import metrics

        def hits():
            return metrics.labeled("dgraph_qcache_result_events_total", label="event") \
                .snapshot().get("hit", 0)

        kind, k = classes["read_back"], _roots(classes["add_film"], 3)[1]
        text = kind.text(k, "c")
        assert _strip(srv.run_query(text)) == {"qc": []}           # not written yet: nobody by that name
        h0 = hits()
        assert _strip(srv.run_query(text)) == {"qc": []}
        assert hits() == h0 + 1                                    # the repeat is a hit
        assert _ask(srv, classes["add_film"], k, "c") is None
        h1 = hits()
        assert _ask(srv, kind, k, "c") is None                     # the same text: the film, not the cached nothing
        assert hits() == h1
        cls = classes["two_hop"]
        r = _roots(cls, 2)[0]
        first = _strip(srv.run_query(cls.text(r, "c")))
        assert _ask(srv, classes["add_film"], k + 1 if k + 1 < reference_rw.POOL else k - 1, "c") is None
        h2 = hits()
        assert _strip(srv.run_query(cls.text(r, "c"))) == first    # name, starring: both written; executed again
        assert hits() == h2
    finally:
        srv.stop()


# -- the parts ------------------------------------------------------------------------------------


@pytest.mark.parametrize("n_adds, n_dels", [(1, 0), (0, 3), (40, 7), (2000, 300)])
def test_a_merged_delta_is_what_a_build_from_the_edges_gives(n_adds, n_dels):
    rng = np.random.default_rng(n_adds + n_dels)
    src, dst = rng.integers(1, 300, 900), rng.integers(1, 700, 900)
    a = A.csr_from_edges(src, dst)
    have = set(zip(np.repeat(a.h_src, np.diff(a.h_offsets)).tolist(), a.host_dst().tolist()))
    adds = {(int(s), int(d)) for s, d in zip(rng.integers(1, 400, n_adds),
                                             rng.integers(700, 900, n_adds))}
    hl = sorted(have)
    dels = {hl[int(i)] for i in rng.integers(0, len(hl), n_dels)}
    got = A._merge(a.h_src, a.h_offsets, a.host_dst(),
                   np.array(sorted(adds), dtype=np.int64).reshape(-1, 2),
                   np.array(sorted(dels), dtype=np.int64).reshape(-1, 2))
    now = np.array(sorted((have | adds) - dels), dtype=np.int64)
    want = A.csr_from_edges(now[:, 0], now[:, 1])
    rows = np.diff(got[1]) > 0            # a row emptied by deletes stays, with no edge
    np.testing.assert_array_equal(got[0][rows], want.h_src)
    np.testing.assert_array_equal(got[1][np.concatenate([[True], rows])], want.h_offsets)
    np.testing.assert_array_equal(got[2], want.host_dst())
    assert got[2].dtype == np.int32


def test_the_top_m_chunk_sums_are_repaired_exactly():
    vals = np.array([5, 3, 3, 3, 1, 1], dtype=np.int64)
    cs = np.concatenate([[0], np.cumsum(vals)])
    got = A._topm_replace(cs, np.array([3, 0, 1, 3]), np.array([4, 2, 0, 3]))
    want = np.sort(np.array([5, 3, 3, 1, 4, 2]))[::-1]
    np.testing.assert_array_equal(got, np.concatenate([[0], np.cumsum(want)]))


def _value(s):
    return TypedValue(TypeID.STRING, s)


def _named_store(n):
    from dgraph_tpu.models.schema import parse_schema

    store = PostingStore()
    parse_schema("name: string @index(term, exact) .", into=store.schema)
    store.bulk_set_values("name", [(u, "", _value(f"Actor {u}")) for u in range(1, n + 1)])
    return store


@pytest.mark.parametrize("tokenizer", ["exact", "term"])
def test_an_index_arena_takes_new_values_in_place(tokenizer):
    store = _named_store(300)
    mgr = A.ArenaManager(store)
    idx = mgr.index("name", tokenizer)
    store.bulk_set_values("name", [(900, "", _value("Newcomer w-5-1")),
                                   (901, "", _value("Aardvark 7")), (902, "", _value("Actor 902"))])
    assert store.value_delta["name"] is not None and len(store.value_delta["name"]) == 3
    again = mgr.index("name", tokenizer)
    assert again is idx                                         # taken in place, not rebuilt
    fresh = A.ArenaManager(store)._build_index("name", tokenizer)
    assert idx.tokens == fresh.tokens
    np.testing.assert_array_equal(idx.csr.h_offsets, fresh.csr.h_offsets)
    np.testing.assert_array_equal(idx.csr.host_dst(), fresh.csr.host_dst())
    store.set_value("name", 5, _value("Renamed"))               # an overwrite: rebuilt
    assert store.value_delta["name"] is None
    assert mgr.index("name", tokenizer) is not idx


@pytest.mark.parametrize("where", ["front", "middle", "end", "everywhere_twice"])
def test_an_index_arena_takes_new_tokens_wherever_they_sort(where):
    store = _named_store(300)                                   # tokens "Actor 1" .. "Actor 300"
    mgr = A.ArenaManager(store)
    idx = mgr.index("name", "exact")
    names = {"front": ["Aardvark 7", "Aardvark 1"], "middle": ["Actor 2000", "Actor 17b"],
             "end": ["Zoe w-5-1", "Zed"],
             "everywhere_twice": ["Aardvark", "Actor 150x", "Zoe"]}[where]
    rounds = 2 if where == "everywhere_twice" else 1
    uid = 900
    for r in range(rounds):
        tokens = idx.tokens
        store.bulk_set_values("name", [(uid + i, "", _value(n + "!" * r)) for i, n in enumerate(names)]
                              + [(uid + 50, "", _value("Actor 7"))])       # a token that is there
        uid += 100
        assert mgr.index("name", "exact") is idx and idx.tokens is tokens   # in place: no copy of the table
    fresh = A.ArenaManager(store)._build_index("name", "exact")
    assert idx.tokens == fresh.tokens and len(idx.tokens) == 300 + rounds * len(names)
    for name in ("h_src", "h_offsets"):
        np.testing.assert_array_equal(getattr(idx.csr, name), getattr(fresh.csr, name))
    np.testing.assert_array_equal(idx.csr.host_dst(), fresh.csr.host_dst())
    assert idx.csr.n_rows == fresh.csr.n_rows == len(idx.tokens)
    for t in fresh.tokens[::7] + fresh.tokens[-3:]:
        assert idx.row_of(t) == fresh.row_of(t) >= 0
        assert idx.row_range(lo=t) == fresh.row_range(lo=t)
        assert idx.row_range(hi=t, hi_open=True) == fresh.row_range(hi=t, hi_open=True)
    assert idx.row_of(fresh.tokens[0][:-1] + "\x00") == -1
    rows = np.arange(idx.csr.n_rows)
    for got, want in zip(idx.csr.expand_host(rows), fresh.csr.expand_host(rows)):
        np.testing.assert_array_equal(got, want)


def test_the_value_mirror_takes_a_new_uid_and_drops_on_an_overwrite():
    store = _named_store(50)
    pd = store.peek("name")
    arr, vals = pd.untagged_mirror()
    store.bulk_set_values("name", [(70, "", _value("x")), (60, "", _value("y"))])
    arr2, vals2 = pd._untagged
    assert arr2.tolist() == list(range(1, 51)) + [60, 70] and len(arr) == 50
    assert [v.value for v in vals2[-2:]] == ["y", "x"]
    store.set_value("name", 60, _value("z"))
    assert pd._untagged is None
    assert pd.untagged_mirror()[1][50].value == "z"


def test_a_tagged_or_repeated_value_overflows_the_value_journal():
    store = _named_store(10)
    store.dirty.clear(), store.delta.clear(), store.value_delta.clear()
    store.bulk_set_values("name", [(20, "", _value("a"))])
    assert store.value_delta["name"] == [(20, _value("a"))] and store.delta["name"] == []
    store.bulk_set_values("name", [(21, "fr", _value("b"))])
    assert store.value_delta["name"] is None and store.delta["name"] is None
