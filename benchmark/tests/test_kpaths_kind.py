"""The k-path cell's yardstick, checked on the CPU: ``reference_kpaths`` against
a brute force of the rule (a heap of whole paths, popped by (length, path)),
its bounded searches against its whole one; ``work_kpaths``' arithmetic; the
``kpaths`` kind's comparison — the reference's own answers come out correct,
planted faults (a path twice, a path one hop too long, a path that visits a
uid twice, a wrong names block, one path where there are k) come out not
correct; the controls ``drop_listed`` and ``first_path_only`` through the
comparison; the pools' order by the k-th path's estimated length; the five
readers on made-up windows.  None of it is part of a
benchmark run.
"""

import collections
import copy
import heapq
import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

import compare  # noqa: E402
import filmgen  # noqa: E402
import reference_kpaths  # noqa: E402
import trafficgen  # noqa: E402
import work_kpaths  # noqa: E402
from run import Observed, World  # noqa: E402

LISTED = ["~p", "q", "p"]


def _graph(seed, n=120, m=130):
    rng = np.random.default_rng(seed)
    e = {}
    for pred in ("p", "q"):
        s, d = rng.integers(1, n + 1, (2, m))
        pairs = np.unique(np.stack([s, d], 1), axis=0)
        e[pred] = (pairs[:, 0], pairs[:, 1])
    return e


def _brute(e, src, dst, k):
    """The rule as a heap of whole paths: pop the least (length, path); a uid
    is taken k times at most; a path never visits a uid twice; an arrival at
    ``dst`` is not expanded."""
    nb = {}
    for tok in LISTED:
        s, d = e[tok.lstrip("~")]
        for u, v in zip(s.tolist(), d.tolist()):
            a, b = (v, u) if tok.startswith("~") else (u, v)
            nb.setdefault(a, set()).add(b)
    heap, taken, out = [(0, [src])], {}, []
    while heap and len(out) < k:
        n, path = heapq.heappop(heap)
        u = path[-1]
        if taken.get(u, 0) >= k:
            continue
        taken[u] = taken.get(u, 0) + 1
        if u == dst:
            out.append(path)
            continue
        for v in sorted(nb.get(u, ())):
            if v not in path:
                heapq.heappush(heap, (n + 1, path + [v]))
    return out


@pytest.mark.parametrize("seed,k", [(1, 2), (2, 3), (3, 3), (4, 5)])
def test_reference_against_a_brute_force(seed, k):
    e = _graph(seed)
    ref = reference_kpaths.KPathReference(e, LISTED, k)
    rng = np.random.default_rng(seed + 50)
    found = 0
    for src, dst in rng.integers(1, 121, (60, 2)).tolist():
        want = _brute(e, src, dst, k)
        got = ref.search(src, dst, with_paths=True)
        assert got["paths"] == want, (src, dst)
        assert got["lengths"] == [len(p) - 1 for p in want]
        assert ref.paths(src, dst) == want            # the bounded searches
        assert got["first"] + got["again"] == got["kept"]
        found += bool(want)
    assert found


def test_work_kpaths_arithmetic():
    assert work_kpaths.kpath_bytes(1000, 10, 5) == 8 * 1000 + 8 * 10 + 4 * 5
    assert work_kpaths.roofline_share(819e9 / 8, 0, 0, 1.0, 819e9) == pytest.approx(100.0)
    assert work_kpaths.roofline_share(0, 5, 5, 1.0, 819e9) is None
    assert work_kpaths.roofline_share(5, 5, 5, 0.0, 819e9) is None


@pytest.fixture(scope="module")
def cell():
    world = World(filmgen.generate(120_000, 11))
    mix = trafficgen.load_json("traffic", "kshortest.json")
    classes = trafficgen.load_classes(mix, world)
    plan = trafficgen.deal(mix, classes, 11)
    return world, classes, plan


def test_the_mix_deals_its_classes_by_weight(cell):
    _, classes, plan = cell
    n = {c: sum(1 for k, _ in plan[:1600] if k == c) for c in classes}
    assert all(abs(n[c] - 1600 * w) <= 1 for c, w in
               {"kpath_to_star": 0.6, "kpath_pair": 0.3, "kpath_costar": 0.1}.items()), n
    for name, kind in classes.items():
        assert kind.k == 3 and "numpaths: 3" in kind.text(0)
        assert len(kind.pairs()) == 4096
    assert all(classes["kpath_costar"].expect(r)["want"]["lengths"][0] == 4 for r in range(20))


def test_the_pairs_are_ranked_by_the_kth_paths_length(cell):
    """Each pool is ordered by the estimated length of the k-th path first,
    the estimate is the reference's (12 standing for 12 and more) on the
    deck's first 60 requests, and two seeds' first 148 requests ask for the
    same shares of 4-, 8- and 12-hop searches."""
    world, classes, plan = cell
    for kind in classes.values():
        assert np.all(np.diff(kind._kth_hops(kind.pairs())) >= 0)
    for c, r in plan[:60]:
        kind = classes[c]
        paths = kind.kref.paths(*kind._uids(r))
        want = min(len(paths[-1]) - 1, 12) if len(paths) == kind.k else 12
        assert int(kind._kth_hops(kind.pairs()[[r]])[0]) == want, (c, r)

    def shares(seed):
        w = World(filmgen.generate(120_000, seed))
        mix = trafficgen.load_json("traffic", "kshortest.json")
        cls = trafficgen.load_classes(mix, w)
        got = collections.Counter()
        for c, r in trafficgen.deal(mix, cls, seed)[:148]:
            got[(c, int(cls[c]._kth_hops(cls[c].pairs()[[r]])[0]))] += 1
        return got

    a, b = shares(5), shares(2147484005)
    assert set(a) == set(b) and all(abs(a[x] - b[x]) <= 2 for x in a), (a, b)


def _judged(cell, walker=None, n=40):
    """What run.py's ``--control`` does: ``walker`` (None: the reference)
    answers the window's requests in the program's place."""
    _, classes, plan = cell
    records = [(0, c, r, 0.0, 0.0, 200, b"") for c, r in plan[:n]]

    def answer_of(c, r):
        return json.dumps({**classes[c].render(r, walker), "server_latency": {}}).encode()

    got = compare.compare(records, classes, answer_of=answer_of)
    return compare.verdict(got["numbers"])[0], got


def test_the_reference_in_the_programs_place_is_correct(cell):
    ok, got = _judged(cell)
    assert ok and got["numbers"]["compared"] == 40 and all(got["ok"])
    assert all(e["edges"] > 0 and e["again"] >= 0 for e in got["expect"])
    assert sum(len(e["want"]["lengths"]) for e in got["expect"]) == 3 * 40


def test_the_plain_walker_in_the_programs_place_is_correct(cell):
    import reference

    ok, got = _judged(cell, reference.Walker(cell[0].g))
    assert ok and got["numbers"]["wrong"] == 0


@pytest.mark.parametrize("control", ["drop_listed", "first_path_only"])
def test_a_control_in_the_programs_place_is_not_correct(cell, control):
    """No path survives a missing arena; one path is not the k asked for."""
    module = trafficgen.load_module("controls", control)
    ok, got = _judged(cell, module.walker(cell[0]))
    assert not ok and got["numbers"]["wrong"] == 40


def _a_path_twice(kind, ans):
    ans["_path_"][1] = copy.deepcopy(ans["_path_"][0])
    if len({json.dumps(p) for p in ans["_path_"]}) == len(ans["_path_"]) or \
            _len(ans["_path_"][1]) != _len(ans["_path_"][2]):
        ans["_path_"][2] = copy.deepcopy(ans["_path_"][0])


def _len(node):
    n = 0
    while len(node) > 1:
        node = next(v for k, v in node.items() if k != "_uid_")[0]
        n += 1
    return n


def _one_hop_too_long(kind, ans):
    """The last path detours over a neighbour of its first uid: two hops
    more, every one an edge, no uid twice where the neighbour is fresh."""
    first = ans["_path_"][-1]
    u = first["_uid_"]
    key, (nxt,) = next((k, v) for k, v in first.items() if k != "_uid_")
    first[key] = [{"_uid_": nxt["_uid_"], key: [{"_uid_": u, key: [nxt]}]}]


def _a_uid_twice(kind, ans):
    node = ans["_path_"][0]
    key, (nxt,) = next((k, v) for k, v in node.items() if k != "_uid_")
    nxt["_uid_"] = node["_uid_"]


def _a_name_missing(kind, ans):
    ans["hops"].pop()


def _one_path_of_k(kind, ans):
    del ans["_path_"][1:]


FAULTS = [_a_path_twice, _one_hop_too_long, _a_uid_twice, _a_name_missing, _one_path_of_k]


@pytest.mark.parametrize("fault", FAULTS, ids=lambda f: f.__name__.strip("_"))
def test_planted_fault_is_not_correct(cell, fault):
    _, classes, plan = cell
    wrong = 0
    for c, r in plan[:10]:
        kind = classes[c]
        exp = kind.expect(r)
        ans = kind.render(r)
        assert kind.check(copy.deepcopy(ans), exp) is None
        fault(kind, ans)
        wrong += kind.check(ans, exp) is not None
    assert wrong == 10


def _obs(before, after, **kw):
    return Observed(counters_before=before, counters_after=after, **kw)


def test_the_readers_on_a_made_up_window():
    read = lambda name: trafficgen.load_module("metrics", name).read  # noqa: E731
    before = {"dgraph_kpath_searches_total": {"device": 10.0, "host": 0.0, "fallback": 0.0},
              "dgraph_kpath_arrivals_total": {"first": 1000.0, "again": 100.0},
              "dgraph_kpath_candidates_total": {"kept": 1100.0, "full": 500.0, "loop": 50.0}}
    after = {"dgraph_kpath_searches_total": {"device": 30.0, "host": 0.0, "fallback": 5.0},
             "dgraph_kpath_arrivals_total": {"first": 7000.0, "again": 2100.0},
             "dgraph_kpath_candidates_total": {"kept": 9100.0, "full": 4500.0, "loop": 450.0}}
    o = _obs(before, after)
    assert read("kpath_device_share")(o) == pytest.approx(100 * 20 / 25)
    assert read("kpath_arrivals_per_query")(o) == pytest.approx(8000 / 20)
    assert read("kpath_again_share")(o) == pytest.approx(100 * 2000 / 8000)
    assert read("kpath_loop_share")(o) == pytest.approx(100 * 400 / 12400)
    bare = _obs({}, {})
    for name in ("kpath_device_share", "kpath_arrivals_per_query", "kpath_again_share",
                 "kpath_loop_share"):
        assert read(name)(bare) is None              # a parent commit: nothing, nothing raised
    led = {"extensions": {"ledger": {"edges": 5, "hop_edges": {"path": 5}}}}
    o = _obs(before, after, trace={"busy_s": 0.5}, peaks={"hbm_bytes_per_s": 819e9},
             expect=[{"edges": 3_000_000, "rows": 1_000_000, "loop_steps": 500_000},
                     {"edges": 9, "rows": 9, "loop_steps": 9}, None],
             tails=[led, {"extensions": {"ledger": {"cache_hits": 1}}}, {}])
    assert read("kpath_roofline")(o) == pytest.approx(
        100 * ((8 * 3e6 + 8 * 1e6 + 4 * 5e5) / 819e9) / 0.5)
    o.counters_before = o.counters_after = {}
    assert read("kpath_roofline")(o) is None
    assert read("kpath_roofline")(_obs({}, {}, trace=None, peaks=None, expect=[], tails=[])) is None


def test_the_benchmark_names_the_new_files():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        b = json.load(f)
    (w,) = [x for x in b["workloads"] if x["name"] == "film-q4-kpaths.kshortest"]
    (c,) = [x for x in b["configs"] if x["name"] == w["config"]]
    with open(os.path.join(os.path.dirname(BENCH), c["file"])) as f:
        cfg = json.load(f)
    assert cfg["name"] == c["name"] and sorted(cfg["reduced"]) == sorted(c["reduced"])
    assert w["chips"] == cfg["chips"] == 1 and w["traffic"] == "kshortest"
    mix = trafficgen.load_json("traffic", "kshortest.json")
    assert mix["needs"] == ["dgraph_kpath_searches_total"] and mix["trace_s"] < 15
    readers = [m for m in b["per_layer"] if m["name"].startswith("kpath_")]
    assert len(readers) == 5 and all(m["workloads"] == [w["name"]] for m in readers)
    assert all(callable(trafficgen.load_module("metrics", m["name"]).read) for m in readers)
