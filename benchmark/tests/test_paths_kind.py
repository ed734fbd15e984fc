"""The path cell's yardstick, checked on the CPU: ``reference_paths`` against a
brute force on a 200-node graph; ``work_paths``' arithmetic; the ``paths``
kind's comparison — the reference's own answers come out correct, planted
faults (a path one hop too long, a hop that is no edge, a path where there is
none and none where there is one, a uid twice, a name missing from the second
block) come out not correct; the four per-layer readers on made-up windows.
None of it is part of a benchmark run.
"""

import copy
import itertools
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
import reference  # noqa: E402
import reference_paths  # noqa: E402
import trafficgen  # noqa: E402
import work_paths  # noqa: E402
from run import Observed, World  # noqa: E402

LISTED = ["~p", "q", "p"]


def _graph(seed, n=200, m=260):
    rng = np.random.default_rng(seed)
    e = {}
    for pred in ("p", "q"):
        s, d = rng.integers(1, n + 1, (2, m))
        keep = s != d
        pairs = np.unique(np.stack([s[keep], d[keep]], 1), axis=0)
        e[pred] = (pairs[:, 0], pairs[:, 1])
    return e


def _brute(e, src, dst):
    """(d or None, edges, rows) by dictionaries and sets: no numpy."""
    nb = {}
    for tok in LISTED:
        s, d = e[tok.lstrip("~")]
        for u, v in zip(s.tolist(), d.tolist()):
            a, b = (v, u) if tok.startswith("~") else (u, v)
            nb.setdefault(a, []).append(b)
    seen, frontier, edges, rows, d = {src}, {src}, 0, 0, 0
    while frontier and dst not in seen:
        rows += len(frontier)
        edges += sum(len(nb.get(u, ())) for u in frontier)
        frontier = {v for u in frontier for v in nb.get(u, ())} - seen
        seen |= frontier
        d += 1
    return (d if dst in seen else None), edges, rows


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_reference_against_a_brute_force(seed):
    e = _graph(seed)
    ref = reference_paths.PathReference(e, LISTED)
    rng = np.random.default_rng(seed + 50)
    found = missing = 0
    for src, dst in rng.integers(1, 201, (120, 2)).tolist():
        if src == dst:
            continue
        r = ref.search(src, dst, with_path=True)
        assert (r["d"], r["edges"], r["rows"]) == _brute(e, src, dst), (src, dst)
        assert r["rows"] == sum(r["levels"])
        if r["d"] is None:
            missing += 1
            assert r["path"] is None
            continue
        found += 1
        p = r["path"]
        assert p[0] == src and p[-1] == dst and len(p) == r["d"] + 1 and len(set(p)) == len(p)
        assert all(ref.holds(u, v) for u, v in zip(p, p[1:]))
    assert found and missing
    assert ref.holds(10**6, 3) == []


def test_work_paths_arithmetic():
    assert work_paths.path_bytes(1000, 10) == 8 * 1000 + 8 * 10
    # 819 GB for a second of busy time at 819 GB/s: the whole roofline
    assert work_paths.roofline_share(819e9 / 8, 0, 1.0, 819e9) == pytest.approx(100.0)
    assert work_paths.roofline_share(3e6, 1e6, 0.02, 819e9) == pytest.approx(
        100 * (8 * 4e6 / 819e9) / 0.02)
    assert work_paths.roofline_share(0, 5, 1.0, 819e9) is None
    assert work_paths.roofline_share(5, 5, 0.0, 819e9) is None


@pytest.fixture(scope="module")
def cell():
    world = World(filmgen.generate(120_000, 11))
    mix = trafficgen.load_json("traffic", "paths.json")
    classes = trafficgen.load_classes(mix, world)
    plan = trafficgen.deal(mix, classes, 11)
    return world, classes, plan


def test_the_mix_deals_its_classes_by_weight(cell):
    _, classes, plan = cell
    n = {c: sum(1 for k, _ in plan[:1600] if k == c) for c in classes}   # 100 shuffled blocks
    assert all(abs(n[c] - 1600 * w) <= 1 for c, w in {"path_to_star": 0.6, "path_pair": 0.3, "path_costar": 0.1}.items()), n
    hub = int(cell[0].actors_by_cast[0])
    for k in classes.values():
        pairs = k.pairs()
        assert len(pairs) == 4096 and (pairs[:, 0] != pairs[:, 1]).all()
        assert hub not in pairs                  # no endpoint is the generator's actor 0
    assert all(classes["path_costar"].expect(r)["want"]["d"] == 4 for r in range(20))


def test_reference_in_the_programs_place_is_correct(cell):
    world, classes, plan = cell
    records = [(0, c, r, 0.0, 0.0, 200, b"") for c, r in plan[:60]]

    def answer_of(c, r):
        return json.dumps({**classes[c].render(r), "server_latency": {}}).encode()

    got = compare.compare(records, classes, answer_of=answer_of)
    ok, _ = compare.verdict(got["numbers"])
    assert ok and got["numbers"]["compared"] == 60 and all(got["ok"])
    assert all(e["edges"] > 0 and e["rows"] == sum(e["levels"]) for e in got["expect"])


def _judged(cell, walker, n=60):
    """What run.py's ``--control`` does: ``walker`` answers the window's
    requests in the program's place, through compare and verdict."""
    _, classes, plan = cell
    records = [(0, c, r, 0.0, 0.0, 200, b"") for c, r in plan[:n]]

    def answer_of(c, r):
        return json.dumps({**classes[c].render(r, walker), "server_latency": {}}).encode()

    got = compare.compare(records, classes, answer_of=answer_of)
    return compare.verdict(got["numbers"])[0], got["numbers"]


def test_the_plain_walker_in_the_programs_place_is_correct(cell):
    ok, numbers = _judged(cell, reference.Walker(cell[0].g))
    assert ok and numbers["compared"] == 60 and numbers["wrong"] == 0


@pytest.mark.parametrize("control", ["drop_listed", "truncate"])
def test_a_control_in_the_programs_place_is_not_correct(cell, control, monkeypatch):
    """``render`` answers from the control's walker: a missing arena leaves
    no path, a capped frontier a longer one or none."""
    module = trafficgen.load_module("controls", control)
    if control == "truncate":
        monkeypatch.setattr(module, "CAP", 64)   # the fixture's levels are a fortieth of the cell's
    ok, numbers = _judged(cell, module.walker(cell[0]))
    assert not ok and numbers["wrong"] >= (60 if control == "drop_listed" else 1)


def test_pair_ids_are_ranked_by_the_searchs_size(cell):
    """Films apart first, then the co-stars' roles: the reference's edges
    climb along the pair ids, so the deck's even walk over them asks every
    seed for the same shares of small and large searches."""
    _, classes, _ = cell
    for name in ("path_pair", "path_to_star"):
        kind = classes[name]
        at = np.arange(0, 4096, 128)
        got = [kind.expect(int(r)) for r in at]
        d = [e["want"]["d"] if e["want"]["d"] is not None else 10**6 for e in got]
        assert d == sorted(d), name
        edges = np.array([e["edges"] for e in got], float)
        ranks = np.argsort(np.argsort(edges))
        assert np.corrcoef(ranks, np.arange(len(at)))[0, 1] > 0.9, name


def _one_hop_too_long(kind, ans, exp):
    """The path detours over a neighbour of its first uid: d + 2 hops, every
    one an edge — only the length is wrong."""
    first = ans["_path_"][0]
    u = int(first["_uid_"], 16)
    key, (nxt,) = next((k, v) for k, v in first.items() if k != "_uid_")
    first[key] = [{"_uid_": nxt["_uid_"], key: [{"_uid_": hex(u), key: [nxt]}]}]


def _a_hop_that_is_no_edge(kind, ans, exp):
    node = ans["_path_"][0]
    key, (nxt,) = next((k, v) for k, v in node.items() if k != "_uid_")
    nxt["_uid_"] = hex(int(nxt["_uid_"], 16) + 1)


def _under_the_wrong_predicate(kind, ans, exp):
    node = ans["_path_"][0]
    key, val = next((k, v) for k, v in node.items() if k != "_uid_")
    del node[key]
    node["starring" if key != "starring" else "performance.actor"] = val


def _no_path(kind, ans, exp):
    ans.pop("_path_")


def _a_name_missing(kind, ans, exp):
    ans["hops"].pop()


def _ends_elsewhere(kind, ans, exp):
    node = ans["_path_"][0]
    while True:
        nxt = [k for k in node if k != "_uid_"]
        child = node[nxt[0]][0]
        if not [k for k in child if k != "_uid_"]:
            del node[nxt[0]]
            return
        node = child


FAULTS = [_one_hop_too_long, _a_hop_that_is_no_edge, _under_the_wrong_predicate, _no_path,
          _a_name_missing, _ends_elsewhere]


@pytest.mark.parametrize("fault", FAULTS, ids=lambda f: f.__name__.strip("_"))
def test_planted_fault_is_not_correct(cell, fault):
    _, classes, plan = cell
    wrong = 0
    for c, r in plan[:12]:
        kind = classes[c]
        exp = kind.expect(r)
        ans = kind.render(r)
        assert kind.check(copy.deepcopy(ans), exp) is None
        fault(kind, ans, exp)
        wrong += kind.check(ans, exp) is not None
    assert wrong == 12


def test_a_path_where_the_reference_finds_none_is_not_correct(cell):
    world, classes, _ = cell
    kind = classes["path_pair"]
    exp = dict(kind.expect(0), want={"d": None, "from": 1, "to": 2})
    assert kind.check(kind.render(0), exp) is not None
    assert kind.check({"_path_": [], "hops": []}, exp) is None


def _obs(counters_before, counters_after, **kw):
    return Observed(counters_before=counters_before, counters_after=counters_after, **kw)


def test_per_layer_readers_on_a_made_up_window():
    read = lambda name: trafficgen.load_module("metrics", name).read  # noqa: E731
    before = {"dgraph_path_searches_total": {"device": 10.0, "host": 1.0},
              "dgraph_path_levels_total": {"": 70.0},
              "dgraph_path_frontier_rows_total": {"": 7000.0}}
    after = {"dgraph_path_searches_total": {"device": 30.0, "host": 6.0},
             "dgraph_path_levels_total": {"": 230.0},
             "dgraph_path_frontier_rows_total": {"": 807000.0}}
    o = _obs(before, after)
    assert read("path_levels_per_query")(o) == pytest.approx(160 / 20)
    assert read("path_device_share")(o) == pytest.approx(100 * 20 / 25)
    assert read("path_rows_per_level")(o) == pytest.approx(800000 / 160)
    # the parent of PR 28 has none of the families: nothing is returned, nothing raised
    bare = _obs({}, {})
    for name in ("path_levels_per_query", "path_device_share", "path_rows_per_level"):
        assert read(name)(bare) is None
    led = {"extensions": {"ledger": {"edges": 5, "hop_edges": {"path": 5}}}}
    o = _obs({}, {}, trace={"busy_s": 0.5}, peaks={"hbm_bytes_per_s": 819e9},
             expect=[{"edges": 3_000_000, "rows": 1_000_000}, {"edges": 9, "rows": 9}, None],
             tails=[led, {"extensions": {"ledger": {"cache_hits": 1}}}, {}])
    assert read("path_roofline")(o) == pytest.approx(100 * (8 * 4e6 / 819e9) / 0.5)
    assert read("path_roofline")(_obs({}, {}, trace=None, peaks=None, expect=[], tails=[])) is None
    o.tails = [{}, {}, {}]
    assert read("path_roofline")(o) is None
