"""The benchmark's own CPU-only checks, where the driver counts them, and the
checks of the cell PR 34 adds.

The first part IS ``benchmark/tests/test_yardstick.py``'s cases that need no
server (PERF.md Open questions 16a): they are imported from that file, which
no PR but a ``benchmark`` one may edit, so nothing is copied and nothing there
is deleted; the three that start a whole server through ``run.py`` stay where
they are, marked ``slow``.  The second part holds ``film-q4-rw.readwrite`` to
its files: the generator that sends a follower (on a stub server: by the same
caller, after the ack, never before), the control that has to come out not
correct, each new reader on a made-up window (``None`` where the program
lacks the family), and the deck (every film written once).
"""

import http.server
import importlib.util
import json
import os
import re
import sys
import threading
import time

import numpy as np
import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark")
sys.path.insert(0, BENCH)

import compare  # noqa: E402
import filmgen  # noqa: E402
import reference_rw  # noqa: E402
import run  # noqa: E402
import trafficgen  # noqa: E402
import work  # noqa: E402
import work_writes  # noqa: E402

_spec = importlib.util.spec_from_file_location(
    "bench_tests_yardstick", os.path.join(BENCH, "tests", "test_yardstick.py"))
_theirs = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_theirs)

# the cases that start no server and race no clock (parametrised ones count a case each)
test_route_split_counts_every_device_route = _theirs.test_route_split_counts_every_device_route
test_device_edge_share_reads_a_mesh_and_a_path_window = \
    _theirs.test_device_edge_share_reads_a_mesh_and_a_path_window
test_reduce_averages_four_planes = _theirs.test_reduce_averages_four_planes
test_one_plane_of_the_four_reads_as_before = _theirs.test_one_plane_of_the_four_reads_as_before
test_traversal_roofline_takes_the_traces_devices = \
    _theirs.test_traversal_roofline_takes_the_traces_devices
test_the_traced_runs_rule_reads_the_cells_chips = \
    _theirs.test_the_traced_runs_rule_reads_the_cells_chips
test_the_profilers_own_error_is_said_not_waited_for = \
    _theirs.test_the_profilers_own_error_is_said_not_waited_for
test_a_cell_finds_everything_it_names = _theirs.test_a_cell_finds_everything_it_names
test_names_lengths_and_the_share_of_four_chip_cells = \
    _theirs.test_names_lengths_and_the_share_of_four_chip_cells



# The two cases that race a thread against the clock are written again here with
# room for a loaded machine (six workers share the driver's cores; theirs allow
# 0.1 s and write the acknowledgement in place, which a reader can meet empty).
def _ack_after(path, seconds):
    def write():
        time.sleep(seconds)
        with open(path + ".tmp", "w") as f:
            json.dump({"return_ns": 1}, f)
        os.replace(path + ".tmp", path)
    threading.Thread(target=write, daemon=True).start()


def test_a_stop_past_the_budget_ends_the_run_with_the_seconds_of_each(tmp_path):
    clock = run.TraceClock(3.0)
    _ack_after(str(tmp_path / "started"), 0.3)
    assert clock.wait("start", str(tmp_path / "started")) == {"return_ns": 1}
    with pytest.raises(run.ProfilerOverBudget) as e:
        clock.wait("stop", str(tmp_path / "stopped"))          # never written
    assert re.match(r"the profiler's stop took \d\.\d s of 3 for start and stop", str(e.value))
    assert set(clock.split) == {"start", "stop"} and "trace_split_s" in str(e.value)
    assert 0.3 <= clock.split["start"] < 2.5
    assert 3.0 <= sum(clock.split.values()) < 6.0              # the two share ONE budget


def test_a_long_set_up_is_not_the_profilers(tmp_path):
    clock = run.TraceClock(2.0)
    time.sleep(2.2)                      # set-up: a first run compiles for minutes
    _ack_after(str(tmp_path / "started"), 0.0)
    clock.wait("start", str(tmp_path / "started"))
    time.sleep(2.2)                      # the window
    _ack_after(str(tmp_path / "stopped"), 0.3)
    clock.wait("stop", str(tmp_path / "stopped"))              # neither ate into the budget
    assert 0.3 <= clock.split["stop"] < 2.0 and clock.split["start"] < 1.5


CELL = "film-q4-rw.readwrite"



@pytest.fixture(scope="module")
def cell():
    bench, w, config = run.find_cell(CELL)
    mix = trafficgen.load_json("traffic", w["traffic"] + ".json")
    world = run.World(filmgen.generate(20_000, 3))
    classes = trafficgen.load_classes(mix, world)
    return bench, w, config, mix, world, classes


# -- the cell against its files -----------------------------------------------------------


def test_the_new_cell_is_one_chip_one_configuration_six_readers(cell):
    bench, w, config, mix, _, _ = cell
    assert (w["config"], w["traffic"], w["chips"]) == ("film21m-q4-rw", "readwrite", 1)
    assert [m["name"] for m in run.metrics_of(bench, "end_to_end", CELL)] == \
        ["query_p50_ms", "setup_s"]
    mine = [m for m in bench["per_layer"] if m.get("workloads") == [CELL]]
    assert [m["name"] for m in mine] == [
        "write_ack_p95_ms", "write_path_ms", "refresh_ms", "layout_rebuild_share",
        "h2d_bytes_per_write", "readwrite_roofline"]
    assert all(m["moves"] == "query_p50_ms" for m in mine)
    base = trafficgen.load_json("configs", "film21m-q4.json")
    for key in ("scale", "upstream_scale", "reduced", "schema", "shapes"):
        assert config[key] == base[key]            # the same graph, cut the same way
    assert set(config["guarantees"]) >= {"server", "writes", "read_your_write", "atomicity",
                                         "reads", "caches"}
    assert "--sync is off" in config["guarantees"]["writes"]


def test_the_mix_is_the_traverse_mix_and_a_tenth_of_writes(cell):
    *_, mix, _, _ = cell
    trav = trafficgen.load_json("traffic", "traverse.json")
    weights = {c["class"]: c["weight"] for c in mix["classes"]}
    assert [c["class"] for c in mix["classes"]] == \
        ["hot_actor4", "two_hop", "coactor3", "add_film", "read_back"]
    assert weights["add_film"] == 0.10 and weights["read_back"] == 0
    for c in trav["classes"]:                      # the reads keep their proportions
        assert weights[c["class"]] == pytest.approx(0.9 * c["weight"])
    assert mix["follow"] == {"add_film": "read_back"} and mix["generator"] == "closed_follow"
    assert mix["warm"] == trav["warm"] and mix["clients"] == 8 and mix["deck"] == 16384


@pytest.mark.parametrize("seed", [1, 3300000523])
def test_the_deck_writes_every_film_once_and_never_deals_a_read_back(cell, seed):
    *_, mix, _, classes = cell
    plan = trafficgen.deal(mix, classes, seed)
    films = [r for c, r in plan if c == "add_film"]
    assert len(films) == len(set(films)) == pytest.approx(0.1 * len(plan), abs=1)
    assert not any(c == "read_back" for c, _ in plan)
    w = classes["add_film"].written
    for lo in range(0, len(films) - 200, 200):     # every stretch writes the same casts
        assert np.mean([w.cast_size(k) for k in films[lo:lo + 200]]) == \
            pytest.approx(float(w.cast.mean()), rel=0.06)
    assert list(classes["read_back"].pool()) == list(classes["add_film"].pool())


def test_a_written_film_is_what_the_issue_says(cell):
    *_, world, classes = cell
    w = classes["add_film"].written
    # the generator's cast law: "mean 6" by name, 4.5 by measure (1,745,909
    # performances over 388k films in the loaded graph too): 17.5 quads a film
    assert 1 <= w.cast.min() and w.cast.max() == 8 and 4 + 3 * w.cast.mean() == \
        pytest.approx(17.5, abs=0.5)
    for k in (0, 17, 65535):
        quads, c = w.quads(k, "w"), w.cast_size(k)
        assert len(quads) == 4 + 3 * c and len(w.blanks(k)) == 1 + 2 * c
        text = classes["add_film"].text(k, "w")
        assert text.startswith("mutation { set {") and text.count("\n") == len(quads) + 1
        assert f'"Film w-{k}"' in text and f'"Newcomer w-{k}-{c}"' in classes["read_back"].text(k, "w")
        assert "qw(func: eq(name" in classes["read_back"].text(k, "w")
        assert w.layout_touch(k) == {"rows": 1 + 3 * c, "chunks": 1 if c > 6 else 0,
                                     "lut": 1 + 3 * c}
    assert reference_rw.isolated(world.g, w, range(0, reference_rw.POOL, 997))
    assert classes["add_film"].expect(5)["edges"] == 0
    assert classes["read_back"].expect(5) == {"edges": 2 + 2 * w.cast_size(5),
                                              "rows": 3 + w.cast_size(5), "root": 5}


def test_a_write_that_reaches_the_walked_graph_fails_the_proof(cell):
    *_, world, classes = cell

    class Hot(reference_rw.Written):
        def quads(self, k, tag):       # a new role for a generated actor: the hot-graph write
            return super().quads(k, tag) + [f"_:p1 <performance.actor> <0x{self.g.actor_base + 1:x}> ."]

    with pytest.raises(AssertionError, match="touches the walked graph"):
        reference_rw.isolated(world.g, Hot(world.g), [3])


# -- closed_follow on a stub server -------------------------------------------------------------


def test_the_follower_is_sent_by_the_same_caller_after_the_ack_never_before():
    gen = trafficgen.load_module("generators", "closed_follow")
    log, lock = [], threading.Lock()

    class Stub(http.server.BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"

        def log_message(self, *a):
            pass

        def do_POST(self):
            body = self.rfile.read(int(self.headers["Content-Length"])).decode()
            with lock:
                log.append(("in", body, self.client_address[1]))
            status = 500 if body == "w:13" else 200         # one write is refused
            out = b"{}"
            with lock:
                log.append(("out", body, self.client_address[1]))
            self.send_response(status)
            self.send_header("Content-Length", str(len(out)))
            self.end_headers()
            self.wfile.write(out)

    httpd = http.server.ThreadingHTTPServer(("127.0.0.1", 0), Stub)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    try:
        plan = [("w" if i % 4 == 0 else "r", i + 1) for i in range(400)]
        win = gen.drive(f"http://127.0.0.1:{httpd.server_address[1]}", "/query", plan,
                        lambda c, r: f"{c}:{r}", 0.6,
                        {"clients": 4, "follow": {"w": "f"}})
    finally:
        httpd.shutdown()
    recs = win["records"]
    assert not win["never_answered"] and len(recs) > 40
    writes = {r[2]: r for r in recs if r[1] == "w"}
    follows = {r[2]: r for r in recs if r[1] == "f"}
    assert set(follows) == {k for k, r in writes.items() if r[5] == 200}   # one each, acked writes only
    assert 13 in writes and writes[13][5] == 500 and 13 not in follows
    for k, f in follows.items():
        w = writes[k]
        assert f[0] == w[0]                      # the same caller
        assert f[3] >= w[4]                      # sent after the ack came back
        mine = [r for r in recs if r[0] == w[0]]
        assert mine[mine.index(w) + 1] is f      # at once: nothing drawn in between
    seen = [e for e in log if e[1].startswith(("w:", "f:"))]
    for k in follows:                            # the server saw the ack leave before the follower arrived
        assert seen.index(("out", f"w:{k}", writes_port(seen, k))) < \
            next(i for i, e in enumerate(seen) if e[0] == "in" and e[1] == f"f:{k}")
    dealt = [r[2] for r in recs if r[1] != "f"]
    assert sorted(dealt) == list(range(1, len(dealt) + 1))      # a prefix of the one sequence
    assert gen.drive.__doc__ and "follow" in gen.__doc__


def test_a_server_without_the_mixs_counter_families_is_refused_at_once():
    """What a parent commit meets on this cell's files: no loop is driven."""
    gen = trafficgen.load_module("generators", "closed_follow")
    posts = []

    class Old(http.server.BaseHTTPRequestHandler):
        def log_message(self, *a):
            pass

        def do_GET(self):
            out = b"# TYPE dgraph_num_queries_total counter\ndgraph_num_queries_total 5\n" \
                  b'dgraph_writes_total_but_not_it{result="ok"} 1\n'
            self.send_response(200)
            self.send_header("Content-Length", str(len(out)))
            self.end_headers()
            self.wfile.write(out)

        def do_POST(self):
            posts.append(1)

    httpd = http.server.ThreadingHTTPServer(("127.0.0.1", 0), Old)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    try:
        mix = trafficgen.load_json("traffic", "readwrite.json")
        assert mix["needs"] == ["dgraph_arena_layout_updates_total", "dgraph_writes_total"]
        with pytest.raises(RuntimeError, match="exposes no .'dgraph_arena_layout_updates_total', 'dgraph_writes_total'."):
            gen.drive(f"http://127.0.0.1:{httpd.server_address[1]}", "/query", [("w", 1)],
                      lambda c, r: "x", 0.2, mix)
    finally:
        httpd.shutdown()
    assert posts == []


def writes_port(seen, k):
    return next(e[2] for e in seen if e[0] == "out" and e[1] == f"w:{k}")


# -- the comparison on made-up records -----------------------------------------------------------


def _records(classes, walker, tag=""):
    recs = []
    for i, (cls, root) in enumerate([("add_film", 7), ("read_back", 7), ("add_film", 9),
                                     ("read_back", 9), ("two_hop", int(classes["two_hop"].pool()[0]))]):
        body = json.dumps({**classes[cls].render(root, walker), "server_latency": {"total": "1ms"}})
        recs.append((0, cls, root, float(i), i + 0.5, 200, body.encode()))
    return recs


def test_the_true_reference_is_correct_and_lost_write_is_not(cell):
    *_, world, classes = cell
    true = compare.compare(_records(classes, world.walker), classes)
    assert compare.verdict(true["numbers"])[0] and true["numbers"]["compared"] == 5
    broken = trafficgen.load_module("controls", "lost_write").walker(world)
    lost = compare.compare(_records(classes, broken), classes)
    ok, shown = compare.verdict(lost["numbers"])
    assert not ok and shown["wrong"]["value"] == 2           # both read-backs, and nothing else
    assert [c for c, good in zip(["w", "r", "w", "r", "t"], lost["ok"]) if not good] == ["r", "r"]


@pytest.mark.parametrize("answer, word", [
    ({"code": "Success", "message": "Done", "uids": {}}, "assigned uids for"),
    ({"code": "Success", "message": "Done", "uids": "same"}, "repeat"),
    ({"code": "ErrorInvalidRequest"}, "no success code"),
])
def test_an_ack_without_its_uids_is_wrong(cell, answer, word):
    *_, classes = cell
    kind = classes["add_film"]
    exp = kind.expect(7)
    if answer.get("uids") == "same":
        answer = {**answer, "uids": dict.fromkeys(exp["blanks"], "0x5")}
    assert word in kind.check(answer, exp)
    assert kind.check(kind.render(7, None), exp) is None


# -- each new reader on a made-up window -----------------------------------------------------------


def _obs(classes, **kw):
    recs = [(0, "add_film", 7, 0.0, 0.040, 200, b""), (0, "read_back", 7, 0.04, 0.05, 200, b""),
            (1, "add_film", 9, 0.0, 0.100, 200, b""), (1, "add_film", 11, 0.2, 0.9, 500, b""),
            (2, "two_hop", 1, 0.0, 0.02, 200, b"")]
    base = dict(
        records=recs, answered=[r for r in recs if r[5] == 200],
        counters_before={"dgraph_num_queries_total": {"": 10.0}},
        counters_after={"dgraph_num_queries_total": {"": 15.0}},
        expect=[classes[c].expect(r) for _, c, r, *_ in recs], ok=[True, True, True, False, True],
        tails=[{}, {"extensions": {"ledger": {"edges": 1}}}, {}, {},
               {"extensions": {"ledger": {"edges": 1}}}],
        trace=None, peaks={"hbm_bytes_per_s": 819e9})
    base.update(kw)
    return run.Observed(**base)


def _grow(base, **families):
    before, after = dict(base.counters_before), dict(base.counters_after)
    for fam, grown in families.items():
        before[fam] = dict.fromkeys(grown, 100.0)
        after[fam] = {k: 100.0 + v for k, v in grown.items()}
    base.counters_before, base.counters_after = before, after
    return base


NEW_READERS = ["write_ack_p95_ms", "write_path_ms", "refresh_ms", "layout_rebuild_share",
               "h2d_bytes_per_write", "readwrite_roofline"]


@pytest.mark.parametrize("name", NEW_READERS)
def test_a_new_reader_returns_none_on_a_parent_without_the_family(cell, name):
    *_, classes = cell
    read = trafficgen.load_module("metrics", name).read
    parent = _obs(classes, records=[r for r in _obs(classes).records if r[1] != "add_film"],
                  trace={"busy_s": 0.5, "devices": 1, "window_s": 1.0})
    _grow(parent, dgraph_ledger_hop_edges_total={"chain": 1e6, "host": 0.0},
          dgraph_ledger_stage_us_total={"parse": 5.0, "h2d": 7.0})
    assert read(parent) is None


def test_write_ack_p95_ms_reads_the_acknowledged_writes(cell):
    read = trafficgen.load_module("metrics", "write_ack_p95_ms").read
    assert read(_obs(cell[-1])) == pytest.approx(97.0)       # of 40 and 100 ms; the refused one is out


def test_the_stage_readers_divide_by_what_they_say(cell):
    obs = _grow(_obs(cell[-1]),
                dgraph_ledger_stage_us_total={"write_lock": 30_000.0, "write_apply": 8_000.0,
                                              "write_wal": 2_000.0, "refresh": 50_000.0},
                dgraph_writes_total={"ok": 2.0, "error": 1.0})
    assert trafficgen.load_module("metrics", "write_path_ms").read(obs) == pytest.approx(20.0)
    assert trafficgen.load_module("metrics", "refresh_ms").read(obs) == pytest.approx(10.0)
    none = _grow(_obs(cell[-1]), dgraph_writes_total={"ok": 0.0, "error": 0.0},
                 dgraph_ledger_stage_us_total={"write_lock": 0.0, "write_apply": 0.0, "write_wal": 0.0})
    assert trafficgen.load_module("metrics", "write_path_ms").read(none) is None


def test_the_counter_readers(cell):
    obs = _grow(_obs(cell[-1]), dgraph_arena_layout_updates_total={"delta": 19.0, "rebuild": 1.0},
                dgraph_arena_refresh_h2d_bytes_total={"": 9_000.0},
                dgraph_writes_total={"ok": 2.0, "error": 0.0})
    assert trafficgen.load_module("metrics", "layout_rebuild_share").read(obs) == pytest.approx(5.0)
    assert trafficgen.load_module("metrics", "h2d_bytes_per_write").read(obs) == pytest.approx(4_500.0)
    still = _grow(_obs(cell[-1]), dgraph_arena_layout_updates_total={"delta": 0.0, "rebuild": 0.0})
    assert trafficgen.load_module("metrics", "layout_rebuild_share").read(still) is None


def test_mirror_copy_share_is_one_entry_at_the_end_for_the_two_written_cells(cell):
    bench = cell[0]
    last = bench["per_layer"][-1]
    assert last == {"name": "mirror_copy_share", "unit": "%", "better": "lower",
                    "source": "program_counter", "layer": "arenas", "moves": "query_p50_ms",
                    "workloads": [CELL, "film-q4-paths-rw.searchwrite"]}
    assert [m["name"] for m in bench["per_layer"]].count("mirror_copy_share") == 1
    for c in last["workloads"]:
        assert "query_p50_ms" in [m["name"] for m in run.metrics_of(bench, "end_to_end", c)]


@pytest.mark.parametrize("grown, share", [
    ({"append": 36.0, "grow": 4.0, "copy": 10.0}, 20.0),     # a film: four arenas at their end, the index's tokens
    ({"append": 40.0, "grow": 0.0, "copy": 0.0}, 0.0),
    ({"append": 0.0, "grow": 0.0, "copy": 3.0}, 100.0),
    ({"append": 0.0, "grow": 0.0, "copy": 0.0}, None),       # no delta reached a mirror
    ({"append": 5.0, "copy": 1.0}, None),                    # a label missing: not this program's family
    (None, None),                                            # the parent: no such family
])
def test_mirror_copy_share_is_copy_over_all_three(cell, grown, share):
    read = trafficgen.load_module("metrics", "mirror_copy_share").read
    obs = _obs(cell[-1])
    if grown is not None:
        _grow(obs, dgraph_arena_mirror_updates_total=grown)
    got = read(obs)
    assert got is None if share is None else got == pytest.approx(share)


def test_the_program_seeds_the_three_labels_mirror_copy_share_reads():
    from dgraph_tpu.utils.metrics import ARENA_MIRROR_UPDATES

    assert set(ARENA_MIRROR_UPDATES.snapshot()) == {"append", "grow", "copy"}
    assert ARENA_MIRROR_UPDATES.name == "dgraph_arena_mirror_updates_total"


def test_readwrite_roofline_adds_the_writes_least_bytes(cell):
    classes = cell[-1]
    read = trafficgen.load_module("metrics", "readwrite_roofline").read
    obs = _grow(_obs(classes, trace={"busy_s": 0.004, "devices": 1, "window_s": 1.0}),
                dgraph_ledger_hop_edges_total={"chain": 1e6, "host": 0.0},
                dgraph_writes_total={"ok": 2.0, "error": 0.0})
    w = classes["add_film"].written
    touch = [w.layout_touch(k) for k in (7, 9)]               # film 11 was refused: not counted
    wrote = work_writes.write_bytes(sum(t["rows"] for t in touch), sum(t["chunks"] for t in touch),
                                    sum(t["lut"] for t in touch))
    rows = classes["read_back"].expect(7)["rows"] + classes["two_hop"].expect(1)["rows"]
    assert wrote == 32 * sum(t["rows"] + t["chunks"] for t in touch) + 4 * sum(t["lut"] for t in touch)
    assert read(obs) == pytest.approx(
        100 * ((work.traversal_bytes(1e6, rows) + wrote) / 819e9) / 0.004)
    assert read(_obs(classes)) is None                        # no trace: nothing, never 0


# == film-q4-paths-rw.searchwrite (PR 36): the cell against its files ===============================

import reference_paths_rw  # noqa: E402
import work_path_writes  # noqa: E402
import work_paths  # noqa: E402

SW_CELL = "film-q4-paths-rw.searchwrite"
SW_LISTED = ["~performance.actor", "~starring", "starring", "performance.actor"]
SW_READERS = ["path_layout_rebuild_share", "path_layout_ms", "h2d_bytes_per_path_write",
              "searchwrite_device_share", "searchwrite_roofline"]


@pytest.fixture(scope="module")
def swcell():
    bench, w, config = run.find_cell(SW_CELL)
    mix = trafficgen.load_json("traffic", w["traffic"] + ".json")
    world = run.World(filmgen.generate(20_000, 3))
    classes = trafficgen.load_classes(mix, world)
    return bench, w, config, mix, world, classes


def test_the_searchwrite_cell_is_one_chip_one_configuration_five_readers(swcell):
    bench, w, config, mix, _, _ = swcell
    assert (w["config"], w["traffic"], w["chips"]) == ("film21m-q4-paths-rw", "searchwrite", 1)
    assert [m["name"] for m in run.metrics_of(bench, "end_to_end", SW_CELL)] == \
        ["query_p50_ms", "setup_s"]                # not edges_per_s: its list stays as it was
    assert next(m for m in bench["end_to_end"] if m["name"] == "edges_per_s")["workloads"] == \
        ["film-q4-paths.shortest"]
    mine = [m for m in bench["per_layer"] if m.get("workloads") == [SW_CELL]]
    assert [m["name"] for m in mine] == SW_READERS and bench["per_layer"][-6:-1] == mine
    assert all(m["moves"] == "query_p50_ms" for m in mine)
    assert [m["layer"] for m in mine] == ["arenas", "arenas", "arenas", "planner", "kernels"]
    assert not any(SW_CELL in m.get("workloads", []) for m in bench["per_layer"][:-6])
    base = trafficgen.load_json("configs", "film21m-q4.json")
    for key in ("scale", "upstream_scale", "reduced", "schema", "shapes"):
        assert config[key] == base[key]            # the same graph, cut the same way
    entry = next(c for c in bench["configs"] if c["name"] == "film21m-q4-paths-rw")
    assert entry["reduced"] == ["quads", "actors"] and entry["source"] == config["source"]
    assert len(entry["source"]) <= 200 and len(w["why"]) <= 200
    assert set(config["guarantees"]) >= {"server", "writes", "read_your_write", "atomicity",
                                         "path", "reads", "caches"}
    assert "--sync is off" in config["guarantees"]["writes"]
    assert "~starring" in config["guarantees"]["read_your_write"]


def test_the_mix_is_the_paths_mix_and_a_tenth_of_writes(swcell):
    *_, mix, _, _ = swcell
    paths = trafficgen.load_json("traffic", "paths.json")
    weights = {c["class"]: c["weight"] for c in mix["classes"]}
    assert [c["class"] for c in mix["classes"]] == \
        ["path_to_star", "path_pair", "path_costar", "add_film", "path_back"]
    assert weights["add_film"] == 0.10 and weights["path_back"] == 0
    for c in paths["classes"]:                     # the searches keep their proportions
        assert weights[c["class"]] == pytest.approx(0.9 * c["weight"])
    assert mix["follow"] == {"add_film": "path_back"} and mix["generator"] == "closed_follow"
    assert mix["warm"] == paths["warm"] and mix["clients"] == 8 and mix["deck"] == 16384
    assert mix["needs"] == ["dgraph_path_layout_updates_total", "dgraph_writes_total"]


@pytest.mark.parametrize("seed", [1, 3600000011])
def test_the_deck_writes_every_film_once_and_never_deals_a_path_back(swcell, seed):
    *_, mix, _, classes = swcell
    plan = trafficgen.deal(mix, classes, seed)
    films = [r for c, r in plan if c == "add_film"]
    assert len(films) == len(set(films)) == pytest.approx(0.1 * len(plan), abs=1)
    assert not any(c == "path_back" for c, _ in plan)
    assert list(classes["path_back"].pool()) == list(classes["add_film"].pool())
    by = {c: sum(1 for x, _ in plan[:1100] if x == c) for c in ("path_to_star", "path_pair",
                                                                 "path_costar", "add_film")}
    for cls, share in {"path_to_star": 594, "path_pair": 297, "path_costar": 99, "add_film": 110}.items():
        assert by[cls] == pytest.approx(share, abs=1)


@pytest.mark.parametrize("c", range(1, 9))
def test_a_path_back_is_what_the_issue_says(swcell, c):
    *_, world, classes = swcell
    # a kind of its own whose every film has cast c (the law's own casts are 3 to 8)
    kind = type(classes["path_back"])("path_back", classes["path_back"].spec, world)
    kind.written.cast = np.full(reference_rw.POOL, c)
    k = 40 + c
    text, exp = kind.text(k, "w"), kind.expect(k)
    paths_text = trafficgen.load_json("queries", "path_to_star.json")["text"]
    assert text == paths_text.replace("Actor $FROM", f"Newcomer w-{k}-{c}").replace(
        "Actor $TO", f"Newcomer w-{k}-1" if c > 1 else f"Film w-{k}").replace(
        "hops(func:", "hopsw(func:")
    if c > 1:      # newcomer, its performance, the film, the first performance, the first newcomer
        assert exp["want"] == {"d": 4, "keys": ["performance.actor", "starring", "starring",
                                               "performance.actor"]}
        assert (exp["edges"], exp["rows"], exp["levels"]) == (3 * c + 1, c + 2, [1, 1, 1, c - 1])
    else:
        assert exp["want"] == {"d": 2, "keys": ["performance.actor", "starring"]}
        assert (exp["edges"], exp["rows"], exp["levels"]) == (3, 2, [1, 1])
    assert exp["path_touch"] == {"rows": 1 + 2 * c, "slots": 4 * c}
    true = kind.render(k, None)
    assert kind.check(true, exp) is None
    assert sorted(h["name"] for h in true["hops"]) == sorted(
        [f"Film -{k}", f"Newcomer -{k}-{c}"] + ([f"Newcomer -{k}-1"] if c > 1 else []))
    for wrong, word in [({}, "0 paths"), ({**true, "hops": true["hops"][:-1]}, "second block"),
                        ({**true, "_path_": [true["_path_"][0]["performance.actor"][0]]}, "hops, the"),
                        ({**true, "_path_": true["_path_"] * 2}, "2 paths")]:
        assert word in kind.check(wrong, exp)


def test_no_listed_predicate_leads_out_of_a_written_film_or_into_one(swcell):
    *_, world, classes = swcell
    w = classes["add_film"].written
    assert reference_paths_rw.closed(world.g, w, range(0, reference_rw.POOL, 997), SW_LISTED)

    class Hot(reference_rw.Written):
        def quads(self, k, tag):       # a new role for a generated actor: a walked node
            return super().quads(k, tag) + [f"_:p1 <performance.actor> <0x{self.g.actor_base + 1:x}> ."]

    class Sequel(reference_rw.Written):
        def quads(self, k, tag):       # a generated film gets a written performance
            return super().quads(k, tag) + [f"<0x{int(self.g.film[0]):x}> <starring> _:p1 ."]

    for planted in (Hot, Sequel):
        with pytest.raises(AssertionError, match="ties the film to the walked graph"):
            reference_paths_rw.closed(world.g, planted(world.g), [3], SW_LISTED)
    # the director and the genre are existing uids, under predicates no search walks
    assert any("<director.film>" in q for q in w.quads(3, "x"))
    with pytest.raises(AssertionError):
        reference_paths_rw.closed(world.g, w, [3], SW_LISTED + ["~director.film"])


def _sw_records(classes, walker):
    recs = []
    for i, (cls, root) in enumerate([("add_film", 7), ("path_back", 7), ("add_film", 9),
                                     ("path_back", 9), ("path_costar", 5)]):
        body = json.dumps({**classes[cls].render(root, walker), "server_latency": {"total": "1ms"}})
        recs.append((0, cls, root, float(i), i + 0.5, 200, body.encode()))
    return recs


def test_the_true_reference_is_correct_and_lost_path_write_is_not(swcell):
    *_, world, classes = swcell
    true = compare.compare(_sw_records(classes, None), classes)
    assert compare.verdict(true["numbers"])[0] and true["numbers"]["compared"] == 5
    broken = trafficgen.load_module("controls", "lost_path_write").walker(world)
    lost = compare.compare(_sw_records(classes, broken), classes)
    ok, shown = compare.verdict(lost["numbers"])
    assert not ok and shown["wrong"]["value"] == 2           # both path-backs, and nothing else
    assert [c for c, good in zip(["w", "b", "w", "b", "s"], lost["ok"]) if not good] == ["b", "b"]


def _sw_obs(classes, **kw):
    led = {"extensions": {"ledger": {"edges": 1, "hop_edges": {"path": 1}}}}
    recs = [(0, "add_film", 7, 0.0, 0.040, 200, b""), (0, "path_back", 7, 0.04, 0.05, 200, b""),
            (1, "add_film", 9, 0.0, 0.100, 200, b""), (1, "add_film", 11, 0.2, 0.9, 500, b""),
            (2, "path_costar", 5, 0.0, 0.02, 200, b"")]
    base = dict(
        records=recs, answered=[r for r in recs if r[5] == 200],
        counters_before={"dgraph_num_queries_total": {"": 10.0}},
        counters_after={"dgraph_num_queries_total": {"": 15.0}},
        expect=[classes[c].expect(r) if s == 200 else None for _, c, r, _, _, s, _ in recs],
        ok=[True, True, True, False, True], tails=[{}, led, {}, {}, led],
        trace=None, peaks={"hbm_bytes_per_s": 819e9})
    base.update(kw)
    return run.Observed(**base)


@pytest.mark.parametrize("name", SW_READERS)
def test_a_searchwrite_reader_returns_none_on_a_parent_without_the_family(swcell, name):
    *_, classes = swcell
    read = trafficgen.load_module("metrics", name).read
    parent = _sw_obs(classes, trace={"busy_s": 0.5, "devices": 1, "window_s": 1.0})
    _grow(parent, dgraph_ledger_hop_edges_total={"path": 1e6},
          dgraph_ledger_stage_us_total={"parse": 5.0, "refresh": 7.0},
          dgraph_writes_total={"ok": 2.0, "error": 0.0},
          **({} if name == "searchwrite_device_share"
             else {"dgraph_path_searches_total": {"device": 3.0, "host": 0.0}}))
    assert read(parent) is None


def test_the_searchwrite_counter_and_stage_readers(swcell):
    classes = swcell[-1]
    obs = _grow(_sw_obs(classes), dgraph_path_layout_updates_total={"delta": 19.0, "rebuild": 1.0},
                dgraph_path_layout_h2d_bytes_total={"": 3_584.0},
                dgraph_writes_total={"ok": 2.0, "error": 1.0},
                dgraph_path_searches_total={"device": 9.0, "host": 1.0},
                dgraph_ledger_stage_us_total={"refresh": 50_000.0, "path_layout": 4_000.0})
    read = lambda name: trafficgen.load_module("metrics", name).read(obs)  # noqa: E731
    assert read("path_layout_rebuild_share") == pytest.approx(5.0)
    assert read("h2d_bytes_per_path_write") == pytest.approx(1_792.0)
    assert read("searchwrite_device_share") == pytest.approx(90.0)
    assert read("path_layout_ms") == pytest.approx(0.8)      # 4 ms over the window's five requests
    still = _grow(_sw_obs(classes), dgraph_path_layout_updates_total={"delta": 0.0, "rebuild": 0.0},
                  dgraph_path_layout_h2d_bytes_total={"": 0.0},
                  dgraph_writes_total={"ok": 0.0, "error": 0.0})
    for name in ("path_layout_rebuild_share", "h2d_bytes_per_path_write"):
        assert trafficgen.load_module("metrics", name).read(still) is None


def test_searchwrite_roofline_adds_the_writes_least_bytes(swcell):
    classes = swcell[-1]
    read = trafficgen.load_module("metrics", "searchwrite_roofline").read
    obs = _grow(_sw_obs(classes, trace={"busy_s": 0.004, "devices": 1, "window_s": 1.0}),
                dgraph_path_layout_updates_total={"delta": 2.0, "rebuild": 0.0})
    back, star = classes["path_back"].expect(7), classes["path_costar"].expect(5)
    c = classes["add_film"].written.cast_size(7)
    # film 7's path-back came back: its write counts; film 9's did not, film 11 was refused
    wrote = work_path_writes.write_bytes(1 + 2 * c, 4 * c)
    assert wrote == 8 * (1 + 2 * c) + 8 * 4 * c
    searched = work_paths.path_bytes(back["edges"] + star["edges"], back["rows"] + star["rows"])
    assert read(obs) == pytest.approx(100 * ((searched + wrote) / 819e9) / 0.004)
    assert read(_sw_obs(classes)) is None                     # no trace: nothing, never 0
    alone = work_paths.roofline_share(back["edges"] + star["edges"], back["rows"] + star["rows"],
                                      0.004, 819e9)
    assert read(obs) > alone
