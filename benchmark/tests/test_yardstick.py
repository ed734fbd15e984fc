"""The yardstick's own parts that a four-chip cell leans on (CPU, no chip;
``python -m pytest benchmark/tests/test_yardstick.py -q``; the three cases
that start a whole server through ``run.py`` are marked ``slow``):

- ``harness.route_split`` counts the path search's and the mesh's edges as
  the device's;
- ``tracered.reduce`` on a hand-made trace of four device planes: busy is
  the mean a chip, gaps and operations are a chip's;
- ``traversal_roofline`` holds the bytes against all the traced chips'
  bandwidth: a quarter of the one-plane share for the same bytes and busy time;
- the traced window's rule and the profiler's budget read the cell's chips
  and the mix's own ``trace_s``;
  a start or stop that outlasts the budget ends the run with the reason, a
  long SET-UP does not; and the whole of it through ``run.py`` with the stop
  slowed underneath (``faults/slow_trace_stop.py``);
- the traverse mix through ``run.py`` on four virtual devices, where the
  server meshes by default: correct as it is, with ``route="mesh"`` carrying
  its edges, and NOT correct with the exchange between chips left out
  (``faults/no_exchange.py``) — the fault a four-chip cell can have;
- ``BENCHMARK.json`` is held to its files.
"""

import json
import os
import re
import subprocess
import sys
import threading
import time

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

import harness  # noqa: E402
import run  # noqa: E402
from selfcheck import four_plane_trace  # noqa: E402
import tracered  # noqa: E402
import trafficgen  # noqa: E402
import work  # noqa: E402


# -- readers that see every device route ------------------------------------------------


@pytest.mark.parametrize("by_route, on_device, total", [
    ({"mesh": 5_373_816.0, "path": 0.0, "host": 4_200.0, "cache": 1_000.0}, 5_373_816.0, 5_379_016.0),
    ({"path": 2_900_000.0, "chain": 0.0}, 2_900_000.0, 2_900_000.0),
    ({"chain": 4_907_160.0, "host": 78_002.0, "path": 0.0}, 4_907_160.0, 4_985_162.0),
    ({"resident": 10.0, "csr": 5.0, "merged": 3.0, "mxu": 2.0, "empty": 0.0, "cache": 80.0}, 20.0, 100.0),
    ({"inline": 7.0, "classed": 3.0, "host": 10.0}, 0.0, 20.0),   # labels nothing books since PR 30
    ({}, 0.0, 0.0),
])
def test_route_split_counts_every_device_route(by_route, on_device, total):
    assert harness.route_split(by_route) == (on_device, total)


def test_device_edge_share_reads_a_mesh_and_a_path_window():
    read = trafficgen.load_module("metrics", "device_edge_share").read
    for label in ("mesh", "path"):
        obs = run.Observed(
            counters_before={"dgraph_ledger_hop_edges_total": {label: 100.0, "host": 50.0}},
            counters_after={"dgraph_ledger_hop_edges_total": {label: 1_100.0, "host": 50.0 + 1.0}})
        assert read(obs) == pytest.approx(100.0 * 1_000 / 1_001)
    assert read(run.Observed(counters_before={}, counters_after={})) is None


# -- a trace of four device planes -------------------------------------------------------


def test_reduce_averages_four_planes():
    r = tracered.reduce(four_plane_trace(), window_s=0.020)
    assert r["devices"] == 4
    assert r["busy_s"] == pytest.approx((3 + 4 + 5 + 6) / 4 / 1e3)     # the mean a chip
    ops = dict(r["device_ops"])
    assert ops["exchange"] == pytest.approx(4 * 2 / 4 / 1e3)           # a chip's seconds
    assert ops["expand"] == pytest.approx((1 + 2 + 3 + 4) / 4 / 1e3)
    gaps = dict(r["idle_gaps"])
    # chip n idles [0,n) [n+2,10) under plan and [11+n,20) under encode
    assert gaps["dgraph.plan"] == pytest.approx(sum(n + (8 - n) for n in range(4)) / 4 / 1e3)
    assert gaps["dgraph.encode"] == pytest.approx(sum(9 - n for n in range(4)) / 4 / 1e3)
    assert sum(gaps.values()) + r["busy_s"] == pytest.approx(0.020)    # a chip's window


def test_one_plane_of_the_four_reads_as_before():
    one = {"planes": [p for p in four_plane_trace()["planes"]
                      if p["name"] in ("/device:TPU:0", "/host:CPU")]}
    r = tracered.reduce(one, window_s=0.020)
    assert r["devices"] == 1 and r["busy_s"] == pytest.approx(0.003)


def test_traversal_roofline_takes_the_traces_devices():
    read = trafficgen.load_module("metrics", "traversal_roofline").read
    tail = {"extensions": {"ledger": {"edges": 1}}}

    def obs(devices, label):
        return run.Observed(
            trace={"busy_s": 0.004, "devices": devices, "window_s": 1.0},
            peaks={"hbm_bytes_per_s": 819e9},
            counters_before={"dgraph_ledger_hop_edges_total": {label: 0.0}},
            counters_after={"dgraph_ledger_hop_edges_total": {label: 1e6}},
            expect=[{"rows": 1e5, "edges": 1e6}], tails=[tail])

    one, four = read(obs(1, "chain")), read(obs(4, "mesh"))
    assert one == pytest.approx(100 * ((8e6 + 8e5) / 819e9) / 0.004)
    assert four == pytest.approx(one / 4)           # the same bytes and busy time, four chips' bandwidth
    assert work.roofline_share(1e6, 1e5, 0.004, 819e9) == pytest.approx(one)   # one chip by default
    assert work.roofline_share(1e6, 1e5, 0.0, 819e9, devices=4) is None        # nothing, never 0


# -- the traced window's rule and the profiler's budget ------------------------------------


def test_the_traced_runs_rule_reads_the_cells_chips():
    one, four = run.trace_rule(1, {}), run.trace_rule(4, {})
    assert one["window_s"] == run.TRACE_WINDOW_S == 15.0       # a one-chip cell keeps its window
    assert four["window_s"] * 4 <= one["window_s"] + 1e-9     # no more chip-seconds of events than one chip's
    assert four["budget_s"] >= one["budget_s"] > 64           # the stops measured: 55-60 s (paths), 64 s (four chips)
    # set-up 160 s, window, the WHOLE budget spent, read, compare and exit: inside the driver's 360 s
    assert 160 + four["window_s"] + four["budget_s"] + 10 + 30 < 360


@pytest.mark.parametrize("traffic, chips, window_s", [
    ("paths", 1, 7.5), ("searchwrite", 1, 7.5),         # the mixes that keep the device busy
    ("traverse", 1, 15.0), ("readwrite", 1, 15.0),      # no trace_s: 15 s / chips
    ("traverse", 4, 3.75),
])
def test_the_traced_window_is_the_mixs(traffic, chips, window_s):
    mix = trafficgen.load_json("traffic", traffic + ".json")
    assert run.trace_rule(chips, mix) == {"window_s": window_s, "budget_s": 120.0}


TRAFFIC = sorted(f[:-5] for f in os.listdir(os.path.join(BENCH, "traffic")) if f.endswith(".json"))


@pytest.mark.parametrize("traffic", TRAFFIC)
def test_a_mixs_own_traced_window_is_shorter_than_the_default(traffic):
    """A mix sets ``trace_s`` to keep the profiler's stop inside its budget:
    a window longer than a one-chip cell's default would do the opposite."""
    mix = trafficgen.load_json("traffic", traffic + ".json")
    if "trace_s" in mix:
        assert isinstance(mix["trace_s"], (int, float)) and 0 < mix["trace_s"] < run.TRACE_WINDOW_S


def _ack_after(path, seconds):
    def write():
        time.sleep(seconds)
        with open(path, "w") as f:
            json.dump({"return_ns": 1}, f)
    threading.Thread(target=write, daemon=True).start()


def test_a_stop_past_the_budget_ends_the_run_with_the_seconds_of_each(tmp_path):
    clock = run.TraceClock(0.4)
    _ack_after(str(tmp_path / "started"), 0.1)
    assert clock.wait("start", str(tmp_path / "started")) == {"return_ns": 1}
    with pytest.raises(run.ProfilerOverBudget) as e:
        clock.wait("stop", str(tmp_path / "stopped"))          # never written
    assert re.match(r"the profiler's stop took 0\.[23]\d* s of 0 for start and stop", str(e.value))
    assert set(clock.split) == {"start", "stop"} and "trace_split_s" in str(e.value)
    assert 0.1 <= clock.split["start"] < 0.2 and sum(clock.split.values()) < 0.6


def test_a_long_set_up_is_not_the_profilers(tmp_path):
    clock = run.TraceClock(0.3)
    time.sleep(0.4)                      # set-up: a first run compiles for minutes
    _ack_after(str(tmp_path / "started"), 0.0)
    clock.wait("start", str(tmp_path / "started"))
    time.sleep(0.4)                      # the window
    _ack_after(str(tmp_path / "stopped"), 0.2)
    clock.wait("stop", str(tmp_path / "stopped"))              # neither ate into the budget
    assert 0.2 <= clock.split["stop"] < 0.3 and clock.split["start"] < 0.1


def test_the_profilers_own_error_is_said_not_waited_for(tmp_path):
    with open(tmp_path / "started", "w") as f:
        json.dump({"error": "RuntimeError('no profiler')"}, f)
    with pytest.raises(RuntimeError, match="the profiler in the server child: RuntimeError"):
        run.TraceClock(5.0).wait("start", str(tmp_path / "started"))


@pytest.mark.slow
def test_a_slow_profiler_stop_ends_the_run_by_itself(monkeypatch, capfd):
    """Through ``run.main`` on the CPU, the hook's stop slowed underneath and
    the rule's budget cut to 3 s: the run says why, with the seconds of each
    phase and the set-up's split, returns 1 and prints no result — long
    before the stop would have returned."""
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setattr(run, "trace_rule", lambda chips, mix: {"window_s": 2.0, "budget_s": 3.0})
    t0 = time.monotonic()
    rc = run.main(["--workload", "film-q4.traverse", "--seed", "6", "--seconds", "2",
                   "--trace", "1", "--quads", "60000", "--fault", "slow_trace_stop"])
    out, err = capfd.readouterr()
    assert rc == 1, err[-2000:]
    assert out.strip() == ""
    last = err.strip().splitlines()[-1]
    assert re.match(r"no result: the profiler's stop took [23]\.\d+ s of 3 ", last), last
    assert "trace_split_s" in last and "setup_split_s" in last and '"warm_s"' in last
    assert "Traceback" not in err
    assert time.monotonic() - t0 < 120


# -- the mesh on four virtual devices -------------------------------------------------------------


def _mesh_run(*extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    return subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", "film-q4.traverse",
         "--seed", "2200000321", "--seconds", "3", "--trace", "0", "--quads", "120000", *extra],
        env=env, capture_output=True, text=True, timeout=900)


@pytest.mark.slow
def test_the_mix_on_a_mesh_is_correct_and_its_edges_ride_the_mesh():
    """A rehearsal exits 3 where the comparison says correct, 4 where not."""
    r = _mesh_run()
    assert r.returncode == 3, r.stderr[-2000:]
    assert "compared wrong: 0 " in r.stderr and "'count': 4" in r.stderr
    routes = json.loads(next(ln for ln in r.stderr.splitlines()
                             if ln.startswith('{"workload"')))["route_edges"]
    assert routes["mesh"] > 0.5 * sum(routes.values()) and routes.get("chain", 0.0) == 0.0, routes


@pytest.mark.slow
def test_the_exchange_between_chips_left_out_is_not_correct():
    r = _mesh_run("--fault", "no_exchange")
    assert r.returncode == 4, r.stderr[-2000:]
    assert "compared wrong: 0 " not in r.stderr
    assert r.stdout.strip() == ""


# -- BENCHMARK.json held to its files --------------------------------------------------------

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
with open(os.path.join(harness.CHECKOUT, "BENCHMARK.json")) as _f:
    BENCHMARK = json.load(_f)
CELLS = [w["name"] for w in BENCHMARK["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_a_cell_finds_everything_it_names(cell):
    bench, w, config = run.find_cell(cell)
    entry = next(c for c in bench["configs"] if c["name"] == w["config"])
    assert entry["file"].startswith(tuple(p + "/" for p in bench["paths"]))
    assert config["name"] == entry["name"] and config["source"] == entry["source"]
    assert sorted(entry["reduced"]) == sorted(config["reduced"])
    assert int(config["chips"]) == int(w["chips"]) and w["chips"] in (1, 4)
    assert int(config["scale"]["quads"]) > 0
    mix = trafficgen.load_json("traffic", w["traffic"] + ".json")
    assert os.path.exists(os.path.join(BENCH, "generators", mix["generator"] + ".py"))
    for c in mix["classes"]:
        spec = trafficgen.load_json("queries", c["class"] + ".json")
        assert os.path.exists(os.path.join(BENCH, "query_kinds", spec["kind"] + ".py"))
    e2e = [m["name"] for m in run.metrics_of(bench, "end_to_end", cell)]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert run.metrics_of(bench, "per_layer", cell)
    for group in ("end_to_end", "per_layer"):
        for m in run.metrics_of(bench, group, cell):
            assert callable(trafficgen.load_module("metrics", m["name"]).read)


def test_names_lengths_and_the_share_of_four_chip_cells():
    b = BENCHMARK
    names = [x["name"] for g in ("configs", "workloads", "end_to_end", "per_layer") for x in b[g]]
    names += [w["traffic"] for w in b["workloads"]] + [k for c in b["configs"] for k in c["reduced"]]
    assert all(NAME.match(n) for n in names), [n for n in names if not NAME.match(n)]
    for g in ("configs", "workloads"):
        assert all(1 <= len(x["why"]) <= 200 for x in b[g])
    assert all(1 <= len(c["source"]) <= 200 for c in b["configs"])
    for g in ("configs", "workloads", "end_to_end", "per_layer"):
        assert len({x["name"] for x in b[g]}) == len(b[g])
    pairs = [(w["config"], w["traffic"]) for w in b["workloads"]]
    assert len(set(pairs)) == len(pairs)
    assert {c["name"] for c in b["configs"]} == {w["config"] for w in b["workloads"]}
    four = sum(1 for w in b["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(b["workloads"]) // 2)
    e2e = {m["name"] for m in b["end_to_end"]}
    for m in b["per_layer"]:
        assert m["moves"] in e2e
    for m in b["end_to_end"] + b["per_layer"]:
        assert set(m.get("workloads", [])) <= set(CELLS)
    for m in b["per_layer"]:          # every cell that prints a reader reports the end-to-end metric it moves
        moved = next(x for x in b["end_to_end"] if x["name"] == m["moves"])
        assert set(m.get("workloads", CELLS)) <= set(moved.get("workloads", CELLS)), m["name"]
