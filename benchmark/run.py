#!/usr/bin/env python3
"""One cell of the benchmark, once.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Reads the cell from ``BENCHMARK.json`` and finds everything that belongs to it
BY NAME: ``configs/<config>.json`` (the deployment), ``traffic/<traffic>.json``
(the mix), ``queries/<class>.json`` + ``query_kinds/<kind>.py`` (the classes),
``generators/<generator>.py`` (the loop) and ``metrics/<metric>.py`` (one
reader per metric, end-to-end or per-layer).  Adding a cell edits none of these files.

A run: generate the film graph from ``--seed`` (the parent never imports
JAX), start ONE server child with default settings and a fresh postings
directory, load through the loader CLI, warm every class of the mix under
block aliases of its own, open the window, close it, read the device's
memory peak, stop the server, then compare every answer with the plain numpy
reference and print the result as the last line of standard output.

``--quads N`` overrides the configuration's scale for a rehearsal on the CPU:
such a run goes through every phase and prints NO result line.  ``--control
NAME`` also puts ``controls/NAME.py``'s broken reference in the program's
place for the same requests and reports what the comparison says of it.
"""

from __future__ import annotations

T_START = __import__("time").monotonic()

import argparse  # noqa: E402
import collections  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import compare  # noqa: E402
import filmgen  # noqa: E402
import harness  # noqa: E402
import reference  # noqa: E402
import stats  # noqa: E402
import tracered  # noqa: E402
import trafficgen  # noqa: E402

TRACE_WINDOW_S = 15.0    # a one-chip traced run's window: the trace comes back whole
KILL_GRACE_S = 10.0      # a server whose profiler does not return is not waited for


def trace_rule(chips: int) -> dict:
    """What a traced run does with the profiler, from the cell's ``chips``
    and nothing else (PERF.md section 2 has the measured seconds behind it).

    ``window_s``: the profiler's stop takes about 120 us a device event of
    the trace, and the events are chips x seconds x what the cell's programs
    run a second — so the window shrinks as the chips grow, and a one-chip
    cell keeps its 15 s.  ``budget_s``: the seconds the profiler's start and
    stop may take TOGETHER; the paths cell's 15 s on one chip need 57-60 of
    them, four chips' 3.75 s of the traverse mix 64.  Set-up has no budget
    (a first run in a checkout compiles for minutes), and the reading of the
    file none either: it is 3-7 s of this process's own, timed beside them."""
    return {"window_s": TRACE_WINDOW_S / max(1, int(chips)), "budget_s": 120.0}


class ProfilerOverBudget(RuntimeError):
    pass


class TraceClock:
    """The profiler's phases of a traced run: ``split`` holds the seconds of
    each, and start and stop share ONE budget — a wait that outlasts what
    the earlier one left ends the run with the seconds of each phase; it is
    not left for the driver to kill."""

    def __init__(self, budget_s: float):
        self.budget_s = float(budget_s)
        self.split = {}

    def wait(self, name: str, path: str) -> dict:
        """The server child's acknowledgement at ``path`` of the phase
        ``name`` (``start``, ``stop``)."""
        t0 = time.monotonic()
        try:
            return _wait_for(path, max(0.0, self.budget_s - sum(self.split.values())))
        except TimeoutError:
            self.split[name] = time.monotonic() - t0
            raise ProfilerOverBudget(
                f"the profiler's {name} took {self.split[name]:.1f} s of {self.budget_s:.0f} "
                f"for start and stop together; trace_split_s {json.dumps(self.split)}"
            ) from None
        finally:
            self.split.setdefault(name, time.monotonic() - t0)


def say(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


class World:
    """What the reference knows: the generated graph and its indexes."""

    def __init__(self, g):
        self.g = g
        self.walker = reference.Walker(g)
        self.names = reference.Names(g)
        self.actors_by_cast = reference.actors_by_cast(g)


Ready = collections.namedtuple(
    "Ready", "identity world classes plan gen warm warm_answers")   # what set_up hands on


class Observed:
    """What the per-layer readers may read."""

    def __init__(self, **kw):
        self.__dict__.update(kw)

    def delta(self, family: str) -> dict:
        """{label: growth over the window} of one of the program's counter
        families (``/debug/prometheus_metrics``)."""
        return harness.delta(self.counters_before, self.counters_after, family)


def find_cell(name: str) -> tuple:
    with open(os.path.join(harness.CHECKOUT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next((w for w in bench["workloads"] if w["name"] == name), None)
    if cell is None:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    cfg = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(os.path.join(harness.CHECKOUT, cfg["file"])) as f:
        config = json.load(f)
    return bench, cell, config


def metrics_of(bench: dict, group: str, cell: str) -> list:
    """The cell's metrics of one group: those with no ``workloads`` key, and
    those that list the cell."""
    return [m for m in bench[group] if cell in m.get("workloads", [cell])]


def warm_up(server, mix, classes, plan, gen) -> tuple:
    """Two steps, both under block aliases of their own (the result cache
    keys on the text, so the window's texts stay cold).  First every class
    of the mix, one call at a time, at roots up and down its pool's sizes:
    arenas are built, the programs of every size are compiled or read from
    the cache, and — the calls being sequential — each answer's ledger can
    be held to the reference.  Then the mix's own loop for a few seconds,
    from the END of the sequence, again and again until a round
    spends under a second compiling: the scheduler merges concurrent hops
    into programs of their own, which no single call reaches.  A mix whose
    rounds are still compiling after ``max_rounds`` of them gives no result."""
    w = mix["warm"]
    compiles = lambda: harness.http_json(server.addr, "/debug/device")["compiles"]  # noqa: E731
    steps, answers = [], []

    def step(what, before, t0):
        after = compiles()
        steps.append({"step": what, "seconds": time.monotonic() - t0,
                      "compiles": after["total"] - before["total"],
                      "compile_s": after["seconds_sum"] - before["seconds_sum"]})
        say(f"warm-up {steps[-1]}")

    before, t0 = compiles(), time.monotonic()
    for name, kind in classes.items():
        pool = kind.pool()
        at = {min(len(pool) - 1, r - 1) for r in w["ranks"]}
        at |= {min(len(pool) - 1, int(q * len(pool))) for q in w["quantiles"]}
        for i in sorted(at):
            body = harness.http(server.addr, "/query?ledger=true",
                                kind.text(int(pool[i]), "w"))
            answers.append((name, int(pool[i]), "w", body.encode()))
    step("ladder", before, t0)
    back = plan[::-1]
    for r in range(int(w["max_rounds"])):
        before, t0 = compiles(), time.monotonic()
        gen.drive(server.addr, "/query", back,
                  lambda cls, root, tag=f"c{r}": classes[cls].text(root, tag),
                  float(w["round_s"]), mix)
        step(f"loop {r}", before, t0)
        if steps[-1]["compile_s"] < float(w["quiet_compile_s"]):
            break
    else:
        raise RuntimeError(f"warm-up still compiles after {w['max_rounds']} rounds: {steps}")
    return {"steps": steps, "left_compile_s": steps[-1]["compile_s"]}, answers


def set_up(server, workdir: str, quads: int, seed: int, mix: dict, chips: int,
           rehearsal: bool, split: dict):
    """Boot (the server calibrates while the parent generates), load, warm
    up.  Returns None where the server is not on the chips the cell asks for
    (and this is no rehearsal), else a ``Ready``."""
    t0 = time.monotonic()
    g = filmgen.generate(quads, seed)
    rdf, schema = os.path.join(workdir, "film.rdf.gz"), os.path.join(workdir, "film.schema")
    with open(schema, "w") as f:
        f.write(filmgen.SCHEMA)
    wrote = filmgen.write_rdf_gz(g, rdf)
    split["generate_s"] = time.monotonic() - t0
    server.wait_healthy()
    split["boot_s"] = time.monotonic() - t0
    dev = harness.http_json(server.addr, "/debug/device")
    identity = harness.device_identity(dev)
    say(f"boot {identity} compile cache {dev['compile_cache']}")
    if (identity["platform"] != "tpu" or identity["count"] < chips) and not rehearsal:
        say(f"no result: the cell needs {chips} TPU chip(s), the server has {identity}")
        return None

    t0 = time.monotonic()
    loader = harness.start_loader(workdir, server.addr, rdf, schema)
    world = World(g)            # the reference's indexes, while the loader runs
    classes = trafficgen.load_classes(mix, world)
    plan = trafficgen.deal(mix, classes, seed)
    out, err = loader.communicate()
    if loader.returncode != 0:
        raise RuntimeError(f"loader exited {loader.returncode}: {err[-2000:]}")
    split["load_s"] = time.monotonic() - t0
    store = harness.http_json(server.addr, "/debug/store")
    loaded_edges = sum(p["edges"] for p in store["predicates"].values())
    if f"loaded {g.n_quads()} quads" not in out or wrote["quads"] != g.n_quads() \
            or loaded_edges != 2 * len(g.film) + 2 * len(g.perf):
        raise RuntimeError(f"the store does not hold the generated graph: "
                           f"{out[-300:]!r}, {loaded_edges} edges")

    t0 = time.monotonic()
    gen = trafficgen.load_module("generators", mix["generator"])
    warm, warm_answers = warm_up(server, mix, classes, plan, gen)
    split["warm_s"] = time.monotonic() - t0
    split["warm_compile_s"] = sum(p["compile_s"] for p in warm["steps"])
    return Ready(identity, world, classes, plan, gen, warm, warm_answers)


def measure(server, control_dir: str, clock, ready, seconds, mix) -> dict:
    """The window, with the program's counters read on either side of it and
    the profiler started before and stopped after it in a traced run.

    Nothing may compile inside the measured window.  A window in which the
    backend compiled for more than the mix's ``window_compile_limit_s`` is
    no measurement: it counts as set-up (it was the best warm-up there is —
    the same sequence), and the window is run again under block aliases of
    its own, so the result cache is as cold as before; after
    ``window_retries`` of those the run gives no result.  A traced run's
    window is profiled once and is not run again: ``compile_s_in_window`` is
    one of its metrics.  ``clock`` is a traced run's ``TraceClock``, else None."""
    w = mix["warm"]
    traced = clock is not None
    attempts = 1 if traced else 1 + int(w["window_retries"])
    if traced:
        open(os.path.join(control_dir, "trace.start"), "w").close()
        clock.wait("start", os.path.join(control_dir, "trace.started"))
    for attempt in range(attempts):
        tag = f"r{attempt}" if attempt else ""
        memo = {}

        def texts(cls, root, tag=tag, memo=memo):
            t = memo.get((cls, root))
            if t is None:
                t = memo[(cls, root)] = ready.classes[cls].text(root, tag)
            return t

        before = harness.counters(server.addr)
        setup_s = time.monotonic() - T_START
        win = ready.gen.drive(server.addr, "/query?ledger=true", ready.plan, texts, seconds, mix)
        after = harness.counters(server.addr)
        compile_s = sum(harness.delta(before, after, "dgraph_xla_compile_seconds_sum").values())
        if compile_s <= float(w["window_compile_limit_s"]):
            break
        say(f"window {attempt}: {compile_s:.1f} s of compiling inside it "
            f"(limit {w['window_compile_limit_s']} s)"
            + ("" if traced or attempt + 1 == attempts else ": set-up; the window is run again"))
    trace_ack = None
    if traced:
        open(os.path.join(control_dir, "trace.stop"), "w").close()
        trace_ack = clock.wait("stop", os.path.join(control_dir, "trace.stopped"))
    return {"win": win, "before": before, "after": after, "setup_s": setup_s,
            "tag": tag, "windows": attempt + 1, "window_compile_s": compile_s,
            "trace_ack": trace_ack, "clock": clock,
            "dev": harness.http_json(server.addr, "/debug/device"),
            "planner": harness.http_json(server.addr, "/debug/planner").get("counts")}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--quads", type=int, default=0,
                    help="rehearsal: override the configuration's scale; no result line")
    ap.add_argument("--control", default="",
                    help="also judge controls/<NAME>.py's answers to the same requests")
    ap.add_argument("--fault", default="", help=argparse.SUPPRESS)  # tests only
    args = ap.parse_args(argv)

    bench, cell, config = find_cell(args.workload)
    mix = trafficgen.load_json("traffic", cell["traffic"] + ".json")
    rehearsal = args.quads > 0
    quads = args.quads or int(config["scale"]["quads"])
    chips = int(cell["chips"])
    traced = bool(args.trace)
    rule = trace_rule(chips)
    seconds = min(args.seconds, rule["window_s"]) if traced else args.seconds
    clock = TraceClock(rule["budget_s"]) if traced else None

    workdir = os.path.join(harness.CHECKOUT, ".bench_work", f"run-{os.getpid()}")
    shutil.rmtree(workdir, ignore_errors=True)
    os.makedirs(workdir)
    control_dir = os.path.join(workdir, "control")
    wrapper = None
    if traced or args.fault:
        wrapper = (["--control", control_dir] if traced else []) \
            + (["--fault", args.fault] if args.fault else [])

    server = None
    split = {}
    try:
        server = harness.Server(workdir, wrapper)
        ready = set_up(server, workdir, quads, args.seed, mix, chips, rehearsal, split)
        if ready is None:
            return 1
        why = harness.unfit(server.addr, chips)
        if rehearsal or not why:
            m = measure(server, control_dir, clock, ready, seconds, mix)
            why = harness.unfit(server.addr, chips)   # a failover inside the window
        server.stop()               # the program's state is freed before the reference runs
        server = None
        if not why and not traced \
                and m["window_compile_s"] > float(mix["warm"]["window_compile_limit_s"]):
            why = [f"{m['windows']} windows, and the last still compiled for "
                   f"{m['window_compile_s']:.1f} s"]
        if why and not rehearsal:
            say("no result: " + "; ".join(why))
            return 1
        return report(args, bench, cell, quads, control_dir, ready, split, m)
    except ProfilerOverBudget as e:
        say(f"no result: {e}; setup_split_s {json.dumps(split)}")
        if server is not None:
            server.stop(grace_s=KILL_GRACE_S)
            server = None
        return 1
    except Exception as e:  # noqa: BLE001 — report, clean up, fail with no result line
        say(traceback.format_exc()[-3000:])
        say(f"no result: {type(e).__name__}: {e}"[:4000])
        if server is not None:
            say("server log tail:\n" + server.log_tail())
        return 1
    finally:
        if server is not None:
            server.stop()
        shutil.rmtree(workdir, ignore_errors=True)


def report(args, bench, cell, quads, control_dir, ready, split, m) -> int:
    """After the window and the server's end: reduce the trace, compare every
    answer with the reference, work out the metrics, print the result."""
    identity, world, classes, _, _, warm, warm_answers = ready
    rehearsal, traced = args.quads > 0, bool(args.trace)
    win, before, after = m["win"], m["before"], m["after"]
    records = win["records"]
    window_s = win["t_close"] - win["t_open"]
    reduced = None
    if traced:
        t0 = time.monotonic()
        reduced = tracered.reduce(
            tracered.load_xplane(tracered.find_xplane(os.path.join(control_dir, "trace"))),
            window_s=m["trace_ack"]["traced_s"], rehearsal=rehearsal,
        )
        m["clock"].split["read"] = time.monotonic() - t0
    t0 = time.monotonic()
    cmp_ = compare.compare(records, classes, tag=m["tag"])
    cmp_["numbers"]["unanswered"] += len(win["never_answered"])
    warm_cmp = compare.compare_warm(warm_answers, classes)
    cmp_["numbers"].update(warm_cmp["numbers"])
    cmp_["first_words"] += warm_cmp["first_words"]
    correct, shown = compare.verdict(cmp_["numbers"])
    check_s = time.monotonic() - t0

    answered = [r for r in records if r[5] == 200]
    lat = [r[4] - r[3] for r in answered]
    obs = Observed(
        records=records, answered=answered, latency_s=lat, ok=cmp_["ok"],
        expect=cmp_["expect"], tails=cmp_["tails"], window_s=window_s,
        t_close=win["t_close"], setup_s=m["setup_s"],
        counters_before=before, counters_after=after, trace=reduced,
        peaks=_peaks(identity["kind"], rehearsal), rehearsal=rehearsal,
    )
    # every metric, end-to-end or per-layer, is a reader of its own, found by name
    wanted = metrics_of(bench, "per_layer" if traced else "end_to_end", cell["name"])
    values = {}
    for x in wanted:
        v = trafficgen.load_module("metrics", x["name"]).read(obs)
        if v is not None:
            values[x["name"]] = {"value": float(v), "unit": x["unit"]}

    device = {**identity, "memory_peak_bytes": harness.memory_peak_bytes(m["dev"])}
    if traced:
        device["busy_s"], device["window_s"] = reduced["busy_s"], reduced["window_s"]
    result = {
        "correct": bool(correct),
        "attempted": len(records) + len(win["never_answered"]),
        "failed": cmp_["numbers"]["unanswered"] + cmp_["numbers"]["wrong"],
        "metrics": values,
        "device": device,
    }
    if traced:
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    by_class = {}
    for r in answered:
        by_class.setdefault(r[1], []).append(r[4] - r[3])
    result["run"] = {
        "workload": cell["name"], "seed": args.seed, "window_s": window_s,
        "windows": m["windows"],
        "answered": len(answered), "correct_answers": sum(cmp_["ok"]),
        "latency_ms": {f"p{q}": 1e3 * stats.percentile(lat, q) for q in (50, 90, 95, 99)}
        if lat else {},
        "setup_split_s": split, "warm_up": warm, "check_s": check_s,
        **({"trace_split_s": m["clock"].split} if traced else {}),
        "bytes_answered": sum(len(r[6]) for r in answered),
        "by_class": {k: {"n": len(v), "sum_s": sum(v),
                         **{f"p{q}_ms": 1e3 * stats.percentile(v, q) for q in (50, 90, 99)},
                         "max_ms": 1e3 * max(v)} for k, v in by_class.items()},
        "route_edges": harness.delta(before, after, "dgraph_ledger_hop_edges_total"),
        "compiles_in_window": sum(
            harness.delta(before, after, "dgraph_xla_compiles_total").values()),
        "compile_s_in_window": sum(
            harness.delta(before, after, "dgraph_xla_compile_seconds_sum").values()),
        "planner_counts": m["planner"],
        "first_words": cmp_["first_words"],
    }
    result["compared"] = shown

    if args.control:
        broken = trafficgen.load_module("controls", args.control).walker(world)
        rendered = {}

        def answer_of(cls, root):
            a = rendered.get((cls, root))
            if a is None:
                a = rendered[(cls, root)] = json.dumps(
                    {**classes[cls].render(root, broken), "server_latency": {}}).encode()
            return a

        c = compare.compare(records, classes, answer_of=answer_of)
        c_ok, c_shown = compare.verdict(c["numbers"])
        print(json.dumps({"control": args.control, "correct": bool(c_ok),
                          "compared": c_shown, "first_words": c["first_words"]}),
              flush=True)

    say(json.dumps(result["run"]))
    for name, nv in shown.items():
        say(f"compared {name}: {nv['value']} (limit {nv['limit']})")
    if rehearsal:
        say(f"rehearsal at {quads} quads on {identity}: no result line. "
            f"correct={correct} metrics={json.dumps(values)}")
        return 3 if correct else 4
    print(json.dumps(result), flush=True)
    return 0


def _wait_for(path: str, timeout_s: float) -> dict:
    """The server child's acknowledgement of a profiler step, or
    ``TimeoutError`` where it does not come in ``timeout_s``."""
    t0 = time.monotonic()
    while not os.path.exists(path):
        if time.monotonic() - t0 > timeout_s:
            raise TimeoutError(os.path.basename(path))
        time.sleep(0.02)
    with open(path) as f:
        ack = json.load(f)
    if ack.get("error"):
        raise RuntimeError(f"the profiler in the server child: {ack['error']}")
    return ack


def _peaks(kind: str, rehearsal: bool) -> dict | None:
    table = trafficgen.load_json("peaks.json")
    if kind not in table:
        if rehearsal:
            return None
        raise RuntimeError(f"device kind {kind!r} is not in benchmark/peaks.json")
    return table[kind]


if __name__ == "__main__":
    sys.exit(main())
