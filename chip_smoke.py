#!/usr/bin/env python3
"""The quickest proof that the served path still runs on the chip.

    python chip_smoke.py            one TPU chip: the default served path
    python chip_smoke.py --mesh     four chips: the mesh serving plane only

Generates the upstream's Freebase-film-shaped data set from ``--seed``
(dgraph_tpu/utils/filmgen.py; 21M quads by default), starts
``python -m dgraph_tpu.cli.server`` as ONE child with default settings —
that child alone touches JAX and owns the chip(s) — loads through
``python -m dgraph_tpu.cli.loader``, POSTs the upstream's query shapes to
``/query``, and checks every answer against a plain numpy walk over the same
generated edge arrays (no JAX, no dgraph_tpu.ops, not the engine's host
route).  Then it reads ``/debug/device``, ``/debug/planner`` and
``/debug/prometheus_metrics`` and fails unless the device did the work: TPU
backend, device routes carried edges, no failover to a host route, every
device-guard domain healthy, calibration measured on this backend, arenas
resident in HBM, and the repeated query compiled nothing.

One JSON object per line on stdout; the LAST line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}`` and
is printed only when every phase and every check passed (exit code 0).  With
no accelerator (``JAX_PLATFORMS=cpu``) the script exits non-zero and prints
no such line: at the default size right after the server reports its
backend, at a small ``--quads`` (a CPU rehearsal of the control flow) after
running every phase.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import shutil
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from dgraph_tpu.utils import filmgen  # noqa: E402  (numpy only — no JAX here)

# below this a run is a rehearsal of the control flow: it runs every phase
# on whatever backend the server has and fails at the end if that is no TPU
REHEARSAL_MAX_QUADS = 2_000_000
DEVICE_ROUTES = ("resident", "inline", "csr", "chain", "classed")
HTTP_TIMEOUT_S = 1100.0


def emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


# -- the plain reference -------------------------------------------------------


class Walker:
    """Level-by-level traversal over the generated edge arrays: for a
    frontier of uids, every (src, dst) edge of the predicate whose src (or,
    reversed, dst) is in the frontier."""

    def __init__(self, g: filmgen.FilmGraph):
        self._idx = {}
        for pred, (src, dst) in g.edges().items():
            for key, a, b in ((pred, src, dst), ("~" + pred, dst, src)):
                order = np.argsort(a, kind="stable")
                self._idx[key] = (a[order], b[order])

    def add_edge(self, pred: str, src: int, dst: int) -> None:
        for key, a, b in ((pred, src, dst), ("~" + pred, dst, src)):
            ka, kb = self._idx[key]
            i = int(np.searchsorted(ka, a, side="right"))
            self._idx[key] = (np.insert(ka, i, a), np.insert(kb, i, b))

    def expand(self, pred: str, frontier: np.ndarray):
        """(edges traversed, sorted unique targets) from a uid set."""
        keys, vals = self._idx[pred]
        f = np.unique(np.asarray(frontier, dtype=np.int64))
        lo = np.searchsorted(keys, f, side="left")
        hi = np.searchsorted(keys, f, side="right")
        deg = hi - lo
        n = int(deg.sum())
        if n == 0:
            return 0, np.empty(0, np.int64)
        starts = np.repeat(lo, deg)
        within = np.arange(n) - np.repeat(np.cumsum(deg) - deg, deg)
        return n, np.unique(vals[starts + within])

    def chain(self, root: np.ndarray, preds) -> list:
        """[(edges, uid set)] per level of a straight chain of predicates."""
        out, f = [], root
        for p in preds:
            n, f = self.expand(p, f)
            out.append((n, f))
        return out


# -- children and HTTP -----------------------------------------------------------


def free_port() -> int:
    """A free TCP port whose +1000 twin (the default gRPC listener) is free
    as well."""
    for _ in range(64):
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
        if port + 1000 > 65535:
            continue
        with socket.socket() as s2:
            try:
                s2.bind(("127.0.0.1", port + 1000))
            except OSError:
                continue
        return port
    raise RuntimeError("no free port pair found")


def child_env() -> dict:
    """The parent's environment, with the checkout importable — nothing
    forced: no DGRAPH_TPU_* knob, no JAX_PLATFORMS."""
    env = dict(os.environ)
    env["PYTHONPATH"] = HERE + os.pathsep + env.get("PYTHONPATH", "")
    return env


def http(addr: str, path: str, body: str | None = None, timeout=HTTP_TIMEOUT_S):
    req = urllib.request.Request(
        addr + path, data=body.encode() if body is not None else None
    )
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.read().decode()


def http_json(addr, path, body=None):
    return json.loads(http(addr, path, body))


class Server:
    """The one child that owns the chip: ``python -m dgraph_tpu.cli.server``
    with default settings, run from the work directory."""

    def __init__(self, workdir: str):
        self.port = free_port()
        self.addr = f"http://127.0.0.1:{self.port}"
        self.log_path = os.path.join(workdir, "server.log")
        self._log = open(self.log_path, "wb")
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "dgraph_tpu.cli.server",
             "--p", os.path.join(workdir, "p"), "--port", str(self.port)],
            cwd=workdir, env=child_env(), stdout=self._log, stderr=subprocess.STDOUT,
        )

    def wait_healthy(self, timeout_s: float = 300.0) -> float:
        t0 = time.monotonic()
        while time.monotonic() - t0 < timeout_s:
            if self.proc.poll() is not None:
                raise RuntimeError(
                    f"server exited with code {self.proc.returncode} during "
                    f"boot:\n{self.log_tail()}"
                )
            try:
                if http(self.addr, "/health", timeout=2.0).strip() == "OK":
                    return time.monotonic() - t0
            except (urllib.error.URLError, OSError):
                pass
            time.sleep(0.25)
        raise RuntimeError(f"server not healthy after {timeout_s}s:\n{self.log_tail()}")

    def log_tail(self, n: int = 4000) -> str:
        self._log.flush()
        with open(self.log_path, "rb") as f:
            return f.read()[-n:].decode(errors="replace")

    def stop(self) -> None:
        if self.proc.poll() is None:
            try:
                http(self.addr, "/admin/shutdown", timeout=10.0)
                self.proc.wait(timeout=120)
            except (urllib.error.URLError, OSError, subprocess.TimeoutExpired):
                pass
        if self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait()
        self._log.close()


def run_loader(workdir: str, addr: str, rdf: str, schema: str) -> dict:
    t0 = time.monotonic()
    r = subprocess.run(
        [sys.executable, "-m", "dgraph_tpu.cli.loader", "-r", rdf, "-s", schema,
         "-d", addr, "--batch", "100000"],
        cwd=workdir, env=child_env(), capture_output=True, text=True,
    )
    secs = time.monotonic() - t0
    if r.returncode != 0:
        raise RuntimeError(f"loader exited {r.returncode}: {r.stderr[-2000:]}")
    m = re.search(r"loaded (\d+) quads", r.stdout)
    n = int(m.group(1)) if m else 0
    return {"quads": n, "seconds": round(secs, 2), "quads_per_s": round(n / secs, 1)}


# -- answers -----------------------------------------------------------------------


def level_objects(node_list, path):
    """All objects at the end of ``path`` (a list of JSON keys) below the
    objects of ``node_list`` — one object per traversed edge."""
    cur = node_list
    for key in path:
        nxt = []
        for obj in cur:
            nxt.extend(obj.get(key, ()))
        cur = nxt
    return cur


class Checks:
    def __init__(self):
        self.failed = []

    def that(self, ok: bool, what: str, **detail) -> bool:
        if not ok:
            self.failed.append(what)
            emit({"check": what, "ok": False, **detail})
        return ok


def ask(addr: str, text: str) -> tuple:
    """POST one query; (response dict, seconds, ledger dict)."""
    t0 = time.monotonic()
    out = http_json(addr, "/query?ledger=true", text)
    return out, time.monotonic() - t0, out.get("extensions", {}).get("ledger", {})


def realias(text: str, tag: str) -> str:
    """The same query under different block aliases: executes again (the
    result cache keys on the text) over programs and arenas already warm."""
    return re.sub(r"\b(t|q|me|dir|leaf)\(func:", rf"\1{tag}(func:", text)


def run_queries(addr, g, walker, checks, mesh: bool) -> dict:
    """The upstream's query shapes against the numpy reference, one JSON
    line each.  Returns the query texts by name."""
    a0 = g.actor_base
    roles = np.bincount(g.perf_actor - a0, minlength=g.n_actors)
    hot = 7 if g.n_actors > 7 and roles[7] else int(np.argmax(roles))
    # the wiki's 3-hop seeds a typical (mid-tail) entity: Actor 250000 of
    # 400000 at the upstream's scale — the nearest actor with a role
    mid = int(g.n_actors * 0.625)
    mid += int(np.argmax(roles[mid:] > 0))
    d11 = 11 if len(g.director) > 11 else 0

    def film_uid(name: str) -> int:  # "Film <director>-<ordinal>"
        d, n = name[5:].split("-")
        f0 = int(np.searchsorted(g.film_dir, int(d)))
        return int(g.film[f0 + int(n)])

    def actor_uid(name: str) -> int:
        return a0 + int(name[6:])

    texts = {}

    def record(name, text, first_s, ledger, expect_edges):
        _, warm_s, led2 = ask(addr, realias(text, "w"))
        texts[name] = text
        emit({
            "query": name,
            "first_ms": round(first_s * 1e3, 1),
            "warm_ms": round(warm_s * 1e3, 1),
            "edges": ledger.get("edges"),
            "expect_edges": expect_edges,
            "hops": ledger.get("hops"),
            "hop_edges": ledger.get("hop_edges"),
            "compiles": ledger.get("compiles"),
            # the re-aliased run: same shape, the planner routes it anew
            "warm_hops": led2.get("hops"),
            "warm_compiles": led2.get("compiles"),
        })
        checks.that(
            ledger.get("edges") == expect_edges,
            f"{name}: edges traversed equal the reference",
            got=ledger.get("edges"), want=expect_edges,
        )

    def same_set(name, level, got, want):
        got = np.unique(np.asarray(sorted(got), dtype=np.int64))
        checks.that(
            np.array_equal(got, want),
            f"{name}: level {level} uid set equals the reference",
            got_n=int(len(got)), want_n=int(len(want)),
        )

    if not mesh:
        # point lookup
        text = "{ t(func: uid(0x1)) { name } }"
        out, s, led = ask(addr, text)
        record("point", text, s, led, 0)
        checks.that(out.get("t") == [{"name": "Genre 0"}], "point: value", got=out.get("t"))

    # 2-hop: a head-of-Zipf actor's films
    text = '{ q(func: eq(name, "Actor %d")) { ~performance.actor { ~starring { name } } } }' % hot
    out, s, led = ask(addr, text)
    lv = walker.chain(np.array([a0 + hot]), ["~performance.actor", "~starring"])
    record("two_hop", text, s, led, sum(n for n, _ in lv))
    perfs = level_objects(out.get("q", []), ["~performance.actor"])
    films = level_objects(perfs, ["~starring"])
    checks.that(len(perfs) == lv[0][0], "two_hop: level 1 edge count",
                got=len(perfs), want=lv[0][0])
    checks.that(len(films) == lv[1][0], "two_hop: level 2 edge count",
                got=len(films), want=lv[1][0])
    same_set("two_hop", 2, {film_uid(f["name"]) for f in films}, lv[1][1])

    # the wiki 3-hop co-actor shape, mid-tail seed
    text = """
    { me(func: eq(name, "Actor %d")) {
        ~performance.actor { ~starring {
          name
          starring { performance.actor { name } }
        } }
    } }""" % mid
    out, s, led = ask(addr, text)
    lv = walker.chain(
        np.array([a0 + mid]),
        ["~performance.actor", "~starring", "starring", "performance.actor"],
    )
    record("three_hop_coactor", text, s, led, sum(n for n, _ in lv))
    films = level_objects(out.get("me", []), ["~performance.actor", "~starring"])
    same_set("three_hop_coactor", 2, {film_uid(f["name"]) for f in films}, lv[1][1])
    cast = level_objects(films, ["starring"])
    checks.that(len(cast) == lv[2][0], "three_hop_coactor: level 3 edge count",
                got=len(cast), want=lv[2][0])
    actors = level_objects(cast, ["performance.actor"])
    checks.that(len(actors) == lv[3][0], "three_hop_coactor: level 4 edge count",
                got=len(actors), want=lv[3][0])
    same_set("three_hop_coactor", 4, {actor_uid(a["name"]) for a in actors}, lv[3][1])
    if mesh:
        return texts

    # the wiki 4-level director detail shape
    text = """
    { dir(func: eq(name, "Director %d")) {
        name
        director.film (orderasc: initial_release_date) {
          name
          initial_release_date
          genre { name }
          starring { performance.actor { name } }
        }
    } }""" % d11
    out, s, led = ask(addr, text)
    root = np.array([g.director[d11]])
    n_f, f_set = walker.expand("director.film", root)
    n_g, g_set = walker.expand("genre", f_set)
    n_s, p_set = walker.expand("starring", f_set)
    n_a, a_set = walker.expand("performance.actor", p_set)
    record("four_level_detail", text, s, led, n_f + n_g + n_s + n_a)
    films = level_objects(out.get("dir", []), ["director.film"])
    same_set("four_level_detail", 1, {film_uid(f["name"]) for f in films}, f_set)
    dates = [f["initial_release_date"] for f in films]
    checks.that(dates == sorted(dates), "four_level_detail: films ordered by date")
    want_dates = sorted(
        g.date_str(int(np.searchsorted(g.film, u))) for u in f_set.tolist()
    )
    checks.that(
        [d[:10] for d in dates] == want_dates, "four_level_detail: dates by value",
        got=dates[:3], want=want_dates[:3],
    )
    same_set("four_level_detail", 2,
             {1 + int(x["name"][6:]) for x in level_objects(films, ["genre"])}, g_set)
    actors = level_objects(films, ["starring", "performance.actor"])
    checks.that(len(actors) == n_a, "four_level_detail: level 4 edge count",
                got=len(actors), want=n_a)
    same_set("four_level_detail", 4, {actor_uid(a["name"]) for a in actors}, a_set)

    # head-of-Zipf celebrity fan-out (var block; the leaf set comes back
    # through a uid() block so it can be compared)
    text = """
    { var(func: eq(name, "Actor %d")) {
        ~performance.actor { ~starring { starring { A as performance.actor } } }
      }
      leaf(func: uid(A)) { name }
    }""" % hot
    out, s, led = ask(addr, text)
    lv = walker.chain(
        np.array([a0 + hot]),
        ["~performance.actor", "~starring", "starring", "performance.actor"],
    )
    record("hot_actor", text, s, led, sum(n for n, _ in lv))
    same_set("hot_actor", 4, {actor_uid(a["name"]) for a in out.get("leaf", [])}, lv[3][1])

    # whole-graph fan-out: every director.film edge at level 0
    text = """
    { var(func: has(director.film)) {
        director.film { starring { A as performance.actor } }
      }
      leaf(func: uid(A)) { name }
    }"""
    out, s, led = ask(addr, text)
    lv = walker.chain(g.director, ["director.film", "starring", "performance.actor"])
    record("fanout", text, s, led, sum(n for n, _ in lv))
    same_set("fanout", 3, {actor_uid(a["name"]) for a in out.get("leaf", [])}, lv[2][1])
    return texts


def mutate_and_read_back(addr, g, walker, checks, on_chip: bool) -> dict:
    """One acknowledged write, then reads that must see it — by value, and
    through the device: a whole-predicate expansion pins the predicate's
    arena in HBM before the write, so the write reaches it as an on-device
    delta merge (the resident tier, TPU backend) and the same expansion
    afterwards reads the merged buffers."""
    scan = "{ var(func: has(director.film)%s) { F as director.film } n(func: uid(F)) { count() } }"
    # all directors but the first: the scan after the write then has a
    # frontier no cached hop can answer (or repair) in its place
    _, warm_s, warm_led = ask(addr, scan % ", offset: 1")
    d = int(g.director[0])
    new = int(g.director[-1]) + filmgen.PER_DIR  # a uid past every window
    t0 = time.monotonic()
    http(addr, "/query", 'mutation { set { <0x%x> <director.film> <0x%x> . '
         '<0x%x> <name> "Smoke Film" . } }' % (d, new, new))
    write_s = time.monotonic() - t0
    walker.add_edge("director.film", d, new)
    out, read_s, _ = ask(addr, "{ q(func: uid(0x%x)) { director.film { name } } }" % d)
    names = [f.get("name") for f in level_objects(out.get("q", []), ["director.film"])]
    checks.that("Smoke Film" in names, "mutation: read-after-write by value", got=names[:20])
    n_f, _ = walker.expand("director.film", np.array([d]))
    checks.that(len(names) == n_f, "mutation: read-after-write edge count",
                got=len(names), want=n_f)
    _, big_s, led = ask(addr, scan % "")
    n_all, _ = walker.expand("director.film", g.director)
    checks.that(led.get("edges") == n_all, "mutation: whole-predicate edges after the write",
                got=led.get("edges"), want=n_all)
    epochs = {
        k[len('{how="'):-2]: int(v)
        for k, v in metric_samples(
            http(addr, "/debug/prometheus_metrics"), "dgraph_resident_epochs_total"
        ).items()
    }
    if on_chip:
        checks.that(
            "resident" in (warm_led.get("hops") or {}) and "resident" in (led.get("hops") or {}),
            "mutation: route:resident carried the whole predicate before and after the write",
            before=warm_led.get("hops"), after=led.get("hops"),
        )
        checks.that(epochs.get("merge", 0) >= 1,
                    "mutation: the write reached the resident arena as an on-device merge",
                    epochs=epochs)
    return {
        "phase": "mutation", "prescan_ms": round(warm_s * 1e3, 1),
        "write_ms": round(write_s * 1e3, 1), "read_ms": round(read_s * 1e3, 1),
        "rescan_ms": round(big_s * 1e3, 1), "prescan_hops": warm_led.get("hops"),
        "rescan_hops": led.get("hops"), "resident_epochs": epochs,
    }


# -- telemetry ---------------------------------------------------------------------


def metric_samples(text: str, name: str) -> dict:
    """{label string: value} of one Prometheus family."""
    out = {}
    for line in text.splitlines():
        m = re.match(rf"^{re.escape(name)}(\{{[^}}]*\}})?\s+([0-9.eE+-]+)$", line)
        if m:
            out[m.group(1) or ""] = float(m.group(2))
    return out


def check_device(addr, checks, loaded_edges: int, mesh: bool) -> dict:
    dev = http_json(addr, "/debug/device")
    plan = http_json(addr, "/debug/planner")
    prom = http(addr, "/debug/prometheus_metrics")
    route_edges = {
        k[len('{route="'):-2]: int(v)
        for k, v in metric_samples(prom, "dgraph_ledger_hop_edges_total").items()
    }
    failovers = metric_samples(prom, "dgraph_device_failover_total")
    on_device = sum(route_edges.get(r, 0) for r in DEVICE_ROUTES)
    if mesh:
        checks.that(route_edges.get("mesh", 0) > 0, "route:mesh served edges",
                    route_edges=route_edges)
    else:
        checks.that(on_device > 0, "device routes served edges", route_edges=route_edges)
    checks.that(all(v == 0 for v in failovers.values()), "no device failover",
                failovers=failovers)
    domains = dev["guard"]["domains"]
    for name, st in domains.items():
        checks.that(
            st["state"] == "healthy" and not st["faults"] and not st["wedged_workers"],
            f"guard domain {name} healthy", status=st,
        )
    cal = plan["calibration"]
    checks.that(cal["source"] != "prior", "calibration measured on this backend", cal=cal["source"])
    resident = dev.get("arenas", {}).get("resident_bytes", 0)
    checks.that(
        resident >= 4 * loaded_edges // 8,
        "arenas resident on the device, at the size of the loaded edges",
        resident_bytes=resident, loaded_edges=loaded_edges,
    )
    mem = dev.get("memory", {})
    peak = {k: (v or {}).get("peak_bytes_in_use") for k, v in mem.items()}
    summary = {
        "phase": "device",
        "backend": dev["backend"], "device_kind": dev["device_kind"],
        "devices": dev["devices"], "jax": dev["jax"],
        "route_edges": route_edges,
        "planner_counts": plan.get("counts"),
        "calibration": {"source": cal["source"], "backend": cal["backend"]},
        "compiles": dev["compiles"],
        "compile_cache": dev["compile_cache"],
        "resident_bytes": resident,
        "peak_hbm_bytes": peak,
        "guard": {k: v["state"] for k, v in domains.items()},
    }
    if mesh:
        per = dev.get("mesh", {}).get("sharded_bytes_by_device", {})
        total = sum(per.values())
        summary["mesh"] = {"width": dev.get("mesh", {}).get("width"),
                           "sharded_bytes_by_device": per}
        checks.that(total > 0 and len(per) == dev["devices"],
                    "every chip holds a shard", per_device=per)
        checks.that(
            total > 0 and max(per.values(), default=0) <= total / 3,
            "no chip holds more than a third of the sharded arenas", per_device=per,
        )
        for name, st in domains.items():
            if name.startswith("mesh"):
                checks.that(not st["faults"], f"{name}: no faults", status=st)
    emit(summary)
    return dev


# -- main ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--quads", type=int, default=filmgen.FULL_QUADS,
                    help="data set size in N-Quads (default: the upstream's 21M)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--mesh", action="store_true",
                    help="the four-chip phase only: one server owning every "
                         "chip, the mesh route checked against the reference")
    ap.add_argument("--workdir", default=os.path.join(HERE, "scratch", "chip_smoke"))
    ap.add_argument("--keep", action="store_true", help="keep the work directory")
    args = ap.parse_args(argv)

    workdir = os.path.abspath(args.workdir + ("_mesh" if args.mesh else ""))
    shutil.rmtree(workdir, ignore_errors=True)
    os.makedirs(workdir)
    emit({"phase": "config", "quads": args.quads, "seed": args.seed,
          "mesh": args.mesh, "full_quads": filmgen.FULL_QUADS,
          "cut": round(args.quads / filmgen.FULL_QUADS, 4)})
    checks = Checks()
    rehearsal = args.quads <= REHEARSAL_MAX_QUADS
    server = None
    device = None
    t_all = time.monotonic()
    try:
        # the server first: what it says about its backend decides whether
        # a full-size run is worth generating data for
        server = Server(workdir)
        boot_s = server.wait_healthy()
        dev = http_json(server.addr, "/debug/device")
        device = {"platform": dev["backend"], "kind": dev["device_kind"],
                  "count": dev["devices"]}
        emit({"phase": "boot", "seconds": round(boot_s, 2), **device,
              "compile_cache": dev["compile_cache"]})
        on_chip = checks.that(
            dev["backend"] == "tpu" and bool(dev["device_kind"]),
            "the server runs on a TPU", device=device,
        )
        if args.mesh:
            on_chip &= checks.that(
                dev["devices"] >= 4 or rehearsal, "four chips", device=device
            )
        if not on_chip and not rehearsal:
            return 1

        g = filmgen.generate(args.quads, args.seed)
        rdf = os.path.join(workdir, "film.rdf.gz")
        schema = os.path.join(workdir, "film.schema")
        with open(schema, "w") as f:
            f.write(filmgen.SCHEMA)
        emit({"phase": "generate", **filmgen.write_rdf_gz(g, rdf),
              "directors": len(g.director), "films": len(g.film),
              "performances": len(g.perf), "actors": g.n_actors})
        walker = Walker(g)

        load = run_loader(workdir, server.addr, rdf, schema)
        store = http_json(server.addr, "/debug/store")
        emit({"phase": "load", **load, "scanner": store["nquad_scanner"]})
        checks.that(load["quads"] == g.n_quads(), "every generated quad was loaded",
                    got=load["quads"], want=g.n_quads())
        loaded_edges = sum(p["edges"] for p in store["predicates"].values())
        want_edges = 2 * len(g.film) + 2 * len(g.perf)
        checks.that(loaded_edges == want_edges, "the store holds every generated edge",
                    got=loaded_edges, want=want_edges)

        texts = run_queries(server.addr, g, walker, checks, args.mesh)
        if not args.mesh:
            emit(mutate_and_read_back(server.addr, g, walker, checks, on_chip))

        # one repeat of an executed text: the result cache answers, and
        # nothing compiles (the wiki 3-hop — a small answer the cache
        # admits; run once more first, since the write above outdated it)
        def compiles_and_hits():
            hits = metric_samples(
                http(server.addr, "/debug/prometheus_metrics"),
                "dgraph_qcache_result_events_total",
            ).get('{event="hit"}', 0)
            return http_json(server.addr, "/debug/device")["compiles"]["total"], hits

        ask(server.addr, texts["three_hop_coactor"])
        before, hits0 = compiles_and_hits()
        _, s, _ = ask(server.addr, texts["three_hop_coactor"])
        after, hits1 = compiles_and_hits()
        emit({"phase": "repeat", "ms": round(s * 1e3, 2), "compiles_before": before,
              "compiles_after": after, "result_cache_hits": hits1 - hits0})
        checks.that(after == before, "the repeated query compiled nothing",
                    before=before, after=after)
        checks.that(hits1 > hits0, "the repeated query hit the result cache")

        check_device(server.addr, checks, loaded_edges, args.mesh)
        emit({"phase": "done", "seconds": round(time.monotonic() - t_all, 1),
              "failed_checks": checks.failed})
    except Exception as e:  # noqa: BLE001 — report, clean up, fail
        checks.failed.append(f"{type(e).__name__}: {e}")
        emit({"phase": "error", "error": f"{type(e).__name__}: {e}"[:4000],
              "server_log_tail": server.log_tail() if server else None})
    finally:
        if server is not None:
            server.stop()
        if not args.keep:
            shutil.rmtree(workdir, ignore_errors=True)
    if checks.failed or device is None:
        return 1
    emit({"ok": True, "device": device})
    return 0


if __name__ == "__main__":
    sys.exit(main())
