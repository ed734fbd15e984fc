#!/usr/bin/env python
"""Open-loop SLO harness: latency-vs-offered-load until saturation.

Every serving number before this harness was CLOSED-loop: N client
threads firing as fast as responses return, which self-throttles
exactly when the server slows down — the measured "QPS" is then a
property of the feedback loop, not of the service, and the tail
latency hides coordinated omission.  This harness is OPEN-loop
(Banyan's serving-quality argument, PAPERS.md; the wrk2 discipline):

- arrivals are a **Poisson process at a swept offered rate** — the
  whole schedule is drawn up front from a seeded RNG, so a run is
  reproducible and the server's slowness cannot postpone the next
  arrival;
- every request's latency is measured **from its scheduled arrival
  time**, so a sender that fell behind charges the wait to the server
  (no coordinated omission);
- each offered-rate step reports **per-class p50/p99/p999 and the shed
  rate** (HTTP 429/504 are outcomes, not errors), and the sweep stops
  once the server is saturated;
- the output is one SLO-curve JSON **keyed by backend**, with a
  detected **saturation knee** — the number every future perf PR (mesh
  serving, Pallas tier) is judged against.

Workload: a mixed production shape — point reads, 2-hop traversals and
a mutation interleave — not a single query family.  Two ROADMAP
follow-ups fold in as arms of the same harness:

- **qos**: the PR-11 antagonist/victim A/B re-measured open-loop —
  victim p999 vs the antagonist's offered load, QoS on vs off —
  replacing the closed-loop ratio;
- **ivm**: the PR-12 write-rate sweep re-measured open-loop — achieved
  QPS and p99 at a FIXED offered read load while the write rate sweeps.

Knobs (env, all sized for the 2-core CI host by default):
  SLO_RATES          offered-load sweep, qps CSV (default "25,50,100,200,400")
  SLO_STEP_SECONDS   seconds per step (4)
  SLO_NODES/SLO_DEG  store size (20000 / 16)
  SLO_WORKERS        sender threads = max in-flight (32)
  SLO_MIX            class weights "point=0.45,khop=0.45,mutation=0.1"
  SLO_CACHE          result/hop cache during the main sweep (1)
  SLO_SAT_STOP       stop the sweep past this shed rate (0.5)
  SLO_QOS / SLO_IVM  run the arms (1 / 1)
  SLO_QOS_RATES      antagonist offered-load sweep ("50,200")
  SLO_VICTIM_RATE    victim offered load, qps (10)
  SLO_IVM_RATE       fixed read load for the ivm arm (50)
  SLO_IVM_WRITE_RATES  write-rate sweep, writes/s CSV ("0,10,25")
  SLO_SEG            run the segmented-execution arm (1)
  SLO_SEG_VICTIM_RATE / SLO_SEG_ANTAG_RATE  seg-arm offered loads (10 / 8)
  SLO_SEG_DELAY_MS   injected per-dispatch device time for the seg arm (80)
  SLO_SEED           RNG seed (7)
  SLO_OUT            also write the JSON to this path
  --backend mesh     (or SLO_BACKEND=mesh) force the mesh serving plane
                     in every server arm (DGRAPH_TPU_MESH=force, all
                     predicates shard-eligible); the JSON's backend key
                     becomes "<backend>-mesh"
  SLO_SMOKE          arm the CI smoke assertions (monotone shed rate,
                     well-formed JSON) — see .github/workflows/ci.yml
"""

from __future__ import annotations

import http.client
import json
import os
import sys
import threading
import time

import numpy as np

from bench import _serving_store, device_identity


# ---------------------------------------------------------------- backend

def _backend_arg() -> str:
    """``--backend mesh`` (or SLO_BACKEND=mesh): run every server arm
    with the mesh serving plane forced on (DGRAPH_TPU_MESH=force, every
    predicate shard-eligible), so the SLO curve measures serving over
    the whole mesh — the output JSON is keyed by backend, so mesh and
    unsharded curves from the same host are directly comparable."""
    if "--backend" in sys.argv:
        which = sys.argv[sys.argv.index("--backend") + 1]
    else:
        which = os.environ.get("SLO_BACKEND", "default")
    if which not in ("default", "mesh"):
        raise SystemExit(f"unknown --backend {which!r} (default | mesh)")
    return which


def _backend_env() -> dict:
    """Extra env pinned into every _ServerArm regime for the selected
    backend (empty = the unsharded default)."""
    if _backend_arg() == "mesh":
        return {
            "DGRAPH_TPU_MESH": "force",
            "DGRAPH_TPU_MESH_SHARD_ROWS": "1",
        }
    return {}


# ---------------------------------------------------------------- helpers

def _env_f(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, default))
    except ValueError:
        return default


def _env_rates(name: str, default: str):
    return [
        float(x) for x in os.environ.get(name, default).split(",")
        if x.strip()
    ]


def pctile(lats, q: float) -> float:
    """Latency percentile in ms over a list of seconds (empty → 0)."""
    if not lats:
        return 0.0
    a = np.sort(np.asarray(lats))
    return float(a[min(len(a) - 1, int(q * (len(a) - 1) + 0.5))]) * 1e3


def latency_summary(lats) -> dict:
    return {
        "n": len(lats),
        "p50_ms": round(pctile(lats, 0.50), 2),
        "p99_ms": round(pctile(lats, 0.99), 2),
        "p999_ms": round(pctile(lats, 0.999), 2),
    }


def poisson_schedule(rate_qps: float, secs: float, rng) -> np.ndarray:
    """Arrival offsets (seconds from step start) of a Poisson process at
    ``rate_qps``, truncated to the step window.  Drawn UP FRONT: the
    server can be arbitrarily slow and the offered load does not move."""
    n = int(rate_qps * secs * 2) + 16
    arrivals = np.cumsum(rng.exponential(1.0 / rate_qps, size=n))
    return arrivals[arrivals < secs]


# -------------------------------------------------------------- workload

def build_mix(n_nodes: int, rng) -> list:
    """The mixed workload: each class is (name, weight, query pool,
    tenant).  Pools are pre-drawn so a step's body generation is a list
    index, never RNG work on the send path."""
    weights = {}
    for part in os.environ.get(
        "SLO_MIX", "point=0.45,khop=0.45,mutation=0.1"
    ).split(","):
        k, _, v = part.partition("=")
        weights[k.strip()] = float(v)
    point = [
        "{ q(func: uid(0x%x)) { c: count(e) } }" % u
        for u in np.unique(rng.integers(1, n_nodes + 1, size=64))
    ]
    khop = []
    for _ in range(64):
        seeds = np.unique(rng.integers(1, n_nodes + 1, size=8))
        ul = ", ".join("0x%x" % u for u in seeds)
        khop.append("{ q(func: uid(%s)) { e { c: count(e) } } }" % ul)
    # mutation interleave: edge toggles on a scratch uid range far above
    # the graph (adds followed by deletes on later draws keep the store
    # from growing without bound across a long sweep)
    mutation = []
    for i in range(64):
        u = 0x500000 + (i % 97)
        verb = "set" if i % 2 == 0 else "delete"
        mutation.append(
            "mutation { %s { <0x%x> <e> <0x%x> . } }" % (verb, u, u + 1)
        )
    pools = {"point": point, "khop": khop, "mutation": mutation}
    return [
        {"name": name, "weight": w, "pool": pools[name], "tenant": ""}
        for name, w in weights.items()
        if w > 0 and name in pools
    ]


# -------------------------------------------------------- open-loop step

def open_loop_step(
    port: int, classes: list, secs: float, seed: int,
    workers: int,
) -> dict:
    """Run one offered-load step against a live server.

    ``classes`` carry their OWN rates: [{name, rate, pool, tenant}] —
    the mixed-workload sweep gives each class a share of one swept
    rate, the qos arm pins the victim's rate while the antagonist's
    sweeps.  Senders are a bounded worker pool pulling a pre-drawn
    merged schedule; when all workers are busy a request starts late
    and the delay is charged to its latency (measured from scheduled
    arrival — the whole point of open loop)."""
    rng = np.random.default_rng(seed)
    events = []  # (offset_s, class index, body, tenant)
    for ci, c in enumerate(classes):
        if c["rate"] <= 0:
            continue
        offs = poisson_schedule(c["rate"], secs, rng)
        pool = c["pool"]
        picks = rng.integers(0, len(pool), size=len(offs))
        for off, pi in zip(offs, picks):
            events.append((float(off), ci, pool[int(pi)], c["tenant"]))
    events.sort(key=lambda e: e[0])
    offered = len(events) / secs if secs else 0.0

    lock = threading.Lock()
    pos = [0]
    per_class = [
        {"lats": [], "ok": 0, "shed": 0, "errors": 0} for _ in classes
    ]
    max_lag = [0.0]
    anchor = time.monotonic() + 0.05

    def sender():
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
        try:
            while True:
                with lock:
                    i = pos[0]
                    pos[0] += 1
                if i >= len(events):
                    return
                off, ci, body, tenant = events[i]
                due = anchor + off
                delay = due - time.monotonic()
                if delay > 0:
                    time.sleep(delay)
                else:
                    with lock:
                        max_lag[0] = max(max_lag[0], -delay)
                headers = {"X-Dgraph-Tenant": tenant} if tenant else {}
                status = -1
                for attempt in (0, 1):
                    try:
                        conn.request(
                            "POST", "/query", body=body.encode(),
                            headers=headers,
                        )
                        r = conn.getresponse()
                        r.read()
                        status = r.status
                        break
                    except OSError:
                        # a keep-alive connection the server closed
                        # between requests raises here — one retry on a
                        # fresh connection absorbs the benign race; a
                        # second failure is a real error (the retry's
                        # extra wait charges this request's latency,
                        # which is the honest accounting)
                        conn.close()
                        conn = http.client.HTTPConnection(
                            "127.0.0.1", port, timeout=60
                        )
                lat = time.monotonic() - due
                rec = per_class[ci]
                with lock:
                    if status == 200:
                        rec["ok"] += 1
                        rec["lats"].append(lat)
                    elif status in (429, 503, 504):
                        # shed IS the mechanism under measurement: the
                        # latency of a shed request is meaningless, the
                        # RATE of shedding is the signal
                        rec["shed"] += 1
                    else:
                        rec["errors"] += 1
        finally:
            conn.close()

    threads = [
        threading.Thread(target=sender, daemon=True, name=f"slo-{i}")
        for i in range(workers)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=secs * 4 + 120)
    # achieved rate over the SCHEDULED window, not sender wall time: a
    # schedule whose last arrival lands early must not inflate the rate
    wall = max(secs, time.monotonic() - anchor - 0.05)

    total_ok = sum(c["ok"] for c in per_class)
    total_shed = sum(c["shed"] for c in per_class)
    total_err = sum(c["errors"] for c in per_class)
    sent = total_ok + total_shed + total_err
    out_classes = {}
    for c, rec in zip(classes, per_class):
        out_classes[c["name"]] = {
            **latency_summary(rec["lats"]),
            "ok": rec["ok"],
            "shed": rec["shed"],
            "errors": rec["errors"],
            "offered_qps": round(c["rate"], 2),
        }
    return {
        "offered_qps": round(offered, 2),
        "achieved_qps": round(total_ok / wall, 2) if wall else 0.0,
        "sent": sent,
        "shed_rate": round(total_shed / max(sent, 1), 4),
        "error_rate": round(total_err / max(sent, 1), 4),
        "max_start_lag_ms": round(max_lag[0] * 1e3, 1),
        "classes": out_classes,
    }


def detect_knee(steps: list) -> dict | None:
    """The saturation knee: the first step where the server visibly
    stopped keeping up — sheds past 1%, or completions under 90% of the
    offered rate.  None = the sweep never saturated (offer more)."""
    for s in steps:
        if s["shed_rate"] > 0.01:
            return {
                "offered_qps": s["offered_qps"],
                "reason": "shed_rate",
                "shed_rate": s["shed_rate"],
            }
        if s["achieved_qps"] < 0.9 * s["offered_qps"]:
            return {
                "offered_qps": s["offered_qps"],
                "reason": "achieved_below_offered",
                "achieved_qps": s["achieved_qps"],
            }
    return None


# ------------------------------------------------------------- server arm

class _ServerArm:
    """Boot a DgraphServer under a pinned env regime, restore on exit —
    the bench.py save/restore contract, as a context manager."""

    def __init__(self, store, env: dict):
        self._store = store
        self._env = env
        self._saved = {}
        self.srv = None

    def __enter__(self):
        for k, v in self._env.items():
            self._saved[k] = os.environ.get(k)
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        try:
            from dgraph_tpu.serve.server import DgraphServer

            self.srv = DgraphServer(self._store)
            self.srv.start()
        except BaseException:
            # a failed boot skips __exit__ (context-manager protocol):
            # restore HERE or this arm's regime leaks into later arms,
            # which run_slo_bench's arm isolation would then measure
            self._restore()
            raise
        return self.srv

    def __exit__(self, et, ev, tb):
        try:
            self.srv.stop()
        finally:
            self._restore()

    def _restore(self):
        for k, v in self._saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def _warmup(port: int, classes: list, n: int = 8) -> None:
    """Untimed compile/cache warmup: one pass over every pool so the
    first measured step never pays XLA compilation.  ``n`` widens the
    pass for arms whose assertions cannot tolerate a single mid-step
    compile (the devfault watchdog)."""
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
    try:
        for c in classes:
            for body in c["pool"][:n]:
                conn.request("POST", "/query", body=body.encode())
                conn.getresponse().read()
    finally:
        conn.close()


# ------------------------------------------------------------------ arms

def run_sweep(store, mix_weights: list, rates, secs, workers, seed) -> dict:
    """The main arm: the mixed workload swept over offered rates on the
    production configuration (scheduler + caches + QoS armed)."""
    sat_stop = _env_f("SLO_SAT_STOP", 0.5)
    steps = []
    with _ServerArm(store, {
        "DGRAPH_TPU_SCHED": "1",
        "DGRAPH_TPU_CACHE": os.environ.get("SLO_CACHE", "1"),
        **_backend_env(),
    }) as srv:
        classes = [
            {**c, "rate": 0.0} for c in mix_weights
        ]
        _warmup(srv.port, classes)
        wsum = sum(c["weight"] for c in classes)
        for step_i, rate in enumerate(rates):
            for c in classes:
                c["rate"] = rate * c["weight"] / wsum
            step = open_loop_step(
                srv.port, classes, secs, seed + step_i, workers
            )
            steps.append(step)
            print(
                f"# slo step: offered={step['offered_qps']} "
                f"achieved={step['achieved_qps']} "
                f"shed={step['shed_rate']}",
                file=sys.stderr,
            )
            if step["shed_rate"] > sat_stop:
                # saturated: further steps only melt the host without
                # adding curve — record that we stopped, not silence
                print(
                    f"# slo sweep stopped at {rate} qps "
                    f"(shed {step['shed_rate']} > {sat_stop})",
                    file=sys.stderr,
                )
                break
    return {"steps": steps, "saturation_knee": detect_knee(steps)}


def run_qos_arm(store, rates, secs, workers, seed) -> dict:
    """Victim p999 vs antagonist offered load, QoS on vs off — the
    PR-11 A/B with the closed-loop ratio replaced by a curve."""
    victim_rate = _env_f("SLO_VICTIM_RATE", 10.0)
    rng = np.random.default_rng(seed + 1000)
    n_nodes = int(_env_f("SLO_NODES", 20_000))
    victim_pool = [
        "{ q(func: uid(0x%x)) { c: count(e) } }" % u
        for u in np.unique(rng.integers(1, n_nodes + 1, size=64))
    ]
    antag_pool = []
    for _ in range(64):
        seeds = np.unique(rng.integers(1, n_nodes + 1, size=64))
        ul = ", ".join("0x%x" % u for u in seeds)
        antag_pool.append(
            "{ q(func: uid(%s)) { e { e { c: count(e) } } } }" % ul
        )
    tenants = json.dumps({
        "victim": {"weight": 8, "priority": "high"},
        "antagonist": {
            "weight": 1, "max_queued": 8, "max_inflight": 1,
            "priority": "low",
        },
    })
    out = {"victim_offered_qps": victim_rate, "tenants": json.loads(tenants)}
    for mode, qos in (("qos_on", "1"), ("qos_off", "0")):
        steps = []
        with _ServerArm(store, {
            "DGRAPH_TPU_SCHED": "1",
            "DGRAPH_TPU_CACHE": "0",  # a cached antagonist stresses nothing
            "DGRAPH_TPU_QOS": qos,
            "DGRAPH_TPU_QOS_TENANTS": tenants,
            **_backend_env(),
        }) as srv:
            classes = [
                {"name": "victim", "rate": victim_rate,
                 "pool": victim_pool, "tenant": "victim"},
                {"name": "antagonist", "rate": 0.0,
                 "pool": antag_pool, "tenant": "antagonist"},
            ]
            _warmup(srv.port, classes)
            for step_i, rate in enumerate(rates):
                classes[1]["rate"] = rate
                step = open_loop_step(
                    srv.port, classes, secs, seed + 2000 + step_i, workers
                )
                v = step["classes"]["victim"]
                a = step["classes"]["antagonist"]
                steps.append({
                    "antagonist_offered_qps": rate,
                    "victim_p50_ms": v["p50_ms"],
                    "victim_p99_ms": v["p99_ms"],
                    "victim_p999_ms": v["p999_ms"],
                    "victim_ok": v["ok"],
                    "antagonist_ok": a["ok"],
                    "antagonist_shed": a["shed"],
                })
                print(
                    f"# slo qos[{mode}] antag={rate} "
                    f"victim_p999={v['p999_ms']}ms "
                    f"antag_shed={a['shed']}",
                    file=sys.stderr,
                )
        out[mode] = steps
    return out


def run_ivm_arm(store, secs, workers, seed) -> dict:
    """Achieved QPS + p99 at a FIXED offered read load while the write
    rate sweeps — the PR-12 write-rate sweep, open-loop."""
    read_rate = _env_f("SLO_IVM_RATE", 50.0)
    write_rates = _env_rates("SLO_IVM_WRITE_RATES", "0,10,25")
    rng = np.random.default_rng(seed + 3000)
    n_nodes = int(_env_f("SLO_NODES", 20_000))
    read_pool = []
    for _ in range(64):
        seeds = np.unique(rng.integers(1, n_nodes + 1, size=8))
        ul = ", ".join("0x%x" % u for u in seeds)
        read_pool.append("{ q(func: uid(%s)) { e { c: count(e) } } }" % ul)
    steps = []
    with _ServerArm(store, {
        "DGRAPH_TPU_SCHED": "1",
        "DGRAPH_TPU_CACHE": "1",
        "DGRAPH_TPU_IVM": "1",
        **_backend_env(),
    }) as srv:
        classes = [{
            "name": "read", "rate": read_rate, "pool": read_pool,
            "tenant": "",
        }]
        _warmup(srv.port, classes)
        for step_i, wr in enumerate(write_rates):
            stop = threading.Event()

            def writer(rate=wr):
                if rate <= 0:
                    return
                conn = http.client.HTTPConnection(
                    "127.0.0.1", srv.port, timeout=60
                )
                i = 0
                try:
                    while not stop.is_set():
                        u = 0x70000 + (i % 97)
                        i += 1
                        for verb in ("set", "delete"):
                            conn.request(
                                "POST", "/query",
                                body=(
                                    "mutation { %s { <0x%x> <e> <0x%x> . } }"
                                    % (verb, u, u + 1)
                                ).encode(),
                            )
                            conn.getresponse().read()
                        if stop.wait(1.0 / rate):
                            return
                except OSError:
                    pass
                finally:
                    conn.close()

            wt = threading.Thread(target=writer, daemon=True)
            wt.start()
            try:
                step = open_loop_step(
                    srv.port, classes, secs, seed + 4000 + step_i, workers
                )
            finally:
                stop.set()
                wt.join(timeout=30)
            r = step["classes"]["read"]
            steps.append({
                "write_rate": wr,
                "achieved_qps": step["achieved_qps"],
                "p50_ms": r["p50_ms"],
                "p99_ms": r["p99_ms"],
                "p999_ms": r["p999_ms"],
                "shed_rate": step["shed_rate"],
            })
            print(
                f"# slo ivm write_rate={wr} "
                f"qps={step['achieved_qps']} p99={r['p99_ms']}ms",
                file=sys.stderr,
            )
    return {"read_offered_qps": read_rate, "steps": steps}


def run_devfault_arm(store, rates, secs, workers, seed) -> dict:
    """p999 vs offered load with a MID-SWEEP wedged-dispatch injection,
    devguard on vs off — the PR-15 device-fault A/B.  The bench shares
    the server's process, so the failpoint arms in-process: halfway
    through the middle step, ``device.hop`` starts hanging for
    ``SLO_DEVFAULT_WEDGE_MS`` (default 1500) up to ``SLO_DEVFAULT_HANGS``
    times.  With the guard on the watchdog (``SLO_DEVFAULT_HANG_MS``,
    default 100) bounds each wedge and hot-fails the hop to host —
    byte-identical answers, p999 stays near the deadline; with the
    guard off every wedge rides the serving path in full."""
    from dgraph_tpu.utils import devguard
    from dgraph_tpu.utils.failpoints import fail
    from dgraph_tpu.utils.metrics import DEVICE_FAILOVER

    wedge_ms = _env_f("SLO_DEVFAULT_WEDGE_MS", 1500.0)
    hangs = int(_env_f("SLO_DEVFAULT_HANGS", 2))
    rng = np.random.default_rng(seed + 5000)
    n_nodes = int(_env_f("SLO_NODES", 20_000))
    pool = []
    for _ in range(64):
        seeds = np.unique(rng.integers(1, n_nodes + 1, size=16))
        ul = ", ".join("0x%x" % u for u in seeds)
        pool.append("{ q(func: uid(%s)) { e { e { c: count(e) } } } }" % ul)
    inject_step = len(rates) // 2
    # under --backend mesh every eligible hop dispatches through the
    # mesh plane, so the wedge must land on ITS seam (the PR 17
    # chip-loss site) — device.hop would never fire, and the arm's
    # guarded failover is then mesh → unsharded instead of device → host
    mesh_arm = _backend_arg() == "mesh"
    site = "device.mesh" if mesh_arm else "device.hop"
    domain = "mesh" if mesh_arm else "device"
    out = {"wedge_ms": wedge_ms, "hangs": hangs, "site": site}
    fp_seed = int(os.environ.get("DGRAPH_TPU_FAILPOINT_SEED", "0"))
    for mode, guard in (("devguard_on", "1"), ("devguard_off", "0")):
        fail.reset(fp_seed)
        steps = []
        with _ServerArm(store, {
            "DGRAPH_TPU_SCHED": "1",
            # cached hops dodge the dispatch seam entirely — the arm
            # must measure the seam, not the cache
            "DGRAPH_TPU_CACHE": "0",
            "DGRAPH_TPU_DEVGUARD": guard,
            "DGRAPH_TPU_DEVICE_COOLDOWN_S": "0.2",
            # pin every hop onto the device dispatch seam (env override
            # = static gate; the planner yields the decision)
            "DGRAPH_TPU_EXPAND_DEVICE_MIN": "1",
            **_backend_env(),
        }) as srv:
            # guards read their env at construction: fresh ones per arm
            devguard.reset_for_tests()
            classes = [
                {"name": "khop", "rate": 0.0, "pool": pool, "tenant": ""}
            ]
            # warm under the DEFAULT (compile-tolerant) deadline, then
            # tighten the live watchdog: a cold XLA compile is slow,
            # not wedged — tightening first would latch the guard sick
            # on warmup compiles and pollute the non-injected steps
            _warmup(srv.port, classes, n=len(pool))
            if mesh_arm and guard == "1":
                # warm the UNSHARDED fallback programs too: the injected
                # step's re-planned hops must not pay first-time XLA
                # compiles (a cold compile is slow, not wedged — it
                # would smear p999 past the wedge bound the smoke
                # asserts).  Arm the chip-loss site for the whole pass
                # so every hop takes the degrade path once, then reset
                fail.arm(site, "error(n=1000000)")
                _warmup(srv.port, classes, n=len(pool))
                fail.reset(fp_seed)
                devguard.reset_for_tests()
            devguard.get(domain).hang_ms = _env_f(
                "SLO_DEVFAULT_HANG_MS", 100.0
            )
            for step_i, rate in enumerate(rates):
                classes[0]["rate"] = rate
                injected = step_i == inject_step
                timer = None
                if injected:
                    timer = threading.Timer(
                        secs / 2.0,
                        lambda: fail.arm(
                            site,
                            f"hang(ms={wedge_ms:g},n={hangs})",
                        ),
                    )
                    timer.start()
                fo0 = sum(DEVICE_FAILOVER.snapshot().values())
                try:
                    step = open_loop_step(
                        srv.port, classes, secs, seed + 6000 + step_i,
                        workers,
                    )
                finally:
                    if timer is not None:
                        timer.cancel()
                k = step["classes"]["khop"]
                steps.append({
                    "offered_qps": step["offered_qps"],
                    "achieved_qps": step["achieved_qps"],
                    "p50_ms": k["p50_ms"],
                    "p99_ms": k["p99_ms"],
                    "p999_ms": k["p999_ms"],
                    "shed_rate": step["shed_rate"],
                    "error_rate": step["error_rate"],
                    "injected": injected,
                    "failovers": (
                        sum(DEVICE_FAILOVER.snapshot().values()) - fo0
                    ),
                    "device_state": devguard.get(domain).state,
                })
                print(
                    f"# slo devfault[{mode}] offered={rate} "
                    f"p999={k['p999_ms']}ms"
                    + (" (wedge injected)" if injected else ""),
                    file=sys.stderr,
                )
            # the n-cap is spent by sweep end: the half-open probe must
            # re-admit the device (guard-off has no state to heal)
            healed = guard == "0"
            deadline = time.monotonic() + 15.0
            while not healed and time.monotonic() < deadline:
                healed = devguard.get(domain).state == "healthy"
                if not healed:
                    time.sleep(0.1)
        fail.reset(fp_seed)
        out[mode] = {"steps": steps, "readmitted": healed}
    devguard.reset_for_tests()
    return out


def run_meshchaos_arm(store, rates, secs, workers, seed) -> dict:
    """Open-loop p50/p99/p999 + shed rate across ONE injected chip-loss
    → staged-rejoin cycle on the elastic mesh fault domain (PR 20).

    Mesh backend only: halfway through the middle offered-load step the
    ``device.mesh`` failpoint kills chip ``SLO_MESHCHAOS_CHIP`` (seeded
    by DGRAPH_TPU_FAILPOINT_SEED, so the cycle is reproducible); the
    domain re-shards onto the surviving sub-mesh in-band, the short
    ``SLO_MESHCHAOS_COOLDOWN_S`` probe re-admits the chip, and the
    warm-then-cutover rejoin restores the full-mesh epoch — all while
    the open-loop schedule keeps firing.  The steps record the latency
    and shed cost of the whole cycle; the cycle record proves it
    actually closed (loss + rejoin reshards, full width restored, zero
    surfaced errors)."""
    from dgraph_tpu.utils import devguard
    from dgraph_tpu.utils.failpoints import fail
    from dgraph_tpu.utils.metrics import MESH_RESHARD, QUERY_RESUMED

    if _backend_arg() != "mesh":
        return {"skipped": "meshchaos arm runs under --backend mesh only"}
    import jax

    if len(jax.devices()) < 2:
        return {"skipped": "meshchaos arm needs a multi-chip mesh"}
    chip = int(_env_f("SLO_MESHCHAOS_CHIP", 1))
    cooldown = _env_f("SLO_MESHCHAOS_COOLDOWN_S", 1.0)
    rng = np.random.default_rng(seed + 9000)
    n_nodes = int(_env_f("SLO_NODES", 20_000))
    pool = []
    # pool size is tunable: each distinct query is a compile candidate
    # and the arm warms the pool at BOTH mesh widths — CPU-mesh smoke
    # runs want a handful, a TPU bench round wants the full spread
    for _ in range(int(_env_f("SLO_MESHCHAOS_POOL", 64))):
        seeds = np.unique(rng.integers(1, n_nodes + 1, size=16))
        ul = ", ".join("0x%x" % u for u in seeds)
        pool.append("{ q(func: uid(%s)) { e { e { c: count(e) } } } }" % ul)
    inject_step = len(rates) // 2
    fp_seed = int(os.environ.get("DGRAPH_TPU_FAILPOINT_SEED", "0"))
    fail.reset(fp_seed)
    out = {"chip": chip, "cooldown_s": cooldown}
    with _ServerArm(store, {
        "DGRAPH_TPU_SCHED": "1",
        "DGRAPH_TPU_CACHE": "0",
        "DGRAPH_TPU_DEVGUARD": "1",
        "DGRAPH_TPU_DEVICE_COOLDOWN_S": f"{cooldown:g}",
        "DGRAPH_TPU_EXPAND_DEVICE_MIN": "1",
        **_backend_env(),
    }) as srv:
        devguard.reset_for_tests()
        dom = getattr(srv.engine.arenas, "mesh_fault", None)
        if dom is None:
            return {
                "skipped": "mesh fault domain off "
                "(DGRAPH_TPU_MESH_ELASTIC=0 or single-chip mesh)"
            }
        total = len(dom.devices)
        classes = [
            {"name": "khop", "rate": 0.0, "pool": pool, "tenant": ""}
        ]
        # warm BOTH widths and the rejoin path before measuring: full
        # mesh first, then a throwaway loss→rejoin cycle so the
        # injected step never pays first-time sub-mesh XLA compiles
        # (a cold compile is slow, not lost capacity)
        _warmup(srv.port, classes, n=len(pool))
        fail.arm("device.mesh", f"error(n=1,chip={chip})")
        _warmup(srv.port, classes, n=len(pool))
        deadline = time.monotonic() + 30.0
        while dom.width < total and time.monotonic() < deadline:
            time.sleep(0.1)
        if dom.width < total:
            return {
                "skipped": "warmup loss→rejoin cycle never converged: "
                + json.dumps(dom.status())
            }
        fail.reset(fp_seed)
        rs0 = dict(MESH_RESHARD.snapshot())
        qr0 = dict(QUERY_RESUMED.snapshot())
        epoch0 = dom.epoch
        steps = []
        for step_i, rate in enumerate(rates):
            classes[0]["rate"] = rate
            injected = step_i == inject_step
            timer = None
            if injected:
                timer = threading.Timer(
                    secs / 2.0,
                    lambda: fail.arm(
                        "device.mesh", f"error(n=1,chip={chip})"
                    ),
                )
                timer.start()
            try:
                step = open_loop_step(
                    srv.port, classes, secs, seed + 9100 + step_i,
                    workers,
                )
            finally:
                if timer is not None:
                    timer.cancel()
            k = step["classes"]["khop"]
            steps.append({
                "offered_qps": step["offered_qps"],
                "achieved_qps": step["achieved_qps"],
                "p50_ms": k["p50_ms"],
                "p99_ms": k["p99_ms"],
                "p999_ms": k["p999_ms"],
                "shed_rate": step["shed_rate"],
                "error_rate": step["error_rate"],
                "injected": injected,
                "epoch": dom.epoch,
                "chips_healthy": dom.width,
            })
            print(
                f"# slo meshchaos offered={rate} p999={k['p999_ms']}ms "
                f"width={dom.width}/{total}"
                + (" (chip loss injected)" if injected else ""),
                file=sys.stderr,
            )
        # the cycle must CLOSE: bounded poll for the staged rejoin
        deadline = time.monotonic() + 30.0
        while dom.width < total and time.monotonic() < deadline:
            time.sleep(0.1)
        rs = {
            k: v - rs0.get(k, 0)
            for k, v in MESH_RESHARD.snapshot().items()
        }
        qr = {
            k: v - qr0.get(k, 0)
            for k, v in QUERY_RESUMED.snapshot().items()
        }
        out.update({
            "steps": steps,
            "cycle": {
                "restored": dom.width == total,
                "chips_total": total,
                "epoch_before": epoch0,
                "epoch_after": dom.epoch,
                "reshards": rs,
                "resumed": qr,
            },
        })
    fail.reset(fp_seed)
    devguard.reset_for_tests()
    return out


# every device dispatch seam the mega-query may route through: the
# planner picks chain vs mask-chain vs multi-hop per store shape, and
# the arm must price the dispatch wherever it lands
_SEG_SITES = ("device.chain", "device.spgemm", "device.multi_hop")


def _seg_cancel_probe(port: int, body: str, tid_int: int) -> dict:
    """Fire one mega-query with a sampled traceparent, /admin/cancel it
    the moment the registry has the token (the query is live), and
    report the wall time from cancel-ack to response completion — the
    observed cancellation latency.  Segmented, the token check at the
    next seam bounds it to ~one segment (499); monolithic, the program
    runs to completion first (200)."""
    from dgraph_tpu.utils.failpoints import fail

    tp = "00-%032x-%016x-01" % (tid_int, tid_int)
    res: dict = {}
    base_hits = sum(fail.hits(s) for s in _SEG_SITES)

    def runner():
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=120)
        try:
            conn.request(
                "POST", "/query", body=body.encode(),
                headers={"Traceparent": tp, "X-Dgraph-Tenant": "antagonist"},
            )
            r = conn.getresponse()
            r.read()
            res["status"] = r.status
        except OSError:
            res["status"] = -1
        finally:
            res["done_at"] = time.monotonic()
            conn.close()

    t = threading.Thread(target=runner, daemon=True)
    t.start()
    # cancelling a QUEUED query measures the pre-run fast path, not the
    # mid-chain latency under test: hold the cancel until the query's
    # first device dispatch fires (the probe runs alone, so the hit
    # delta is attributable)
    deadline = time.monotonic() + 30.0
    while (time.monotonic() < deadline and t.is_alive()
           and sum(fail.hits(s) for s in _SEG_SITES) == base_hits):
        time.sleep(0.002)
    cancel_at = None
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=10)
    try:
        while time.monotonic() < deadline and t.is_alive():
            conn.request("GET", "/admin/cancel?trace_id=%032x" % tid_int)
            r = conn.getresponse()
            r.read()
            if r.status == 200:
                cancel_at = time.monotonic()
                break
            time.sleep(0.02)  # 404: not admitted yet
    finally:
        conn.close()
    t.join(timeout=120)
    if cancel_at is None or "done_at" not in res:
        return {"error": "cancel never landed on a live query"}
    return {
        "status": res.get("status"),
        "cancel_to_done_ms": round((res["done_at"] - cancel_at) * 1e3, 1),
    }


def run_seg_arm(store, secs, workers, seed) -> dict:
    """Victim p999 under a MEGA-QUERY antagonist, segmentation on vs
    off — the PR-18 A/B.  The antagonist sends deep light (var-block)
    chains — 6 uid levels, pinned onto the fused mask-chain driver via
    DGRAPH_TPU_MXU_JOIN=force + the static chain-gate override, so the
    route never wobbles mid-arm — whose per-dispatch device time is
    injected at the ``device.spgemm`` failpoint with EQUAL total work
    per query in both modes: segmented (k=1) pays delay_ms at each of
    the 6 segment dispatches, monolithic pays 6×delay_ms at its single
    dispatch.  (A materialized 6-deep chain would be a response-encode
    bomb — deg^6 nested output nodes; the var-block shape is the real
    mega-query: all device work, tiny response.)
    The victim is a critical-priority point-read tenant: with
    segmentation on, a queued victim cohort preempts the running
    antagonist at the next seam (dgraph_segment_preempt_us records the
    wait), so its p999 is bounded by ~one segment; off, it waits out
    whole programs.  A mid-flight /admin/cancel probe per mode measures
    the cancellation latency the same way."""
    from dgraph_tpu import obs
    from dgraph_tpu.utils.failpoints import fail
    from dgraph_tpu.utils.metrics import SEGMENT_PREEMPT_US

    # a hair of head sampling so the cancel probe's SAMPLED traceparent
    # joins (the process recorder was built with ratio 0, under which
    # nothing joins and /admin/cancel can target nothing); restored to
    # the env default in the finally
    obs.configure(ratio=1e-9)

    victim_rate = _env_f("SLO_SEG_VICTIM_RATE", 10.0)
    antag_rate = _env_f("SLO_SEG_ANTAG_RATE", 8.0)
    delay_ms = _env_f("SLO_SEG_DELAY_MS", 80.0)
    levels = 6
    total_ms = delay_ms * levels
    rng = np.random.default_rng(seed + 7000)
    n_nodes = int(_env_f("SLO_NODES", 20_000))
    victim_pool = [
        "{ q(func: uid(0x%x)) { uid } }" % u
        for u in np.unique(rng.integers(1, n_nodes + 1, size=64))
    ]
    body = "v as e"
    for _ in range(levels - 1):
        body = "e { %s }" % body
    antag_pool = []
    for _ in range(32):
        seeds = np.unique(rng.integers(1, n_nodes + 1, size=8))
        ul = ", ".join("0x%x" % u for u in seeds)
        antag_pool.append(
            "{ var(func: uid(%s)) { %s } "
            "q(func: uid(v), first: 1) { uid } }" % (ul, body)
        )
    tenants = json.dumps({
        "victim": {"weight": 8, "priority": "critical"},
        "antagonist": {"weight": 1, "max_queued": 16,
                       "priority": "standard"},
    })
    out = {
        "victim_offered_qps": victim_rate,
        "antagonist_offered_qps": antag_rate,
        "delay_ms": delay_ms,
        "levels": levels,
        "total_injected_ms": total_ms,
        "tenants": json.loads(tenants),
    }
    fp_seed = int(os.environ.get("DGRAPH_TPU_FAILPOINT_SEED", "0"))
    try:
        _run_seg_modes(
            store, secs, workers, seed, out, fp_seed,
            victim_pool, antag_pool, tenants, delay_ms, total_ms,
            victim_rate, antag_rate,
        )
    finally:
        obs.configure()  # back to the env-default recorder
    return out


def _run_seg_modes(
    store, secs, workers, seed, out, fp_seed,
    victim_pool, antag_pool, tenants, delay_ms, total_ms,
    victim_rate, antag_rate,
) -> None:
    from dgraph_tpu.utils.failpoints import fail
    from dgraph_tpu.utils.metrics import SEGMENT_PREEMPT_US

    for mode, seg_env, per_dispatch_ms in (
        ("seg_on",
         {"DGRAPH_TPU_SEGMENT": "force", "DGRAPH_TPU_SEGMENT_K": "1"},
         delay_ms),
        ("seg_off", {"DGRAPH_TPU_SEGMENT": "0"}, total_ms),
    ):
        fail.reset(fp_seed)
        with _ServerArm(store, {
            "DGRAPH_TPU_SCHED": "1",
            # a cached mega-query stresses nothing; and cached chains
            # dodge the dispatch seam the arm must measure
            "DGRAPH_TPU_CACHE": "0",
            "DGRAPH_TPU_QOS": "1",
            "DGRAPH_TPU_QOS_TENANTS": tenants,
            # pin the deep chain onto the fused mask-chain driver (env
            # override = static gate; the planner yields the decision)
            "DGRAPH_TPU_CHAIN_THRESHOLD": "1",
            "DGRAPH_TPU_MXU_JOIN": "force",
            # one flush worker: the victim must actually queue behind
            # the running mega-query — with a second worker free the
            # A/B measures nothing
            "DGRAPH_TPU_SCHED_CONCURRENCY": "1",
            **seg_env,
            **_backend_env(),
        }) as srv:
            classes = [
                {"name": "victim", "rate": victim_rate,
                 "pool": victim_pool, "tenant": "victim"},
                {"name": "antagonist", "rate": antag_rate,
                 "pool": antag_pool, "tenant": "antagonist"},
            ]
            _warmup(srv.port, classes)
            p0 = SEGMENT_PREEMPT_US.count()
            # arm AFTER warmup: compiles are slow, not under test.  The
            # delay prices each device dispatch, whichever driver the
            # planner routes the chain to (chain / mask-chain /
            # multi-hop); victims are point lookups on the host route
            # and never pay it.
            for site in _SEG_SITES:
                fail.arm(site, f"delay(ms={per_dispatch_ms:g})")
            try:
                step = open_loop_step(
                    srv.port, classes, secs, seed + 7000, workers
                )
                cancel = _seg_cancel_probe(
                    srv.port, antag_pool[0],
                    0x5E60 + (1 if mode == "seg_on" else 2),
                )
            finally:
                fail.reset(fp_seed)
            v = step["classes"]["victim"]
            a = step["classes"]["antagonist"]
            out[mode] = {
                "victim_p50_ms": v["p50_ms"],
                "victim_p99_ms": v["p99_ms"],
                "victim_p999_ms": v["p999_ms"],
                "victim_ok": v["ok"],
                "antagonist_ok": a["ok"],
                "antagonist_shed": a["shed"],
                "preempts": SEGMENT_PREEMPT_US.count() - p0,
                "cancel": cancel,
            }
            print(
                f"# slo seg[{mode}] victim_p999={v['p999_ms']}ms "
                f"preempts={out[mode]['preempts']} "
                f"cancel={cancel}",
                file=sys.stderr,
            )


# ------------------------------------------------------------------ main

def run_slo_bench() -> dict:
    import jax

    from dgraph_tpu.obs import device as _device

    _device.install_compile_listener()
    _device.stamp_build_info()
    seed = int(_env_f("SLO_SEED", 7))
    n_nodes = int(_env_f("SLO_NODES", 20_000))
    deg = int(_env_f("SLO_DEG", 16))
    secs = _env_f("SLO_STEP_SECONDS", 4.0)
    workers = int(_env_f("SLO_WORKERS", 32))
    rates = _env_rates("SLO_RATES", "25,50,100,200,400")
    rng = np.random.default_rng(seed)
    store = _serving_store(n_nodes, deg)
    mix = build_mix(n_nodes, rng)

    sweep = run_sweep(store, mix, rates, secs, workers, seed)
    qos = None
    if os.environ.get("SLO_QOS", "1") != "0":
        try:
            qos = run_qos_arm(
                store, _env_rates("SLO_QOS_RATES", "50,200"), secs,
                workers, seed,
            )
        except Exception as e:  # arm isolation: the curve survives
            qos = {"error": f"{type(e).__name__}: {e}"}
    ivm = None
    if os.environ.get("SLO_IVM", "1") != "0":
        try:
            ivm = run_ivm_arm(store, secs, workers, seed)
        except Exception as e:
            ivm = {"error": f"{type(e).__name__}: {e}"}
    devfault = None
    if os.environ.get("SLO_DEVFAULT", "1") != "0":
        try:
            devfault = run_devfault_arm(
                store, _env_rates("SLO_DEVFAULT_RATES", "20,40"), secs,
                workers, seed,
            )
        except Exception as e:
            devfault = {"error": f"{type(e).__name__}: {e}"}
    seg = None
    if os.environ.get("SLO_SEG", "1") != "0":
        try:
            seg = run_seg_arm(store, secs, workers, seed)
        except Exception as e:
            seg = {"error": f"{type(e).__name__}: {e}"}
    meshchaos = None
    if os.environ.get("SLO_MESHCHAOS", "1") != "0":
        try:
            meshchaos = run_meshchaos_arm(
                store, _env_rates("SLO_MESHCHAOS_RATES", "20,40"), secs,
                workers, seed,
            )
        except Exception as e:
            meshchaos = {"error": f"{type(e).__name__}: {e}"}

    from dgraph_tpu.obs import ledger as _ledgermod

    out = {
        "metric": "slo_curve",
        # keyed by backend: the mesh arm's curve must never be compared
        # to an unsharded curve under the same key
        "backend": jax.default_backend()
        + ("-mesh" if _backend_arg() == "mesh" else ""),
        "nodes": n_nodes,
        "deg": deg,
        "step_seconds": secs,
        "workers": workers,
        "mix": {c["name"]: c["weight"] for c in mix},
        "offered_sweep": sweep["steps"],
        "saturation_knee": sweep["saturation_knee"],
        "qos": qos,
        "ivm": ivm,
        "devfault": devfault,
        "seg": seg,
        "meshchaos": meshchaos,
        # the serving-path cost account for the whole run (obs/ledger.py):
        # edges/sec across the sweep is achieved_qps × edges-per-query,
        # and this is the series it reconciles against
        "ledger": _ledgermod.aggregate_summary(),
    }
    return out


def smoke_check(out: dict) -> None:
    """The CI gate (SLO_SMOKE=1): the harness is well-formed and the
    physics points the right way — shed rate must be monotone
    non-decreasing in offered load (small tolerance for scheduler
    noise at tiny step sizes)."""
    for key in (
        "metric", "backend", "offered_sweep", "saturation_knee", "mix",
    ):
        assert key in out, f"slo smoke: missing key {key!r}"
    steps = out["offered_sweep"]
    assert len(steps) >= 2, "slo smoke: need at least two offered-load steps"
    for s in steps:
        assert s["sent"] > 0, "slo smoke: a step sent nothing"
        assert s["error_rate"] == 0.0, (
            f"slo smoke: non-shed errors at offered={s['offered_qps']}"
        )
        for cls in s["classes"].values():
            assert cls["p999_ms"] >= cls["p99_ms"] >= cls["p50_ms"] >= 0
    sheds = [s["shed_rate"] for s in steps]
    for a, b in zip(sheds, sheds[1:]):
        assert b >= a - 0.02, (
            f"slo smoke: shed rate not monotone across offered load "
            f"({sheds})"
        )
    dv = out.get("devfault")
    if dv and "error" not in dv:
        on, off = dv["devguard_on"], dv["devguard_off"]
        assert on["readmitted"], (
            "devfault smoke: device not re-admitted after the wedge healed"
        )
        inj_on = next(s for s in on["steps"] if s["injected"])
        inj_off = next(s for s in off["steps"] if s["injected"])
        assert inj_on["failovers"] > 0, (
            "devfault smoke: the wedge never drove a host failover"
        )
        for s in on["steps"]:
            assert s["error_rate"] == 0.0, (
                "devfault smoke: guard-on arm surfaced errors"
            )
        # structural separation: the watchdog bounds the wedge (guard
        # on), the legacy path eats it in full (guard off)
        assert inj_on["p999_ms"] < dv["wedge_ms"], (
            f"devfault smoke: guard did not bound the wedge "
            f"(p999 {inj_on['p999_ms']}ms vs wedge {dv['wedge_ms']}ms)"
        )
        assert inj_off["p999_ms"] >= dv["wedge_ms"] * 0.6, (
            "devfault smoke: guard-off arm never observed the wedge"
        )
    mc = out.get("meshchaos")
    if mc and "error" not in mc and "skipped" not in mc:
        cyc = mc["cycle"]
        assert cyc["restored"], (
            "meshchaos smoke: staged rejoin never restored the full mesh"
        )
        assert cyc["reshards"].get("loss", 0) >= 1, (
            "meshchaos smoke: the injected loss never drove a reshard"
        )
        assert cyc["reshards"].get("rejoin", 0) >= 1, (
            "meshchaos smoke: no rejoin cutover was recorded"
        )
        assert cyc["epoch_after"] > cyc["epoch_before"], (
            "meshchaos smoke: the mesh epoch never advanced"
        )
        for s in mc["steps"]:
            # chip loss is CAPACITY, not errors: the whole cycle —
            # loss, degraded sub-mesh serving, rejoin cutover — must
            # surface zero non-shed errors
            assert s["error_rate"] == 0.0, (
                f"meshchaos smoke: surfaced errors at "
                f"offered={s['offered_qps']}"
            )
    sg = out.get("seg")
    if sg and "error" not in sg:
        on, off = sg["seg_on"], sg["seg_off"]
        total = sg["total_injected_ms"]
        # structural separation: with segmentation on the critical
        # victim preempts at seams (p999 bounded under one program);
        # off, it waits out whole monolithic programs
        assert on["preempts"] > 0, (
            "seg smoke: segmentation never drove a preemption"
        )
        assert on["victim_p999_ms"] < total, (
            f"seg smoke: victim p999 not bounded with segmentation on "
            f"({on['victim_p999_ms']}ms vs program {total}ms)"
        )
        assert off["victim_p999_ms"] >= total * 0.6, (
            "seg smoke: monolithic arm never made the victim wait"
        )
        assert on["victim_p999_ms"] < off["victim_p999_ms"], (
            f"seg smoke: victim p999 did not improve "
            f"({on['victim_p999_ms']}ms on vs {off['victim_p999_ms']}ms off)"
        )
        con, coff = on["cancel"], off["cancel"]
        if "error" not in con and "error" not in coff:
            # mid-chain cancel completes within ~one segment (3x slack
            # for CI scheduling noise) vs the monolithic remainder
            assert con["cancel_to_done_ms"] < sg["delay_ms"] * 3, (
                f"seg smoke: cancel latency not segment-bounded "
                f"({con['cancel_to_done_ms']}ms)"
            )
            assert con["cancel_to_done_ms"] < coff["cancel_to_done_ms"], (
                "seg smoke: segmentation did not shorten cancel latency"
            )


def main() -> None:
    dev = device_identity()
    print(f"# backend: {dev}", file=sys.stderr)
    out = {**run_slo_bench(), **dev}
    if os.environ.get("SLO_SMOKE") == "1":
        smoke_check(out)
        print("# slo smoke: OK", file=sys.stderr)
    body = json.dumps(out)
    print(body)
    path = os.environ.get("SLO_OUT", "")
    if path:
        with open(path, "w") as f:
            f.write(body + "\n")


if __name__ == "__main__":
    main()
