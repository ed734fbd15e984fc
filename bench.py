"""Serving-path A/B arms over in-process servers: serving, durability,
QoS and IVM.  (The repo's benchmark is ``benchmark/run.py``; these arms
are older records of single features, each against its own off switch.)

Prints ONE json line: {"serving", "durability", "qos", "ivm", "planner",
"platform", "device_kind", "device_count"}.  "serving" is the closed-loop
multi-client A/B (run_serving_bench), three arms over one zipf workload:
the cohort scheduler (DGRAPH_TPU_SCHED=1) vs the serial per-request
path (=0), both cache-off, plus the two-tier query cache arm
(DGRAPH_TPU_CACHE=1, ISSUE 3) reported as "cache_on" with
"cache_qps_ratio" (warm-QPS over the cache-off scheduler arm) and
"tier2_hit_rate" (guarded nonzero) — with QPS, p50/p99 latency, mean
cohort occupancy, flush-reason counts and a cross-arm response-parity
check.
Environment knobs: BENCH_SERVE (0 skips the serving A/B) /
BENCH_CLIENTS / BENCH_SERVE_SECONDS / BENCH_SERVE_NODES /
BENCH_SERVE_DEG; BENCH_MUT, BENCH_QOS, BENCH_IVM (0 skips that arm) with
their BENCH_MUT_* / BENCH_QOS_* / BENCH_IVM_* sizes; BENCH_ONLY=qos|ivm
runs that arm alone.

No fallback hides the device: the script uses the backend JAX gives it
(``JAX_PLATFORMS=cpu`` for a rehearsal), names ``platform``,
``device_kind`` and ``device_count`` in every result, never retries at a
smaller scale, and exits non-zero when any arm failed (a failed arm's
error still lands in the JSON beside the arms that ran).  One process
owns the chip: every server this script starts is in-process.
"""

import json
import os
import sys
import time

import numpy as np

def device_identity() -> dict:
    """What every result of a bench script names, so that a number can
    never be read for a device it was not taken on.  Also points JAX's
    persistent compilation cache at its one place (utils/jaxcache.py)."""
    import jax

    from dgraph_tpu.utils import jaxcache

    jaxcache.configure()
    devs = jax.devices()
    return {
        "platform": devs[0].platform,
        "device_kind": devs[0].device_kind,
        "device_count": len(devs),
    }


def _serving_store(n_nodes: int, deg: int, seed: int = 13):
    """Small serving graph: one uid predicate 'e' with ~deg out-edges per
    node + a name value per node (gives filters something to chew)."""
    from dgraph_tpu.models import PostingStore

    rng = np.random.default_rng(seed)
    store = PostingStore()
    store.apply_schema("e: uid @count .\nname: string .")
    src = np.repeat(np.arange(1, n_nodes + 1, dtype=np.int64), deg)
    dst = rng.integers(1, n_nodes + 1, size=len(src)).astype(np.int64)
    store.bulk_set_uid_edges("e", src, dst)
    return store


def _serving_mode(
    sched_on: bool, store, variants, clients: int, secs: float,
    cache_on: bool = False,
):
    """One closed-loop run: ``clients`` threads fire queries for ``secs``
    against a fresh DgraphServer (scheduler gated by ``sched_on``, the
    two-tier query cache by ``cache_on``).
    Returns (qps, p50_ms, p99_ms, {query: response}, completed)."""
    import json as _json
    import threading

    os.environ["DGRAPH_TPU_SCHED"] = "1" if sched_on else "0"
    os.environ["DGRAPH_TPU_CACHE"] = "1" if cache_on else "0"
    from dgraph_tpu.serve.server import DgraphServer

    srv = DgraphServer(store)
    srv.start()
    try:
        import http.client

        def mkconn():
            return http.client.HTTPConnection(
                "127.0.0.1", srv.port, timeout=30
            )

        def post_on(conn, q):
            # persistent connection (the server speaks HTTP/1.1
            # keep-alive): no TCP handshake per query
            conn.request("POST", "/query", body=q.encode())
            r = conn.getresponse()
            body = r.read()
            if r.status != 200:
                raise RuntimeError(f"HTTP {r.status}: {body[:200]!r}")
            return _json.loads(body.decode())

        warm = mkconn()
        canon = {}
        for q in variants:  # warmup + canonical responses (untimed)
            out = post_on(warm, q)
            out.pop("server_latency", None)
            canon[q] = out
        warm.close()

        lat_lock = threading.Lock()
        lats: list = []
        errs: list = []
        stop_at = [0.0]

        # zipf query popularity (s = BENCH_SERVE_ZIPF, 0 = uniform):
        # serving traffic has hot queries, and hot queries are what the
        # scheduler's singleflight coalescing dedups — a uniform draw
        # would benchmark a traffic shape real services never see
        s = float(os.environ.get("BENCH_SERVE_ZIPF", 1.1))
        w = 1.0 / np.power(np.arange(1, len(variants) + 1, dtype=np.float64), s)
        probs = w / w.sum()

        def client(cid: int):
            rng = np.random.default_rng(1000 + cid)  # same draw both modes
            my = []
            conn = mkconn()
            try:
                while time.monotonic() < stop_at[0]:
                    q = variants[int(rng.choice(len(variants), p=probs))]
                    t0 = time.monotonic()
                    out = post_on(conn, q)
                    my.append(time.monotonic() - t0)
                    out.pop("server_latency", None)
                    if out != canon[q]:
                        raise AssertionError(f"response diverged for {q!r}")
            except Exception as e:
                errs.append(e)
            finally:
                conn.close()
            with lat_lock:
                lats.extend(my)

        ts = [
            threading.Thread(target=client, args=(c,), daemon=True)
            for c in range(clients)
        ]
        stop_at[0] = time.monotonic() + secs
        t0 = time.monotonic()
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=secs + 60)
        wall = time.monotonic() - t0
        if errs:
            raise errs[0]
        if not lats:
            raise RuntimeError("serving bench made no requests")
        a = np.sort(np.asarray(lats))
        return (
            len(a) / wall,
            float(a[int(0.50 * (len(a) - 1))]) * 1e3,
            float(a[int(0.99 * (len(a) - 1))]) * 1e3,
            canon,
            len(a),
        )
    finally:
        srv.stop()


def run_serving_bench():
    """Closed-loop multi-client serving benchmark (ISSUE 2 + ISSUE 3):
    three arms over the same zipf workload with response-parity checks —
    scheduler on (cache off) vs the serial per-request path (the PR 2
    batching A/B, both cache-off so the ratio still isolates batching),
    plus the two-tier query cache on (ISSUE 3's warm-path A/B: cache_on
    vs the cache-off scheduler arm).  Guards that the cache-on arm's
    tier-2 hit rate is nonzero — a zipf head that never hits means the
    cache is mis-keyed, and the headline ratio would be a lie.
    Returns the dict merged into the headline JSON under "serving"."""
    clients = int(os.environ.get("BENCH_CLIENTS", 32))
    secs = float(os.environ.get("BENCH_SERVE_SECONDS", 4.0))
    n_nodes = int(os.environ.get("BENCH_SERVE_NODES", 20_000))
    deg = int(os.environ.get("BENCH_SERVE_DEG", 16))
    store = _serving_store(n_nodes, deg)

    # 64 same-shape-family 2-hop variants (different seed uids): cohorts
    # coalesce them, and the count leaf keeps responses JSON-light so the
    # measurement stays on traversal, not encoding
    rng = np.random.default_rng(5)
    variants = []
    for _ in range(64):
        seeds = np.unique(rng.integers(1, n_nodes + 1, size=8))
        ul = ", ".join("0x%x" % u for u in seeds)
        variants.append("{ q(func: uid(%s)) { e { c: count(e) } } }" % ul)

    from statistics import median

    from dgraph_tpu.utils.metrics import (
        QCACHE_RESULT_EVENTS,
        SCHED_COHORT_OCCUPANCY,
        SCHED_FLUSHES,
    )

    reps = max(1, int(os.environ.get("BENCH_SERVE_REPS", 2)))
    _occ0, occ_sum0, c0 = SCHED_COHORT_OCCUPANCY.snapshot()
    fl0 = SCHED_FLUSHES.snapshot()
    qc0 = QCACHE_RESULT_EVENTS.snapshot()
    # interleave the modes: the shared host's load swings throughput ~2×
    # between runs (same caveat as the headline bench), so paired runs +
    # medians are the only defensible comparison.  The two sched arms run
    # CACHE-OFF so their ratio still isolates the batching win; the cache
    # arm compares against the cache-off scheduler arm.
    on_runs, off_runs, cache_runs = [], [], []
    canon_on = canon_off = canon_cache = None
    n_on = n_off = n_cache = 0
    for _ in range(reps):
        qps, p50, p99, canon_on, n1 = _serving_mode(
            True, store, variants, clients, secs
        )
        on_runs.append((qps, p50, p99))
        n_on += n1
        qps, p50, p99, canon_off, n2 = _serving_mode(
            False, store, variants, clients, secs
        )
        off_runs.append((qps, p50, p99))
        n_off += n2
        qps, p50, p99, canon_cache, n3 = _serving_mode(
            True, store, variants, clients, secs, cache_on=True
        )
        cache_runs.append((qps, p50, p99))
        n_cache += n3
    _occ1, occ_sum1, c1 = SCHED_COHORT_OCCUPANCY.snapshot()
    fl1 = SCHED_FLUSHES.snapshot()
    qc1 = QCACHE_RESULT_EVENTS.snapshot()
    identical = canon_on == canon_off == canon_cache
    assert identical, "sched/cache arm responses diverged"
    # tier-2 guard: the zipf head MUST hit (nonzero hit rate) or the
    # cache arm measured nothing
    t2_hits = qc1.get("hit", 0) - qc0.get("hit", 0)
    t2_miss = qc1.get("miss", 0) - qc0.get("miss", 0)
    t2_rate = t2_hits / max(t2_hits + t2_miss, 1)
    assert t2_hits > 0, (
        "cache-on serving arm reported a ZERO tier-2 hit rate under the "
        "zipf workload — the result cache never engaged"
    )
    flushes = {k: fl1.get(k, 0) - fl0.get(k, 0) for k in fl1}
    flushes = {k: v for k, v in flushes.items() if v}
    n_flush = max(c1 - c0, 1)
    qps_on = median(r[0] for r in on_runs)
    qps_off = median(r[0] for r in off_runs)
    qps_cache = median(r[0] for r in cache_runs)
    return {
        "clients": clients,
        "seconds": secs,
        "reps": reps,
        "sched_on": {
            "qps": round(qps_on, 1),
            "p50_ms": round(median(r[1] for r in on_runs), 2),
            "p99_ms": round(median(r[2] for r in on_runs), 2),
            "qps_runs": [round(r[0], 1) for r in on_runs],
            "requests": n_on,
        },
        "sched_off": {
            "qps": round(qps_off, 1),
            "p50_ms": round(median(r[1] for r in off_runs), 2),
            "p99_ms": round(median(r[2] for r in off_runs), 2),
            "qps_runs": [round(r[0], 1) for r in off_runs],
            "requests": n_off,
        },
        "cache_on": {
            "qps": round(qps_cache, 1),
            "p50_ms": round(median(r[1] for r in cache_runs), 2),
            "p99_ms": round(median(r[2] for r in cache_runs), 2),
            "qps_runs": [round(r[0], 1) for r in cache_runs],
            "requests": n_cache,
        },
        "qps_ratio": round(qps_on / qps_off, 3) if qps_off else None,
        # ISSUE 3 headline: warm-QPS ratio, cache-on over the cache-off
        # scheduler arm (same sched config, only DGRAPH_TPU_CACHE flips)
        "cache_qps_ratio": round(qps_cache / qps_on, 3) if qps_on else None,
        "tier2_hit_rate": round(t2_rate, 4),
        "cohort_occupancy_mean": round((occ_sum1 - occ_sum0) / n_flush, 2),
        "flush_reasons": flushes,
        "responses_identical": identical,
    }


def _qos_mode(
    qos_on: bool,
    store,
    victim_qs,
    antag_qs,
    v_clients: int,
    a_clients: int,
    secs: float,
    tenants_json: str,
):
    """One closed-loop antagonist/victim run: ``v_clients`` victim
    threads fire light point reads under tenant ``victim`` while
    ``a_clients`` antagonist threads flood heavy traversals under
    tenant ``antagonist``.  ``qos_on`` flips DGRAPH_TPU_QOS — the PR-11
    A/B.  Cache is OFF for both arms (an antagonist whose repeats hit
    the result cache would stress nothing).  Antagonist 429s (quota
    sheds) are counted, not errors — being shed IS the mechanism under
    test.  Returns (victim qps, p50_ms, p99_ms, antag_ok, antag_shed)."""
    import json as _json
    import threading

    # save/restore EVERYTHING this arm pins: a later arm (or the
    # operator's own exports) must not inherit this arm's regime
    saved = {
        k: os.environ.get(k)
        for k in ("DGRAPH_TPU_SCHED", "DGRAPH_TPU_CACHE",
                  "DGRAPH_TPU_QOS", "DGRAPH_TPU_QOS_TENANTS")
    }
    os.environ["DGRAPH_TPU_SCHED"] = "1"
    os.environ["DGRAPH_TPU_CACHE"] = "0"
    os.environ["DGRAPH_TPU_QOS"] = "1" if qos_on else "0"
    os.environ["DGRAPH_TPU_QOS_TENANTS"] = tenants_json
    from dgraph_tpu.serve.server import DgraphServer

    srv = DgraphServer(store)
    srv.start()
    try:
        import http.client

        def post_on(conn, q, tenant):
            conn.request(
                "POST", "/query", body=q.encode(),
                headers={"X-Dgraph-Tenant": tenant},
            )
            r = conn.getresponse()
            body = r.read()
            return r.status, body

        warm = http.client.HTTPConnection("127.0.0.1", srv.port, timeout=30)
        for q in (victim_qs + antag_qs)[:4]:  # compile warmup, untimed
            post_on(warm, q, "warmup")
        warm.close()

        lock = threading.Lock()
        v_lats: list = []
        a_ok = [0]
        a_shed = [0]
        errs: list = []
        stop_at = [0.0]

        def victim(cid: int):
            rng = np.random.default_rng(100 + cid)
            conn = http.client.HTTPConnection("127.0.0.1", srv.port, timeout=60)
            my = []
            try:
                while time.monotonic() < stop_at[0]:
                    q = victim_qs[int(rng.integers(len(victim_qs)))]
                    t0 = time.monotonic()
                    status, body = post_on(conn, q, "victim")
                    if status != 200:
                        raise RuntimeError(
                            f"victim HTTP {status}: {body[:120]!r}"
                        )
                    _json.loads(body.decode())
                    my.append(time.monotonic() - t0)
            except Exception as e:
                errs.append(e)
            finally:
                conn.close()
            with lock:
                v_lats.extend(my)

        def antagonist(cid: int):
            rng = np.random.default_rng(900 + cid)
            conn = http.client.HTTPConnection("127.0.0.1", srv.port, timeout=60)
            ok = shed = 0
            try:
                while time.monotonic() < stop_at[0]:
                    q = antag_qs[int(rng.integers(len(antag_qs)))]
                    try:
                        status, _body = post_on(conn, q, "antagonist")
                    except OSError:
                        conn.close()
                        conn = http.client.HTTPConnection(
                            "127.0.0.1", srv.port, timeout=60
                        )
                        continue
                    if status == 200:
                        ok += 1
                    elif status == 429:
                        shed += 1
                        # honor back-pressure minimally: a real client
                        # would sleep Retry-After; the flood sleeps just
                        # enough not to busy-spin the accept loop
                        time.sleep(0.002)
                    else:
                        raise RuntimeError(f"antagonist HTTP {status}")
            except Exception as e:
                errs.append(e)
            finally:
                conn.close()
            with lock:
                a_ok[0] += ok
                a_shed[0] += shed

        ts = [
            threading.Thread(target=victim, args=(c,), daemon=True)
            for c in range(v_clients)
        ] + [
            threading.Thread(target=antagonist, args=(c,), daemon=True)
            for c in range(a_clients)
        ]
        stop_at[0] = time.monotonic() + secs
        t0 = time.monotonic()
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=secs + 120)
        wall = time.monotonic() - t0
        if errs:
            raise errs[0]
        if not v_lats:
            raise RuntimeError("qos bench victim made no requests")
        a = np.sort(np.asarray(v_lats))
        return (
            len(a) / wall,
            float(a[int(0.50 * (len(a) - 1))]) * 1e3,
            float(a[int(0.99 * (len(a) - 1))]) * 1e3,
            a_ok[0],
            a_shed[0],
        )
    finally:
        srv.stop()
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def run_qos_bench():
    """Antagonist-isolation benchmark (PR 11's headline robustness
    number).  Three arms over one store:

    - ``victim_solo`` — victim tenant alone, QoS on: the baseline SLO.
    - ``qos_on``      — victim + antagonist flood, QoS on: the
      antagonist is quota-shed (max_queued) and weight-limited, and the
      victim's p99 must stay within ``BENCH_QOS_FACTOR`` (default 3×)
      of its solo p99 — asserted, not just reported.
    - ``qos_off``     — the SAME mix with DGRAPH_TPU_QOS=0: shows the
      leak (victim p99 blowup with no per-tenant machinery).

    Sized by BENCH_QOS_NODES/DEG/SECONDS/VICTIM_CLIENTS/ANTAG_CLIENTS;
    BENCH_QOS_ASSERT=0 downgrades the assertion to reporting (the CI
    smoke keeps it on with a generous factor — a 2-core shared runner
    proves the harness, not the SLO)."""
    n_nodes = int(os.environ.get("BENCH_QOS_NODES", 20_000))
    deg = int(os.environ.get("BENCH_QOS_DEG", 16))
    secs = float(os.environ.get("BENCH_QOS_SECONDS", 3.0))
    v_clients = int(os.environ.get("BENCH_QOS_VICTIM_CLIENTS", 4))
    a_clients = int(os.environ.get("BENCH_QOS_ANTAG_CLIENTS", 16))
    factor = float(os.environ.get("BENCH_QOS_FACTOR", 3.0))
    do_assert = os.environ.get("BENCH_QOS_ASSERT", "1") != "0"
    store = _serving_store(n_nodes, deg)

    rng = np.random.default_rng(17)
    # victim: single-uid point reads with a count leaf — the 1ms-class
    # traffic whose SLO the antagonist must not wreck
    victim_qs = [
        "{ q(func: uid(0x%x)) { c: count(e) } }" % u
        for u in np.unique(rng.integers(1, n_nodes + 1, size=64))
    ]
    # antagonist: wide 2-hop expansions from 64-seed lists — each one
    # orders of magnitude more engine work than a victim read
    antag_qs = []
    for _ in range(128):
        seeds = np.unique(rng.integers(1, n_nodes + 1, size=64))
        ul = ", ".join("0x%x" % u for u in seeds)
        antag_qs.append("{ q(func: uid(%s)) { e { e { c: count(e) } } } }" % ul)

    # the QoS envelope under test: the victim outweighs the antagonist
    # 8:1 for cohort slots, and the antagonist's own queue/inflight
    # quota sheds its flood at admission instead of letting it occupy
    # the global queue
    tenants = json.dumps({
        "victim": {"weight": 8, "priority": "interactive"},
        "antagonist": {
            "weight": 1, "max_queued": 8, "max_inflight": 1,
            "priority": "batch",
        },
    })

    solo_qps, solo_p50, solo_p99, _ok, _shed = _qos_mode(
        True, store, victim_qs, antag_qs, v_clients, 0, secs, tenants
    )
    on_qps, on_p50, on_p99, on_ok, on_shed = _qos_mode(
        True, store, victim_qs, antag_qs, v_clients, a_clients, secs, tenants
    )
    off_qps, off_p50, off_p99, off_ok, off_shed = _qos_mode(
        False, store, victim_qs, antag_qs, v_clients, a_clients, secs, tenants
    )
    # floor: on a noisy shared host a 0.3ms solo p99 would make any
    # ratio meaningless — compare against at least a 5ms baseline
    base = max(solo_p99, 5.0)
    isolation = on_p99 / base
    leak = off_p99 / base
    out = {
        "seconds": secs,
        "victim_clients": v_clients,
        "antagonist_clients": a_clients,
        "tenants": json.loads(tenants),
        "victim_solo": {
            "qps": round(solo_qps, 1), "p50_ms": round(solo_p50, 2),
            "p99_ms": round(solo_p99, 2),
        },
        "qos_on": {
            "victim_qps": round(on_qps, 1),
            "victim_p50_ms": round(on_p50, 2),
            "victim_p99_ms": round(on_p99, 2),
            "antagonist_ok": on_ok,
            "antagonist_shed": on_shed,
        },
        "qos_off": {
            "victim_qps": round(off_qps, 1),
            "victim_p50_ms": round(off_p50, 2),
            "victim_p99_ms": round(off_p99, 2),
            "antagonist_ok": off_ok,
            "antagonist_shed": off_shed,
        },
        # the headline pair: bounded with QoS on, the leak without
        "victim_p99_factor_qos_on": round(isolation, 3),
        "victim_p99_factor_qos_off": round(leak, 3),
        "bound_factor": factor,
        "isolation_holds": bool(isolation <= factor),
    }
    if do_assert:
        assert on_shed > 0, (
            "qos bench: the antagonist was never quota-shed — the "
            "per-tenant admission quota did not engage"
        )
        assert isolation <= factor, (
            f"qos bench: victim p99 under antagonist flood "
            f"({on_p99:.1f}ms) exceeded {factor}x its solo baseline "
            f"({solo_p99:.1f}ms, floored to {base:.1f}ms)"
        )
    return out


def _ivm_mode(
    ivm_on: bool, store, variants, clients: int, secs: float,
    write_rate: float, write_pred: str, cache_on: bool = True,
):
    """One closed-loop read run with a paced writer beside it.

    ``clients`` reader threads fire the zipf variant mix while ONE
    writer toggles edges on ``write_pred`` at ``write_rate``/s (each
    toggle is an add immediately followed by its delete, so the run
    ends at the state it started — what makes the post-quiesce parity
    probe meaningful).  Cache ON both arms; only DGRAPH_TPU_IVM flips:
    the off arm is the store.version-keyed baseline every mutation
    global-invalidates.  Returns (qps, completed, final_responses)."""
    import json as _json
    import threading

    saved = {
        k: os.environ.get(k)
        for k in ("DGRAPH_TPU_SCHED", "DGRAPH_TPU_CACHE", "DGRAPH_TPU_IVM")
    }
    os.environ["DGRAPH_TPU_SCHED"] = "1"
    os.environ["DGRAPH_TPU_CACHE"] = "1" if cache_on else "0"
    os.environ["DGRAPH_TPU_IVM"] = "1" if ivm_on else "0"
    from dgraph_tpu.serve.server import DgraphServer

    srv = DgraphServer(store)
    srv.start()
    try:
        import http.client

        def mkconn():
            return http.client.HTTPConnection(
                "127.0.0.1", srv.port, timeout=30
            )

        def post_on(conn, q):
            conn.request("POST", "/query", body=q.encode())
            r = conn.getresponse()
            body = r.read()
            if r.status != 200:
                raise RuntimeError(f"HTTP {r.status}: {body[:200]!r}")
            return _json.loads(body.decode())

        warm = mkconn()
        for q in variants:
            post_on(warm, q)

        lat_lock = threading.Lock()
        done = [0]
        errs: list = []
        stop_at = [0.0]
        quiesce = threading.Event()

        s = float(os.environ.get("BENCH_SERVE_ZIPF", 1.1))
        w = 1.0 / np.power(
            np.arange(1, len(variants) + 1, dtype=np.float64), s
        )
        probs = w / w.sum()

        def reader(cid: int):
            rng = np.random.default_rng(2000 + cid)  # same draw each arm
            n = 0
            conn = mkconn()
            try:
                while time.monotonic() < stop_at[0]:
                    q = variants[int(rng.choice(len(variants), p=probs))]
                    post_on(conn, q)
                    n += 1
            except Exception as e:
                errs.append(e)
            finally:
                conn.close()
            with lat_lock:
                done[0] += n

        def writer():
            # paced edge toggles: add + revert, one WAL'd mutation each,
            # single-edge journal deltas (the repair path's shape)
            if write_rate <= 0:
                return
            conn = mkconn()
            i = 0
            try:
                while time.monotonic() < stop_at[0]:
                    u = 0x70000 + (i % 97)
                    i += 1
                    post_on(conn, "mutation { set { <0x%x> <%s> <0x%x> . } }"
                            % (u, write_pred, u + 1))
                    post_on(conn, "mutation { delete { <0x%x> <%s> <0x%x> . } }"
                            % (u, write_pred, u + 1))
                    time.sleep(1.0 / write_rate)
            except Exception as e:
                if not quiesce.is_set():
                    errs.append(e)
            finally:
                conn.close()

        ts = [
            threading.Thread(target=reader, args=(c,), daemon=True)
            for c in range(clients)
        ]
        wt = threading.Thread(target=writer, daemon=True)
        stop_at[0] = time.monotonic() + secs
        t0 = time.monotonic()
        for t in ts:
            t.start()
        wt.start()
        for t in ts:
            t.join(timeout=secs + 60)
        wall = time.monotonic() - t0
        quiesce.set()
        wt.join(timeout=secs + 60)
        if errs:
            raise errs[0]
        # post-quiesce probe: the writer reverted every toggle, so a
        # correctly-invalidated (or correctly-REPAIRED) cache must now
        # answer exactly the initial state — through the warm cache
        final = {}
        conn = mkconn()
        for q in variants:
            out = post_on(conn, q)
            out.pop("server_latency", None)
            final[q] = out
        conn.close()
        return done[0] / wall, done[0], final
    finally:
        srv.stop()
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def _ivm_subscription_demo(store) -> dict:
    """The live-query acceptance probe: a registered subscription gets
    exactly ONE trace-linked push after an affecting mutation, and
    nothing for an unrelated-predicate mutation."""
    import json as _json
    import urllib.request

    from dgraph_tpu import obs

    saved = {
        k: os.environ.get(k)
        for k in ("DGRAPH_TPU_SCHED", "DGRAPH_TPU_CACHE", "DGRAPH_TPU_IVM")
    }
    os.environ["DGRAPH_TPU_SCHED"] = "1"
    os.environ["DGRAPH_TPU_CACHE"] = "1"
    os.environ["DGRAPH_TPU_IVM"] = "1"
    rec = obs.configure(ratio=1.0, seed=11)  # every eval traced
    from dgraph_tpu.serve.server import DgraphServer

    srv = DgraphServer(store)
    srv.start()
    try:
        base = srv.addr

        def post(path, body):
            return urllib.request.urlopen(
                urllib.request.Request(base + path, data=body.encode()),
                timeout=15,
            )

        reg = _json.load(post(
            "/subscribe", "{ s(func: uid(0x1)) { e { c: count(e) } } }"
        ))
        sid = reg["sub_id"]
        sub = srv.subs.get(sid)
        ev0 = sub.next_event(timeout=10)  # the snapshot
        assert ev0 and ev0["kind"] == "snapshot", ev0
        # unrelated predicate: NO push
        post("/query", 'mutation { set { <0x9999> <unrelated_w> "x" . } }')
        quiet = sub.next_event(timeout=1.0)
        assert quiet is None, f"unrelated mutation pushed: {quiet}"
        # affecting predicate: exactly one push, trace-linked
        post("/query", "mutation { set { <0x1> <e> <0x2> . } }")
        ev = sub.next_event(timeout=10)
        assert ev is not None and ev["kind"] == "update", ev
        assert ev["trace_id"], "push was not trace-linked"
        tr = rec.trace(ev["trace_id"])
        assert tr is not None and any(
            s["name"] == "subs.eval" for s in tr["spans"]
        ), "push trace_id does not resolve to a subs.eval trace"
        post("/subscribe/cancel?id=" + sid, "")
        # revert so later arms see the initial graph
        post("/query", "mutation { delete { <0x1> <e> <0x2> . } }")
        return {
            "pushed_seq": ev["seq"],
            "trigger_preds": ev["preds"],
            "trace_linked": True,
            "unrelated_pushed_nothing": True,
        }
    finally:
        srv.stop()
        obs.configure(ratio=0.0)
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def run_ivm_bench():
    """Write-rate sweep (ISSUE 12): QPS of the warm two-tier cache as a
    paced writer runs beside the readers — predicate-scoped
    invalidation + delta repair (DGRAPH_TPU_IVM=1, default) against the
    ``store.version``-keyed baseline (=0) where ANY write invalidates
    EVERY cached hop and response.  Writers toggle an UNRELATED
    predicate (the production shape: writes spread across predicates,
    reads concentrate) plus a hot-predicate row that must engage the
    delta-REPAIR path; both assert post-quiesce parity against the
    initial canonical responses, and the subscription demo asserts the
    live-query push contract.  Returns the dict published under "ivm"
    in the headline JSON."""
    from statistics import median

    from dgraph_tpu.utils.metrics import IVM_REPAIRS, QCACHE_RESULT_EVENTS

    clients = int(os.environ.get("BENCH_IVM_CLIENTS", 12))
    secs = float(os.environ.get("BENCH_IVM_SECONDS", 3.0))
    n_nodes = int(os.environ.get("BENCH_IVM_NODES", 8_000))
    deg = int(os.environ.get("BENCH_IVM_DEG", 12))
    rates = [
        float(x)
        for x in os.environ.get("BENCH_IVM_WRITE_RATES", "0,25").split(",")
    ]
    reps = max(1, int(os.environ.get("BENCH_IVM_REPS", 2)))
    store = _serving_store(n_nodes, deg)

    rng = np.random.default_rng(17)
    variants = []
    for _ in range(32):
        seeds = np.unique(rng.integers(1, n_nodes + 1, size=8))
        ul = ", ".join("0x%x" % u for u in seeds)
        variants.append("{ q(func: uid(%s)) { e { c: count(e) } } }" % ul)

    # canonical truth: cache OFF (cache_on=False — the ground truth
    # must come from the cache-less execution path, or a deterministic
    # staleness bug could corrupt canon and probe identically), no
    # writer (the writer always reverts, so every post-quiesce probe
    # must reproduce these bytes)
    _q, _n, canon = _ivm_mode(
        True, store, variants, clients=2, secs=0.3, write_rate=0,
        write_pred="unrelated_w", cache_on=False,
    )

    sweep = []
    for rate in rates:
        on_runs, off_runs = [], []
        for _ in range(reps):
            qps, _n, fin = _ivm_mode(
                True, store, variants, clients, secs, rate, "unrelated_w"
            )
            assert fin == canon, (
                f"IVM-on arm diverged after quiesce at rate {rate}"
            )
            on_runs.append(qps)
            qps, _n, fin = _ivm_mode(
                False, store, variants, clients, secs, rate, "unrelated_w"
            )
            assert fin == canon, (
                f"baseline arm diverged after quiesce at rate {rate}"
            )
            off_runs.append(qps)
        qps_on = median(on_runs)
        qps_off = median(off_runs)
        sweep.append({
            "write_rate": rate,
            "qps_ivm_on": round(qps_on, 1),
            "qps_ivm_off": round(qps_off, 1),
            "ratio": round(qps_on / qps_off, 3) if qps_off else None,
        })

    # hot-predicate row: writes hit the READ predicate, so the win must
    # come from the delta-REPAIR path keeping hop entries warm — assert
    # it actually engaged
    hot_rate = float(os.environ.get("BENCH_IVM_HOT_RATE", "25"))
    rep0 = IVM_REPAIRS.snapshot()
    t2_0 = QCACHE_RESULT_EVENTS.snapshot()
    hot_qps, _n, fin = _ivm_mode(
        True, store, variants, clients, secs, hot_rate, "e"
    )
    assert fin == canon, "hot-write IVM arm diverged after quiesce"
    rep1 = IVM_REPAIRS.snapshot()
    hop_repaired = (
        rep1.get(("hop", "repaired"), 0) - rep0.get(("hop", "repaired"), 0)
    )
    assert hop_repaired > 0, (
        "hot-write arm never engaged the hop repair path"
    )
    t2_1 = QCACHE_RESULT_EVENTS.snapshot()

    nz = [row for row in sweep if row["write_rate"] > 0]
    headline = nz[-1]["ratio"] if nz else None
    return {
        "clients": clients,
        "seconds": secs,
        "reps": reps,
        "qps_vs_write_rate": sweep,
        # the ISSUE 12 headline: warm-cache QPS under writes, scoped
        # invalidation over the global-version baseline
        "write_rate_qps_ratio": headline,
        "hot_write": {
            "write_rate": hot_rate,
            "qps": round(hot_qps, 1),
            "hop_entries_repaired": hop_repaired,
            "tier2_events": {
                k: t2_1.get(k, 0) - t2_0.get(k, 0) for k in t2_1
            },
        },
        "subscription": _ivm_subscription_demo(store),
        "parity_asserted": True,
    }


def _mutation_mode(
    group_commit: bool, clients: int, secs: float, tmp: str,
    fsync_ms: float = 0.0,
):
    """One closed-loop durable-mutation run: ``clients`` threads fire
    single-edge mutations against a fresh DgraphServer over a fresh
    --sync DurableStore (fsync-per-acknowledged-write contract).
    ``group_commit`` flips DGRAPH_TPU_GROUP_COMMIT — the ISSUE 6 A/B:
    per-write fsync inside the write lock vs one shared fsync per convoy
    of concurrent writers.  ``fsync_ms`` > 0 models a production disk by
    arming ``wal.post_flush=delay(ms=...)`` (the failpoint fires inside
    the fsync critical section, so the per-write arm serializes behind
    it while the group-commit convoy shares one delay — same mechanism,
    calibrated medium).  Returns (writes/s, p99_ms, writes, fsyncs)."""
    import json as _json
    import threading

    os.environ["DGRAPH_TPU_GROUP_COMMIT"] = "1" if group_commit else "0"
    os.environ["DGRAPH_TPU_SNAPSHOTTER"] = "0"  # isolate the fsync cost
    from dgraph_tpu.models.wal import DurableStore
    from dgraph_tpu.serve.server import DgraphServer
    from dgraph_tpu.utils.failpoints import fail
    from dgraph_tpu.utils.metrics import (
        GROUP_COMMIT_SYNCS,
        GROUP_COMMIT_WRITES,
    )

    if fsync_ms > 0:
        fail.arm("wal.post_flush", f"delay(ms={fsync_ms:g})")
    store = DurableStore(
        os.path.join(tmp, "gc1" if group_commit else "gc0"),
        sync_writes=True,
    )
    srv = DgraphServer(store)
    srv.start()
    try:
        import http.client

        def post_on(conn, q):
            conn.request("POST", "/query", body=q.encode())
            r = conn.getresponse()
            body = r.read()
            if r.status != 200:
                raise RuntimeError(f"HTTP {r.status}: {body[:200]!r}")
            return _json.loads(body.decode())

        warm = http.client.HTTPConnection("127.0.0.1", srv.port, timeout=30)
        post_on(warm, "mutation { schema { bm: string . } }")
        warm.close()
        w0 = GROUP_COMMIT_WRITES.value()
        s0 = GROUP_COMMIT_SYNCS.value()
        lat_lock = threading.Lock()
        lats: list = []
        errs: list = []
        stop_at = [time.monotonic() + 3600]

        def client(cid: int):
            conn = http.client.HTTPConnection(
                "127.0.0.1", srv.port, timeout=30
            )
            my = []
            uid = (cid + 1) << 24  # disjoint uid ranges per writer
            try:
                while time.monotonic() < stop_at[0]:
                    uid += 1
                    t0 = time.monotonic()
                    post_on(
                        conn,
                        'mutation { set { <0x%x> <bm> "x" . } }' % uid,
                    )
                    my.append(time.monotonic() - t0)
            except Exception as e:
                errs.append(e)
            finally:
                conn.close()
            with lat_lock:
                lats.extend(my)

        ts = [
            threading.Thread(target=client, args=(c,), daemon=True)
            for c in range(clients)
        ]
        stop_at[0] = time.monotonic() + secs
        t0 = time.monotonic()
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=secs + 60)
        wall = time.monotonic() - t0
        if errs:
            raise errs[0]
        if not lats:
            raise RuntimeError("mutation bench made no writes")
        a = np.sort(np.asarray(lats))
        return (
            len(a) / wall,
            float(a[int(0.99 * (len(a) - 1))]) * 1e3,
            GROUP_COMMIT_WRITES.value() - w0,
            GROUP_COMMIT_SYNCS.value() - s0,
        )
    finally:
        srv.stop()
        if fsync_ms > 0:
            fail.disarm("wal.post_flush")
        os.environ.pop("DGRAPH_TPU_GROUP_COMMIT", None)
        os.environ.pop("DGRAPH_TPU_SNAPSHOTTER", None)


def run_mutation_bench():
    """Durable-write A/B (ISSUE 6): --sync mutation throughput with
    concurrent writers, group commit on vs per-write fsync.  Interleaved
    reps + medians, same discipline as the serving bench.  The
    ``fsync_share`` line is the amortization factor the metrics pair
    (dgraph_group_commit_{writes,syncs}_total) exposes in production."""
    import shutil
    import tempfile
    from statistics import median

    clients = int(os.environ.get("BENCH_MUT_CLIENTS", 8))
    secs = float(os.environ.get("BENCH_MUT_SECONDS", 2.0))
    reps = max(1, int(os.environ.get("BENCH_MUT_REPS", 2)))
    # modeled-disk arm: a calibrated fsync latency (EBS/network media
    # run 5-30ms; local NVMe 0.5-3ms).  This CPU container's page-cache
    # fsync is so cheap the exclusive engine section dominates both
    # arms — the modeled arm shows the mechanism at production fsync
    # cost.  0 disables.  (Measured here at 15ms/8 writers: ~2.9x and
    # fsync_share ~2.4, capped by the 2-core host's GIL-contended
    # engine section, not by the commit protocol.)
    fsync_ms = float(os.environ.get("BENCH_MUT_FSYNC_MS", 15.0))
    tmp = tempfile.mkdtemp(prefix="dgraph-bench-mut-")

    def _arm_pair(sub: str, ms: float):
        on_runs, off_runs = [], []
        writes = syncs = 0
        for r in range(reps):
            d = os.path.join(tmp, f"{sub}-r{r}")
            os.makedirs(d, exist_ok=True)
            wps, p99, w, s = _mutation_mode(
                True, clients, secs, d, fsync_ms=ms
            )
            on_runs.append((wps, p99))
            writes += w
            syncs += s
            wps, p99, _w, _s = _mutation_mode(
                False, clients, secs, d, fsync_ms=ms
            )
            off_runs.append((wps, p99))
        wps_on = median(x[0] for x in on_runs)
        wps_off = median(x[0] for x in off_runs)
        return {
            "group_commit": {
                "writes_per_sec": round(wps_on, 1),
                "p99_ms": round(median(x[1] for x in on_runs), 2),
            },
            "per_write_fsync": {
                "writes_per_sec": round(wps_off, 1),
                "p99_ms": round(median(x[1] for x in off_runs), 2),
            },
            # the ISSUE 6 headline: durable writes/s, shared fsync over
            # fsync-per-acknowledged-write, same writer fleet
            "group_commit_ratio": (
                round(wps_on / wps_off, 3) if wps_off else None
            ),
            # >1 = convoys actually shared fsyncs (writes per fsync,
            # group-commit arm only)
            "fsync_share": round(writes / max(syncs, 1), 2),
        }

    try:
        out = {
            "clients": clients,
            "seconds": secs,
            "reps": reps,
            "sync": True,
            "real_disk": _arm_pair("real", 0.0),
        }
        if fsync_ms > 0:
            out["modeled_disk"] = {
                "fsync_ms": fsync_ms,
                **_arm_pair("model", fsync_ms),
            }
        return out
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def run_arms(dev: dict) -> list:
    """Every arm in turn; returns the names of the arms that failed."""
    # measured-cost planner: run (or load) the micro-calibration pass up
    # front so every route decision in this run prices from THIS host's
    # rates, and the calibration file is fresh for the next server boot
    from dgraph_tpu.query import planner

    if planner.enabled():
        planner.boot(measure_now=True)
    # a DGRAPH_TPU_PLANNER=0 arm must not mutate planner state: no
    # measurement pass, no calibration-file overwrite — the operator
    # disabled the planner, the bench honors it

    failed = []

    def arm(name: str, gate: str, fn):
        """One optional arm (``gate``=0 skips it).  A failure lands in
        the JSON beside the arms that ran — and in ``failed``, so the
        process still exits non-zero."""
        if os.environ.get(gate, "1") == "0":
            return None
        try:
            return fn()
        except Exception as e:
            failed.append(name)
            return {"error": f"{type(e).__name__}: {e}"}

    # closed-loop multi-client serving mode (cohort scheduler A/B)
    serving = arm("serving", "BENCH_SERVE", run_serving_bench)
    # durable-mutation A/B (group commit vs per-write fsync)
    durability = arm("durability", "BENCH_MUT", run_mutation_bench)
    # antagonist/victim isolation A/B (PR 11)
    qos_arm = arm("qos", "BENCH_QOS", run_qos_bench)
    # write-rate sweep (ISSUE 12): warm-cache QPS under a paced writer,
    # predicate-scoped invalidation + delta repair vs the
    # store.version-keyed baseline
    ivm_arm = arm("ivm", "BENCH_IVM", run_ivm_bench)
    # planner honesty row: every route decision this process made (the
    # serving arms run in-process) with the measured mispredict rate —
    # future bench rounds show route choice alongside throughput, and a
    # rising mispredict rate means the calibration no longer fits
    cal = planner.calibration_info()
    planner_summary = {
        **planner.mispredict_stats(),
        "decisions_by_route": planner.debug_summary()["counts"],
        "calibration_source": cal["source"],
        "calibrated_dispatch_us": round(cal["rates"]["dispatch_us"], 2),
        "calibrated_device_edge_us": round(
            cal["rates"]["device_edge_us"], 5
        ),
        "calibrated_host_edge_us": round(cal["rates"]["host_edge_us"], 5),
    }
    print(
        json.dumps(
            {
                # multi-client serving A/B (BENCH_SERVE=0 skips;
                # BENCH_CLIENTS / BENCH_SERVE_SECONDS size it)
                "serving": serving,
                # durable-mutation A/B (BENCH_MUT=0 skips;
                # BENCH_MUT_CLIENTS / BENCH_MUT_SECONDS size it)
                "durability": durability,
                # antagonist/victim multi-tenant QoS A/B (BENCH_QOS=0
                # skips; BENCH_QOS_* size it) — victim p99 bounded with
                # QoS on, the leak shown with QoS off
                "qos": qos_arm,
                # IVM write-rate sweep (BENCH_IVM=0 skips; BENCH_IVM_*
                # size it) — QPS-vs-write-rate curve, scoped
                # invalidation over the global-version baseline, repair
                # engagement + live-query push demo
                "ivm": ivm_arm,
                # measured-cost planner (PR 10): per-route decision
                # counts + mispredict rate + the calibrated rates that
                # drove this run's routing
                "planner": planner_summary,
                # self-describing record: the device every number here
                # was taken on
                **dev,
            }
        )
    )
    return failed


def main() -> int:
    dev = device_identity()
    print(f"# backend: {dev}", file=sys.stderr)
    if os.environ.get("BENCH_ONLY") == "qos":
        # standalone qos smoke (CI): the antagonist/victim harness runs
        # without paying for the other arms — the job exists so the
        # harness itself cannot rot
        print(json.dumps({"qos": run_qos_bench(), **dev}))
        return 0
    if os.environ.get("BENCH_ONLY") == "ivm":
        # standalone IVM smoke (CI): the write-rate sweep + live-query
        # push demo at tiny sizes — same rot-guard contract as qos
        print(json.dumps({"ivm": run_ivm_bench(), **dev}))
        return 0
    failed = run_arms(dev)
    if failed:
        print(f"# failed arms: {', '.join(failed)}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
