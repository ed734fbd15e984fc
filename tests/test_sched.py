"""Cohort scheduler (dgraph_tpu/sched/): correctness under concurrency,
flush triggers, admission control, deadline shed, and the compile-count
guard (coalescing must ride PR 1's bounded program cache).

Deterministic where possible: flush-trigger and compile-count tests
drive `CohortScheduler._flush` / knob-tuned scheduler instances
directly instead of racing wall-clock timing.
"""

import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from dgraph_tpu.models import PostingStore
from dgraph_tpu.sched import (
    Cohort,
    CohortScheduler,
    HopMerger,
    SchedDeadlineError,
    SchedOverloadError,
    SchedRequest,
    hop_signature,
)
from dgraph_tpu.serve.server import DgraphServer
from dgraph_tpu.utils.metrics import (
    Histogram,
    MetricsRegistry,
    SCHED_FLUSHES,
)


# ------------------------------------------------------------- histogram


def test_histogram_counts_and_mean():
    h = Histogram("h", (1.0, 10.0, 100.0))
    for v in (0.5, 5.0, 50.0, 500.0, 1.0):
        h.observe(v)
    cum, s, c = h.snapshot()
    # cumulative: ≤1 → {0.5, 1.0}; ≤10 adds 5.0; ≤100 adds 50.0; the
    # tail slot is +Inf (everything)
    assert cum == [2, 3, 4, 5]
    assert c == 5
    assert s == pytest.approx(556.5)
    assert h.mean() == pytest.approx(556.5 / 5)


def test_histogram_prometheus_exposition():
    reg = MetricsRegistry()
    h = reg.histogram("dgraph_test_seconds", (0.01, 0.1))
    h.observe(0.005)
    h.observe(0.05)
    h.observe(5.0)
    text = reg.prometheus_text()
    assert "# TYPE dgraph_test_seconds histogram" in text
    assert 'dgraph_test_seconds_bucket{le="0.01"} 1' in text
    assert 'dgraph_test_seconds_bucket{le="0.1"} 2' in text
    assert 'dgraph_test_seconds_bucket{le="+Inf"} 3' in text
    assert "dgraph_test_seconds_count 3" in text
    assert "dgraph_test_seconds_sum 5.055" in text


# ------------------------------------------------------------- signature


def _parse(text):
    from dgraph_tpu import gql

    return gql.parse(text, None)


def test_signature_buckets_same_shape_together():
    a = hop_signature(_parse("{ q(func: uid(0x1)) { name friend { name } } }"), 7)
    b = hop_signature(_parse("{ q(func: uid(0x2)) { name friend { name } } }"), 7)
    assert a == b  # different uid, same shape family


def test_signature_splits_on_version_preds_and_depth():
    q = "{ q(func: uid(0x1)) { name friend { name } } }"
    base = hop_signature(_parse(q), 7)
    assert hop_signature(_parse(q), 8) != base  # mutation boundary
    assert hop_signature(
        _parse("{ q(func: uid(0x1)) { age friend { name } } }"), 7
    ) != base  # predicate set
    assert hop_signature(
        _parse("{ q(func: uid(0x1)) { name friend { friend { name } } } }"), 7
    ) != base  # hop count


def test_signature_buckets_root_capacity():
    def uids(n):
        return ", ".join("0x%x" % u for u in range(1, n + 1))

    small = hop_signature(_parse("{ q(func: uid(%s)) { name } }" % uids(3)), 1)
    small2 = hop_signature(_parse("{ q(func: uid(%s)) { name } }" % uids(9)), 1)
    big = hop_signature(_parse("{ q(func: uid(%s)) { name } }" % uids(500)), 1)
    assert small == small2  # both inside the floor bucket
    assert small != big     # 500 uids bucket apart from single-digit roots


# ------------------------------------------------------------- hop merger


def _toy_expand(adj):
    """expand_fn over a dict adjacency: deterministic per row, like the
    engine's CSR expansion."""

    def expand(src):
        outs = [np.asarray(adj.get(int(u), []), dtype=np.int64) for u in src]
        seg = np.zeros(len(src) + 1, dtype=np.int64)
        np.cumsum([len(o) for o in outs], out=seg[1:])
        flat = (
            np.concatenate(outs) if outs else np.empty(0, dtype=np.int64)
        )
        return flat, seg

    return expand


def test_hop_merger_exact_vs_solo():
    adj = {1: [10, 11], 2: [], 3: [12], 5: [10, 13, 14]}
    expand = _toy_expand(adj)
    calls = []

    def counted(src):
        calls.append(np.asarray(src))
        return expand(src)

    merger = HopMerger(expected=3, window_s=0.5)
    srcs = [np.array([1, 2]), np.array([3, 5]), np.array([1, 5])]
    results = [None] * 3

    def run(i):
        results[i] = merger.submit(("p", False, 0), srcs[i], counted)

    ts = [threading.Thread(target=run, args=(i,)) for i in range(3)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=10)
    assert len(calls) == 1  # ONE union dispatch for all three members
    assert merger.merged_dispatches == 2
    for i, src in enumerate(srcs):
        want_flat, want_seg = expand(src)
        got_flat, got_seg = results[i]
        assert np.array_equal(got_flat, want_flat), (i, got_flat, want_flat)
        assert np.array_equal(got_seg, want_seg)


def test_hop_merger_leave_unblocks_stragglers():
    merger = HopMerger(expected=2, window_s=30.0)  # window too long to wait out
    merger.leave()  # peer finished before submitting anything
    t0 = time.monotonic()
    flat, seg = merger.submit(
        ("p", False, 0), np.array([1]), _toy_expand({1: [2]})
    )
    assert time.monotonic() - t0 < 5.0  # quorum of 1: no window wait
    assert list(flat) == [2] and list(seg) == [0, 1]


def test_hop_merger_propagates_errors():
    merger = HopMerger(expected=1)

    def boom(src):
        raise ValueError("nope")

    with pytest.raises(ValueError, match="nope"):
        merger.submit(("p", False, 0), np.array([1]), boom)


# ------------------------------------------------------------- fixtures


def _post(addr, body, headers=None, timeout=30):
    req = urllib.request.Request(
        addr + "/query", data=body.encode(), method="POST",
        headers=headers or {},
    )
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.loads(r.read().decode())


SEED = """
mutation { schema {
  name: string @index(exact) .
  age: int @index(int) .
  friend: uid @reverse @count .
} set {
  <0x1> <name> "Ann" .   <0x1> <age> "31" .
  <0x2> <name> "Ben" .   <0x2> <age> "29" .
  <0x3> <name> "Cara" .  <0x3> <age> "40" .
  <0x1> <friend> <0x2> . <0x1> <friend> <0x3> .
  <0x2> <friend> <0x3> . <0x3> <friend> <0x1> .
} }
"""

WORKLOAD = [
    '{ q(func: uid(0x1)) { name friend { name age } } }',
    '{ q(func: uid(0x2)) { name friend { name age } } }',
    '{ q(func: eq(name, "Ann")) { name friend { name } } }',
    '{ q(func: uid(0x3)) { c: count(friend) } }',
    '{ q(func: ge(age, 30), orderasc: age) { name age } }',
    '{ q(func: uid(0x1)) { friend @filter(ge(age, 30)) { name } } }',
]


@pytest.fixture()
def srv():
    server = DgraphServer(PostingStore())
    server.start()
    _post(server.addr, SEED)
    yield server
    server.stop()


# ---------------------------------------------- parity with serial path


def test_scheduled_matches_serial(srv, monkeypatch):
    """N threads firing a mixed workload through the scheduler produce
    responses identical to DGRAPH_TPU_SCHED=0 serial execution."""
    assert srv.scheduler is not None  # default-on gate

    # serial goldens from a scheduler-off server over an identical store
    monkeypatch.setenv("DGRAPH_TPU_SCHED", "0")
    serial = DgraphServer(PostingStore())
    serial.start()
    try:
        assert serial.scheduler is None
        _post(serial.addr, SEED)
        want = {}
        for q in WORKLOAD:
            out = _post(serial.addr, q)
            out.pop("server_latency", None)
            want[q] = out
    finally:
        serial.stop()

    results, errs = [], []

    def client(seed):
        rng = np.random.default_rng(seed)
        try:
            for _ in range(6):
                q = WORKLOAD[int(rng.integers(len(WORKLOAD)))]
                out = _post(srv.addr, q)
                out.pop("server_latency", None)
                results.append((q, out))
        except Exception as e:  # pragma: no cover
            errs.append(e)

    ts = [threading.Thread(target=client, args=(s,)) for s in range(8)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=60)
    assert not errs, errs[:3]
    assert len(results) == 48
    for q, out in results:
        assert out == want[q], q


# ------------------------------------------------------------- triggers


def _flush_reasons():
    return SCHED_FLUSHES.snapshot()


def test_flush_trigger_full(srv):
    sched = CohortScheduler(srv, max_batch=3, flush_ms=60_000, queue_cap=64)
    # idle trigger would fire first; pin the loop's beat way up so only
    # a FULL cohort can flush
    sched.idle_beat_s = 60.0
    try:
        before = _flush_reasons().get("full", 0)
        parsed = [_parse(WORKLOAD[0]) for _ in range(3)]
        outs = [None] * 3

        def go(i):
            outs[i], _ = sched.run(parsed[i])

        ts = [threading.Thread(target=go, args=(i,)) for i in range(3)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=30)
        assert all(o is not None and "q" in o for o in outs)
        assert _flush_reasons().get("full", 0) == before + 1
        assert sched._flushes >= 1
    finally:
        sched.stop()


def test_flush_trigger_deadline(srv):
    sched = CohortScheduler(srv, max_batch=64, flush_ms=30.0, queue_cap=64)
    sched.idle_beat_s = 60.0  # idle can't fire; only the 30ms deadline can
    try:
        before = _flush_reasons().get("deadline", 0)
        t0 = time.monotonic()
        out, _ = sched.run(_parse(WORKLOAD[0]))
        assert "q" in out
        assert time.monotonic() - t0 >= 0.02  # sat out the flush deadline
        assert _flush_reasons().get("deadline", 0) == before + 1
    finally:
        sched.stop()


def test_flush_trigger_idle(srv):
    sched = CohortScheduler(srv, max_batch=64, flush_ms=60_000, queue_cap=64)
    try:
        before = _flush_reasons().get("idle", 0)
        t0 = time.monotonic()
        out, _ = sched.run(_parse(WORKLOAD[0]))
        assert "q" in out
        # flush deadline is a minute out: only the idle trigger explains
        # completing quickly
        assert time.monotonic() - t0 < 30.0
        assert _flush_reasons().get("idle", 0) == before + 1
    finally:
        sched.stop()


# ---------------------------------------------------- admission control


def test_shed_on_overload(srv):
    sched = CohortScheduler(srv, max_batch=64, flush_ms=5.0, queue_cap=3)
    try:
        srv._engine_lock.acquire_write()  # wedge the engine
        try:
            done = []
            ts = []
            for i in range(3):

                def go():
                    try:
                        sched.run(_parse(WORKLOAD[0]))
                        done.append("ok")
                    except Exception as e:  # pragma: no cover
                        done.append(e)

                t = threading.Thread(target=go, daemon=True)
                t.start()
                ts.append(t)
            # wait until all 3 are admitted & in flight (depth == cap)
            for _ in range(200):
                if sched._depth >= 3:
                    break
                time.sleep(0.01)
            assert sched._depth == 3
            with pytest.raises(SchedOverloadError):
                sched.run(_parse(WORKLOAD[0]))
        finally:
            srv._engine_lock.release_write()
        for t in ts:
            t.join(timeout=30)
        assert done == ["ok", "ok", "ok"]  # queued work drains after unwedge
    finally:
        sched.stop()


def test_shed_on_deadline_http(srv):
    """A request whose X-Dgraph-Timeout budget lapses behind a long write
    sheds with HTTP 504 instead of executing late."""
    srv._engine_lock.acquire_write()
    res = {}

    def go():
        try:
            _post(srv.addr, WORKLOAD[0], headers={"X-Dgraph-Timeout": "0.05"})
            res["out"] = "ok"
        except urllib.error.HTTPError as e:
            res["out"] = e.code

    t = threading.Thread(target=go)
    t.start()
    time.sleep(0.5)  # way past the 50ms budget
    srv._engine_lock.release_write()
    t.join(timeout=30)
    assert res["out"] == 504


def test_zero_budget_sheds_immediately(srv):
    """timeout_s <= 0 means the budget is already spent (a gRPC deadline
    that lapsed in transit): shed, never execute."""
    with pytest.raises(SchedDeadlineError):
        srv.scheduler.run(_parse(WORKLOAD[0]), timeout_s=0.0)


def test_overload_http_code(srv):
    """Queue-cap shed surfaces as HTTP 429."""
    srv.scheduler.queue_cap = 0  # everything sheds
    try:
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(srv.addr, WORKLOAD[0])
        assert ei.value.code == 429
    finally:
        srv.scheduler.queue_cap = 256


# --------------------------------------------------- compile-count guard


def test_cohort_compiles_one_program_family(srv, monkeypatch):
    """Coalescing K same-shape requests into one cohort compiles at most
    one program per bucketed shape family: a second identical-shape
    cohort, dispatched afresh, adds ZERO to dgraph_xla_compiles_total."""
    from dgraph_tpu.query import engine as engine_mod
    from dgraph_tpu.utils.metrics import XLA_COMPILES

    srv.engine.expand_device_min = 1  # every level takes the device route
    dispatched = []
    program = engine_mod._packed_expand_csr

    def counted(*args, **kw):
        dispatched.append(1)
        return program(*args, **kw)

    monkeypatch.setattr(engine_mod, "_packed_expand_csr", counted)

    def cohort_of(uids):
        reqs = [
            SchedRequest(_parse("{ q(func: uid(0x%x)) { friend { name } } }" % u))
            for u in uids
        ]
        c = Cohort(("t",))
        c.reqs = reqs
        return c

    c1 = cohort_of([1, 2, 3])
    srv.scheduler._flush(c1, "full")
    for r in c1.reqs:
        out, _ = r.wait()
        assert "q" in out.tree()
    assert dispatched, "the device route did not engage"
    n1, d1 = XLA_COMPILES.value(), len(dispatched)

    # the second cohort must dispatch, not read the first one's hops back
    srv.engine.arenas.hop_cache._c.drop_where(lambda k: True)
    c2 = cohort_of([2, 3, 1])
    srv.scheduler._flush(c2, "full")
    for r in c2.reqs:
        r.wait()
    assert len(dispatched) > d1
    assert XLA_COMPILES.value() == n1  # zero new compiles for the family


# ------------------------------------------------------------- metrics


def test_sched_metrics_exposed(srv):
    for q in WORKLOAD[:3]:
        _post(srv.addr, q)
    with urllib.request.urlopen(
        srv.addr + "/debug/prometheus_metrics", timeout=10
    ) as r:
        text = r.read().decode()
    assert "dgraph_sched_cohort_occupancy_bucket" in text
    assert "dgraph_sched_flushes_total" in text
    assert "dgraph_sched_queue_wait_seconds_bucket" in text
    assert "dgraph_query_latency_seconds_bucket" in text


def test_merged_hops_counted(srv):
    """A deterministic hand-built cohort of same-shape requests must
    merge its hop dispatches (the cross-request coalescing win).
    Merging is gated to device-routed expansions, so force that regime."""
    from dgraph_tpu.utils.metrics import SCHED_MERGED_HOPS

    srv.engine.expand_device_min = 1
    # the three flush threads have to meet inside the merger's window: a
    # window a loaded host cannot outlast (the third arrival closes the
    # group at once, so nobody waits it out)
    srv.scheduler.merge_window_s = 30.0
    before = SCHED_MERGED_HOPS.value()
    reqs = [
        SchedRequest(_parse("{ q(func: uid(0x%x)) { friend { name } } }" % u))
        for u in (1, 2, 3)
    ]
    c = Cohort(("m",))
    c.reqs = reqs
    srv.scheduler._flush(c, "full")
    for r in reqs:
        r.wait()
    assert SCHED_MERGED_HOPS.value() > before


def test_merged_hops_ride_mesh_path(srv):
    """A cohort-merged UNION frontier must ride the row-sharded mesh
    path (parallel/mesh.py::sharded_expand_segments) unchanged — it is
    order-agnostic and deterministic per row, so every member still
    gets its exact segments."""
    if srv.engine.arenas.mesh is None:
        pytest.skip("single-device environment")
    old = srv.engine.arenas.shard_threshold
    srv.engine.arenas.shard_threshold = 1  # every arena shards
    srv.engine.expand_device_min = 1  # and the merger gate opens
    try:
        reqs = [
            SchedRequest(_parse("{ q(func: uid(0x%x)) { friend { name } } }" % u))
            for u in (1, 2, 3)
        ]
        c = Cohort(("mesh",))
        c.reqs = reqs
        srv.scheduler._flush(c, "full")
        outs = [r.wait()[0].tree() for r in reqs]
        assert sorted(f["name"] for f in outs[0]["q"][0]["friend"]) == [
            "Ben", "Cara",
        ]
        assert [f["name"] for f in outs[1]["q"][0]["friend"]] == ["Cara"]
        assert [f["name"] for f in outs[2]["q"][0]["friend"]] == ["Ann"]
    finally:
        srv.engine.arenas.shard_threshold = old


def test_singleflight_coalesces_identical_requests(srv, monkeypatch):
    """Equal-key cohort members (same text/vars/debug) execute ONCE; the
    duplicates share the leader's result — identical to solo output."""
    from dgraph_tpu.query.engine import QueryEngine
    from dgraph_tpu.utils.metrics import SCHED_COALESCED

    runs = []
    orig = QueryEngine.run_parsed

    def counting(self, parsed):
        runs.append(1)
        return orig(self, parsed)

    monkeypatch.setattr(QueryEngine, "run_parsed", counting)
    text = WORKLOAD[0]
    reqs = [
        SchedRequest(_parse(text), key=(text, "", False)) for _ in range(4)
    ]
    c = Cohort(("sf",))
    c.reqs = reqs
    before = SCHED_COALESCED.value()
    srv.scheduler._flush(c, "full")
    answers = [r.wait()[0] for r in reqs]
    assert len(runs) == 1  # one execution for four requests
    assert SCHED_COALESCED.value() == before + 3
    assert all(a is answers[0] for a in answers)  # one Answer, one encoding
    outs = [a.tree() for a in answers]
    assert outs[0]["q"][0]["name"] == "Ann"


def test_singleflight_attaches_to_inflight(srv, monkeypatch):
    """An identical request arriving while its twin EXECUTES (not just
    queues) attaches to it: one engine run serves both."""
    from dgraph_tpu.query.engine import QueryEngine
    from dgraph_tpu.utils.metrics import SCHED_COALESCED

    gate = threading.Event()
    entered = threading.Event()
    runs = []
    orig = QueryEngine.run_parsed

    def gated(self, parsed):
        runs.append(1)
        entered.set()
        assert gate.wait(20)
        return orig(self, parsed)

    monkeypatch.setattr(QueryEngine, "run_parsed", gated)
    text = WORKLOAD[0]
    key = (text, "", False)
    outs = []

    def go():
        outs.append(srv.scheduler.run(_parse(text), key=key)[0])

    t1 = threading.Thread(target=go)
    t1.start()
    assert entered.wait(10)  # leader mid-execution; key registered
    before = SCHED_COALESCED.value()
    t2 = threading.Thread(target=go)
    t2.start()
    for _ in range(200):  # wait for the attach, not a second execution
        if SCHED_COALESCED.value() >= before + 1:
            break
        time.sleep(0.01)
    assert SCHED_COALESCED.value() == before + 1
    gate.set()
    t1.join(timeout=20)
    t2.join(timeout=20)
    assert len(runs) == 1  # the twin never ran
    assert len(outs) == 2 and outs[0] == outs[1]
    assert outs[0]["q"][0]["name"] == "Ann"


# ------------------------------------------------------------- shutdown


def test_stop_fails_queued_requests(srv):
    sched = CohortScheduler(srv, max_batch=64, flush_ms=60_000, queue_cap=64)
    sched.idle_beat_s = 60.0  # nothing flushes on its own
    errs = []

    def go():
        try:
            sched.run(_parse(WORKLOAD[0]))
        except Exception as e:
            errs.append(e)

    t = threading.Thread(target=go)
    t.start()
    for _ in range(200):
        if sched._depth:
            break
        time.sleep(0.01)
    sched.stop()
    t.join(timeout=10)
    assert len(errs) == 1 and isinstance(errs[0], SchedOverloadError)
