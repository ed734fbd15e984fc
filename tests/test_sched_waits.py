"""What a queued request waits for (PR 38): the ``queue`` stage split by
cause — ``dgraph_sched_wait_us_total{batch,worker,writer,start,twin}`` —
the engine lock's readers-barred clock, the flush workers' busy time, the
``dgraph.read_lock`` annotation, and the benchmark's readers of them.

Each outcome a request can have in the scheduler (run, in-cohort twin,
twin attached in flight, deadline shed, overload shed) books causes that
add up to its ``queue`` stage; a writer's hold lands under ``writer`` and
a busy pool under ``worker``.
"""

import glob
import os
import sys
import threading
import time

import pytest

from dgraph_tpu import gql
from dgraph_tpu.models import PostingStore
from dgraph_tpu.obs import ledger as _ledger
from dgraph_tpu.sched import (
    CohortScheduler,
    SchedDeadlineError,
    SchedOverloadError,
)
from dgraph_tpu.serve.server import DgraphServer
from dgraph_tpu.utils.metrics import (
    ENGINE_LOCK_BARRED_US,
    LEDGER_STAGE_US,
    SCHED_WAIT_US,
    SCHED_WORKER_BUSY_US,
    SCHED_WORKERS,
    metrics,
)
from dgraph_tpu.utils.rwlock import RWLock

BENCH = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark"
)
sys.path.insert(0, BENCH)

import run  # noqa: E402
import trafficgen  # noqa: E402

CAUSES = _ledger.WAIT_CAUSES
Q = "{ q(func: uid(0x1)) { uid friend { uid } } }"
Q2 = "{ q(func: uid(0x2)) { uid } }"


@pytest.fixture()
def srv():
    store = PostingStore()
    store.apply_schema("friend: [uid] .")
    store.set_edge("friend", 1, 2)
    store.set_edge("friend", 1, 3)
    server = DgraphServer(store)
    yield server
    server.stop()


def _parse(text):
    return gql.parse(text, None)


def _run(sched, text, led, **kw):
    """``sched.run`` on this thread under ``led``; the outcome or the
    exception raised."""
    tok = _ledger.activate(led)
    try:
        return sched.run(_parse(text), **kw)
    except Exception as e:  # noqa: BLE001 — the outcome under test
        return e
    finally:
        _ledger.deactivate(tok)


def _in_threads(fns):
    out = [None] * len(fns)

    def go(i):
        out[i] = fns[i]()

    ts = [threading.Thread(target=go, args=(i,)) for i in range(len(fns))]
    for t in ts:
        t.start()
    return ts, out


def _join(ts):
    for t in ts:
        t.join(timeout=30)
        assert not t.is_alive()


def _assert_partition(led):
    """The causes sum to the ``queue`` stage within 1 us, and drained
    they sum to exactly the stage's own microseconds."""
    queue_ms = led.stages["queue"]
    assert queue_ms > 0
    assert all(v >= 0 for v in led.waits.values()), led.waits
    assert abs(sum(led.waits.values()) - queue_ms) < 1e-3, (led.waits, queue_ms)
    waits = dict(led.waits)
    before_w, before_q = SCHED_WAIT_US.snapshot(), LEDGER_STAGE_US.snapshot()
    _ledger.finish(led)
    after_w, after_q = SCHED_WAIT_US.snapshot(), LEDGER_STAGE_US.snapshot()
    drained = sum(after_w[c] - before_w.get(c, 0) for c in CAUSES)
    assert drained == after_q["queue"] - before_q.get("queue", 0)
    return waits


def _wedged(srv, hold_s):
    """A writer that holds the exclusive side for ``hold_s``."""
    got = threading.Event()

    def write():
        srv._engine_lock.acquire_write()
        got.set()
        time.sleep(hold_s)
        srv._engine_lock.release_write()

    t = threading.Thread(target=write)
    t.start()
    assert got.wait(10)
    return t


# ------------------------------------------------- causes sum to the stage


def test_every_label_is_there_from_boot():
    text = metrics.prometheus_text()
    for c in CAUSES:
        assert f'dgraph_sched_wait_us_total{{cause="{c}"}}' in text
    assert "dgraph_engine_lock_barred_us_total " in text
    assert "# TYPE dgraph_engine_lock_barred_us_total counter" in text


def test_a_run_partitions_its_queue_wait(srv):
    sched = CohortScheduler(srv, max_batch=64, flush_ms=2.0, queue_cap=64)
    try:
        led = _ledger.start()
        out = _run(sched, Q, led)
        assert not isinstance(out, Exception), out
        waits = _assert_partition(led)
        assert waits["twin"] == 0.0
        assert waits["start"] > 0.0
    finally:
        sched.stop()


def test_an_in_cohort_twin_partitions_and_books_twin(srv):
    sched = CohortScheduler(srv, max_batch=2, flush_ms=60_000, queue_cap=64)
    sched.idle_beat_s = 60.0    # only a FULL cohort flushes: both ride one
    try:
        leds = [_ledger.start(), _ledger.start()]
        ts, outs = _in_threads(
            [lambda i=i: _run(sched, Q, leds[i], key="same") for i in range(2)]
        )
        _join(ts)
        assert not any(isinstance(o, Exception) for o in outs), outs
        waits = [_assert_partition(led) for led in leds]
        twins = [w for w in waits if w["twin"] > 0]
        assert len(twins) == 1
        assert twins[0]["start"] == 0.0
        # filled at the second arrival: its batch phase is nothing
        assert min(w["batch"] for w in waits) < 1.0
    finally:
        sched.stop()


def test_a_twin_attached_in_flight_books_all_of_it_as_twin(srv):
    sched = CohortScheduler(srv, max_batch=64, flush_ms=2.0, queue_cap=64)
    try:
        writer = _wedged(srv, 0.3)
        lead, follow = _ledger.start(), _ledger.start()
        ts, outs = _in_threads([lambda: _run(sched, Q, lead, key="same")])
        # the leader's flush registers it in flight, then blocks on the lock
        for _ in range(500):
            if "same" in sched._inflight:
                break
            time.sleep(0.002)
        assert "same" in sched._inflight
        ts2, outs2 = _in_threads([lambda: _run(sched, Q, follow, key="same")])
        _join(ts + ts2)
        writer.join(timeout=30)
        assert not isinstance(outs[0], Exception), outs
        assert not isinstance(outs2[0], Exception), outs2
        assert follow.coalesced == 1
        waits = _assert_partition(follow)
        assert waits["twin"] == pytest.approx(sum(waits.values()))
        lead_waits = _assert_partition(lead)
        assert lead_waits["writer"] > 200.0
    finally:
        sched.stop()


def test_a_deadline_shed_partitions_what_elapsed(srv):
    sched = CohortScheduler(srv, max_batch=64, flush_ms=2.0, queue_cap=64)
    try:
        writer = _wedged(srv, 0.2)
        led = _ledger.start()
        out = _run(sched, Q, led, timeout_s=0.05)
        writer.join(timeout=30)
        assert isinstance(out, SchedDeadlineError), out
        waits = _assert_partition(led)
        assert waits["writer"] > 100.0
    finally:
        sched.stop()


def test_a_zero_budget_shed_before_the_lock_partitions(srv):
    sched = CohortScheduler(srv, max_batch=64, flush_ms=2.0, queue_cap=64)
    try:
        led = _ledger.start()
        out = _run(sched, Q, led, timeout_s=0.0)
        assert isinstance(out, SchedDeadlineError), out
        waits = _assert_partition(led)
        assert waits["twin"] == 0.0
    finally:
        sched.stop()


def test_an_overload_shed_books_its_instant_as_batch(srv):
    sched = CohortScheduler(srv, max_batch=64, flush_ms=2.0, queue_cap=0)
    try:
        led = _ledger.start()
        out = _run(sched, Q, led)
        assert isinstance(out, SchedOverloadError), out
        waits = _assert_partition(led)
        assert waits["batch"] == pytest.approx(sum(waits.values()))
    finally:
        sched.stop()


def test_nothing_is_booked_without_a_ledger(srv, monkeypatch):
    sched = CohortScheduler(srv, max_batch=64, flush_ms=2.0, queue_cap=64)
    try:
        before = SCHED_WAIT_US.snapshot()
        out, _ = sched.run(_parse(Q))
        assert "q" in out
        assert SCHED_WAIT_US.snapshot() == before
    finally:
        sched.stop()


# --------------------------------------------------- writer against worker


def test_a_writers_hold_is_booked_under_writer_not_worker(srv):
    sched = CohortScheduler(srv, max_batch=64, flush_ms=2.0, queue_cap=64)
    try:
        led = _ledger.start()
        srv._engine_lock.acquire_write()
        try:
            ts, outs = _in_threads([lambda: _run(sched, Q, led)])
            time.sleep(0.05)
        finally:
            srv._engine_lock.release_write()
        _join(ts)
        assert not isinstance(outs[0], Exception), outs
        waits = _assert_partition(led)
        assert waits["writer"] >= 45.0, waits
        assert waits["worker"] < 1.0, waits
    finally:
        sched.stop()


def test_a_busy_pool_is_booked_under_worker(srv):
    sched = CohortScheduler(
        srv, max_batch=64, flush_ms=2.0, queue_cap=64, concurrency=1
    )
    assert SCHED_WORKERS.value() == 1
    slow = threading.Event()
    run_one = sched._run_one

    def held(req, merger, flush_span=None):
        if req.key == "slow":
            slow.set()
            time.sleep(0.05)
        return run_one(req, merger, flush_span)

    sched._run_one = held
    try:
        busy0 = SCHED_WORKER_BUSY_US.value()
        first = _ledger.start()
        ts, outs = _in_threads([lambda: _run(sched, Q, first, key="slow")])
        assert slow.wait(10)
        led = _ledger.start()
        out = _run(sched, Q2, led)
        _join(ts)
        assert not isinstance(out, Exception), out
        waits = _assert_partition(led)
        _assert_partition(first)
        assert waits["worker"] >= 35.0, waits
        assert waits["writer"] == 0.0
        assert SCHED_WORKER_BUSY_US.value() - busy0 >= 50_000
    finally:
        sched.stop()


# ------------------------------------------------------- the barred clock


def test_the_barred_clock_counts_an_open_interval_to_the_read():
    lock = RWLock()
    assert lock.barred_s() == 0.0
    lock.acquire_write()
    b1 = lock.barred_s()
    time.sleep(0.02)
    b2 = lock.barred_s()
    assert b2 - b1 >= 0.019
    lock.release_write()
    b3 = lock.barred_s()
    time.sleep(0.02)
    assert lock.barred_s() == b3 >= b2


def test_a_waiting_writer_bars_readers_too():
    lock = RWLock()
    lock.acquire_read()
    waiting = threading.Thread(
        target=lambda: (lock.acquire_write(), lock.release_write())
    )
    waiting.start()
    for _ in range(500):
        if lock._writers_waiting:
            break
        time.sleep(0.001)
    b0 = lock.barred_s()
    time.sleep(0.02)
    assert lock.barred_s() - b0 >= 0.019
    lock.release_read()
    waiting.join(timeout=10)
    assert not waiting.is_alive()


def test_the_barred_clock_is_monotone_under_racing_writers():
    lock = RWLock()
    stop = threading.Event()
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)

    def churn():
        while not stop.is_set():
            lock.acquire_write()
            lock.release_write()
            lock.acquire_read()
            lock.release_read()

    ts = [threading.Thread(target=churn) for _ in range(4)]
    try:
        for t in ts:
            t.start()
        last = 0.0
        for _ in range(20_000):
            b = lock.barred_s()
            assert b >= last
            last = b
    finally:
        stop.set()
        for t in ts:
            t.join(timeout=10)
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in ts)
    assert last > 0.0


def test_the_servers_lock_is_the_exported_counter(srv):
    before = ENGINE_LOCK_BARRED_US.value()
    srv._engine_lock.acquire_write()
    try:
        time.sleep(0.02)
        assert ENGINE_LOCK_BARRED_US.value() - before >= 19_000
    finally:
        srv._engine_lock.release_write()


# ---------------------------------------------------- the profiler's clock


def test_a_profiler_session_shows_the_read_lock_wait(srv, tmp_path):
    import jax
    from jax.profiler import ProfileData

    sched = CohortScheduler(srv, max_batch=64, flush_ms=2.0, queue_cap=64)
    try:
        sched.run(_parse(Q))   # compile outside the session
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            writer = _wedged(srv, 0.03)
            out, _ = sched.run(_parse(Q2))
            writer.join(timeout=30)
        finally:
            jax.profiler.stop_trace()
        assert "q" in out
    finally:
        sched.stop()
    (pb,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
    waits = [
        e.duration_ns
        for plane in ProfileData.from_file(pb).planes
        if plane.name.startswith("/host:")
        for ln in plane.lines
        for e in ln.events
        if e.name == "dgraph.read_lock"
    ]
    assert waits, "no dgraph.read_lock on the host plane"
    assert max(waits) >= 20e6     # the wait behind the writer, not the hold


# ------------------------------------------------------ the benchmark's readers


def _obs(before, after, window_s=10.0):
    return run.Observed(counters_before=before, counters_after=after,
                        window_s=window_s)


def _reader(name):
    return trafficgen.load_module("metrics", name).read


WAIT_READERS = {
    "queue_worker_ms": "worker",
    "queue_batch_ms": "batch",
    "queue_writer_ms": "writer",
    "queue_twin_ms": "twin",
}


@pytest.mark.parametrize("name", sorted(WAIT_READERS))
def test_a_wait_reader_is_a_mean_ms_a_request(name):
    cause = WAIT_READERS[name]
    q = {"dgraph_num_queries_total": {"": 100.0}}
    parent = _obs(q, {"dgraph_num_queries_total": {"": 300.0}})
    assert _reader(name)(parent) is None     # the parent has no family
    zero = {c: 0.0 for c in CAUSES}
    grown = dict(zero, **{cause: 50_000.0})
    obs = _obs({**q, "dgraph_sched_wait_us_total": zero},
               {"dgraph_num_queries_total": {"": 300.0},
                "dgraph_sched_wait_us_total": grown})
    assert _reader(name)(obs) == pytest.approx(50_000 / 1e3 / 200)
    idle = _obs({**q, "dgraph_sched_wait_us_total": zero},
                {**q, "dgraph_sched_wait_us_total": zero})
    assert _reader(name)(idle) is None       # no request in the window


UP = "dgraph_uptime_seconds"


def test_flush_worker_busy_share_reads_the_pool():
    read = _reader("flush_worker_busy_share")
    parent = {UP: {"": 110.0}, "dgraph_num_queries_total": {"": 5.0}}
    assert read(_obs({UP: {"": 100.0}}, parent)) is None
    # the scrapes lie 10.4 s apart round a 10 s client window: the share
    # is over the scrapes' interval, the counter's own
    obs = _obs({UP: {"": 100.0}, "dgraph_sched_worker_busy_us_total": {"": 1e6}},
               {UP: {"": 110.4}, "dgraph_sched_worker_busy_us_total": {"": 21.8e6},
                "dgraph_sched_workers": {"": 2.0}})
    assert read(obs) == pytest.approx(100.0)


def test_readers_barred_share_reads_the_lock():
    read = _reader("readers_barred_share")
    parent = {UP: {"": 110.0}, "dgraph_num_queries_total": {"": 5.0}}
    assert read(_obs({UP: {"": 100.0}}, parent)) is None
    obs = _obs({UP: {"": 100.0}, "dgraph_engine_lock_barred_us_total": {"": 2e6}},
               {UP: {"": 110.0}, "dgraph_engine_lock_barred_us_total": {"": 6.5e6}})
    assert read(obs) == pytest.approx(45.0)
