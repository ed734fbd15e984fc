"""Device fault domain (utils/devguard.py): unit half for the state
machine / watchdog / classifier / shared half-open helpers, and the
seeded chaos half — wedged-dispatch mid-serving keeps answering
byte-identically via host failover with bounded latency, HBM OOM
triggers LRU-evict + one retry, mesh chip-loss re-plans unsharded, the
device is re-admitted after the failpoint n-cap expires, and
DGRAPH_TPU_DEVGUARD=0 restores legacy behavior.
"""

import json
import threading
import time
import urllib.request

import numpy as np
import pytest

from dgraph_tpu.models import PostingStore
from dgraph_tpu.query import QueryEngine
from dgraph_tpu.utils import devguard
from dgraph_tpu.utils.devguard import (
    DeviceFaultError,
    DeviceGuard,
    DeviceHangError,
    DeviceSickError,
)
from dgraph_tpu.utils.failpoints import fail
from dgraph_tpu.utils.health import CooldownProbeLoop, HalfOpenGate
from dgraph_tpu.utils.metrics import DEVICE_FAILOVER, DEVICE_FAULTS


@pytest.fixture(autouse=True)
def _clean():
    fail.reset()
    devguard.reset_for_tests()
    yield
    fail.reset()
    devguard.reset_for_tests()


def _wait(cond, timeout=10.0, step=0.02):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(step)
    return False


# ------------------------------------------------- shared half-open helpers


def test_half_open_gate_cooldown_then_single_probe():
    g = HalfOpenGate()
    g.open(100.0)
    # cooldown not elapsed: refused with the remaining wait
    ok, retry, tok = g.admit(100.5, 2.0, half_open=False)
    assert (ok, tok) == (False, None) and retry == pytest.approx(1.5)
    # elapsed: exactly one probe slot
    ok, _r, tok = g.admit(102.5, 2.0, half_open=False)
    assert ok and tok is not None
    ok2, _r2, tok2 = g.admit(102.6, 2.0, half_open=True)
    assert not ok2 and tok2 is None
    # release frees the slot for the next prober
    g.release(tok)
    ok3, _r3, tok3 = g.admit(102.7, 2.0, half_open=True)
    assert ok3 and tok3 == tok + 1


def test_half_open_gate_stale_token_release_is_noop():
    g = HalfOpenGate()
    g.open(0.0)
    ok, _r, tok = g.admit(5.0, 2.0, half_open=False)
    assert ok
    g.open(6.0)  # probe failed elsewhere: slot cleared, cooldown restarts
    ok2, _r2, tok2 = g.admit(9.0, 2.0, half_open=False)
    assert ok2
    g.release(tok)  # the OLD prober's release must not free the NEW slot
    ok3, _r3, _t3 = g.admit(9.1, 2.0, half_open=True)
    assert not ok3
    g.release(tok2)
    ok4, _r4, _t4 = g.admit(9.2, 2.0, half_open=True)
    assert ok4


def test_cooldown_probe_loop_waits_one_interval_first():
    calls = []
    active = threading.Event()
    active.set()

    def probe():
        calls.append(time.monotonic())
        return True

    loop = CooldownProbeLoop(probe, 0.15, active.is_set, name="t")
    t0 = time.monotonic()
    assert loop.start()
    assert not loop.start()  # idempotent while alive
    assert _wait(lambda: calls, timeout=5.0)
    assert calls[0] - t0 >= 0.13  # cooldown FIRST, no instant re-prove
    assert len(calls) == 1  # healed: loop exited


def test_cooldown_probe_loop_stops_when_inactive():
    calls = []
    active = threading.Event()
    active.set()
    loop = CooldownProbeLoop(
        lambda: calls.append(1) or False, 0.05, active.is_set, name="t"
    )
    loop.start()
    assert _wait(lambda: len(calls) >= 2, timeout=5.0)
    active.clear()  # latch cleared elsewhere: loop must wind down
    time.sleep(0.12)
    n = len(calls)
    time.sleep(0.15)
    assert len(calls) == n


# ------------------------------------------------------- guard state machine


def test_classifier():
    assert devguard.classify(OSError("boom")) == "transient"
    assert (
        devguard.classify(
            RuntimeError("RESOURCE_EXHAUSTED: Out of memory while ...")
        )
        == "oom"
    )
    assert devguard.classify(ValueError("shape bug")) is None
    try:
        from jax._src.lib import xla_client

        exc = xla_client.XlaRuntimeError("INTERNAL: something")
        assert devguard.classify(exc) == "transient"
    except ImportError:
        pass


class MosaicError(Exception):
    """Stands in for jax's Pallas class of the same name (matched by name)."""


class XlaRuntimeError(RuntimeError):
    """Stands in for jaxlib's runtime error class (matched by name)."""


def _marked_by_a_failed_compile(exc):
    devguard.note_compile_end(0.1, exc)
    return exc


@pytest.mark.parametrize(
    "exc, kind",
    [
        # what the compiler refuses is a bug in the program, whatever
        # class or memory space the refusal names — never failed over
        (NotImplementedError("Unimplemented primitive in Pallas TPU lowering: cumsum"), None),
        (MosaicError("INTERNAL: Mosaic failed to compile TPU kernel: ..."), None),
        (XlaRuntimeError("RESOURCE_EXHAUSTED: XLA:TPU compile permanent error. "
                         "Ran out of memory in memory space hbm"), None),
        (_marked_by_a_failed_compile(XlaRuntimeError(
            "RESOURCE_EXHAUSTED: Allocation (size=268435456) would exceed "
            "memory (size=134217728) ... space=vmem")), None),
        # the same classes at run time stay device faults
        (XlaRuntimeError("RESOURCE_EXHAUSTED: Error allocating device buffer"), "oom"),
        (XlaRuntimeError("INTERNAL: device halted"), "transient"),
    ],
)
def test_classifier_compiler_refusals_are_bugs_not_faults(exc, kind):
    assert devguard.classify(exc) == kind


def test_watchdog_does_not_count_time_spent_compiling():
    """Compiling is host work and outlasts the hang deadline at deployed
    widths: the deadline bounds the dispatch, not the compile before it."""
    g = DeviceGuard("t", hang_ms=150, cooldown_s=10.0)

    def cold_dispatch():
        devguard.note_compile_begin()
        time.sleep(0.6)  # an XLA compile, as obs/device.py reports one
        devguard.note_compile_end(0.6, None)
        time.sleep(0.05)  # the dispatch itself
        return 7

    assert g.run("op", cold_dispatch) == 7
    assert g.state == "healthy" and not g.faults
    # the same wall time without a compile in it is a hang
    with pytest.raises(DeviceHangError):
        g.run("op", lambda: time.sleep(0.65) or 7)


def test_failed_compile_inside_the_guard_raises_unwrapped():
    g = DeviceGuard("t", hang_ms=500, cooldown_s=10.0)

    def refused():
        devguard.note_compile_begin()
        exc = XlaRuntimeError("RESOURCE_EXHAUSTED: ... space=smem")
        devguard.note_compile_end(0.01, exc)
        raise exc

    with pytest.raises(XlaRuntimeError):
        g.run("op", refused)
    assert g.state == "healthy" and not g.faults and g.failovers == 0


def test_suspect_then_sick_then_probe_readmits():
    g = DeviceGuard("t", hang_ms=500, cooldown_s=0.05, sick_after=2)

    def boom():
        raise OSError("injected")

    with pytest.raises(DeviceFaultError):
        g.run("op", boom)
    assert g.state == "suspect"
    # a success between faults resets the consecutive walk
    assert g.run("op", lambda: 1) == 1
    assert g.state == "healthy"
    for _ in range(2):
        with pytest.raises(DeviceFaultError):
            g.run("op", boom)
    assert g.state == "sick"
    with pytest.raises(DeviceSickError):
        g.run("op", lambda: 1)  # shed without dispatch
    assert _wait(lambda: g.state == "healthy", timeout=10.0)
    assert g.status()["readmissions"] == 1
    assert g.run("op", lambda: 2) == 2


def test_hang_latches_sick_within_deadline_and_worker_is_abandoned():
    g = DeviceGuard("t", hang_ms=100, cooldown_s=10.0, sick_after=3)
    t0 = time.monotonic()
    with pytest.raises(DeviceHangError):
        g.run("op", lambda: time.sleep(1.0) or 7)
    elapsed = time.monotonic() - t0
    assert elapsed < 0.8, f"watchdog did not bound the wait ({elapsed:.2f}s)"
    assert g.state == "sick"
    assert g.status()["wedged_workers"] == 1
    assert g.faults.get("hang") == 1


def test_probe_failure_reopens_cooldown():
    state = {"bad": True}

    def probe():
        if state["bad"]:
            raise OSError("still wedged")

    g = DeviceGuard(
        "t", hang_ms=200, cooldown_s=0.03, sick_after=1, probe_fn=probe
    )
    with pytest.raises(DeviceFaultError):
        g.run("op", lambda: (_ for _ in ()).throw(OSError("x")))
    assert g.state == "sick"
    assert _wait(lambda: g.status()["probes_failed"] >= 1, timeout=5.0)
    assert g.state == "sick"
    state["bad"] = False
    assert _wait(lambda: g.state == "healthy", timeout=5.0)


def test_non_device_errors_never_masked():
    g = DeviceGuard("t", hang_ms=500, cooldown_s=1.0)
    with pytest.raises(ValueError):
        g.run("op", lambda: (_ for _ in ()).throw(ValueError("shape bug")))
    assert g.state == "healthy"  # not a device fault, not counted


def test_guard_disabled_is_inline_passthrough(monkeypatch):
    monkeypatch.setenv("DGRAPH_TPU_DEVGUARD", "0")
    g = DeviceGuard("t", hang_ms=1, cooldown_s=1.0)
    tid = threading.get_ident()
    # runs on the CALLER thread (no worker, no deadline)
    assert g.run("op", threading.get_ident) == tid
    with pytest.raises(OSError):
        g.run("op", lambda: (_ for _ in ()).throw(OSError("raw")))
    assert g.state == "healthy"


def test_contextvars_propagate_to_guard_worker():
    import contextvars

    v = contextvars.ContextVar("v", default="unset")
    v.set("request-scoped")
    g = DeviceGuard("t", hang_ms=1000, cooldown_s=1.0)
    assert g.run("op", v.get) == "request-scoped"


# --------------------------------------------------------- failpoint actions


def test_xla_oom_failpoint_classifies_as_oom():
    fail.arm("site.x", "xla_oom(n=1)")
    with pytest.raises(BaseException) as ei:
        fail.point("site.x")
    assert devguard.classify(ei.value) == "oom"
    fail.point("site.x")  # n-cap spent: no-op


def test_hang_failpoint_sleeps():
    fail.arm("site.h", "hang(ms=80,n=1)")
    t0 = time.monotonic()
    fail.point("site.h")
    assert time.monotonic() - t0 >= 0.07
    assert fail.hits("site.h") == 1


# ------------------------------------------------------- engine chaos suite


def _mk_engine(n=40, deg=3):
    st = PostingStore()
    eng = QueryEngine(st)
    rng = np.random.default_rng(11)
    lines = [f'<0x{i:x}> <name> "node {i}" .' for i in range(1, n + 1)]
    for i in range(1, n + 1):
        for d in rng.integers(1, n + 1, size=deg):
            lines.append(f"<0x{i:x}> <link> <0x{d:x}> .")
    eng.run(
        "mutation { schema { name: string @index(term) . "
        "link: uid @reverse @count . } set { %s } }" % "\n".join(lines)
    )
    # force every expansion onto the device route and defeat the hop
    # cache so each run re-dispatches (the chaos point must be HIT)
    eng.expand_device_min = 0
    eng.arenas.hop_cache = None
    return eng


_CHAOS_Q = "{ q(func: uid(0x1)) { name link { name link { name } } } }"


def _strip(out: dict) -> dict:
    return {k: v for k, v in out.items() if k != "degraded"}


@pytest.mark.chaos
def test_wedged_dispatch_serves_byte_identical_with_bounded_latency(
    monkeypatch,
):
    """The acceptance proof: hang(ms=) armed at the hop-dispatch site
    mid-serving → every query returns byte-identical to a fault-free
    run via host failover, latency bounded by the watchdog deadline
    (never the wedge duration), the reroutes are counted, and the
    device is re-admitted once the failpoint n-cap expires."""
    monkeypatch.setenv("DGRAPH_TPU_DEVICE_COOLDOWN_S", "0.1")
    devguard.reset_for_tests()
    # warm with the default (compile-tolerant) deadline, THEN tighten
    # the watchdog: a cold XLA compile is slow, not wedged
    baseline = _mk_engine().run(_CHAOS_Q)
    assert "degraded" not in baseline

    eng = _mk_engine()
    warm = eng.run(_CHAOS_Q)  # compile outside the fault window
    assert _strip(warm) == baseline
    devguard.get().hang_ms = 150
    fail.seed(0)
    fail.arm("device.hop", "hang(ms=1500,n=2)")
    fo0 = DEVICE_FAILOVER.snapshot().get("host", 0)

    t0 = time.monotonic()
    out1 = eng.run(_CHAOS_Q)
    elapsed = time.monotonic() - t0
    assert _strip(out1) == baseline, "failover run diverged from baseline"
    # bounded: one watchdog deadline + host work, never the 1.5s wedge
    assert elapsed < 1.2, f"hang leaked into the serving path ({elapsed:.2f}s)"
    assert eng.stats["device_failover"] >= 1
    assert out1["degraded"]["device"]["failovers"] >= 1
    assert DEVICE_FAILOVER.snapshot().get("host", 0) > fo0
    assert devguard.get().state == "sick"

    # wedge #1 wakes, probe re-admits; the n-cap still has one hang left
    assert _wait(lambda: devguard.get().state == "healthy", timeout=15.0)
    out2 = eng.run(_CHAOS_Q)
    assert _strip(out2) == baseline
    assert _wait(lambda: fail.hits("device.hop") == 2, timeout=15.0)

    # n-cap expired: after re-admission the device serves again, clean
    assert _wait(lambda: devguard.get().state == "healthy", timeout=15.0)
    out3 = eng.run(_CHAOS_Q)
    assert _strip(out3) == baseline
    assert "degraded" not in out3
    assert eng.stats["device_failover"] == 0
    assert eng.stats["device_expand_ms"] > 0, "device route never resumed"
    assert devguard.get().status()["readmissions"] >= 2


@pytest.mark.chaos
def test_hbm_oom_evicts_lru_and_retries_once(monkeypatch):
    monkeypatch.setenv("DGRAPH_TPU_DEVICE_COOLDOWN_S", "0.1")
    devguard.reset_for_tests()
    eng = _mk_engine()
    # warm a SECOND arena so the pressure valve has an LRU victim
    eng.run("{ q(func: uid(0x2)) { ~link { name } } }")
    baseline = eng.run(_CHAOS_Q)
    ev0 = eng.arenas.evictions
    retry0 = DEVICE_FAILOVER.snapshot().get("evict_retry", 0)
    fail.seed(0)
    fail.arm("device.hop", "xla_oom(n=1)")
    out = eng.run(_CHAOS_Q)
    assert _strip(out) == _strip(baseline)
    assert eng.arenas.evictions > ev0, "OOM did not trigger LRU eviction"
    assert DEVICE_FAILOVER.snapshot().get("evict_retry", 0) == retry0 + 1
    # the retry SUCCEEDED: no host failover, no degraded annotation
    assert eng.stats["device_failover"] == 0
    assert "degraded" not in out
    assert devguard.get().state in ("suspect", "healthy")


@pytest.mark.chaos
@pytest.mark.skipif(
    len(__import__("jax").devices()) < 8, reason="needs 8-device mesh"
)
def test_mesh_chip_loss_replans_unsharded(monkeypatch):
    from dgraph_tpu.parallel import make_mesh

    monkeypatch.setenv("DGRAPH_TPU_DEVICE_COOLDOWN_S", "0.1")
    devguard.reset_for_tests()
    plain = _mk_engine()
    baseline = plain.run(_CHAOS_Q)

    st = PostingStore()
    eng = QueryEngine(st, mesh=make_mesh(8, data=2), shard_threshold=1)
    rng = np.random.default_rng(11)
    lines = [f'<0x{i:x}> <name> "node {i}" .' for i in range(1, 41)]
    for i in range(1, 41):
        for d in rng.integers(1, 41, size=3):
            lines.append(f"<0x{i:x}> <link> <0x{d:x}> .")
    eng.run(
        "mutation { schema { name: string @index(term) . "
        "link: uid @reverse @count . } set { %s } }" % "\n".join(lines)
    )
    eng.expand_device_min = 0
    eng.arenas.hop_cache = None
    fail.seed(0)
    fail.arm("device.mesh", "error(n=1)")
    fo0 = DEVICE_FAILOVER.snapshot().get("unsharded", 0)
    out = eng.run(_CHAOS_Q)
    assert _strip(out) == _strip(baseline), "unsharded re-plan diverged"
    assert DEVICE_FAILOVER.snapshot().get("unsharded", 0) > fo0
    # the fault is scoped: the mesh domain took it (later successful
    # mesh hops legitimately walk suspect back to healthy), the
    # single-device dispatch plane never saw a fault
    assert devguard.get("mesh").faults.get("transient", 0) >= 1
    assert devguard.get("device").faults == {}
    # failpoint spent: the next expansion rides the mesh again
    out2 = eng.run(_CHAOS_Q)
    assert _strip(out2) == _strip(baseline)


@pytest.mark.chaos
def test_devguard_off_restores_legacy_behavior(monkeypatch):
    """DGRAPH_TPU_DEVGUARD=0: hangs block inline (and then complete),
    faults propagate raw, responses never carry the annotation."""
    monkeypatch.setenv("DGRAPH_TPU_DEVGUARD", "0")
    devguard.reset_for_tests()
    baseline = _mk_engine().run(_CHAOS_Q)
    eng = _mk_engine()
    fail.seed(0)
    fail.arm("device.hop", "hang(ms=60,n=1)")
    out = eng.run(_CHAOS_Q)  # blocks through the sleep, then serves
    assert out == baseline  # no degraded key, byte-identical
    assert eng.stats["device_failover"] == 0
    # an injected OOM is fatal on the legacy path — exactly as before
    fail.arm("device.hop", "xla_oom(n=1)")
    with pytest.raises(Exception) as ei:
        eng.run(_CHAOS_Q)
    assert "RESOURCE_EXHAUSTED" in str(ei.value)


@pytest.mark.chaos
def test_sick_device_prices_chain_and_mxu_out(monkeypatch):
    """A sick device declines every fused route up front (the planner's
    cost factor armed, the seam check otherwise) — per-level host
    execution serves, byte-identically."""
    monkeypatch.setenv("DGRAPH_TPU_DEVICE_COOLDOWN_S", "60")
    devguard.reset_for_tests()
    eng = _mk_engine(n=60, deg=4)
    q = "{ v as var(func: uid(0x1)) { link { link { l2 as link } } } " \
        "q(func: uid(v, l2), first: 3) { name } }"
    baseline = eng.run(q)
    g = devguard.get()
    g.note_fault("hang", "test")  # latch sick by hand
    assert g.state == "sick"
    assert devguard.cost_factor() > 1.0
    out = eng.run(q)
    assert _strip(out) == _strip(baseline)
    rejects = " ".join(eng.stats["chain_reject"])
    assert "device" in rejects or eng.stats["chain_fused_levels"] == 0


# ----------------------------------------------------------- health surface


def test_health_detail_carries_device_section():
    from dgraph_tpu.serve.server import DgraphServer

    store = PostingStore()
    store.apply_schema("name: string .")
    srv = DgraphServer(store)
    srv.start()
    try:
        with urllib.request.urlopen(
            srv.addr + "/health?detail=1", timeout=30
        ) as r:
            detail = json.loads(r.read().decode())
        assert detail["device"]["enabled"] is True
        # touching the guard registers the domain in the summary
        devguard.get().run("op", lambda: 1)
        with urllib.request.urlopen(
            srv.addr + "/health?detail=1", timeout=30
        ) as r:
            detail = json.loads(r.read().decode())
        dom = detail["device"]["domains"]["device"]
        assert dom["state"] == "healthy"
        assert set(dom) >= {"faults", "failovers", "probes_ok", "hang_ms"}
        with urllib.request.urlopen(
            srv.addr + "/debug/device", timeout=30
        ) as r:
            dbg = json.loads(r.read().decode())
        assert dbg["guard"]["domains"]["device"]["state"] == "healthy"
    finally:
        srv.stop()


# ------------------------------------------- eviction vs in-flight expansion


def test_eviction_races_inflight_expand_never_serves_dropped_arena():
    """drop_arena under HBM budget pressure while another thread's
    expansion holds the arena: id-keyed hop-cache entries must never be
    served for a dropped arena.  The put-after-drop window is real —
    the pin is that a REBUILT arena (potentially recycling the id) can
    never hit a dead entry, because every fill is re-keyed against the
    live arena object and the drop purges the id's entries while the
    object is still alive."""
    st = PostingStore()
    st.apply_schema("a: uid .\nb: uid .")
    for i in range(1, 33):
        st.set_edge("a", i, i + 1)
        st.set_edge("b", i, i + 1)
    eng = QueryEngine(st, arena_budget_bytes=1)  # evict on every build
    am = eng.arenas
    assert am.hop_cache is not None
    src = np.arange(1, 33, dtype=np.int64)

    stop = threading.Event()
    errs = []

    def expander():
        # an in-flight reader holding its arena reference across the
        # eviction window, repeatedly filling/probing the hop cache
        while not stop.is_set():
            try:
                arena = am.data("a")
                out, seg = eng.expander._expand_cached(arena, src, "a")
                # a served entry must always describe THIS arena's data
                if len(out) != 32:
                    errs.append(f"wrong expansion: {len(out)} edges")
            except Exception as e:  # pragma: no cover - surfaced below
                errs.append(repr(e))

    t = threading.Thread(target=expander, daemon=True)
    t.start()
    try:
        deadline = time.monotonic() + 1.5
        while time.monotonic() < deadline:
            am.data("b")  # 1-byte budget: every build evicts the other
            am.data("a")
    finally:
        stop.set()
        t.join(timeout=10)
    assert not errs, errs[:3]
    assert am.evictions > 0
    # freshness survives the race: a write after the storm must never be
    # masked by an entry filled against a dropped arena (version-keyed
    # entries make a same-id alias unservable the moment the store
    # moves; a hit at the SAME version is byte-identical by definition)
    st.set_edge("a", 1, 40)
    arena = am.data("a")
    out, _seg = eng.expander._expand_cached(arena, src, "a")
    assert len(out) == 33, "stale dropped-arena entry served after write"


def test_delta_epoch_flip_races_inflight_expand_never_serves_stale():
    """Delta-driven twin of the eviction race above (PR 16): apply_delta
    mutates the arena IN PLACE — same object, same id(), so the PR-15
    id-purge never fires — and bumps only its epoch.  Entries filled at
    the pre-delta epoch must never satisfy a post-delta probe: every
    writer round adds one edge, so two expansions observing the SAME
    epoch must serve identical edge counts (a stale hit would pair an
    old count with a new epoch), and counts must grow with the epoch."""
    st = PostingStore()
    st.apply_schema("a: uid .")
    for i in range(1, 33):
        st.set_edge("a", i, i + 1)
    eng = QueryEngine(st)
    am = eng.arenas
    assert am.hop_cache is not None
    src = np.arange(1, 33, dtype=np.int64)

    stop = threading.Event()
    errs = []

    # (id(arena), epoch) -> edge count served at that epoch.  An epoch is
    # one arena's: a full rebuild (a journal window lost to the storm)
    # hands out a NEW arena that counts from 0 again.  ``alive`` keeps
    # every arena met, so that no id is handed out twice
    seen = {}
    alive = {}

    def expander():
        while not stop.is_set():
            try:
                arena = am.data("a")
                e0 = arena.epoch
                out, _seg = eng.expander._expand_cached(arena, src, "a")
                if arena.epoch != e0:
                    continue  # flip mid-read: no epoch to pin it to
                n = len(out)
                alive[id(arena)] = arena
                want = seen.setdefault((id(arena), e0), n)
                if n != want:
                    errs.append(
                        f"epoch {e0} served {n} edges, previously {want}"
                    )
                prior = [
                    v for (a, k), v in seen.items()
                    if a == id(arena) and k < e0
                ]
                if prior and n < max(prior):
                    errs.append(
                        f"epoch {e0} served {n} < earlier epoch's "
                        f"{max(prior)}"
                    )
            except Exception as e:  # pragma: no cover - surfaced below
                errs.append(repr(e))

    t = threading.Thread(target=expander, daemon=True)
    t.start()
    flips = 0
    try:
        deadline = time.monotonic() + 1.5
        while time.monotonic() < deadline:
            st.set_edge("a", 1, 1000 + flips)
            am.data("a")  # refresh applies the delta: epoch flip in place
            flips += 1
    finally:
        stop.set()
        t.join(timeout=10)
    assert not errs, errs[:3]
    assert flips > 0
    # post-storm: the cache holds NOTHING keyed before the last flip
    # (journal windows may coalesce writer rounds, so the final epoch
    # can trail `flips` — but every written edge must be served), and a
    # fresh expansion reflects every write
    a = am.data("a")
    assert a.epoch > 0
    stale = am.hop_cache._c.drop_where(
        lambda k: k[0] == id(a) and k[3] != a.epoch
    )
    assert stale == 0, f"{stale} stale-epoch entries survived the storm"
    out, _seg = eng.expander._expand_cached(a, src, "a")
    assert len(out) == 32 + flips, "stale-epoch entry served after storm"
