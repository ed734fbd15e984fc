"""Measured-cost adaptive planner (PR 10): calibration lifecycle, route
break-evens, the BENCH21M chain_reject regression pin, the
DGRAPH_TPU_PLANNER=0 byte-identical kill switch through the full serving
path, adaptive cohort bounds, and the repeat-shape compile guard."""

import json
import urllib.request
from dataclasses import replace

import jax
import numpy as np
import pytest

from dgraph_tpu.models import PostingStore
from dgraph_tpu.models.types import TypeID, TypedValue
from dgraph_tpu.query import planner
from dgraph_tpu.query.engine import QueryEngine
from dgraph_tpu.utils import planconfig
from dgraph_tpu.utils.calibrate import PRIORS, load, measure, save


@pytest.fixture(autouse=True)
def _fresh_planner(monkeypatch):
    """Each test starts from priors with an empty ring, and never reads
    a calibration file another test (or a bench run) persisted."""
    monkeypatch.setenv("DGRAPH_TPU_CALIBRATION_FILE", "")
    planner._reset_for_tests()
    yield
    planner._reset_for_tests()


class _Eng:
    """chain_threshold carrier for decision-only tests."""

    chain_threshold = planconfig.CHAIN_THRESHOLD_DEFAULT


# --------------------------------------------------------------- planconfig


def test_planconfig_defaults_and_override_detection(monkeypatch):
    # the two historical 262144 twins resolve to ONE documented default
    assert planconfig.chain_threshold() == 262144
    assert planconfig.kway_device_min() == 262144
    assert planconfig.expand_device_min() == 262144
    assert planconfig.chain_max_capc() == 1 << 21
    assert planconfig.mask_max_lanes() == 1 << 22
    assert not planconfig.overridden("DGRAPH_TPU_CHAIN_THRESHOLD")
    monkeypatch.setenv("DGRAPH_TPU_CHAIN_THRESHOLD", "1024")
    assert planconfig.overridden("DGRAPH_TPU_CHAIN_THRESHOLD")
    assert planconfig.chain_threshold() == 1024
    # a typo'd knob falls back instead of crashing boot
    monkeypatch.setenv("DGRAPH_TPU_KWAY_DEVICE_MIN", "lots")
    assert planconfig.kway_device_min() == 262144


# --------------------------------------------------------------- calibration


def test_calibration_file_roundtrip(tmp_path):
    path = str(tmp_path / "calib.json")
    cal = replace(
        PRIORS, dispatch_us=55.5, host_edge_us=0.011, backend="cpu",
        source="measured", measured_at=123.0,
    )
    save(cal, path)
    back = load(path, backend="cpu")
    assert back is not None and back.source == "file"
    assert back.dispatch_us == 55.5 and back.host_edge_us == 0.011
    assert back.rates() == cal.rates()
    # a calibration from another backend must never price this boot
    assert load(path, backend="tpu") is None
    # corrupt / wrong-version files degrade to None, not a crash
    (tmp_path / "calib.json").write_text("{not json")
    assert load(path, backend="cpu") is None
    (tmp_path / "calib.json").write_text(json.dumps({"version": 99}))
    assert load(path, backend="cpu") is None


def test_boot_loads_persisted_calibration(tmp_path, monkeypatch):
    path = str(tmp_path / "calib.json")
    save(
        replace(
            PRIORS, dispatch_us=42.0, backend=jax.default_backend(),
            source="measured",
        ),
        path,
    )
    monkeypatch.setenv("DGRAPH_TPU_CALIBRATION_FILE", path)
    cal = planner.boot()
    assert cal.source == "file" and cal.dispatch_us == 42.0
    assert planner.calibration_info()["rates"]["dispatch_us"] == 42.0


@pytest.mark.parametrize(
    "backend, want_source", [("cpu", "prior"), ("tpu", "measured")]
)
def test_boot_without_a_file_measures_on_every_backend_but_cpu(
    tmp_path, monkeypatch, backend, want_source
):
    """The shipped priors are CPU-backend numbers: a cpu boot serves on
    them (and compiles nothing), any other backend measures at boot and
    persists — rates taken on a CPU never price routes on a chip."""
    path = tmp_path / "calib.json"
    monkeypatch.setenv("DGRAPH_TPU_CALIBRATION_FILE", str(path))
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    ran = []

    def fake_measure():
        ran.append(1)
        return replace(PRIORS, dispatch_us=7.0, backend=backend, source="measured")

    monkeypatch.setattr(planner, "measure", fake_measure)
    cal = planner.boot()
    assert cal.source == want_source
    assert len(ran) == (want_source == "measured")
    assert path.exists() == (want_source == "measured")
    if want_source == "measured":
        # the next boot on that backend is warm: the file, no second pass
        assert planner.boot().source == "file" and len(ran) == 1


def test_micro_calibration_measures_positive_rates():
    cal = measure(edges=1 << 12, reps=2)
    assert cal.source == "measured" and cal.backend == jax.default_backend()
    for k, v in cal.rates().items():
        assert v > 0, k
    # sanity: a dispatch costs more than one gathered edge
    assert cal.dispatch_us > cal.device_edge_us


# ------------------------------------------------------------ route decisions


def test_chain_route_break_even_and_overrides(monkeypatch):
    # the BENCH21M shape: 168342 est edges sat below the static 262144
    # and must now fuse
    fuse, dec = planner.chain_route(_Eng(), 168342, 3)
    assert fuse and dec["route"] == "chain"
    assert dec["est_chosen_us"] < dec["est_other_us"]
    # small chains keep per-level execution
    fuse, dec = planner.chain_route(_Eng(), 1000, 3)
    assert not fuse and dec["route"] == "perlevel"
    # kill switch: static threshold, no decision dict (legacy messages)
    monkeypatch.setenv("DGRAPH_TPU_PLANNER", "0")
    fuse, dec = planner.chain_route(_Eng(), 168342, 3)
    assert not fuse and dec is None
    monkeypatch.delenv("DGRAPH_TPU_PLANNER")
    # a pinned env knob is an operator override even with the planner on
    monkeypatch.setenv("DGRAPH_TPU_CHAIN_THRESHOLD", "262144")
    fuse, dec = planner.chain_route(_Eng(), 168342, 3)
    assert not fuse and dec is None
    monkeypatch.delenv("DGRAPH_TPU_CHAIN_THRESHOLD")
    # ...and so is a runtime assignment (tests/bench arms pin the gate)
    e = _Eng()
    e.chain_threshold = 0
    fuse, dec = planner.chain_route(e, 10, 3)
    assert fuse and dec is None


def test_expand_kway_merge_break_evens(monkeypatch):
    dflt = planconfig.EXPAND_DEVICE_MIN_DEFAULT
    dev, dec = planner.expand_route(500, dflt)
    assert not dev and dec["route"] == "host"
    dev, dec = planner.expand_route(50_000, dflt)
    assert dev and dec["route"] == "device" and dec["units"] == 50_000
    # runtime-assigned min restores the static compare
    dev, dec = planner.expand_route(50_000, 1 << 62)
    assert not dev and dec is None
    assert not planner.merge_gate(500.0, dflt)
    assert planner.merge_gate(50_000.0, dflt)
    use, dec = planner.kway_route(1_000, 3)
    assert use is False and dec["route"] == "host"
    use, dec = planner.kway_route(100_000, 3)
    assert use is True and dec["route"] == "device"
    # pinned kway knob → the caller's static gate
    monkeypatch.setenv("DGRAPH_TPU_KWAY_DEVICE_MIN", "7")
    assert planner.kway_route(100_000, 3) == (None, None)


def test_note_outcome_refines_rates_and_counts_mispredicts():
    r0 = planner.rates()["host_edge_us"]
    dec = {
        "kind": "expand", "route": "host", "units": 100_000,
        "est_chosen_us": 100.0, "est_other_us": 200.0,
    }
    planner.record(None, dec)
    # measured latency lands past the REJECTED route's estimate: the
    # model picked the wrong side → mispredict + rate refinement
    planner.note_outcome(dec, 5000.0)
    assert dec.get("mispredict") is True
    assert dec["actual_us"] == 5000.0
    stats = planner.mispredict_stats()
    assert stats["decisions"] == 1 and stats["mispredicts"] == 1
    assert stats["mispredict_rate"] == 1.0
    assert planner.rates()["host_edge_us"] != r0  # EWMA moved
    # dispatch-dominated sizes get no verdict (no honest rate at 100 els)
    small = {
        "kind": "expand", "route": "host", "units": 100,
        "est_chosen_us": 1.0, "est_other_us": 2.0,
    }
    planner.note_outcome(small, 5000.0)
    assert "mispredict" not in small


# ------------------------------------------- the BENCH21M 3-hop regression


def _chain_store(n=1024, deg=55, seed=11, spread=1):
    """One uid predicate whose 3-level chain estimates ≈ 3·n·deg edges —
    tuned to land the BENCH21M shape's ~168k, ABOVE the calibrated
    break-even and BELOW the old static 262144.  ``spread`` spaces the
    node uids across a wide universe, the way a 21M-quad corpus does —
    which is exactly what prices the MXU mask tier out (mask lanes over
    DGRAPH_TPU_MXU_MASK_MAX) and leaves the chain scan as the winning
    route, matching the real BENCH21M condition."""
    rng = np.random.default_rng(seed)
    store = PostingStore()
    store.apply_schema("f: uid .\nname: string @index(term) .")
    uids = 1 + np.arange(n, dtype=np.int64) * spread
    for i in range(n):
        u = int(uids[i])
        store.set_value("name", u, TypedValue(TypeID.STRING, f"node {u}"))
        for v in rng.choice(uids, size=deg, replace=False):
            store.set_edge("f", u, int(v))
    return store


CHAIN_Q = "{ var(func: has(f)) { f { f { f } } } }"


def test_bench21m_3hop_shape_routes_to_chain_scan(monkeypatch):
    """The regression pin: the 3-hop ~168k-fan-out shape the static
    threshold rejected (`chain_reject: "fan-out estimate 168342 below
    threshold 262144"`, BENCH21M r5) must ride the chain scan under the
    calibrated model — and still reject byte-identically with the
    legacy message under DGRAPH_TPU_PLANNER=0."""
    store = _chain_store(spread=9777)  # ~10M-uid universe, like the corpus
    eng = QueryEngine(store)
    eng.run(CHAIN_Q)
    assert eng.stats["chain_fused_levels"] == 3, eng.stats["chain_reject"]
    decs = [d for d in eng.stats["planner"] if d["kind"] == "chain"]
    assert decs and decs[0]["route"] == "chain"
    # the pinned shape: between the calibrated break-even and the old gate
    assert 100_000 < decs[0]["units"] < 262144
    assert decs[0]["est_chosen_us"] < decs[0]["est_other_us"]

    monkeypatch.setenv("DGRAPH_TPU_PLANNER", "0")
    eng0 = QueryEngine(store)
    eng0.run(CHAIN_Q)
    assert eng0.stats["chain_fused_levels"] == 0
    assert any(
        "below threshold 262144" in r for r in eng0.stats["chain_reject"]
    ), eng0.stats["chain_reject"]
    assert "planner" not in eng0.stats  # zero planner traffic at =0


class _CompileCounter:
    """Counts XLA compiles via jax.monitoring while active (the PR-4
    budget hook's mechanism, scoped to a with-block)."""

    _active = None
    _installed = False

    def __init__(self):
        self.compiles = 0

    @classmethod
    def _install(cls):
        if cls._installed:
            return

        def on_event(event, duration, **kw):
            c = cls._active
            if c is not None and event.endswith("backend_compile_duration"):
                c.compiles += 1

        jax.monitoring.register_event_duration_secs_listener(on_event)
        cls._installed = True

    def __enter__(self):
        type(self)._install()
        type(self)._active = self
        return self

    def __exit__(self, *exc):
        type(self)._active = None
        return False


def test_repeat_same_shape_query_adds_zero_programs():
    """Planner decisions are deterministic for a steady shape: the
    second run of the planner-routed chain compiles NOTHING new."""
    eng = QueryEngine(_chain_store(spread=9777))
    eng.run(CHAIN_Q)
    assert eng.stats["chain_fused_levels"] == 3
    with _CompileCounter() as cc:
        eng.run(CHAIN_Q)
    assert eng.stats["chain_fused_levels"] == 3
    assert cc.compiles == 0, f"{cc.compiles} new programs on repeat shape"


# ------------------------------------------------------- full serving path


def _post(addr, body, timeout=30):
    req = urllib.request.Request(
        addr + "/query", data=body.encode(), method="POST"
    )
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.loads(r.read().decode())


def _get(addr, path, timeout=10):
    with urllib.request.urlopen(addr + path, timeout=timeout) as r:
        return json.loads(r.read().decode())


SERVE_QS = [
    CHAIN_Q,
    '{ q(func: uid(0x1)) { name f (first: 3) { name } } }',
    '{ q(func: uid(0x2, 0x3)) { name } }',
    CHAIN_Q,  # repeat exercises the result cache
]


def test_serving_path_parity_planner_on_off(monkeypatch):
    """Acceptance: DGRAPH_TPU_PLANNER=0 is a byte-identical kill switch
    end-to-end — same responses through the FULL serving path (scheduler
    + cache on) — and with the planner armed /debug/planner explains the
    decisions, the calibration source and the adaptive cohort state."""
    from dgraph_tpu.serve.server import DgraphServer

    store = _chain_store(n=256, deg=20)

    def run_server():
        srv = DgraphServer(store)
        srv.start()
        try:
            assert srv.scheduler is not None  # scheduler armed
            assert srv.engine.arenas.hop_cache is not None  # cache armed
            out = []
            for q in SERVE_QS:
                r = _post(srv.addr, q)
                r.pop("server_latency", None)
                out.append(r)
            dbg = _get(srv.addr, "/debug/planner")
            adaptive = srv.scheduler._adaptive
        finally:
            srv.stop()
        return out, dbg, adaptive

    got, dbg, adaptive = run_server()
    assert dbg["enabled"] is True
    assert dbg["calibration"]["source"] in ("prior", "file", "measured")
    assert dbg["counts"], "no decisions recorded through the serving path"
    assert dbg["recent"] and all("kind" in d for d in dbg["recent"])
    assert "mispredict_total" in dbg and "join" in dbg
    # adaptive admission armed (no knob pinned) and state surfaced
    assert adaptive is not None
    assert dbg["sched"]["max_batch"] >= dbg["sched"]["base_batch"]

    planner._reset_for_tests()
    monkeypatch.setenv("DGRAPH_TPU_PLANNER", "0")
    want, dbg0, adaptive0 = run_server()
    assert json.dumps(got, sort_keys=True) == json.dumps(
        want, sort_keys=True
    )
    assert dbg0["enabled"] is False
    assert adaptive0 is None  # static knobs at =0
    assert dbg0["counts"] == {}  # zero planner traffic


def test_sched_knob_pin_disables_adaptive_admission(monkeypatch):
    from dgraph_tpu.serve.server import DgraphServer

    monkeypatch.setenv("DGRAPH_TPU_SCHED_MAX_BATCH", "16")
    srv = DgraphServer(_chain_store(n=32, deg=4))
    try:
        assert srv.scheduler is not None
        assert srv.scheduler._adaptive is None
        assert srv.scheduler.max_batch == 16
    finally:
        srv.stop()


# ------------------------------------------------------- adaptive cohorts


def test_adaptive_cohort_bounds_under_seeded_load_ramp():
    """Deterministic seeded ramp: occupancy/wait climb, the controller
    widens cohorts and tightens the deadline INSIDE its hard bounds,
    then decays back to base when the load drains."""
    ctl = planner.CohortController(32, 0.002)
    lo_f, base_f = 0.002 / 8, 0.002
    seen_mb, seen_fs = set(), set()
    rng = np.random.default_rng(7)
    for _ in range(60):  # ramp up: full cohorts, waits far past deadline
        occ = int(ctl.max_batch * (0.9 + 0.1 * rng.random()))
        mb, fs = ctl.update(occ, queue_wait_s=0.05, service_s=0.01)
        assert 32 <= mb <= 256
        assert lo_f - 1e-12 <= fs <= base_f + 1e-12
        seen_mb.add(mb)
        seen_fs.add(fs)
    assert ctl.max_batch == 256, "cap should saturate under the ramp"
    assert ctl.flush_s == pytest.approx(lo_f)
    assert len(seen_mb) > 1 and len(seen_fs) > 1  # it MOVED, stepwise
    for _ in range(200):  # drain: idle beats
        mb, fs = ctl.update(0, queue_wait_s=0.0, service_s=0.0)
        assert 32 <= mb <= 256
        assert lo_f - 1e-12 <= fs <= base_f + 1e-12
    assert ctl.max_batch == 32, "cap should decay back to base"
    assert ctl.flush_s == pytest.approx(base_f)
    st = ctl.state()
    assert st["updates"] == 260 and st["base_batch"] == 32
