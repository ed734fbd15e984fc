"""Elastic mesh fault domain (mesh/fault.py): chip loss is a CAPACITY
event, not a route event.

The contract pinned here, end to end over HTTP and at the executor:
- a CHIP-attributed fault (``device.mesh=error(chip=N)``) evicts that
  chip and re-shards the plan onto the surviving sub-mesh — every
  in-flight and subsequent query answers byte-identically to the
  healthy run, the route STAYS sharded (no unsharded failover is
  counted), and the response carries the ``degraded.mesh`` epoch
  disclosure;
- a segmented query that loses its chip (or observes an epoch flip at
  a ``segments.seam()``) drains its host-mirrored carry and resumes
  under the new plan, byte-identically;
- a healed chip re-enters via warm-then-cutover behind the devguard
  probe: a failing warm (``mesh.warm`` failpoint) re-latches the chip
  and NEVER bounces the serving plan (flapping containment);
- sequential double loss converges (8 → 7 → 6) without a failed query;
- repeat-shape queries after an epoch flip add only the bounded
  sub-mesh program shapes — and zero on the flip BACK to the memoized
  boot mesh;
- ``DGRAPH_TPU_MESH_ELASTIC=0`` restores the PR 15/17 behavior: the
  same chip fault latches the whole plane and degrades to unsharded.
"""

import json
import time
import urllib.request

import numpy as np
import pytest

import jax

from dgraph_tpu.models import PostingStore
from dgraph_tpu.serve.server import DgraphServer
from dgraph_tpu.utils import devguard
from dgraph_tpu.utils.failpoints import _Action, fail

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs 8-device mesh"
)


def _post(addr, path, body):
    req = urllib.request.Request(
        addr + path, data=body.encode(), method="POST"
    )
    with urllib.request.urlopen(req, timeout=30) as r:
        return json.loads(r.read().decode())


_SCHEMA_AND_DATA = None


def _dataset(n=120, seed=3):
    global _SCHEMA_AND_DATA
    if _SCHEMA_AND_DATA is None:
        rng = np.random.default_rng(seed)
        lines = [f'<0x{i:x}> <name> "node {i}" .' for i in range(1, n + 1)]
        for i in range(1, n + 1):
            for d in rng.integers(1, n + 1, size=4):
                lines.append(f"<0x{i:x}> <link> <0x{d:x}> .")
        _SCHEMA_AND_DATA = (
            "mutation { schema { name: string @index(term) . "
            "link: uid @reverse @count . } set { %s } }" % "\n".join(lines)
        )
    return _SCHEMA_AND_DATA


QUERIES = [
    "{ q(func: uid(0x1)) { name link { name link { name } } } }",
    "{ q(func: uid(0x2, 0x3, 0x5)) { link @filter(ge(count(link), 1)) { _uid_ } } }",
    "{ q(func: uid(0x4)) { count(link) count(~link) } }",
    "{ q(func: uid(0x1)) @recurse(depth: 3) { name link } }",
]


def _boot(monkeypatch, mesh: str = "force", cache: str = "0", **env):
    monkeypatch.setenv("DGRAPH_TPU_MESH", mesh)
    monkeypatch.setenv("DGRAPH_TPU_MESH_SHARD_ROWS", "1")
    monkeypatch.setenv("DGRAPH_TPU_CACHE", cache)
    for k, v in env.items():
        monkeypatch.setenv(k, v)
    srv = DgraphServer(PostingStore())
    srv.start()
    _post(srv.addr, "/query", _dataset())
    return srv


def _ask(srv, q):
    out = _post(srv.addr, "/query", q)
    out.pop("server_latency", None)
    return out


def _until(cond, secs=15.0, every=0.05):
    """Bounded condition-polling (the deflake discipline): no naked
    sleeps around epoch-flip observation — poll the condition with a
    hard deadline and fail loudly past it."""
    deadline = time.monotonic() + secs
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(every)
    return False


# -- grammar / attribution (no server) ---------------------------------------


def test_chip_selector_grammar():
    """chip= parses on error/xla_oom, is rejected on kinds that carry
    no exception for attribution, and the raised message carries the
    chip tag devguard.chip_of reads."""
    a = _Action.parse("error(p=1,n=1,chip=3)")
    assert (a.kind, a.n, a.chip) == ("error", 1, 3)
    a = _Action.parse("xla_oom(chip=0)")
    assert (a.kind, a.chip) == ("xla_oom", 0)
    assert _Action.parse("error(n=2)").chip == -1
    for bad in ("crash(chip=1)", "hang(chip=2,ms=10)", "delay(chip=0)"):
        with pytest.raises(ValueError):
            _Action.parse(bad)
    fp = fail.__class__(seed=0)
    fp.arm("t.site", "error(chip=5)")
    with pytest.raises(OSError) as ei:
        fp.point("t.site")
    assert "chip=5" in str(ei.value)
    assert devguard.chip_of(ei.value) == 5
    # attribution walks the cause chain (DeviceFaultError wraps the raw
    # failpoint/XLA error)
    wrapped = devguard.DeviceFaultError("mesh", "op", "transient", "x")
    wrapped.__cause__ = ei.value
    assert devguard.chip_of(wrapped) == 5
    assert devguard.chip_of(RuntimeError("no attribution")) is None


# -- loss: route stays sharded ------------------------------------------------


@pytest.mark.chaos
def test_chip_loss_stays_sharded_byte_identical(monkeypatch):
    """Single chip loss mid-query: every response byte-identical to the
    healthy (and unsharded) run, the route STAYS sharded on the
    surviving 7-chip sub-mesh — asserted via the rebuilt shard widths
    AND the absence of any unsharded-failover disclosure."""
    monkeypatch.setenv("DGRAPH_TPU_DEVICE_COOLDOWN_S", "60")
    devguard.reset_for_tests()
    plain = _boot(monkeypatch, mesh="0")
    meshed = _boot(monkeypatch)
    try:
        baseline = {q: _ask(plain, q) for q in QUERIES}
        for q in QUERIES:
            assert _ask(meshed, q) == baseline[q]
        dom = meshed.engine.arenas.mesh_fault
        assert dom is not None and dom.width == 8
        fail.seed(0)
        fail.arm("device.mesh", "error(n=1,chip=3)")
        out = _ask(meshed, QUERIES[0])
        deg = out.pop("degraded")
        assert out == baseline[QUERIES[0]], "post-loss response diverged"
        assert deg["mesh"]["chips_healthy"] == 7, deg
        assert deg["mesh"]["chips_total"] == 8, deg
        # the route stayed MESH: no unsharded failover was counted
        assert "device" not in deg, deg
        assert devguard.get("mesh").state == devguard.HEALTHY
        assert meshed.engine.arenas.mesh is not dom.boot_mesh
        # every subsequent query serves sharded at the survivor width
        # (count-only queries never dispatch to the mesh, so only
        # mesh-routed ones carry the capacity disclosure)
        for q in QUERIES:
            out = _ask(meshed, q)
            out.pop("degraded", None)
            assert out == baseline[q]
        sh = meshed.engine.arenas._sharded
        assert sh and all(e[1].n_shards == 7 for e in sh.values()), {
            k: e[1].n_shards for k, e in sh.items()
        }
        # operator surface: /health names the evicted chip and epoch
        h = json.loads(
            urllib.request.urlopen(
                meshed.addr + "/health?detail=1", timeout=30
            ).read()
        )
        assert h["mesh"]["chips"]["3"] == "sick (evicted)", h["mesh"]
        assert h["mesh"]["chips_healthy"] == 7
        assert h["mesh"]["epoch"] == dom.epoch
    finally:
        fail.reset()
        devguard.reset_for_tests()
        plain.stop()
        meshed.stop()


@pytest.mark.chaos
def test_staged_rejoin_restores_full_mesh(monkeypatch):
    """The healed chip re-enters behind the devguard probe via
    warm-then-cutover: full-mesh epoch restored, disclosure gone,
    results still byte-identical — and the flip back to the memoized
    boot mesh recompiles nothing (checked by the compile-guard test)."""
    monkeypatch.setenv("DGRAPH_TPU_DEVICE_COOLDOWN_S", "0.2")
    devguard.reset_for_tests()
    srv = _boot(monkeypatch)
    try:
        baseline = {q: _ask(srv, q) for q in QUERIES}
        dom = srv.engine.arenas.mesh_fault
        epoch0 = dom.epoch
        reshards0 = dom.status()["reshards"]
        fail.seed(0)
        fail.arm("device.mesh", "error(n=1,chip=2)")
        out = _ask(srv, QUERIES[0])
        # with a short cooldown the rejoin can land before the response
        # is even stamped — assert the query RESUMED (loss observed),
        # not a width the background probe may already have restored
        assert out.pop("degraded")["mesh"]["resumed"] >= 1
        assert dom.status()["reshards"] >= reshards0 + 1
        assert _until(
            lambda: dom.width == 8
            and dom.status()["reshards"] >= reshards0 + 2
        ), f"rejoin never converged: {dom.status()}"
        assert dom.epoch > epoch0
        assert dom.mesh is dom.boot_mesh, (
            "rejoin-to-full must reuse the memoized boot Mesh"
        )
        for q in QUERIES:
            out = _ask(srv, q)
            assert "degraded" not in out, out.get("degraded")
            assert out == baseline[q]
        assert dom.status()["chips"]["2"] == "healthy"
    finally:
        fail.reset()
        devguard.reset_for_tests()
        srv.stop()


@pytest.mark.chaos
def test_flapping_chip_never_cuts_over(monkeypatch):
    """A chip whose rejoin WARM keeps failing (the ``mesh.warm``
    failpoint) re-latches sick every probe cycle: the serving plan
    never flips back until a warm fully passes — live traffic never
    bounces on a flapping chip."""
    monkeypatch.setenv("DGRAPH_TPU_DEVICE_COOLDOWN_S", "0.2")
    devguard.reset_for_tests()
    srv = _boot(monkeypatch)
    try:
        baseline = _ask(srv, QUERIES[0])
        dom = srv.engine.arenas.mesh_fault
        fail.seed(0)
        fail.arm("mesh.warm", "error")  # every warm fails until disarmed
        fail.arm("device.mesh", "error(n=1,chip=5)")
        out = _ask(srv, QUERIES[0])
        assert out.pop("degraded")["mesh"]["chips_healthy"] == 7
        epoch7 = dom.epoch
        # at least two probe cycles flap (warm fails, chip re-latches):
        # the epoch must NOT move for as long as the flapping lasts
        assert _until(lambda: fail.hits("mesh.warm") >= 2), dom.status()
        assert dom.width == 7 and dom.epoch == epoch7, dom.status()
        out = _ask(srv, QUERIES[0])
        assert out.pop("degraded")["mesh"]["chips_healthy"] == 7
        assert out == baseline
        # the chip stops flapping: the next warm passes and the cutover
        # restores the full mesh
        fail.disarm("mesh.warm")
        assert _until(lambda: dom.width == 8), dom.status()
        out = _ask(srv, QUERIES[0])
        assert "degraded" not in out and out == baseline
    finally:
        fail.reset()
        devguard.reset_for_tests()
        srv.stop()


@pytest.mark.chaos
def test_sequential_double_loss_converges(monkeypatch):
    """Losing a second chip while already degraded re-shards again
    (8 → 7 → 6); every query stays byte-identical and sharded."""
    monkeypatch.setenv("DGRAPH_TPU_DEVICE_COOLDOWN_S", "60")
    devguard.reset_for_tests()
    srv = _boot(monkeypatch)
    try:
        baseline = {q: _ask(srv, q) for q in QUERIES}
        dom = srv.engine.arenas.mesh_fault
        fail.seed(0)
        for chip, left in ((3, 7), (5, 6)):
            fail.arm("device.mesh", f"error(n=1,chip={chip})")
            out = _ask(srv, QUERIES[0])
            deg = out.pop("degraded")
            assert out == baseline[QUERIES[0]]
            assert deg["mesh"]["chips_healthy"] == left, deg
            assert "device" not in deg, deg
        assert dom.width == 6
        for q in QUERIES:
            out = _ask(srv, q)
            out.pop("degraded", None)
            assert out == baseline[q]
        sh = srv.engine.arenas._sharded
        assert sh and all(e[1].n_shards == 6 for e in sh.values())
        st = dom.status()
        assert st["chips"]["3"] == "sick (evicted)"
        assert st["chips"]["5"] == "sick (evicted)"
        assert devguard.get("mesh").state == devguard.HEALTHY
    finally:
        fail.reset()
        devguard.reset_for_tests()
        srv.stop()


# -- drain-and-resume ---------------------------------------------------------


@pytest.mark.chaos
def test_segmented_query_resumes_after_losing_its_chip(monkeypatch):
    """An in-flight SEGMENTED multi-hop whose second segment hits the
    evicted chip drains its host-mirrored carry, re-plans under the new
    epoch and resumes — byte-identical frontiers and totals, route
    still mesh."""
    monkeypatch.setenv("DGRAPH_TPU_DEVICE_COOLDOWN_S", "60")
    monkeypatch.setenv("DGRAPH_TPU_SEGMENT", "force")
    monkeypatch.setenv("DGRAPH_TPU_SEGMENT_K", "1")
    devguard.reset_for_tests()
    srv = _boot(monkeypatch)
    try:
        ex = srv.engine.arenas.mesh_executor()
        dom = srv.engine.arenas.mesh_fault
        src = np.array([1, 2, 3], dtype=np.int64)
        cap = 1024  # above the worst level: full parity, no truncation
        fs0, tot0 = ex.multi_hop("link", False, src, 3, cap, {})
        assert dom.width == 8
        # segment 1 passes (after=1), segment 2 loses chip 2 mid-query
        from dgraph_tpu.sched import segments

        fail.seed(0)
        fail.arm("device.mesh", "error(n=1,after=1,chip=2)")
        stats = {}
        prev = segments.activate(segments.SegmentContext(stats=stats))
        try:
            fs1, tot1 = ex.multi_hop("link", False, src, 3, cap, stats)
        finally:
            segments.deactivate(prev)
        assert np.array_equal(fs1, fs0) and np.array_equal(tot1, tot0)
        assert dom.width == 7
        assert stats["mesh_degraded"]["resumed"] >= 1, stats
        assert stats.get("resumed", {}).get("loss", 0) >= 1, stats
        assert stats.get("device_failover", 0) == 0, stats
    finally:
        fail.reset()
        devguard.reset_for_tests()
        srv.stop()


@pytest.mark.chaos
def test_segmented_query_resumes_across_epoch_flip_at_seam(monkeypatch):
    """A segmented query whose chip survives, but whose EPOCH flips
    between segments (another query's loss / a rejoin cutover),
    observes the fence at the seam and re-plans — byte-identical."""
    monkeypatch.setenv("DGRAPH_TPU_DEVICE_COOLDOWN_S", "60")
    monkeypatch.setenv("DGRAPH_TPU_SEGMENT", "force")
    monkeypatch.setenv("DGRAPH_TPU_SEGMENT_K", "1")
    devguard.reset_for_tests()
    srv = _boot(monkeypatch)
    try:
        from dgraph_tpu.sched import segments
        from dgraph_tpu.utils.failpoints import FailpointError

        ex = srv.engine.arenas.mesh_executor()
        dom = srv.engine.arenas.mesh_fault
        src = np.array([1, 2, 3], dtype=np.int64)
        cap = 1024
        fs0, tot0 = ex.multi_hop("link", False, src, 3, cap, {})
        flipped = []

        def flip_once():
            # fires INSIDE segments.seam(), i.e. between segments of
            # the in-flight query — exactly where a concurrent loss
            # lands relative to this query
            if not flipped:
                flipped.append(1)
                dom._sink(
                    "transient",
                    "mesh.multi_hop",
                    FailpointError("concurrent loss (chip=4)"),
                )

        stats = {}
        prev = segments.activate(
            segments.SegmentContext(preempt=flip_once, stats=stats)
        )
        try:
            fs1, tot1 = ex.multi_hop("link", False, src, 3, cap, stats)
        finally:
            segments.deactivate(prev)
        assert flipped and dom.width == 7
        assert np.array_equal(fs1, fs0) and np.array_equal(tot1, tot0)
        assert stats.get("resumed", {}).get("epoch", 0) >= 1, stats
    finally:
        fail.reset()
        devguard.reset_for_tests()
        srv.stop()


# -- bounded program growth ---------------------------------------------------


@pytest.mark.chaos
def test_epoch_flip_adds_only_bounded_program_shapes(monkeypatch):
    """Repeat-shape queries after an epoch flip add only the sub-mesh
    program shapes (one compile round at the new width); the SECOND
    pass at that width — and the flip back to the memoized boot mesh —
    compile nothing."""
    from dgraph_tpu.analysis.pytest_budget import compile_count

    monkeypatch.setenv("DGRAPH_TPU_DEVICE_COOLDOWN_S", "0.2")
    devguard.reset_for_tests()
    srv = _boot(monkeypatch)
    try:
        dom = srv.engine.arenas.mesh_fault
        baseline = {q: _ask(srv, q) for q in QUERIES}
        fail.seed(0)
        # hold the chip out: every rejoin warm fails until we disarm,
        # so the 7-chip epoch stays pinned for the counted passes (and
        # the failed warm compiles nothing — the failpoint fires before
        # any program build)
        fail.arm("mesh.warm", "error")
        fail.arm("device.mesh", "error(n=1,chip=1)")
        _ask(srv, QUERIES[0])  # evicts chip 1 → 7-chip epoch
        assert dom.width == 7
        first = {}
        for q in QUERIES:  # one warm round at the new width
            out = _ask(srv, q)
            out.pop("degraded", None)
            first[q] = out
        assert first == baseline
        c0 = compile_count()
        for q in QUERIES:
            out = _ask(srv, q)
            out.pop("degraded", None)
            assert out == baseline[q]
        assert compile_count() == c0, (
            f"repeat queries on the settled sub-mesh compiled "
            f"{compile_count() - c0} new program(s)"
        )
        # rejoin flips back to the MEMOIZED boot mesh: the lru-cached
        # programs hash-hit, so repeat queries compile nothing at all
        fail.disarm("mesh.warm")
        assert _until(lambda: dom.width == 8), dom.status()
        _ask(srv, QUERIES[0])  # settle (sharded views re-adopted/built)
        c0 = compile_count()
        for q in QUERIES:
            assert _ask(srv, q) == baseline[q]
        assert compile_count() == c0, (
            f"post-rejoin repeat queries compiled {compile_count() - c0} "
            "new program(s)"
        )
    finally:
        fail.reset()
        devguard.reset_for_tests()
        srv.stop()


# -- observability / gate -----------------------------------------------------


@pytest.mark.chaos
def test_mesh_metrics_and_scrape_surface(monkeypatch):
    """The satellite metrics: epoch gauge, healthy-chip gauge, reshard
    counters by reason, reshard latency histogram and resume counters
    all land on /metrics."""
    monkeypatch.setenv("DGRAPH_TPU_DEVICE_COOLDOWN_S", "0.2")
    devguard.reset_for_tests()
    srv = _boot(monkeypatch)
    try:
        dom = srv.engine.arenas.mesh_fault
        fail.seed(0)
        fail.arm("device.mesh", "error(n=1,chip=6)")
        _ask(srv, QUERIES[0])
        assert _until(lambda: dom.width == 8), dom.status()
        text = (
            urllib.request.urlopen(srv.addr + "/metrics", timeout=30)
            .read()
            .decode()
        )
        assert 'dgraph_mesh_reshard_total{reason="loss"}' in text
        assert 'dgraph_mesh_reshard_total{reason="rejoin"}' in text
        assert "dgraph_mesh_epoch" in text
        assert "dgraph_mesh_chips_healthy 8" in text
        assert "dgraph_mesh_reshard_seconds" in text
        assert "dgraph_query_resumed_total" in text
    finally:
        fail.reset()
        devguard.reset_for_tests()
        srv.stop()


@pytest.mark.chaos
def test_elastic_off_restores_plane_latch(monkeypatch):
    """DGRAPH_TPU_MESH_ELASTIC=0: the identical chip-attributed fault
    latches the WHOLE mesh plane and degrades to unsharded — the exact
    PR 15/17 behavior, byte for byte."""
    monkeypatch.setenv("DGRAPH_TPU_DEVICE_COOLDOWN_S", "60")
    monkeypatch.setenv("DGRAPH_TPU_MESH_ELASTIC", "0")
    devguard.reset_for_tests()
    srv = _boot(monkeypatch)
    try:
        assert srv.engine.arenas.mesh_fault is None
        baseline = _ask(srv, QUERIES[0])
        fail.seed(0)
        fail.arm("device.mesh", "error(n=1,chip=3)")
        out = _ask(srv, QUERIES[0])
        deg = out.pop("degraded")
        assert out == baseline
        assert deg["device"]["failovers"] >= 1, deg
        assert "mesh" not in deg, deg
        assert int(srv.engine.arenas.mesh.shape["model"]) == 8
    finally:
        fail.reset()
        devguard.reset_for_tests()
        srv.stop()
