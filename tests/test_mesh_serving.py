"""Mesh serving plane e2e (PR 17): ONE DgraphServer drives the whole
(virtual 8-device) mesh, with the cross-chip frontier exchange running
INSIDE the compiled programs.

The serving contract pinned here, end to end over HTTP:
- ``DGRAPH_TPU_MESH=force`` + ``DGRAPH_TPU_MESH_SHARD_ROWS=1`` answers
  byte-identically to ``DGRAPH_TPU_MESH=0`` (the docs/deploy.md parity
  switch — operators can flip the mesh off and nothing changes but
  latency),
- ``MeshPlan`` placement (which chip owns which uid-range shard) is
  byte-invisible to results — mesh/plan.py's correctness argument,
- a repeat same-shape query compiles NOTHING new (the steps memoize on
  (mesh, cap, hops); recompiles-per-query was the reference's
  per-query planning tax this plane deletes),
- the per-request ledger attributes mesh width and exchange bytes
  (?ledger=true), so chip-time and ICI traffic are charged, not free,
- a chip loss mid-query (``device.mesh`` failpoint) degrades that
  level to the unsharded route — correct answers WITH the ``degraded``
  disclosure, never an outage — and the mesh serves again once the
  fault clears.
"""

import json
import urllib.request

import numpy as np
import pytest

import jax

from dgraph_tpu.models import PostingStore
from dgraph_tpu.serve.server import DgraphServer
from dgraph_tpu.utils import devguard
from dgraph_tpu.utils.failpoints import fail

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
    """One deterministic graph for every server in this module (the
    parity tests compare servers, so they must load identical bytes)."""
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


def _boot(monkeypatch, mesh: str, cache: str = "1"):
    """A loaded loopback server under the given DGRAPH_TPU_MESH mode.
    shard_rows=1 makes EVERY predicate mesh-eligible — the parity tests
    must exercise the sharded route, not quietly skip it.  cache="0"
    disables the result/hop tier for tests that need a repeat query to
    actually RE-EXECUTE (placement rebuild, chip-loss injection)."""
    monkeypatch.setenv("DGRAPH_TPU_MESH", mesh)
    monkeypatch.setenv("DGRAPH_TPU_MESH_SHARD_ROWS", "1")
    monkeypatch.setenv("DGRAPH_TPU_CACHE", cache)
    srv = DgraphServer(PostingStore())
    srv.start()
    _post(srv.addr, "/query", _dataset())
    return srv


def _ask(srv, q, path="/query"):
    out = _post(srv.addr, path, q)
    out.pop("server_latency", None)
    return out


def test_server_byte_identity_sharded_vs_unsharded(monkeypatch):
    plain = _boot(monkeypatch, mesh="0")
    meshed = _boot(monkeypatch, mesh="force")
    try:
        for q in QUERIES:
            a = _ask(plain, q)
            b = _ask(meshed, q)
            assert a == b, f"mesh serving diverged for {q}"
            assert "degraded" not in b  # healthy = no disclosure
        # the mesh path actually ran (sharded arenas built + served)
        assert meshed.engine.arenas._sharded, "sharded route never taken"
        assert plain.engine.arenas.mesh is None
        # and it stays identical ACROSS a mutation (dirty invalidation
        # rebuilds the sharded view, it doesn't serve stale shards)
        mut = 'mutation { set { <0x1> <link> <0x70> . <0x70> <name> "NEW" . } }'
        _post(plain.addr, "/query", mut)
        _post(meshed.addr, "/query", mut)
        for q in QUERIES:
            assert _ask(plain, q) == _ask(meshed, q)
    finally:
        plain.stop()
        meshed.stop()


def test_mesh_plan_placement_is_byte_invisible(monkeypatch):
    """Rolling a predicate's shard 0 onto a different chip (MeshPlan
    offsets, rebalance) must not change one byte of any response —
    placement decides WHERE rows live, never WHAT the query returns."""
    srv = _boot(monkeypatch, mesh="force", cache="0")
    try:
        before = {q: _ask(srv, q) for q in QUERIES}
        plan = srv.engine.arenas.mesh_plan
        assert plan is not None
        # force every placed predicate onto a DIFFERENT nonzero offset
        # (offset_for assigned them least-loaded; perturb directly so the
        # test doesn't depend on the greedy order)
        with plan._lock:
            for i, pred in enumerate(list(plan.placement)):
                plan.placement[pred] = (
                    plan.placement[pred] + 1 + i
                ) % plan.n_shards or 1
            plan.version += 1
        after = {q: _ask(srv, q) for q in QUERIES}
        assert after == before, "placement leaked into results"
        # the perturbed offsets really were applied (sharded cache
        # invalidates on offset mismatch, rebuilds under the new roll)
        sh = srv.engine.arenas._sharded
        assert sh and all(
            e[2] == plan.placement.get(
                ("~" + k[0]) if k[1] else k[0], 0
            )
            for k, e in sh.items()
        )
        # a full rebalance (the operator surface) keeps parity too
        plan.rebalance()
        assert {q: _ask(srv, q) for q in QUERIES} == before
    finally:
        srv.stop()


def test_repeat_query_compiles_nothing_new(monkeypatch):
    """Same-shape repeat queries ride memoized compiled steps: zero jit
    cache misses on the re-run — per-query recompilation is the tax the
    mesh plane's (mesh, cap, hops)-keyed builders exist to delete."""
    from dgraph_tpu.analysis.pytest_budget import compile_count

    srv = _boot(monkeypatch, mesh="force")
    try:
        for q in QUERIES:  # warm every program the shapes need
            _ask(srv, q)
        first = {q: _ask(srv, q) for q in QUERIES}
        c0 = compile_count()
        second = {q: _ask(srv, q) for q in QUERIES}
        assert second == first
        assert compile_count() == c0, (
            f"repeat same-shape queries compiled {compile_count() - c0} "
            "new program(s)"
        )
    finally:
        srv.stop()


def test_mesh_ledger_attributes_chips_and_exchange(monkeypatch):
    """?ledger=true on a mesh-served query accounts the mesh width and
    the cross-chip exchange payload — ICI traffic is charged to the
    request that moved it, not invisible."""
    srv = _boot(monkeypatch, mesh="force")
    try:
        out = _post(srv.addr, "/query?ledger=true", QUERIES[0])
        led = out["extensions"]["ledger"]
        assert led["mesh_chips"] == 8, led
        assert led["exchange_bytes"] > 0, led
        assert led["mesh_ms"] > 0, led
    finally:
        srv.stop()


@pytest.mark.chaos
def test_chip_loss_degrades_to_unsharded_then_recovers(monkeypatch):
    """A chip fault inside a mesh dispatch (the PR 15 ``device.mesh``
    failpoint) re-plans that level unsharded: the response is correct
    AND carries the ``degraded`` device disclosure; the spent failpoint
    leaves the next request riding the mesh again, undisclosed."""
    monkeypatch.setenv("DGRAPH_TPU_DEVICE_COOLDOWN_S", "0.1")
    devguard.reset_for_tests()
    plain = _boot(monkeypatch, mesh="0", cache="0")
    meshed = _boot(monkeypatch, mesh="force", cache="0")
    try:
        q = QUERIES[0]
        baseline = _ask(plain, q)
        assert _ask(meshed, q) == baseline  # healthy parity first
        fail.seed(0)
        fail.arm("device.mesh", "error(n=1)")
        out = _ask(meshed, q)
        deg = out.pop("degraded")
        assert out == baseline, "degraded re-plan diverged"
        assert deg["device"]["failovers"] >= 1, deg
        # the fault latched the MESH domain only — the single-device
        # dispatch plane it degraded onto never saw one
        assert devguard.get("mesh").faults.get("transient", 0) >= 1
        assert devguard.get("device").faults == {}
        # failpoint spent: the mesh serves the next request, clean
        out2 = _ask(meshed, q)
        assert out2 == baseline and "degraded" not in out2
    finally:
        fail.disarm("device.mesh")
        devguard.reset_for_tests()
        plain.stop()
        meshed.stop()
