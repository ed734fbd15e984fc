"""Segmented dataflow execution (PR 18): bounded program segments with
scheduler yield points.

Byte-identity of the segmented drivers against their monolithic
programs (ops-level array equality AND end-to-end through the armed
DgraphServer across DGRAPH_TPU_SEGMENT modes), the bounded jit cache at
fixed k, the planner's segment_route mode discipline, the seam yield
points themselves (cancellation within ~one segment, higher-priority
preemption at a seam, the early-exit counter), and the PR 18 slot
accounting fix (a deadline lapse at a seam frees the tenant's
max_inflight slot before the 504 surfaces).
"""

import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import jax.numpy as jnp

from dgraph_tpu import ops
from dgraph_tpu.models import PostingStore
from dgraph_tpu.models.arena import csr_dense_from_edges
from dgraph_tpu.ops import batch as bops
from dgraph_tpu.query import QueryEngine
from dgraph_tpu.sched import CancelToken, QueryCancelledError, segments
from dgraph_tpu.serve.server import DgraphServer
from dgraph_tpu.utils.failpoints import fail
from dgraph_tpu.utils.metrics import (
    SEGMENT_DISPATCHES,
    SEGMENT_PREEMPT_US,
    SEGMENT_YIELDS,
)


def _post(addr, body, headers=None, timeout=60):
    req = urllib.request.Request(
        addr + "/query", data=body.encode(), method="POST",
        headers=headers or {},
    )
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.loads(r.read().decode())


def _post_async(addr, body, headers, res):
    try:
        res["out"] = _post(addr, body, headers=headers)
    except urllib.error.HTTPError as e:
        res["code"] = e.code
        res["body"] = json.loads(e.read().decode())
    except Exception as e:  # pragma: no cover
        res["err"] = e
    finally:
        res["done_at"] = time.monotonic()


# ------------------------------------------------- planner mode discipline


def test_segment_route_mode_discipline(monkeypatch):
    from dgraph_tpu.query import planner

    # '0' never segments, regardless of size
    monkeypatch.setenv("DGRAPH_TPU_SEGMENT", "0")
    assert planner.segment_route(64, 10**6, "chain") == (0, None)
    # 'force' always segments at the k knob, clamped to n_steps
    monkeypatch.setenv("DGRAPH_TPU_SEGMENT", "force")
    monkeypatch.setenv("DGRAPH_TPU_SEGMENT_K", "4")
    assert planner.segment_route(6, 1, "chain")[0] == 4
    assert planner.segment_route(3, 1, "chain")[0] == 3
    monkeypatch.setenv("DGRAPH_TPU_SEGMENT_K", "1")
    assert planner.segment_route(6, 1, "multi_hop")[0] == 1
    # a 1-step program has no seam to yield at in ANY mode
    assert planner.segment_route(1, 10**9, "chain") == (0, None)


def test_seam_is_noop_without_context_and_counts_cancel():
    # no active context: a seam must cost nothing and raise nothing
    prev = segments.activate(None)
    try:
        segments.seam("chain")
    finally:
        segments.deactivate(prev)
    # a cancelled token raises at the seam AND counts the yield reason
    tok = CancelToken()
    tok.cancel("admin")
    prev = segments.activate(segments.SegmentContext(token=tok))
    try:
        before = SEGMENT_YIELDS.snapshot().get("cancel", 0)
        with pytest.raises(QueryCancelledError):
            segments.seam("chain")
        assert SEGMENT_YIELDS.snapshot().get("cancel", 0) == before + 1
    finally:
        segments.deactivate(prev)


# --------------------------------------------- ops-level driver parity


def _csr(seed=5, n=400, e=3000):
    rng = np.random.default_rng(seed)
    src = rng.integers(1, n + 1, size=e)
    dst = rng.integers(1, n + 1, size=e)
    return csr_dense_from_edges(src, dst, n)


@pytest.mark.parametrize("k", [1, 2, 4])
@pytest.mark.parametrize("track_visited", [False, True])
def test_multi_hop_segmented_matches_monolithic(monkeypatch, k, track_visited):
    a = _csr()
    cap = ops.bucket(a.n_edges)
    f0 = np.array([7, 100, 231], dtype=np.int64)

    def run():
        fr = jnp.asarray(ops.pad_to(f0, cap))
        vis = (
            jnp.asarray(ops.pad_to(f0, cap))
            if track_visited
            else jnp.full((cap,), ops.sets.SENT, dtype=jnp.int32)
        )
        fs, totals, final = bops.multi_hop(
            a.offsets, a.dst, fr, vis, 5, cap, track_visited=track_visited
        )
        return np.asarray(fs), np.asarray(totals), np.asarray(final)

    monkeypatch.setenv("DGRAPH_TPU_SEGMENT", "0")
    want = run()
    monkeypatch.setenv("DGRAPH_TPU_SEGMENT", "force")
    monkeypatch.setenv("DGRAPH_TPU_SEGMENT_K", str(k))
    before = SEGMENT_DISPATCHES.snapshot().get("multi_hop", 0)
    got = run()
    assert SEGMENT_DISPATCHES.snapshot().get("multi_hop", 0) == before + 1
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)


def test_multi_hop_fixed_k_jit_cache_bounded(monkeypatch):
    """Repeat shapes at fixed k must not lower new executables: the
    segment grouping is (k-hop body + at most one remainder)."""
    from dgraph_tpu.analysis.pytest_budget import compile_count

    a = _csr(seed=9)
    cap = ops.bucket(a.n_edges)
    monkeypatch.setenv("DGRAPH_TPU_SEGMENT", "force")
    monkeypatch.setenv("DGRAPH_TPU_SEGMENT_K", "2")

    def run():
        fr = jnp.asarray(ops.pad_to(np.array([3, 44], np.int64), cap))
        vis = jnp.full((cap,), ops.sets.SENT, dtype=jnp.int32)
        return bops.multi_hop(a.offsets, a.dst, fr, vis, 5, cap)

    run()  # compiles the 2-hop body + the 1-hop remainder
    c0 = compile_count()
    run()
    assert compile_count() == c0, (
        f"{compile_count() - c0} new programs on a repeat shape"
    )


# ------------------------------------------- engine-level chain parity


SCHEMA = """
    name: string @index(exact) .
    knows: uid @reverse .
    likes: uid .
"""


def _build_engine(seed=1, n=60, threshold=0):
    rng = np.random.default_rng(seed)
    lines = []
    for u in range(1, n + 1):
        lines.append(f'<0x{u:x}> <name> "P{u}" .')
        for pred, fan in (("knows", 4), ("likes", 3)):
            for v in rng.integers(1, n + 1, size=rng.integers(1, fan + 1)):
                lines.append(f"<0x{u:x}> <{pred}> <0x{int(v):x}> .")
    eng = QueryEngine(PostingStore())
    eng.run("mutation { schema { %s } }" % SCHEMA)
    eng.run("mutation { set { %s } }" % "\n".join(lines))
    eng.chain_threshold = threshold
    return eng


CHAIN_QS = [
    # deep materialize chain → the fused chain driver (query/chain.py)
    '{ q(func: eq(name, "P1")) { knows { knows { knows { knows { name } } } } } }',
    # value leaves + mixed preds along the chain
    '{ q(func: eq(name, "P2")) { name knows { likes { knows { name } } } } }',
    # light var-block chain → _try_chain_scan / ops.multi_hop
    '{ var(func: eq(name, "P1")) { knows { knows { v as knows } } } '
    '  r(func: uid(v)) { name } }',
    # var bound mid-chain
    '{ var(func: eq(name, "P3")) { m as knows { likes { knows } } } '
    '  r(func: uid(m)) { name } }',
]


@pytest.mark.parametrize(
    "mode,k", [("force", "1"), ("force", "2"), ("auto", None)]
)
def test_engine_chain_segmented_byte_identical(monkeypatch, mode, k):
    monkeypatch.setenv("DGRAPH_TPU_MXU_JOIN", "0")  # pin the chain tier
    monkeypatch.setenv("DGRAPH_TPU_SEGMENT", "0")
    want = [_build_engine().run(q) for q in CHAIN_QS]
    monkeypatch.setenv("DGRAPH_TPU_SEGMENT", mode)
    if k is not None:
        monkeypatch.setenv("DGRAPH_TPU_SEGMENT_K", k)
    before = SEGMENT_DISPATCHES.snapshot()
    eng = _build_engine()
    got = [eng.run(q) for q in CHAIN_QS]
    assert json.dumps(got, sort_keys=True, default=str) == json.dumps(
        want, sort_keys=True, default=str
    )
    if mode == "force":
        # the segmented drivers really ran (no silent monolithic fallback)
        after = SEGMENT_DISPATCHES.snapshot()
        gained = {
            d: after.get(d, 0) - before.get(d, 0)
            for d in ("chain", "multi_hop")
        }
        assert any(v > 0 for v in gained.values()), gained


def test_engine_mask_chain_segmented_byte_identical(monkeypatch):
    """The MXU mask-chain tier (query/joinplan.py) segments to the same
    masks: force the tier on and compare across segment modes."""
    monkeypatch.setenv("DGRAPH_TPU_MXU_JOIN", "force")
    q = (
        '{ var(func: eq(name, "P1")) { knows { knows { v as knows } } } '
        '  r(func: uid(v)) { name } }'
    )
    monkeypatch.setenv("DGRAPH_TPU_SEGMENT", "0")
    want = _build_engine().run(q)
    monkeypatch.setenv("DGRAPH_TPU_SEGMENT", "force")
    monkeypatch.setenv("DGRAPH_TPU_SEGMENT_K", "1")
    before = SEGMENT_DISPATCHES.snapshot().get("mask_chain", 0)
    eng = _build_engine()
    got = eng.run(q)
    assert got == want
    routes = [r.get("route") for r in eng.stats.get("join_routes", [])]
    if "mxu" in routes:
        # tier engaged → the segmented driver must have been the one
        # that served it
        assert SEGMENT_DISPATCHES.snapshot().get("mask_chain", 0) > before


# ----------------------------------------------------- mesh driver parity


@pytest.mark.skipif(
    len(__import__("jax").devices()) < 8, reason="needs 8-device mesh"
)
def test_mesh_chain_segmented_byte_identical(monkeypatch):
    from dgraph_tpu.parallel import make_mesh

    def build():
        rng = np.random.default_rng(3)
        eng = QueryEngine(
            PostingStore(), mesh=make_mesh(8, data=2), shard_threshold=1
        )
        lines = [f'<0x{i:x}> <name> "node {i}" .' for i in range(1, 201)]
        for i in range(1, 201):
            for d in rng.integers(1, 201, size=4):
                lines.append(f"<0x{i:x}> <link> <0x{d:x}> .")
        eng.run(
            "mutation { schema { name: string . link: uid . } set { %s } }"
            % "\n".join(lines)
        )
        eng.chain_threshold = 0
        return eng

    q = (
        '{ var(func: uid(0x1)) { link { link { v as link } } } '
        '  r(func: uid(v), first: 5) { name } }'
    )
    monkeypatch.setenv("DGRAPH_TPU_SEGMENT", "0")
    want = build().run(q)
    monkeypatch.setenv("DGRAPH_TPU_SEGMENT", "force")
    monkeypatch.setenv("DGRAPH_TPU_SEGMENT_K", "1")
    before = SEGMENT_DISPATCHES.snapshot().get("mesh", 0)
    got = build().run(q)
    assert got == want
    if SEGMENT_DISPATCHES.snapshot().get("mesh", 0) == before:
        pytest.skip("store routed off the fused mesh chain")


# ------------------------------------- end-to-end server byte identity


PARITY_SEED = """
mutation { schema {
  name: string @index(exact) .
  friend: uid @reverse .
} set {
  <0x1> <name> "Ann" .  <0x2> <name> "Ben" . <0x3> <name> "Cara" .
  <0x4> <name> "Dan" .  <0x5> <name> "Eve" . <0x6> <name> "Fay" .
  <0x1> <friend> <0x2> . <0x2> <friend> <0x3> .
  <0x3> <friend> <0x4> . <0x4> <friend> <0x5> .
  <0x5> <friend> <0x6> . <0x2> <friend> <0x4> .
} }
"""

PARITY_QS = [
    '{ q(func: uid(0x1)) { friend { friend { friend { friend { name } } } } } }',
    '{ q(func: eq(name, "Ann")) { name friend { name friend { name } } } }',
    '{ var(func: uid(0x1)) { friend { friend { v as friend } } } '
    '  r(func: uid(v)) { name } }',
    '{ q(func: uid(0x3)) { ~friend { name } friend { name } } }',
]


def test_segment_modes_byte_identical_through_armed_server(monkeypatch):
    """Acceptance: DGRAPH_TPU_SEGMENT=0 and segmentation ON serve
    byte-identical responses end-to-end through DgraphServer with
    scheduler+cache+planner+QoS armed."""
    def serve(seg_env):
        for key in ("DGRAPH_TPU_SEGMENT", "DGRAPH_TPU_SEGMENT_K"):
            monkeypatch.delenv(key, raising=False)
        for key, val in seg_env.items():
            monkeypatch.setenv(key, val)
        monkeypatch.setenv("DGRAPH_TPU_SCHED", "1")
        monkeypatch.setenv("DGRAPH_TPU_QOS", "1")
        monkeypatch.setenv("DGRAPH_TPU_CACHE", "1")
        monkeypatch.setenv("DGRAPH_TPU_PLANNER", "1")
        monkeypatch.setenv("DGRAPH_TPU_CHAIN_THRESHOLD", "1")
        server = DgraphServer(PostingStore())
        server.start()
        try:
            _post(server.addr, PARITY_SEED)
            out = []
            for q in PARITY_QS:
                for _ in range(2):  # second pass exercises the caches
                    r = _post(server.addr, q)
                    r.pop("server_latency", None)
                out.append(r)
            return out
        finally:
            server.stop()

    legacy = serve({"DGRAPH_TPU_SEGMENT": "0"})
    assert serve({
        "DGRAPH_TPU_SEGMENT": "force", "DGRAPH_TPU_SEGMENT_K": "1"
    }) == legacy
    assert serve({
        "DGRAPH_TPU_SEGMENT": "force", "DGRAPH_TPU_SEGMENT_K": "2"
    }) == legacy
    assert serve({"DGRAPH_TPU_SEGMENT": "auto"}) == legacy


# ---------------------------------------------- yield point: cancellation


CANCEL_Q = (
    '{ q(func: eq(name, "P1")) '
    '{ knows { knows { knows { knows { knows { name } } } } } } }'
)


def test_cancel_latency_bounded_to_one_segment(monkeypatch):
    """Mid-chain cancellation surfaces at the NEXT seam: with a
    per-segment delay failpoint armed, the cancelled query must stop
    after strictly fewer dispatches than the chain has levels — the
    monolithic path would pay every level before answering."""
    monkeypatch.setenv("DGRAPH_TPU_MXU_JOIN", "0")
    monkeypatch.setenv("DGRAPH_TPU_SEGMENT", "force")
    monkeypatch.setenv("DGRAPH_TPU_SEGMENT_K", "1")
    eng = _build_engine()
    eng.run(CANCEL_Q)  # warm the compile caches
    eng.cancel = tok = CancelToken()
    h0 = fail.hits("device.chain")
    y0 = SEGMENT_YIELDS.snapshot().get("cancel", 0)
    fail.arm("device.chain", "delay(ms=120)")
    try:
        def cancel_on_first_dispatch():
            stop = time.monotonic() + 10
            while time.monotonic() < stop:
                if fail.hits("device.chain") > h0:
                    tok.cancel("admin")
                    return
                time.sleep(0.002)

        t = threading.Thread(target=cancel_on_first_dispatch, daemon=True)
        t0 = time.monotonic()
        t.start()
        with pytest.raises(QueryCancelledError):
            eng.run(CANCEL_Q)
        elapsed = time.monotonic() - t0
        t.join(timeout=10)
    finally:
        fail.disarm("device.chain")
    dispatched = fail.hits("device.chain") - h0
    assert 0 < dispatched < 5, dispatched  # stopped mid-chain
    # the 5-level chain pays 120ms per segment: dying at the first or
    # second seam keeps the total well under the monolithic 600ms
    assert elapsed < 0.48, elapsed
    assert SEGMENT_YIELDS.snapshot().get("cancel", 0) == y0 + 1


# ----------------------------------------------- yield point: preemption


SEG_CHAIN_SEED = """
mutation { schema { name: string @index(exact) . friend: uid . } set {
  <0x1> <friend> <0x2> . <0x2> <friend> <0x3> .
  <0x3> <friend> <0x4> . <0x4> <friend> <0x5> .
  <0x5> <friend> <0x6> . <0x6> <name> "end" .
  <0x9> <name> "vip" .
} }
"""

SEG_CHAIN_Q = (
    "{ q(func: uid(0x1)) "
    "{ friend { friend { friend { friend { friend { name } } } } } } }"
)


def _seg_server(monkeypatch, tenants, concurrency="1"):
    monkeypatch.setenv("DGRAPH_TPU_SCHED", "1")
    monkeypatch.setenv("DGRAPH_TPU_QOS", "1")
    monkeypatch.setenv("DGRAPH_TPU_CACHE", "0")
    monkeypatch.setenv("DGRAPH_TPU_CHAIN_THRESHOLD", "1")
    monkeypatch.setenv("DGRAPH_TPU_SCHED_CONCURRENCY", concurrency)
    monkeypatch.setenv("DGRAPH_TPU_SEGMENT", "force")
    monkeypatch.setenv("DGRAPH_TPU_SEGMENT_K", "1")
    monkeypatch.setenv("DGRAPH_TPU_QOS_TENANTS", json.dumps(tenants))
    server = DgraphServer(PostingStore())
    server.start()
    _post(server.addr, SEG_CHAIN_SEED)
    return server


def test_critical_preempts_running_standard_at_seam(monkeypatch):
    """A critical-class arrival runs at the standard query's next
    segment boundary, not behind its remaining segments: the one flush
    worker donates the seam, and dgraph_segment_preempt_us records the
    wait."""
    server = _seg_server(monkeypatch, {
        "bulk": {"weight": 1, "priority": "standard"},
        "vip": {"weight": 1, "priority": "critical"},
    })
    try:
        # warm compiles for both shapes (timings below assume no XLA)
        _post(server.addr, SEG_CHAIN_Q, {"X-Dgraph-Tenant": "bulk"})
        _post(server.addr, '{ q(func: uid(0x9)) { name } }',
              {"X-Dgraph-Tenant": "vip"})
        p0 = SEGMENT_PREEMPT_US.count()
        h0 = fail.hits("device.chain")
        fail.arm("device.chain", "delay(ms=150)")
        try:
            antag, vip = {}, {}
            ta = threading.Thread(
                target=_post_async,
                args=(server.addr, SEG_CHAIN_Q,
                      {"X-Dgraph-Tenant": "bulk"}, antag),
            )
            ta.start()
            # wait for the antagonist's FIRST segment to be running so
            # the vip genuinely arrives mid-query
            stop = time.monotonic() + 10
            while time.monotonic() < stop and fail.hits("device.chain") == h0:
                time.sleep(0.002)
            tv = threading.Thread(
                target=_post_async,
                args=(server.addr, '{ q(func: uid(0x9)) { name } }',
                      {"X-Dgraph-Tenant": "vip"}, vip),
            )
            tv.start()
            tv.join(timeout=60)
            ta.join(timeout=60)
        finally:
            fail.disarm("device.chain")
        assert vip.get("out", {}).get("q") == [{"name": "vip"}], vip
        assert antag.get("out", {}).get("q"), antag
        # ordering: the vip finished while the 5x150ms antagonist was
        # still mid-chain
        assert vip["done_at"] < antag["done_at"]
        assert SEGMENT_PREEMPT_US.count() > p0, "no seam donated"
    finally:
        server.stop()


# --------------------------------- slot release on deadline at a seam


def test_deadline_at_seam_releases_inflight_slot(monkeypatch):
    """Satellite fix: a max_inflight=1 tenant whose query 504s at a
    segment seam must get its slot back IMMEDIATELY — a follow-up query
    from the same tenant runs instead of queueing behind the corpse's
    remaining segments."""
    server = _seg_server(monkeypatch, {
        "meter": {"weight": 1, "priority": "standard", "max_inflight": 1},
    }, concurrency="2")
    try:
        _post(server.addr, SEG_CHAIN_Q, {"X-Dgraph-Tenant": "meter"})
        fail.arm("device.chain", "delay(ms=200)")
        try:
            dead = {}
            # 5 levels x 200ms = 1s of chain; the 300ms budget lapses
            # at the first or second seam
            _post_async(
                server.addr, SEG_CHAIN_Q,
                {"X-Dgraph-Tenant": "meter", "X-Dgraph-Timeout": "0.3"},
                dead,
            )
            assert dead.get("code") == 504, dead
        finally:
            fail.disarm("device.chain")
        # the slot is free NOW: an unarmed follow-up admits and serves
        # without tripping the inflight cap
        t0 = time.monotonic()
        out = _post(server.addr, SEG_CHAIN_Q, {"X-Dgraph-Tenant": "meter"})
        assert out["q"], out
        assert time.monotonic() - t0 < 5.0
        state = json.loads(urllib.request.urlopen(
            server.addr + "/debug/store", timeout=10
        ).read().decode())
        qos = state.get("qos") or {}
        assert qos.get("inflight", {}).get("meter", 0) == 0
    finally:
        server.stop()
