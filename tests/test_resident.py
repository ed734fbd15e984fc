"""Device-resident data plane (PR 16): ResidentArena epoch buffers, the
``route:resident`` engine program (the TPU backend's; picked here by
DeviceExpander's constructor argument), hop-cache epoch keys, and the
HBM accounting of double-buffered flips.

The acceptance pins from ISSUE 16:

- a warm resident hop is TRANSFER-FREE: the kernel runs device-in,
  device-out under ``jax.transfer_guard("disallow")`` with zero ledger
  h2d/d2h bytes;
- the resident program is byte-identical to the ``csr`` program through
  the full serving path (DgraphServer with scheduler + cache + planner
  armed), and to the host route on the same store;
- deltas cross the host→device boundary as (row, dst) pairs only: the
  on-device merge produces the next epoch's buffers, the flip is
  atomic, and the previous epoch stays pinned as the shadow;
- ``device_bytes()`` counts live AND shadow exactly once (constant
  across the flip window — no transient double-count), and the
  ArenaManager budget evicts on the INCLUSIVE footprint.
"""

import json
import urllib.request

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dgraph_tpu import ops
from dgraph_tpu.models import PostingStore
from dgraph_tpu.models.arena import ResidentArena, csr_dense_from_edges
from dgraph_tpu.obs import ledger as ledgermod
from dgraph_tpu.query import engine as engine_mod
from dgraph_tpu.query.engine import DeviceExpander, QueryEngine

# the pallas-interpret CI job re-runs this module on its own (these
# tests also run inside tier-1 — the marker adds a name, not an excuse)
pytestmark = pytest.mark.pallas_interpret


def _rand_arena(rng, n, n_edges):
    src = rng.integers(1, n, size=n_edges)
    dst = rng.integers(1, n, size=n_edges)
    return csr_dense_from_edges(src, dst, n)


def _expand_via(ra, a, rows_np, interpret=True):
    """Run the resident kernel and unpack to the engine's (out, seg)."""
    cap = ops.bucket(int(np.sum(
        a.h_offsets[rows_np[rows_np >= 0] + 1]
        - a.h_offsets[rows_np[rows_np >= 0]]
    )) or 1)
    packed = np.asarray(ra.expand_packed(
        jnp.asarray(rows_np.astype(np.int32)), cap, interpret=interpret
    ))
    return packed[:cap], packed[cap:], cap


# ------------------------------------------------------------ arena core


def test_resident_seed_matches_host_expand():
    rng = np.random.default_rng(0)
    a = _rand_arena(rng, 400, 5000)
    ra = a.resident()
    assert a.resident() is ra  # lazy build is cached
    f = np.unique(rng.integers(0, a.n_rows, size=48)).astype(np.int64)
    rows = ops.pad_rows(f, ops.bucket(len(f)))
    out, seg, cap = _expand_via(ra, a, rows)
    w_out, w_seg, w_total = ops.gather_reference(
        a.h_offsets, a.host_dst(), rows, cap
    )
    assert np.array_equal(out, w_out)
    assert np.array_equal(seg, w_seg)


def test_resident_warm_hop_is_transfer_free_and_ledger_zero():
    """THE tentpole pin: after warm-up, a resident hop with a
    device-resident frontier crosses the host boundary in NEITHER
    direction — jax.transfer_guard("disallow") stays silent and the
    ledger records zero h2d/d2h bytes during the call."""
    rng = np.random.default_rng(1)
    a = _rand_arena(rng, 400, 5000)
    ra = a.resident()
    f = np.unique(rng.integers(0, a.n_rows, size=48)).astype(np.int64)
    rows_dev = jax.device_put(
        np.asarray(ops.pad_rows(f, 64), dtype=np.int32)
    )
    cap = 8192
    # warm: compile + constant staging happen here, outside the guard
    ra.expand_packed(rows_dev, cap, interpret=True).block_until_ready()
    led = ledgermod.Ledger()
    tok = ledgermod.activate(led)
    try:
        with jax.transfer_guard("disallow"):
            out = ra.expand_packed(rows_dev, cap, interpret=True)
            out.block_until_ready()
    finally:
        ledgermod.deactivate(tok)
    assert led.bytes_h2d == 0 and led.bytes_d2h == 0


def test_resident_delta_merges_on_device():
    """apply_delta through the HOST mirrors drives the DEVICE merge
    (same ResidentArena object: no reseed), flips the epoch, pins the
    old buffers as the shadow, and the post-flip kernel output matches
    the post-delta host mirrors exactly."""
    rng = np.random.default_rng(2)
    a = _rand_arena(rng, 300, 4000)
    ra = a.resident()
    off0, dst0 = ra.off, ra.dst
    assert a.epoch == 0 and ra._prev is None
    # adds on EXISTING source rows (row universe unchanged → merge, not
    # reseed); dels must exist
    srcs = a.h_src[[3, 7, 11]]
    adds = np.array(
        [[int(s), 2_000_000 + i] for i, s in enumerate(srcs)], np.int64
    )
    r0 = int(a.h_src[5])
    dels = np.array(
        [[r0, int(a.host_dst()[a.h_offsets[5]])]], dtype=np.int64
    )
    a.apply_delta(adds, dels)
    assert a.epoch == 1
    assert a._resident is ra, "in-budget delta must not reseed"
    assert ra._prev is not None and ra._prev[0] is off0
    assert ra._prev[1] is dst0
    f = np.unique(np.concatenate([
        np.searchsorted(a.h_src, srcs), [5],
        rng.integers(0, a.n_rows, size=24),
    ])).astype(np.int64)
    rows = ops.pad_rows(f, ops.bucket(len(f)))
    out, seg, cap = _expand_via(ra, a, rows)
    w_out, w_seg, _ = ops.gather_reference(
        a.h_offsets, a.host_dst(), rows, cap
    )
    assert np.array_equal(out, w_out)
    assert np.array_equal(seg, w_seg)
    # the NEXT flip releases the first shadow
    a.apply_delta(
        np.array([[int(srcs[0]), 3_000_000]], np.int64),
        np.zeros((0, 2), np.int64),
    )
    assert a.epoch == 2
    assert ra._prev[1] is not dst0


def test_resident_reseeds_on_structural_change():
    """A delta introducing a NEW source row renumbers every row index:
    the resident arena reseeds (fresh upload becomes the next epoch)
    and the old buffers ride along as the new object's shadow."""
    rng = np.random.default_rng(3)
    a = _rand_arena(rng, 100, 900)
    ra = a.resident()
    off0, dst0 = ra.off, ra.dst
    new_src = int(a.h_src.max()) + 5
    a.apply_delta(np.array([[new_src, 7]], np.int64),
                  np.zeros((0, 2), np.int64))
    nra = a._resident
    assert nra is not ra, "new source row must reseed"
    assert nra._prev == (off0, dst0)
    rows = ops.pad_rows(
        np.array([np.searchsorted(a.h_src, new_src)], np.int64), 8
    )
    out, seg, cap = _expand_via(nra, a, rows)
    w_out, w_seg, _ = ops.gather_reference(
        a.h_offsets, a.host_dst(), rows, cap
    )
    assert np.array_equal(out, w_out)


# ------------------------------------------------- HBM accounting (sat. c)


def test_device_bytes_counts_live_and_shadow_once():
    """No double-count in the flip window: after a same-shape device
    merge the footprint is exactly live + shadow (== 2x the seeded
    footprint), and it stays CONSTANT across subsequent flips (each
    flip releases the old shadow as it pins the new one)."""
    rng = np.random.default_rng(4)
    a = _rand_arena(rng, 200, 2500)
    ra = a.resident()
    base = ra.device_bytes()
    assert base == int(ra.off.nbytes + ra.dst.nbytes)
    src0 = int(a.h_src[0])
    for k in range(3):
        a.apply_delta(
            np.array([[src0, 5_000_000 + k]], np.int64),
            np.zeros((0, 2), np.int64),
        )
        # the merge preserves buffer shapes, so live == shadow == base
        assert a.resident().device_bytes() == 2 * base, k
    # the arena-level accountant sees the inclusive figure
    assert a.device_bytes() >= 2 * base


def test_budget_eviction_sees_resident_shadow_bytes():
    """The ArenaManager LRU accounts the resident tier's live+shadow
    footprint: once an arena's recorded bytes include them, a budget
    sized below that footprint evicts it on the next build — and the
    running total reconciles with the per-entry records."""
    st = PostingStore()
    st.apply_schema("a: uid .\nb: uid .")
    for i in range(1, 65):
        st.set_edge("a", i, i + 1)
        st.set_edge("b", i, i + 1)
    eng = QueryEngine(st)
    am = eng.arenas
    a = am.data("a")
    ra = a.resident()
    st.set_edge("a", 1, 999)  # delta → device merge → shadow pinned
    a = am.data("a")  # refresh applies the delta AND re-touches the LRU
    assert a.epoch == 1 and a._resident._prev is not None
    lkey = (id(am._data), "a")
    recorded = am._lru[lkey]
    assert recorded >= a._resident.device_bytes()
    assert am._lru_total == sum(am._lru.values())
    # budget below the resident-inclusive footprint: building "b" must
    # evict "a" (the LRU victim) even though its NON-resident tensors
    # alone would fit
    am.budget_bytes = recorded - 1
    am.data("b")
    assert am.evictions >= 1
    assert "a" not in am._data, "resident bytes invisible to the evictor"


# ------------------------------------------------ hop-cache epochs (sat. b)


def test_stale_epoch_entries_never_survive_a_delta():
    """After a delta-driven epoch flip, NO entry keyed at the old epoch
    remains for the arena id: the repair pass re-keys what it can carry
    forward and _try_apply_delta's drop_stale_epoch sweep removes the
    rest — a post-delta probe can only ever hit post-delta bytes."""
    st = PostingStore()
    st.apply_schema("friend: uid .")
    for i in range(1, 33):
        st.set_edge("friend", i, i + 1)
    eng = QueryEngine(st)
    am = eng.arenas
    assert am.hop_cache is not None
    src = np.arange(1, 33, dtype=np.int64)
    a = am.data("friend")
    out0, _ = eng.expander._expand_cached(a, src, "friend")
    assert len(out0) == 32 and len(am.hop_cache) >= 1
    st.set_edge("friend", 1, 200)
    a = am.data("friend")
    assert a.epoch == 1
    stale = am.hop_cache._c.drop_where(
        lambda k: k[0] == id(a) and k[3] != a.epoch
    )
    assert stale == 0, f"{stale} stale-epoch entries survived the flip"
    out1, _ = eng.expander._expand_cached(a, src, "friend")
    assert len(out1) == 33
    assert 200 in np.asarray(out1)


def test_hop_key_carries_epoch():
    from dgraph_tpu.cache.hop import HopCache

    hc = HopCache(budget_bytes=1 << 20)
    st = PostingStore()
    st.apply_schema("p: uid .")
    st.set_edge("p", 1, 2)
    eng = QueryEngine(st)
    a = eng.arenas.data("p")
    src = np.array([1], dtype=np.int64)
    k0 = hc.key_for(a, "p", False, src)
    assert k0[3] == a.epoch
    a.epoch += 1
    k1 = hc.key_for(a, "p", False, src)
    assert k1 != k0 and k1[3] == k0[3] + 1


# -------------------------------------------------- engine route parity


def _seed_big(st, rows=100, fanout=64, seed=7):
    st.apply_schema("friend: uid .")
    rng = np.random.default_rng(seed)
    for s in range(1, rows + 1):
        for d in np.unique(rng.integers(1000, 9000, size=fanout)):
            st.set_edge("friend", s, int(d))


def _resident_engine(st) -> QueryEngine:
    eng = QueryEngine(st)
    eng.expander = DeviceExpander(eng, program="resident")
    return eng


def test_resident_route_byte_identical_to_csr(monkeypatch):
    """The resident program takes the big hop as route:resident and the
    bytes are identical to the platform's program (csr on the CPU
    backend) on the same store.  The device threshold is PINNED (static
    fallback) so the decision can't drift with the planner's online
    rate refinement — interpret-mode kernel timings on CPU are
    meaningless as routing signal."""
    monkeypatch.setenv("DGRAPH_TPU_EXPAND_DEVICE_MIN", "1000")
    st = PostingStore()
    _seed_big(st)
    src = np.arange(1, 101, dtype=np.int64)

    eng_r = _resident_engine(st)
    a = eng_r.arenas.data("friend")
    out_r, seg_r = eng_r.expander.expand(a, src, attr="friend")
    assert eng_r.expander._route == "resident"

    eng_h = QueryEngine(st)
    ah = eng_h.arenas.data("friend")
    out_h, seg_h = eng_h.expander.expand(ah, src, attr="friend")
    assert eng_h.expander._route == "csr"

    assert np.array_equal(np.asarray(out_r), np.asarray(out_h))
    assert np.array_equal(np.asarray(seg_r), np.asarray(seg_h))
    # and vs the host route directly (the devguard fallback contract)
    w_out, w_seg = ah.expand_host(ah.rows_for_uids_host(src))
    assert np.array_equal(np.asarray(out_r), np.asarray(w_out))
    assert np.array_equal(np.asarray(seg_r), np.asarray(w_seg))
    # the program is the platform's: csr on the CPU backend
    assert eng_h.expander.program == "csr"


def test_resident_route_ledger_attribution(monkeypatch):
    """The engine charges the resident hop's REAL boundary crossings —
    the frontier upload (h2d) and the packed fetch (d2h) — and nothing
    else: no staged-arena bytes (the staging term the planner prices at
    zero for this route)."""
    monkeypatch.setenv("DGRAPH_TPU_EXPAND_DEVICE_MIN", "1000")
    st = PostingStore()
    _seed_big(st)
    eng = _resident_engine(st)
    a = eng.arenas.data("friend")
    a.resident()  # seed OUTSIDE the measured window
    src = np.arange(1, 101, dtype=np.int64)
    led = ledgermod.Ledger()
    tok = ledgermod.activate(led)
    try:
        eng.expander.expand(a, src, attr="friend")
    finally:
        ledgermod.deactivate(tok)
    assert eng.expander._route == "resident"
    assert 0 < led.bytes_h2d <= 4096, "frontier upload only"
    assert led.bytes_d2h > 0
    ra = a.resident()
    assert led.bytes_h2d < ra.dst.nbytes, "arena re-staged on a hop"


def test_resident_faulted_dispatch_falls_back_to_host(monkeypatch):
    """Devguard brackets route:resident as a device-domain dispatch: a
    fault inside it must degrade to the byte-identical host fallback,
    not surface to the caller."""
    from dgraph_tpu.utils import devguard
    from dgraph_tpu.utils.failpoints import fail

    monkeypatch.setenv("DGRAPH_TPU_EXPAND_DEVICE_MIN", "1000")
    fail.reset()
    devguard.reset_for_tests()
    try:
        st = PostingStore()
        _seed_big(st)
        eng = _resident_engine(st)
        a = eng.arenas.data("friend")
        src = np.arange(1, 101, dtype=np.int64)
        want_out, want_seg = a.expand_host(a.rows_for_uids_host(src))
        fail.arm("device.hop", "error(n=1)")
        out, seg = eng.expander.expand(a, src, attr="friend")
        assert eng.expander._route == "host"
        assert np.array_equal(np.asarray(out), np.asarray(want_out))
        assert np.array_equal(np.asarray(seg), np.asarray(want_seg))
    finally:
        fail.reset()
        devguard.reset_for_tests()


# ---------------------------------------------- full serving path (server)


SEED_ROWS, SEED_FAN = 4, 1600  # hub rows: 6400 edges > the resident
#                                break-even at prior rates (~5.3k)


def _serve_once(monkeypatch, program):
    """One query through a server whose every engine (the scheduler makes
    one a request) resolves its device program to ``program``, as if the
    platform were the one that runs it."""
    from dgraph_tpu.serve.server import DgraphServer

    monkeypatch.setenv("DGRAPH_TPU_SCHED", "1")
    monkeypatch.setenv("DGRAPH_TPU_CACHE", "1")
    monkeypatch.setenv("DGRAPH_TPU_EXPAND_DEVICE_MIN", "1000")
    monkeypatch.setattr(engine_mod, "_platform_program", lambda: program)
    st = PostingStore()
    st.apply_schema("follows: uid .")
    for s in range(1, SEED_ROWS + 1):
        for d in range(SEED_FAN):
            st.set_edge("follows", s, 100_000 + s * 10_000 + d)
    server = DgraphServer(st)
    server.start()
    try:
        q = """{ q(func: uid(0x1, 0x2, 0x3, 0x4)) {
                   uid follows { uid } } }"""
        req = urllib.request.Request(
            server.addr + "/query?ledger=true&debug=true",
            data=q.encode(), method="POST",
        )
        with urllib.request.urlopen(req, timeout=60) as r:
            out = json.loads(r.read().decode())
        return out
    finally:
        server.stop()


def test_serving_path_byte_identical_across_programs(monkeypatch):
    """ISSUE 16 acceptance: the csr program is byte-identical to the
    resident program through the FULL serving path — DgraphServer with
    the scheduler, result/hop caches and planner armed — while the
    ledger proves the resident server actually took route:resident."""
    off = _serve_once(monkeypatch, "csr")
    frc = _serve_once(monkeypatch, "resident")
    hops_off = off.pop("extensions")["ledger"].get("hops", {})
    hops_frc = frc.pop("extensions")["ledger"].get("hops", {})
    off.pop("server_latency", None)  # debug timings, not data
    frc.pop("server_latency", None)
    assert off == frc
    assert "resident" not in hops_off
    assert hops_frc.get("resident", 0) >= 1, hops_frc
