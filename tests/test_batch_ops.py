"""Batched/fused frontier executor (ops/batch.py) — property tests.

Seeded-random agreement tests between the batched [B, L] kernels and the
scalar sets.py ops across ragged valid-lengths, empty sets, and all-SENT
rows; the lax.scan multi-hop driver vs a host BFS; and goldens with
every level on the device route and chains fused against every level on
the host route and no chain.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from dgraph_tpu import ops
from dgraph_tpu.ops.sets import SENT
from dgraph_tpu.models.arena import csr_dense_from_edges


def _rand_set(rng, lo, hi, max_n, L):
    """A sorted-unique-padded row: sometimes empty, sometimes full."""
    n = int(rng.integers(0, max_n + 1))
    return ops.pad_to(np.unique(rng.integers(lo, hi, size=n)), L)


# ---------------------------------------------------------------- set ops


def test_batched_set_ops_vs_scalar():
    rng = np.random.default_rng(42)
    B, L = 9, 64
    for _ in range(8):
        A = np.stack([_rand_set(rng, 0, 90, 50, L) for _ in range(B)])
        Bm = np.stack([_rand_set(rng, 0, 90, 50, L) for _ in range(B)])
        A[0, :] = SENT  # all-SENT row
        gi = np.asarray(ops.intersect_batch(jnp.asarray(A), jnp.asarray(Bm)))
        gd = np.asarray(ops.difference_batch(jnp.asarray(A), jnp.asarray(Bm)))
        gm = np.asarray(ops.member_mask_batch(jnp.asarray(A), jnp.asarray(Bm)))
        for i in range(B):
            av, bv = A[i][A[i] != SENT], Bm[i][Bm[i] != SENT]
            assert np.array_equal(gi[i], ops.pad_to(np.intersect1d(av, bv), L))
            assert np.array_equal(gd[i], ops.pad_to(np.setdiff1d(av, bv), L))
            want_m = np.isin(A[i], bv) & (A[i] != SENT)
            assert np.array_equal(gm[i], want_m)


def test_union_many_batch_vs_scalar():
    rng = np.random.default_rng(7)
    B, K, L = 5, 3, 32
    mats = np.stack([
        np.stack([_rand_set(rng, 0, 60, 20, L) for _ in range(K)])
        for _ in range(B)
    ])
    got = np.asarray(ops.union_many_batch(jnp.asarray(mats)))
    for i in range(B):
        vals = mats[i][mats[i] != SENT]
        assert np.array_equal(got[i], ops.pad_to(np.unique(vals), K * L))


def test_sort_unique_batch_vs_scalar():
    rng = np.random.default_rng(3)
    x = rng.integers(0, 50, size=(6, 48)).astype(np.int32)
    x[2, :] = SENT
    got = np.asarray(ops.sort_unique_batch(jnp.asarray(x)))
    for i in range(6):
        vals = x[i][x[i] != SENT]
        assert np.array_equal(got[i], ops.pad_to(np.unique(vals), 48))


# ----------------------------------------------------- fused hop programs


@pytest.fixture(scope="module")
def graph():
    rng = np.random.default_rng(5)
    n = 600
    src = rng.integers(1, n + 1, size=5000)
    dst = rng.integers(1, n + 1, size=5000)
    # one celebrity source beyond the widest gather class → the dense
    # heavy bucket must serve it
    heavy_dst = rng.integers(1, n + 1, size=3000)
    src = np.concatenate([src, np.full(3000, 17)])
    dst = np.concatenate([dst, heavy_dst])
    return csr_dense_from_edges(src, dst, n)


def test_expand_ascending_vs_host(graph):
    a = graph
    rng = np.random.default_rng(1)
    for _ in range(10):
        f = np.unique(rng.integers(1, 601, size=int(rng.integers(1, 120))))
        rows = ops.pad_rows(f, ops.bucket(len(f)))
        cap = ops.bucket(max(1, int(a.degree_of_rows(f).sum())))
        out, total = ops.expand_ascending(
            a.offsets, a.dst, jnp.asarray(rows), cap
        )
        out = np.asarray(out)
        want, _ = a.expand_host(f)
        assert int(total) == len(want)
        assert np.array_equal(np.sort(out[out != SENT]), np.sort(want))


def test_expand_filter_compact_vs_scalar_ops(graph):
    a = graph
    rng = np.random.default_rng(2)
    for trial in range(8):
        f = np.unique(rng.integers(1, 601, size=40))
        cap = ops.bucket(max(1, int(a.degree_of_rows(f).sum())))
        rows = jnp.asarray(ops.pad_rows(f, ops.bucket(len(f))))
        knp = [
            np.unique(rng.integers(1, 601, size=int(rng.integers(0, 400))))
            for _ in range(trial % 3)
        ]
        keeps = tuple(
            jnp.asarray(ops.pad_to(k, ops.bucket(max(1, len(k))))) for k in knp
        )
        u, total = ops.expand_filter_compact(a.offsets, a.dst, rows, cap, keeps)
        u = np.asarray(u)
        u = u[u != SENT]
        out, _ = a.expand_host(f)
        want = np.unique(out)
        for k in knp:
            want = np.intersect1d(want, k)
        assert np.array_equal(u, want)
        assert int(total) == len(out)  # raw traversal count, pre-filter


def test_expand_filter_compact_batch_matches_scalar(graph):
    a = graph
    rng = np.random.default_rng(9)
    B, L = 6, 64
    fs = [np.unique(rng.integers(1, 601, size=30)) for _ in range(B)]
    rows = jnp.asarray(np.stack([ops.pad_rows(f, L) for f in fs]))
    cap = ops.bucket(max(int(a.degree_of_rows(f).sum()) for f in fs))
    keep = np.unique(rng.integers(1, 601, size=300))
    kj = (jnp.asarray(ops.pad_to(keep, ops.bucket(len(keep)))),)
    ub, tb = ops.expand_filter_compact_batch(a.offsets, a.dst, rows, cap, kj)
    for i, f in enumerate(fs):
        us, ts = ops.expand_filter_compact(
            a.offsets, a.dst, rows[i], cap, kj
        )
        assert np.array_equal(np.asarray(ub[i]), np.asarray(us))
        assert int(tb[i]) == int(ts)


# ------------------------------------------------------------- multi-hop


def test_multi_hop_vs_host_bfs(graph):
    a = graph
    rng = np.random.default_rng(11)
    f0 = np.unique(rng.integers(1, 601, size=12))
    cap = ops.bucket(a.n_edges)
    fr = jnp.asarray(ops.pad_to(f0, cap))
    vis = jnp.asarray(ops.pad_to(f0, cap))
    fs, totals, _ = ops.multi_hop(
        a.offsets, a.dst, fr, vis, 3, cap, track_visited=True
    )
    fs, totals = np.asarray(fs), np.asarray(totals)
    cur, seen = f0, f0.copy()
    for h in range(3):
        out, _ = a.expand_host(cur)
        assert int(totals[h]) == len(out)
        nxt = np.setdiff1d(np.unique(out), seen)
        assert np.array_equal(fs[h][fs[h] != SENT], nxt)
        seen = np.union1d(seen, nxt)
        cur = nxt


def test_multi_hop_no_visited(graph):
    a = graph
    f0 = np.array([17, 200, 300], dtype=np.int64)
    cap = ops.bucket(a.n_edges)
    fr = jnp.asarray(ops.pad_to(f0, cap))
    vis = jnp.full((cap,), SENT, dtype=jnp.int32)
    fs, totals, _ = ops.multi_hop(a.offsets, a.dst, fr, vis, 2, cap)
    cur = f0
    for h in range(2):
        out, _ = a.expand_host(cur)
        assert int(totals[h]) == len(out)
        cur = np.unique(out)
        assert np.array_equal(np.asarray(fs[h])[np.asarray(fs[h]) != SENT], cur)


# ------------------------------------------------------ mesh batch entry


def test_mesh_batched_frontiers(graph):
    import jax

    if len(jax.devices()) < 4:
        pytest.skip("needs the virtual multi-device mesh")
    from dgraph_tpu.parallel import make_mesh
    from dgraph_tpu.parallel.mesh import batched_expand_frontiers

    a = graph
    rng = np.random.default_rng(13)
    mesh = make_mesh(8, data=4)
    B, R = 6, 32
    fr = np.stack([
        ops.pad_to(np.unique(rng.integers(1, 601, size=20)), R)
        for _ in range(B)
    ])
    cap = ops.bucket(a.n_edges)
    f2, totals = batched_expand_frontiers(
        mesh, a.offsets, a.dst, fr, cap, n_hops=2
    )
    for i in range(B):
        f = fr[i][fr[i] != SENT]
        o1, _ = a.expand_host(f)
        f1 = np.unique(o1)
        o2, _ = a.expand_host(f1)
        got = f2[i][f2[i] != SENT]
        assert np.array_equal(got, np.unique(o2))
        assert totals[i, 0] == len(o1) and totals[i, 1] == len(o2)


# ------------------------------------------- engine: device/host goldens


@pytest.fixture(scope="module")
def store():
    from dgraph_tpu.models import PostingStore
    from dgraph_tpu.query import QueryEngine

    st = PostingStore()
    eng = QueryEngine(st)
    eng.run(
        "mutation { schema { friend: uid @reverse . "
        'name: string @index(exact) . age: int @index(int) . } }'
    )
    rng = np.random.default_rng(21)
    st.bulk_set_uid_edges(
        "friend",
        rng.integers(1, 250, size=2500),
        rng.integers(1, 250, size=2500),
    )
    # names chosen to REVERSE uid order under orderasc(name), so the
    # ordered-root golden below really feeds a permuted frontier
    eng.run(
        'mutation { set { <0x1> <name> "root" . <0x3> <name> "m" . '
        '<0x5> <name> "a" . } }'
    )
    return st


GOLDEN_QUERIES = [
    '{ me(func: uid(1, 2, 3)) { _uid_ friend { _uid_ friend { _uid_ } } } }',
    '{ me(func: uid(5)) { friend @filter(uid(1, 2, 3, 4, 5, 6, 7, 8)) '
    '{ _uid_ } } }',
    '{ v as var(func: uid(1, 2)) { friend { friend } } '
    'me(func: uid(v)) { _uid_ } }',
    '{ var(func: uid(3)) @recurse(depth: 3) { w as friend } '
    'me(func: uid(w)) { _uid_ } }',
    # ordered root: dest_uids are name-permuted, NOT ascending — the
    # fused recurse/scan paths must reject and fall back (a permuted
    # frontier silently corrupts expand_ascending's slot telescoping)
    '{ var(func: uid(1, 3, 5), orderasc: name) @recurse(depth: 3) '
    '{ w as friend } me(func: uid(w)) { _uid_ } }',
    '{ me(func: uid(2)) @cascade { _uid_ name friend { _uid_ } } }',
]


@pytest.mark.parametrize("qi", range(len(GOLDEN_QUERIES)))
def test_goldens_device_vs_host(store, qi):
    """Every level on the device route with chains fused, and every level
    on the host route with no chain, must produce identical responses."""
    from dgraph_tpu.query import QueryEngine

    q = GOLDEN_QUERIES[qi]
    dev = QueryEngine(store)
    dev.expand_device_min = 0
    dev.chain_threshold = 0
    host = QueryEngine(store)
    host.expand_device_min = 1 << 62
    host.chain_threshold = 1 << 62
    assert dev.run(q) == host.run(q)


def test_cascade_prune_vectorized(store):
    """@cascade pruning (now np.isin-vectorized) drops parents missing a
    value child."""
    from dgraph_tpu.query import QueryEngine

    eng = QueryEngine(store)
    got = eng.run('{ me(func: uid(1, 2, 3)) @cascade { _uid_ name } }')
    # 0x1 and 0x3 carry names; 0x2 must prune
    assert [x["_uid_"] for x in got["me"]] == ["0x1", "0x3"]
