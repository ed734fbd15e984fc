"""Property tests: JAX set kernels == NumPy reference on random inputs.

Mirrors algo/uidlist_test.go in the reference (random sorted lists,
intersect/merge/difference correctness) plus CSR expansion.
"""

import numpy as np
import pytest

from dgraph_tpu import ops
from dgraph_tpu.ops import ref
from dgraph_tpu.ops import SENT


def rand_set(rng, max_len=64, max_val=200):
    n = rng.integers(0, max_len + 1)
    return np.unique(rng.integers(0, max_val, size=n)).astype(np.int32)


def unpad(x):
    x = np.asarray(x)
    return x[x != SENT]


@pytest.fixture(scope="module")
def rng():
    return np.random.default_rng(42)


def test_sort_unique(rng):
    for _ in range(20):
        n = rng.integers(0, 50)
        raw = rng.integers(0, 60, size=n).astype(np.int32)
        cap = ops.bucket(max(1, n))
        got = unpad(ops.sort_unique(ops.pad_to(raw, cap)))
        np.testing.assert_array_equal(got, np.unique(raw))


@pytest.mark.parametrize("op,refop", [
    ("intersect", ref.intersect),
    ("difference", ref.difference),
])
def test_binary_ops(rng, op, refop):
    fn = getattr(ops, op)
    for _ in range(30):
        a, b = rand_set(rng), rand_set(rng)
        cap = ops.bucket(max(1, len(a), len(b)))
        got = unpad(fn(ops.pad_to(a, cap), ops.pad_to(b, cap)))
        np.testing.assert_array_equal(got, refop(a, b))


def test_union(rng):
    for _ in range(30):
        a, b = rand_set(rng), rand_set(rng)
        cap = ops.bucket(max(1, len(a), len(b)))
        got = unpad(ops.union(ops.pad_to(a, cap), ops.pad_to(b, cap)))
        np.testing.assert_array_equal(got, ref.union(a, b))


def test_intersect_many(rng):
    for _ in range(10):
        k = rng.integers(2, 6)
        lists = [rand_set(rng, max_val=80) for _ in range(k)]
        cap = ops.bucket(max(1, max(len(l) for l in lists)))
        mat = np.stack([ops.pad_to(l, cap) for l in lists])
        got = unpad(ops.intersect_many(mat))
        np.testing.assert_array_equal(got, ref.intersect_many(lists))


def test_union_many(rng):
    for _ in range(10):
        k = rng.integers(2, 6)
        lists = [rand_set(rng, max_val=80) for _ in range(k)]
        cap = ops.bucket(max(1, max(len(l) for l in lists)))
        mat = np.stack([ops.pad_to(l, cap) for l in lists])
        got = unpad(ops.union_many(mat))
        np.testing.assert_array_equal(got, ref.union_many(lists))


def test_member_mask(rng):
    for _ in range(20):
        a, s = rand_set(rng), rand_set(rng)
        cap = ops.bucket(max(1, len(a), len(s)))
        pa = ops.pad_to(a, cap)
        got = np.asarray(ops.member_mask(pa, ops.pad_to(s, cap)))
        want = np.zeros(cap, dtype=bool)
        want[: len(a)] = ref.member_mask(a, s)
        np.testing.assert_array_equal(got, want)


def make_csr(rng, nrows=10, max_deg=8, max_val=100):
    lists = [np.sort(rng.choice(max_val, size=rng.integers(0, max_deg), replace=False)).astype(np.int32)
             for _ in range(nrows)]
    offsets = np.zeros(nrows + 1, dtype=np.int32)
    offsets[1:] = np.cumsum([len(l) for l in lists])
    dst = np.concatenate(lists) if lists else np.empty(0, dtype=np.int32)
    return offsets, dst.astype(np.int32), lists


def test_expand_csr(rng):
    for _ in range(15):
        offsets, dst, lists = make_csr(rng)
        nrows = len(lists)
        b = rng.integers(1, 6)
        rows = rng.integers(-1, nrows, size=b).astype(np.int32)
        want = ref.expand_csr(offsets, dst, rows)
        cap = ops.bucket(max(1, len(want)))
        out, seg, total = ops.expand_csr(offsets, dst, rows, cap)
        out, seg = np.asarray(out), np.asarray(seg)
        assert int(total) == len(want)
        np.testing.assert_array_equal(out[: len(want)], want)
        assert np.all(out[len(want):] == SENT)
        # seg maps each slot to the input position that produced it
        want_seg = np.concatenate(
            [np.full(len(lists[r]), i) for i, r in enumerate(rows) if r >= 0]
            or [np.empty(0, dtype=np.int64)]
        )
        np.testing.assert_array_equal(seg[: len(want)], want_seg)
        assert np.all(seg[len(want):] == -1)


def test_expand_csr_empty_arena():
    offsets = np.zeros(4, dtype=np.int32)
    dst = np.empty(0, dtype=np.int32)
    out, seg, total = ops.expand_csr(offsets, dst, np.array([0, 1, 2], np.int32), 8)
    assert int(total) == 0
    assert np.all(np.asarray(out) == SENT)
    assert np.all(np.asarray(seg) == -1)


def test_rows_of(rng):
    src = np.unique(rng.integers(0, 100, size=20)).astype(np.int32)
    cap = ops.bucket(len(src))
    psrc = ops.pad_to(src, cap)
    uids = np.array([src[0], 101, src[-1], SENT], dtype=np.int32)
    got = np.asarray(ops.rows_of(psrc, ops.pad_to(uids[:3], 4)))
    assert got[0] == 0
    assert got[1] == -1
    assert got[2] == len(src) - 1
    assert got[3] == -1


def test_range_rows():
    rows, n = ops.range_rows(2, 5, 8)
    np.testing.assert_array_equal(np.asarray(rows), [2, 3, 4, -1, -1, -1, -1, -1])
    assert int(n) == 3
    rows, n = ops.range_rows(0, 10, 4)  # overflow: truncated, n signals it
    assert int(n) == 10
    np.testing.assert_array_equal(np.asarray(rows), [0, 1, 2, 3])


def test_expand_inline_seg_degree_boundaries():
    """expand_inline_seg (inline-head layout) reproduces the reference CSR
    expansion exactly: inline ∪ overflow lanes = the row's full target
    multiset, totals exact, -1 skips honored, across degree edge cases
    (1, INLINE, INLINE+1, INLINE+8, big)."""
    import numpy as np
    import jax
    from dgraph_tpu import ops
    from dgraph_tpu.models.arena import csr_from_edges
    from dgraph_tpu.ops.sets import SENT

    rng = np.random.default_rng(11)
    # degrees hitting every boundary around INLINE and chunk width
    # (0-degree uids simply have no row in the arena)
    degs = [1, ops.INLINE - 1, ops.INLINE, ops.INLINE + 1,
            ops.INLINE + 7, ops.INLINE + 8, ops.INLINE + 9, 40, 100]
    src, dst = [], []
    for u, d in enumerate(degs):
        tgts = rng.choice(5000, size=d, replace=False)
        src += [u + 1] * d
        dst += list(tgts)
    a = csr_from_edges(np.array(src, np.int64), np.array(dst, np.int64))
    metap, ov = a.inline_layout()
    # expand every row + skips, ascending-distinct with -1 interleaved
    rows = np.array([0, -1, 1, 2, 3, -1, 4, 5, 6, 7, 8, -1], np.int32)
    capc = int(a.ov_chunk_degree_of_rows(rows).sum()) or 1
    capc = ops.bucket_fine(capc)
    inline, ovout, total, _ovseg = ops.expand_inline_seg(
        metap, ov, jax.device_put(rows), capc
    )
    inline, ovout = np.asarray(inline), np.asarray(ovout)
    got = np.concatenate([inline.reshape(-1), ovout.reshape(-1)])
    got = np.sort(got[got != SENT])
    want, _ = a.expand_host(rows)
    assert int(total) == len(want)
    assert np.array_equal(got, np.sort(want.astype(np.int32)))
    # per-row: inline lanes hold the FIRST min(deg, INLINE) targets ascending
    for i, r in enumerate(rows):
        if r < 0:
            assert (inline[i] == SENT).all()
            continue
        tgts = np.sort(np.asarray(a.expand_host(np.array([r]))[0]))
        head = inline[i][inline[i] != SENT]
        assert np.array_equal(head, tgts[: len(head)].astype(np.int32))
        assert len(head) == min(len(tgts), ops.INLINE)


def test_bucket_fine_steps():
    from dgraph_tpu.ops.sets import bucket_fine, bucket

    assert bucket_fine(1) == 8 and bucket_fine(8) == 8
    assert bucket_fine(9) == 9  # 8 + step(1)
    assert bucket_fine(22008) == 22528  # < bucket's 32768
    assert bucket_fine(1 << 20) == 1 << 20
    for n in (17, 100, 5000, 22008, 70000):
        b = bucket_fine(n)
        assert n <= b <= bucket(n)
        assert b - n <= max(1, b >> 3)
    assert bucket_fine(3) == 8  # floor


def test_expand_inline_seg_owners():
    """expand_inline_seg's overflow owners reconstruct the exact per-row
    uid matrix (inline-then-overflow per row, ascending)."""
    import numpy as np
    import jax
    from dgraph_tpu import ops
    from dgraph_tpu.models.arena import csr_from_edges
    from dgraph_tpu.ops.sets import SENT

    rng = np.random.default_rng(3)
    src = rng.integers(1, 200, size=3000)
    dst = rng.integers(1, 5000, size=3000)
    a = csr_from_edges(src, dst)
    metap, ov = a.inline_layout()
    rows = np.array([0, -1, 3, 5, 9, 20, -1, 40, a.n_rows - 1], np.int32)
    capc = ops.bucket_fine(int(a.ov_chunk_degree_of_rows(rows).sum()) or 1)
    inline, ovout, total, ovseg = ops.expand_inline_seg(
        metap, ov, jax.device_put(rows), capc
    )
    inline, ovout, ovseg = map(np.asarray, (inline, ovout, ovseg))
    want, wptr = a.expand_host(rows)
    assert int(total) == len(want)
    # reassemble per-row: inline lanes then overflow chunks owned by it
    for i, r in enumerate(rows):
        exp = want[wptr[i] : wptr[i + 1]].astype(np.int64)
        inl = inline[i][inline[i] != SENT].astype(np.int64)
        ovi = ovout[ovseg == i].reshape(-1)
        ovi = ovi[ovi != SENT].astype(np.int64)
        got = np.concatenate([inl, ovi])
        assert np.array_equal(got, exp), (i, r)


@pytest.mark.parametrize("seed", range(8))
def test_expand_inline_seg_fuzz(seed):
    """Randomized graphs × random ascending frontiers with skips: the
    inline+overflow reassembly must equal expand_host exactly (values,
    per-row grouping, order)."""
    import numpy as np
    import jax
    from dgraph_tpu import ops
    from dgraph_tpu.models.arena import csr_from_edges
    from dgraph_tpu.ops.sets import SENT
    from dgraph_tpu.query.chain import inline_to_matrix

    rng = np.random.default_rng(100 + seed)
    n_nodes = int(rng.integers(20, 400))
    n_edges = int(rng.integers(1, 3000))
    src = rng.integers(1, n_nodes + 1, size=n_edges)
    # mix: mostly small rows + a few heavy hubs straddling chunk bounds
    dst = rng.integers(1, 4 * n_nodes, size=n_edges)
    hub = int(rng.integers(1, n_nodes + 1))
    extra = rng.integers(1, 4 * n_nodes, size=int(rng.integers(0, 90)))
    src = np.concatenate([src, np.full(len(extra), hub)])
    dst = np.concatenate([dst, extra])
    a = csr_from_edges(src, dst)
    metap, ov = a.inline_layout()

    n_pick = int(rng.integers(1, a.n_rows + 1))
    rows = np.sort(rng.choice(a.n_rows, size=n_pick, replace=False)).astype(np.int32)
    # interleave skips
    skips = rng.random(n_pick) < 0.2
    rows_sk = rows.copy()
    rows_sk[skips] = -1
    capc = ops.bucket_fine(int(a.ov_chunk_degree_of_rows(rows_sk).sum()) or 1)
    inline, ovout, total, ovseg = ops.expand_inline_seg(
        metap, ov, jax.device_put(rows_sk), capc
    )
    out, seg_ptr = inline_to_matrix(
        np.asarray(inline), np.asarray(ovout).reshape(-1), np.asarray(ovseg),
        len(rows_sk),
    )
    want, wptr = a.expand_host(rows_sk)
    assert int(total) == len(want)
    assert np.array_equal(out, want)
    assert np.array_equal(seg_ptr, wptr)
