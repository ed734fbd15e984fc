"""Core fixed-shape sorted-set kernels (JAX).

Data representation
-------------------
A *uid set* is an int32 vector, sorted ascending, with all padding slots
holding ``SENT`` (int32 max).  Because the sentinel is the maximum value,
padding always sorts to the end, so "compact the valid entries" is just a
sort.  All kernels preserve this invariant: inputs and outputs are
sorted-unique-padded unless documented otherwise.  The normative
statement of the contract — including the ``[B, L]`` batch-axis layout
of ops/batch.py, the row-vector (-1 skip) dialect of the expansion
kernels, and the bucketing rules — lives in docs/sets-contract.md.

Why this shape: the reference's algo layer (algo/uidlist.go:42-300 in
/root/reference) walks variable-length sorted []uint64 slices with adaptive
linear/galloping/binary intersection.  On TPU, data-dependent branching is
poison; instead every op is a fixed-shape vector program — searchsorted
(binary search vectorized over lanes), sort (bitonic on the VPU), masked
select — which XLA fuses and tiles.  Dynamic result sizes are handled by
power-of-two *bucketing* of capacities (``bucket``) so jit caches a small
number of compiled shapes.

uids are dense int32 "local ids" assigned at ingest by the uid dictionary
(models/uids.py), not the reference's sparse uint64 space: 64-bit ints are
emulated (slow) on TPU, and dense ids double as direct indexes into value
arenas.

Every jit factory here is registered in the device-program contract
registry (dgraph_tpu/analysis/programs.py): scan-freedom, the int32
dtype discipline, transfer-freedom and the pow2 bucket-key soundness of
expand_csr are checked against golden jaxpr fingerprints by ``python -m
dgraph_tpu.analysis --programs`` — a structural change here must be
re-blessed there (docs/analysis.md "Program contracts").
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

# Padding sentinel: int32 max. Sorts after every valid uid.
SENT = (1 << 31) - 1


def bucket(n: int, floor: int = 8) -> int:
    """Round ``n`` up to a power of two (>= floor) to bound jit cache size."""
    b = floor
    while b < n:
        b <<= 1
    return b


def bucket_fine(n: int, floor: int = 8) -> int:
    """Round ``n`` up to a 1/8-step of a power of two (>= floor).

    Pow2 bucketing wastes up to 2× of every capacity-proportional cost
    (gather indices, scan length, sort width); 1/8 steps cap the waste at
    12.5% for 8× the jit-cache shapes.  Use where one compiled program
    serves a long batch (bench.py, bulk pipelines); latency-sensitive
    mixed query streams keep ``bucket``."""
    if n <= floor:
        return floor
    k = (int(n) - 1).bit_length() - 1
    base = 1 << k
    step = max(1, base >> 3)
    return base + -(-(n - base) // step) * step


def pad_to(x: np.ndarray, size: int, fill: int = SENT) -> np.ndarray:
    """Pad a host int array to ``size`` with ``fill`` (host-side helper)."""
    x = np.asarray(x, dtype=np.int32)
    out = np.full(size, fill, dtype=np.int32)
    out[: x.shape[0]] = x
    return out


def pad_rows(x: np.ndarray, size: int) -> np.ndarray:
    """Pad a host row-index array to ``size`` with -1 (the 'skip' marker
    expand_csr expects — NOT the SENT uid sentinel)."""
    return pad_to(x, size, fill=-1)


@jax.jit
def count_valid(x: jnp.ndarray) -> jnp.ndarray:
    """Number of non-padding entries."""
    return jnp.sum(x != SENT).astype(jnp.int32)


@jax.jit
def compact(x: jnp.ndarray) -> jnp.ndarray:
    """Re-establish the invariant after masking: sort so SENT pads the tail."""
    return sort_desc_free(x)


@jax.jit
def sort_unique(x: jnp.ndarray) -> jnp.ndarray:
    """Sort and deduplicate a padded vector (not necessarily sorted/unique).

    Equivalent of the dedup in algo.MergeSorted (algo/uidlist.go:249-296),
    done as: sort, mark adjacent duplicates, replace with SENT, re-sort.
    """
    x = sort_desc_free(x)
    dup = jnp.concatenate([jnp.zeros((1,), dtype=bool), x[1:] == x[:-1]])
    return sort_desc_free(jnp.where(dup, SENT, x))


@jax.jit
def member_mask(a: jnp.ndarray, s: jnp.ndarray) -> jnp.ndarray:
    """Boolean mask: which entries of ``a`` are present in sorted-unique ``s``.

    Vectorized binary search — the TPU analog of algo.IndexOf
    (algo/uidlist.go:300) applied batchwise.  Padding entries map to False.
    """
    pos = jnp.clip(jnp.searchsorted(s, a), 0, s.shape[0] - 1)
    return (s[pos] == a) & (a != SENT)


@jax.jit
def intersect(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """a ∩ b for sorted-unique-padded sets (result shaped like ``a``).

    Replaces algo.IntersectWith's adaptive linear/jump/binary variants
    (algo/uidlist.go:42-181) with one uniform vectorized binary search —
    the adaptivity is pointless on SIMD hardware where all lanes run anyway.
    """
    return sort_desc_free(jnp.where(member_mask(a, b), a, SENT))


@jax.jit
def difference(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """a \\ b for sorted-unique-padded sets (algo.Difference, uidlist.go:217)."""
    keep = (~member_mask(a, b)) & (a != SENT)
    return sort_desc_free(jnp.where(keep, a, SENT))


@jax.jit
def union(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """a ∪ b, result capacity |a|+|b| (algo.MergeSorted for k=2)."""
    return sort_unique(jnp.concatenate([a, b]))


def _intersect_pair_sorted(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """a ∩ b via duplicate detection over the sorted concatenation: both
    inputs are sorted-UNIQUE, so an element of the merged sort equal to
    its successor appears in both sets.  Two bitonic sorts, zero
    searchsorted — jnp.searchsorted lowers to a lax.scan (even its
    'unrolled' method keeps the scan primitive), and the k-way tree
    reduction below must be PROVABLY scan-free (its contract in
    analysis/programs.py asserts it on the jaxpr).  Result shaped like
    ``a`` (|a ∩ b| ≤ |a|)."""
    z = sort_desc_free(jnp.concatenate([a, b]))
    dup = (z[:-1] == z[1:]) & (z[:-1] != SENT)
    dup = jnp.concatenate([dup, jnp.zeros((1,), bool)])
    return sort_desc_free(jnp.where(dup, z, SENT))[: a.shape[0]]


@jax.jit
def intersect_many(mat: jnp.ndarray) -> jnp.ndarray:
    """Intersect the K rows of a [K, L] padded matrix (algo.IntersectSorted,
    algo/uidlist.go:183-215) as a LOG-DEPTH TREE REDUCTION: rows pair
    off and intersect vmapped per round, halving K each time — ⌈log2 K⌉
    data-parallel rounds instead of the K-1-step serial ``lax.scan``
    fold this kernel used to lower to (every scan step waited on the
    previous accumulator; the tree's rounds each run all their pairwise
    intersections in parallel lanes).  Odd widths pad by duplicating
    the last row — intersection is idempotent, so the duplicate is a
    no-op.  The program contract asserts the jaxpr contains no ``scan``
    primitive."""
    k = mat.shape[0]
    while k > 1:
        if k % 2:
            mat = jnp.concatenate([mat, mat[-1:]])
            k += 1
        mat = jax.vmap(_intersect_pair_sorted)(mat[0::2], mat[1::2])
        k //= 2
    return mat[0]


@jax.jit
def union_many(mat: jnp.ndarray) -> jnp.ndarray:
    """Union of the K rows of a [K, L] padded matrix (k-way MergeSorted,
    algo/uidlist.go:249 — the min-heap becomes one flat sort).  Already
    scan-free: a single bitonic sort over the flattened matrix is
    log²-depth, strictly shallower than a tree of per-round merge
    sorts, so no reduction tree is needed here (the program contracts
    assert the no-scan property for both k-way folds)."""
    return sort_unique(mat.reshape(-1))


@jax.jit
def mask_to_set(values: jnp.ndarray, keep: jnp.ndarray) -> jnp.ndarray:
    """Select ``values`` where ``keep``, as a sorted-unique-padded set."""
    return sort_unique(jnp.where(keep, values, SENT))


@partial(jax.jit, static_argnames=("cap",))
def expand_csr(
    offsets: jnp.ndarray,
    dst: jnp.ndarray,
    rows: jnp.ndarray,
    cap: int,
):
    """Batched posting-list gather: the single hot kernel of the engine.

    Replaces the reference's per-key loop in worker.processTask
    (worker/task.go:287-440: N badger lookups + N iterations) with one
    vectorized CSR expansion over the device-resident arena.

    Args:
      offsets: int32[S+1] CSR row offsets of the arena.
      dst:     int32[E] packed target uids, ascending within each row.
      rows:    int32[B] arena row indices to expand; negative = skip.
      cap:     static output capacity (bucketed total degree).

    Returns:
      out:   int32[cap] concatenated target uids, grouped by source (each
             group sorted ascending), SENT-padded.
      seg:   int32[cap] index into ``rows`` that produced each slot, -1 pad.
             (out, seg) is the uid_matrix of the reference (task.proto:52)
             in CSR form.
      total: int32 scalar, number of valid slots.
    """
    nrows = rows.shape[0]
    if dst.shape[0] == 0:  # edgeless arena: nothing to gather (static shape)
        return (
            jnp.full((cap,), SENT, dtype=jnp.int32),
            jnp.full((cap,), -1, dtype=jnp.int32),
            jnp.int32(0),
        )
    valid = rows >= 0
    r = jnp.where(valid, rows, 0)
    deg = jnp.where(valid, offsets[r + 1] - offsets[r], 0)
    cum = jnp.cumsum(deg)
    total = cum[-1] if nrows > 0 else jnp.int32(0)
    start = cum - deg
    # Owner of output slot i = the row whose [start, start+deg) covers i:
    # scatter an indicator at each productive row's start slot, prefix-sum
    # to get the owning productive-row ordinal, map through the compacted
    # row list — O(cap) memory-bound work.
    productive = deg > 0
    slot = jnp.where(productive, start, cap)  # cap = dropped
    ind = jnp.zeros((cap,), dtype=jnp.int32).at[slot].set(1, mode="drop")
    k = jnp.cumsum(ind) - 1  # ordinal of the owning productive row
    prows = jnp.nonzero(productive, size=nrows, fill_value=0)[0].astype(jnp.int32)
    seg = prows[jnp.clip(k, 0, nrows - 1)]
    segc = jnp.clip(seg, 0, nrows - 1)
    i = jnp.arange(cap, dtype=jnp.int32)
    within = i - start[segc]
    edge = offsets[r[segc]] + within
    ok = i < total
    out = jnp.where(ok, dst[jnp.clip(edge, 0, dst.shape[0] - 1)], SENT)
    return out, jnp.where(ok, segc, -1), total.astype(jnp.int32)


CHUNK = 8  # chunk width in uids: 8 × int32 = 32 bytes, one aligned granule
INLINE = 6  # inline posting-head lanes in the meta-plus row (32B granule)


def _ov_slot_map(cs, cd, capc):
    """Overflow slot→chunk map, with no owner search.  ``cs``/``cd`` are
    the chunk start and chunk count of an ascending sequence of DISTINCT
    rows (count 0 = skip, anywhere): per productive row j scatter
    ``cs[j] - prev_productive_chunk_end[j]`` at its output start,
    prefix-sum, add the slot iota.  Telescoping makes slot i of row j
    read ``cs[j] + (i - out_start[j])`` — the exact chunk id.  One
    scatter + three scans, everything else elementwise.

    Returns (chunkid[capc], ok[capc], cstart, productive)."""
    ccum = jnp.cumsum(cd)
    cstart = ccum - cd
    productive = cd > 0
    end = jnp.where(productive, cs + cd, 0)
    pe = jnp.concatenate([jnp.zeros((1,), end.dtype), jax.lax.cummax(end)[:-1]])
    slot = jnp.where(productive, cstart, capc)
    dvec = (
        jnp.zeros((capc,), dtype=jnp.int32)
        .at[slot]
        .set(jnp.where(productive, cs - pe, 0).astype(jnp.int32), mode="drop")
    )
    i = jnp.arange(capc, dtype=jnp.int32)
    chunkid = jnp.cumsum(dvec) + i
    return chunkid, i < ccum[-1], cstart, productive


def _ov_owner_map(cstart, productive, capc, nrows):
    """Owner of each chunk slot: ordinal of the owning productive row by
    scatter+scan, mapped back to its position in the row vector."""
    slot = jnp.where(productive, cstart, capc)
    ivec = jnp.zeros((capc,), dtype=jnp.int32).at[slot].set(1, mode="drop")
    k = jnp.cumsum(ivec) - 1
    k_row = jnp.cumsum(productive.astype(jnp.int32)) - 1
    pos_of_ord = (
        jnp.zeros((nrows,), dtype=jnp.int32)
        .at[jnp.where(productive, k_row, nrows)]
        .set(jnp.arange(nrows, dtype=jnp.int32), mode="drop")
    )
    return pos_of_ord[jnp.clip(k, 0, nrows - 1)]


@partial(jax.jit, static_argnames=("capc",))
def expand_inline_seg(
    metap: jnp.ndarray,
    ov_chunks: jnp.ndarray,
    rows: jnp.ndarray,
    capc: int,
):
    """Inline-head expansion with per-overflow-chunk owner indices: the
    fused chain's posting gather (query/chain.py).

    The decisive cost on TPU is gather-engine index rate (~5-20ns per
    32-byte row regardless of locality — measured, docs/ROOFLINE.md), and
    the mean posting list is ~8 long.  The layout inlines the first
    INLINE targets INTO the metadata row, so one gather serves both
    metadata and the whole list for short rows; only rows with degree >
    INLINE touch the 8-wide overflow chunk table.  Same semantics as the
    reference's per-key posting iteration (worker/task.go:287-440).

    Layout (CSRArena.inline_layout):
      metap:     int32[S, 8] - lane0 = overflow chunk start, lane1 =
                 degree (overflow chunk count derives on device:
                 ceil(max(0, deg-INLINE)/8)), lanes 2..7 = first INLINE
                 targets ascending, SENT-padded.
      ov_chunks: int32[cap, 8] - targets INLINE.. of each row, 8 per
                 chunk, ascending, SENT pad lanes; a row's chunks side by
                 side, rows in order (``_ov_slot_map`` leans on it); SENT
                 rows past the chunks in use, up to the arena's bucketed
                 capacity (models/arena.py ``_ov_capacity``: an eighth-step,
                 so a write moves no static shape).  The table's length is
                 read in a ``clip`` only: ``capc`` slots are gathered
                 whatever it is (that padding the table "costs gather rate, not
                 just HBM" was held here from before the chip; PR 34's
                 pairs on the traverse cell are in PERF.md section 6).

    Args:
      rows: int32[B] row ids, ascending over valid entries, DISTINCT;
            -1 = skip (anywhere).
      capc: static overflow-chunk capacity.

    Returns (inline[B, INLINE], ov[capc, 8], total, ovseg[capc]): inline
    and overflow targets (SENT pad), the true edge count, and ovseg[j] =
    index into ``rows`` owning overflow chunk j, -1 on padding (an
    inline slot's owner is its row position, so only the overflow side
    needs a computed seg)."""
    nc = ov_chunks.shape[0]
    nrows = rows.shape[0]
    valid = rows >= 0
    r = jnp.where(valid, rows, 0)
    m = metap[r]
    inline = jnp.where(valid[:, None], m[:, 2:], SENT)
    cs = jnp.where(valid, m[:, 0], 0)
    dg = jnp.where(valid, m[:, 1], 0)
    cd = (jnp.maximum(dg - INLINE, 0) + 7) >> 3
    chunkid, ok, cstart, productive = _ov_slot_map(cs, cd, capc)
    ov = ov_chunks[jnp.clip(jnp.where(ok, chunkid, 0), 0, nc - 1)]
    ov = jnp.where(ok[:, None], ov, SENT)
    ovseg = _ov_owner_map(cstart, productive, capc, nrows)
    return inline, ov, jnp.sum(dg).astype(jnp.int32), jnp.where(ok, ovseg, -1)


def sort_desc_free(x: jnp.ndarray) -> jnp.ndarray:
    """Ascending value sort WITHOUT the stability iota: jnp.sort lowers to
    a stable two-operand (value, iota) sort — measurably slower on TPU.
    Set kernels only ever sort bare values, where stability is
    meaningless, so they use this."""
    return jax.lax.sort(x, dimension=x.ndim - 1, is_stable=False)


@jax.jit
def frontier_rows(f: jnp.ndarray) -> jnp.ndarray:
    """Frontier uids → row indices for a *dense* arena (row i == uid i):
    just map padding to the skip marker."""
    return jnp.where(f == SENT, -1, f).astype(jnp.int32)


@jax.jit
def rows_of(src: jnp.ndarray, uids: jnp.ndarray) -> jnp.ndarray:
    """Map uids to arena row indices via the sorted ``src`` column.

    Returns int32[B]; -1 where the uid has no row (or is padding).
    """
    pos = jnp.clip(jnp.searchsorted(src, uids), 0, src.shape[0] - 1)
    hit = (src[pos] == uids) & (uids != SENT)
    return jnp.where(hit, pos.astype(jnp.int32), -1)


@partial(jax.jit, static_argnames=("cap",))
def range_rows(lo: jnp.ndarray, hi: jnp.ndarray, cap: int):
    """Row indices [lo, hi) as an int32[cap] vector, -1 padded.

    Used for inequality functions: host binary-searches the sorted token
    table for the bucket range, the device unions that contiguous range of
    index posting lists (the analog of worker/sort.go's bucket walk and
    worker/task.go:542-585's inequality handling).

    Returns (rows, n) where n = hi - lo is the true count; like
    expand_csr's ``total``, n > cap signals the caller chose too small a
    cap and must re-bucket — the output alone is silently truncated.
    """
    i = jnp.arange(cap, dtype=jnp.int32)
    n = (hi - lo).astype(jnp.int32)
    return jnp.where(i < n, lo + i, -1), n
