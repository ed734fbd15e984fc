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

from dgraph_tpu.utils.planconfig import expand_impl

# Padding sentinel: int32 max. Sorts after every valid uid.
SENT = (1 << 31) - 1

# expand_csr owner-computation strategy; see comment in expand_csr.
# (Knob read lives in utils/planconfig.py with the other route/kernel
# selection knobs — graftlint: naked-route-threshold.)
_EXPAND_IMPL = expand_impl()


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
    reduction below must be PROVABLY scan-free (bench_ops.py asserts
    it on the jaxpr).  Result shaped like ``a`` (|a ∩ b| ≤ |a|)."""
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
    no-op.  bench_ops.py asserts the lowered program contains no
    ``scan`` primitive."""
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
    sorts, so no reduction tree is needed here (bench_ops.py asserts
    the no-scan property for both k-way folds)."""
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
    # Owner of output slot i = the row whose [start, start+deg) covers i.
    # Two interchangeable constructions (DGRAPH_TPU_EXPAND_IMPL):
    #  "scan"  (default): scatter an indicator at each productive row's
    #          start slot, prefix-sum to get the owning productive-row
    #          ordinal, map through the compacted row list — O(cap)
    #          memory-bound work.
    #  "search": vectorized binary search over the cumulative degrees —
    #          cap×log(nrows) random gathers; slower at large caps but a
    #          safe fallback while the scan path is qualified per stack.
    if _EXPAND_IMPL == "search":
        i = jnp.arange(cap, dtype=jnp.int32)
        seg = jnp.searchsorted(cum, i, side="right").astype(jnp.int32)
        segc = jnp.clip(seg, 0, nrows - 1)
    else:
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


@partial(jax.jit, static_argnames=("n_universe", "cap"))
def unique_dense(x: jnp.ndarray, n_universe: int, cap: int) -> jnp.ndarray:
    """Sort-free dedup for dense uid spaces: scatter into a presence mask
    over [0, n_universe], then fixed-size nonzero (cumsum-based
    compaction).  O(n_universe + |x|) memory-bound work instead of the
    O(n log^2 n) bitonic sorts of sort_unique — the reason the engine
    uses dense int32 uids.  Result is ascending, SENT-padded; silently
    truncates if more than ``cap`` distinct values (callers size cap to
    the universe or the input length)."""
    mask = jnp.zeros(n_universe + 2, dtype=bool)
    slot = jnp.where((x >= 0) & (x <= n_universe), x, n_universe + 1)
    mask = mask.at[slot].set(True)
    mask = mask.at[n_universe + 1].set(False)
    idx = jnp.nonzero(mask, size=cap, fill_value=SENT)[0]
    return idx.astype(jnp.int32)


@jax.jit
def unique_rows_sorted(x: jnp.ndarray) -> jnp.ndarray:
    """Deduplicate a padded uid vector into *dense-arena row* form without
    compaction: sort, then mark duplicates and padding as -1 (expand_csr's
    skip marker).  One sort + one compare — no universe-sized scatter, no
    nonzero compaction; the price is that the result keeps the input's
    capacity (harmless: skip rows cost nothing in the expansion kernel).
    This is the frontier-dedup that replaces unique_dense on the 2-hop
    hot path (TPU scatters serialize; sorts ride the VPU)."""
    x = sort_desc_free(x)
    first = jnp.concatenate([jnp.ones((1,), dtype=bool), x[1:] != x[:-1]])
    keep = first & (x != SENT)
    return jnp.where(keep, x, -1).astype(jnp.int32)


CHUNK = 8  # chunk width in uids: 8 × int32 = 32 bytes, one aligned granule


@partial(jax.jit, static_argnames=("capc", "with_seg"))
def expand_chunked(
    meta8: jnp.ndarray,
    chunk_dst: jnp.ndarray,
    rows: jnp.ndarray,
    capc: int,
    with_seg: bool = False,
):
    """Chunked CSR expansion: the fast path of the posting-list gather.

    Replaces expand_csr's per-element scalar gathers with per-*chunk*
    row gathers from a [NC, CHUNK] layout (one 32-byte aligned granule per
    index — measured ~2× cheaper per index than scalar gathers on v5e,
    and each index fetches CHUNK uids instead of one).

    The slot→chunk mapping needs no owner search at all when ``rows`` is
    an ascending sequence of *distinct* row ids (with -1 skips anywhere —
    exactly what sort-based dedup produces): per productive row j scatter
    ``delta_j = chunk_start[j] - prev_productive_chunk_end[j]`` at its
    output start, prefix-sum, add the slot iota.  Telescoping makes slot
    i of row j read ``chunk_start[j] + (i - out_start[j])`` — the exact
    chunk id.  One scatter + three scans + two row gathers per hop,
    everything else elementwise.  (Replaces the reference's per-key
    posting iteration, worker/task.go:287-440, same as expand_csr.)

    Args:
      meta8:     int32[Sb, 8] per-row metadata, lanes 0..2 =
                 (chunk_start, chunk_count, degree); rest zero-pad.
      chunk_dst: int32[NCb, CHUNK] chunk-packed target uids, ascending
                 within each row, SENT in padding lanes.
      rows:      int32[B] row ids, ascending over the valid entries, each
                 valid row DISTINCT; -1 = skip (may appear anywhere).
      capc:      static chunk capacity of the output.
      with_seg:  also return seg: int32[capc] index into ``rows`` owning
                 each chunk slot (-1 pad) — costs one extra scatter+scan.

    Returns:
      out:    int32[capc, CHUNK] target uids, SENT-padded.
      total:  int32 — number of valid uids (true edge count).
      seg:    int32[capc] or None (see with_seg).
    """
    nc = chunk_dst.shape[0]
    valid = rows >= 0
    r = jnp.where(valid, rows, 0)
    m = meta8[r]  # [B, 8] one row gather
    cs = jnp.where(valid, m[:, 0], 0)
    cd = jnp.where(valid, m[:, 1], 0)
    dg = jnp.where(valid, m[:, 2], 0)
    ccum = jnp.cumsum(cd)
    totc = ccum[-1]
    cstart = ccum - cd
    productive = cd > 0
    # exclusive running max of productive rows' chunk-range ends
    end = jnp.where(productive, cs + cd, 0)
    pe = jnp.concatenate(
        [jnp.zeros((1,), end.dtype), jax.lax.cummax(end)[:-1]]
    )
    delta = cs - pe
    slot = jnp.where(productive, cstart, capc)
    dvec = (
        jnp.zeros((capc,), dtype=jnp.int32)
        .at[slot]
        .set(jnp.where(productive, delta, 0).astype(jnp.int32), mode="drop")
    )
    i = jnp.arange(capc, dtype=jnp.int32)
    chunkid = jnp.cumsum(dvec) + i
    ok = i < totc
    out = chunk_dst[jnp.clip(jnp.where(ok, chunkid, 0), 0, nc - 1)]
    out = jnp.where(ok[:, None], out, SENT)
    total = jnp.sum(dg).astype(jnp.int32)
    if not with_seg:
        return out, total, None
    # owner ordinal per slot: scatter +1 at each productive start, scan,
    # then map ordinal -> position in ``rows`` via a second compaction
    ivec = (
        jnp.zeros((capc,), dtype=jnp.int32)
        .at[slot]
        .set(1, mode="drop")
    )
    k = jnp.cumsum(ivec) - 1  # ordinal among productive rows
    k_row = jnp.cumsum(productive.astype(jnp.int32)) - 1
    nrows = rows.shape[0]
    pos_of_ord = (
        jnp.zeros((nrows,), dtype=jnp.int32)
        .at[jnp.where(productive, k_row, nrows)]
        .set(jnp.arange(nrows, dtype=jnp.int32), mode="drop")
    )
    seg = pos_of_ord[jnp.clip(k, 0, nrows - 1)]
    return out, total, jnp.where(ok, seg, -1)


INLINE = 6  # inline posting-head lanes in the meta-plus row (32B granule)


def expand_inline(
    metap: jnp.ndarray,
    ov_chunks: jnp.ndarray,
    rows: jnp.ndarray,
    capc: int,
):
    """Inline-head expansion: the round-4 fast path of the posting gather.

    The decisive cost on TPU is gather-engine index rate (~5-20ns per
    32-byte row regardless of locality — measured, docs/ROOFLINE.md), and
    expand_chunked paid TWO row gathers per frontier row (meta + >= 1
    chunk) even though the mean posting list is ~8 long.  This layout
    inlines the first INLINE targets INTO the metadata row, so one gather
    serves both metadata and the whole list for short rows; only rows
    with degree > INLINE touch the 8-wide overflow chunk table.  Against
    the same worker/task.go:287-440 baseline semantics, hop-level gather
    index counts drop ~2x (bench.py: 2.855x -> beyond 6x vs CPU).

    Layout (CSRArena.inline_layout):
      metap:     int32[S, 8] - lane0 = overflow chunk start, lane1 =
                 degree (overflow chunk count derives on device:
                 ceil(max(0, deg-INLINE)/8)), lanes 2..7 = first INLINE
                 targets ascending, SENT-padded.
      ov_chunks: int32[NCov, 8] - targets INLINE.. of each row, 8 per
                 chunk, ascending, SENT pad lanes; UNPADDED row count
                 (pow2-padding the table costs gather rate, not just HBM).

    Args:
      rows: int32[B] row ids, ascending over valid entries, DISTINCT;
            -1 = skip (anywhere).
      capc: static overflow-chunk capacity.

    Returns:
      inline: int32[B, INLINE] inline targets (SENT pad).
      ov:     int32[capc, 8] overflow targets (SENT pad).
      total:  int32 - true edge count (sum of degrees).

    This is exactly the grouped kernel with the slot-map prefix spanning
    every row (one shared implementation — the scan/scatter chain lives
    only in expand_inline_grouped).
    """
    return expand_inline_grouped(metap, ov_chunks, rows, capc, rows.shape[0])


# Grouped (skey) coding for inline arenas: stored target ids carry a
# "no-overflow" bit above the uid so one value sort groups rows WITH
# overflow chunks into an ascending prefix — the slot-map scatter then
# runs on a short static prefix instead of the whole frontier.
#
# Capacity: uid < 2^29 (536M rows per arena shard — an order of magnitude
# above the 21M flagship corpus; beyond it callers fall back to the plain
# inline layout).  The bit budget is exact: max skey = (2^29 - 1) | 2^29 =
# 2^30 - 1 < SENT (2^31 - 1), so SENT still sorts strictly last and no
# encoded value can collide with it.  GROUP_BIT = 30 would make
# uid 2^30 - 1 with the no-overflow bit encode EXACTLY SENT — that one
# uid would vanish into padding — hence 29 is the int32 ceiling.
GROUP_BIT = 29
GROUP_MASK = (1 << GROUP_BIT) - 1


def skey_encode(uids: np.ndarray, has_ov: np.ndarray) -> np.ndarray:
    """Host-side: pack uid + no-overflow group bit (see GROUP_BIT)."""
    return (uids | (np.where(has_ov, 0, 1) << GROUP_BIT)).astype(np.int32)


@jax.jit
def skey_uid(v: jnp.ndarray) -> jnp.ndarray:
    """Decode a packed skey lane to its uid; SENT passes through."""
    return jnp.where(v == SENT, SENT, v & GROUP_MASK)


def _ov_slot_map(cs, cd, capc):
    """Shared overflow slot→chunk construction (the scatter + prefix-sum
    telescoping documented in expand_chunked): returns (chunkid[capc],
    ok[capc], cstart, productive)."""
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
    """Shared owner-per-chunk-slot construction (expand_chunked with_seg):
    ordinal of the owning productive row by scatter+scan, mapped back to
    its position in the row vector."""
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


@partial(jax.jit, static_argnames=("capc", "pcap"))
def expand_inline_grouped(
    metap: jnp.ndarray,
    ov_chunks: jnp.ndarray,
    rows: jnp.ndarray,
    capc: int,
    pcap: int,
):
    """expand_inline over a GROUP-ORDERED frontier: every row with
    overflow chunks sits in ``rows[:pcap]`` (what sorting skey-coded
    values produces — see skey_encode).  The metadata gather still covers
    every row (inline lanes), but the overflow slot-map — cumsum, cummax
    and the scatter, the expensive scan chain — runs only on the
    productive prefix.  Outputs carry skey-coded targets; decode with
    skey_uid.

    rows beyond pcap MUST have degree <= INLINE (grouping invariant);
    rows: ascending-distinct within each group, -1 skips anywhere."""
    nc = ov_chunks.shape[0]
    valid = rows >= 0
    r = jnp.where(valid, rows, 0)
    m = metap[r]  # [B, 8] one gather serves inline heads + metadata
    inline = jnp.where(valid[:, None], m[:, 2:], SENT)
    dg = jnp.where(valid, m[:, 1], 0)
    total = jnp.sum(dg).astype(jnp.int32)
    # overflow slot-map on the prefix only
    vp = valid[:pcap]
    cs = jnp.where(vp, m[:pcap, 0], 0)
    cd = (jnp.maximum(jnp.where(vp, dg[:pcap], 0) - INLINE, 0) + 7) >> 3
    chunkid, ok, _cstart, _productive = _ov_slot_map(cs, cd, capc)
    ov = ov_chunks[jnp.clip(jnp.where(ok, chunkid, 0), 0, nc - 1)]
    ov = jnp.where(ok[:, None], ov, SENT)
    return inline, ov, total


def _ov_slot_map_pallas(cs: jnp.ndarray, cd: jnp.ndarray, capc: int):
    """Slot→chunk map via the Pallas kernel (ops/pallas_slotmap.py): one
    VMEM-resident pass replaces the XLA scatter + three O(n log n) scans
    (docs/ROOFLINE.md Path-onward #2, ~15-20% of device time).  Inputs
    pad up to the kernel's 128-lane granularity; the CPU backend runs the
    kernel in interpret mode so the path stays testable there (on a TPU
    it compiles through Mosaic or fails — see pallas_slotmap.py Status).

    Returns (chunkid[capc] clipped to >= 0, ok[capc])."""
    from dgraph_tpu.ops.pallas_slotmap import slotmap_pallas

    pcap = cs.shape[0]
    pp = ((pcap + 127) >> 7) << 7
    cc = ((capc + 127) >> 7) << 7
    csp = jnp.zeros((pp,), jnp.int32).at[:pcap].set(cs)
    cdp = jnp.zeros((pp,), jnp.int32).at[:pcap].set(cd)
    interp = jax.default_backend() == "cpu"
    cid = slotmap_pallas(csp[None], cdp[None], cc, interpret=interp)[0, :capc]
    ok = cid >= 0
    return jnp.where(ok, cid, 0), ok


@partial(jax.jit, static_argnames=("capc", "pcap"))
def expand_inline_grouped_pallas(
    metap: jnp.ndarray,
    ov_chunks: jnp.ndarray,
    rows: jnp.ndarray,
    capc: int,
    pcap: int,
):
    """expand_inline_grouped with the overflow slot-map computed by the
    Pallas kernel instead of the XLA scatter/scan chain — identical
    semantics and invariants (productive rows form the ascending prefix
    of ``rows[:pcap]``; -1 skips only at/after the prefix tail, which the
    skey-sorted frontiers guarantee since SENT sorts last)."""
    nc = ov_chunks.shape[0]
    valid = rows >= 0
    r = jnp.where(valid, rows, 0)
    m = metap[r]
    inline = jnp.where(valid[:, None], m[:, 2:], SENT)
    dg = jnp.where(valid, m[:, 1], 0)
    total = jnp.sum(dg).astype(jnp.int32)
    vp = valid[:pcap]
    cs = jnp.where(vp, m[:pcap, 0], 0)
    cd = (jnp.maximum(jnp.where(vp, dg[:pcap], 0) - INLINE, 0) + 7) >> 3
    chunkid, ok = _ov_slot_map_pallas(cs, cd, capc)
    ov = ov_chunks[jnp.clip(jnp.where(ok, chunkid, 0), 0, nc - 1)]
    ov = jnp.where(ok[:, None], ov, SENT)
    return inline, ov, total


def use_slotmap_pallas() -> bool:
    """Should grouped expansions route their slot-map through the Pallas
    kernel?  DGRAPH_TPU_SLOTMAP (utils/planconfig.py): 'force' = yes, on
    any backend (interpret mode on CPU — the parity-test mode; on a TPU
    it compiles through Mosaic or fails).  '0' and the default '1' = no:
    auto no longer selects the kernel on the TPU backend, because the
    chip's compiler refuses it (ops/pallas_slotmap.py Status)."""
    from dgraph_tpu.utils import planconfig

    return planconfig.slotmap_pallas() == "force"


def expand_inline_grouped_auto(
    metap: jnp.ndarray,
    ov_chunks: jnp.ndarray,
    rows: jnp.ndarray,
    capc: int,
    pcap: int,
):
    """Knob-dispatched grouped expansion: the seam grouped-frontier
    consumers (bench.py's device-dedup pipeline) call so the slot-map
    backend — XLA scan/scatter chain vs the Pallas kernel — is an
    operator decision, not a code fork.  Reads the knob at call/trace
    time; callers embedding this in a long-lived jitted pipeline bind
    the backend at trace time (set the knob before compiling, as with
    the program-shape constants in utils/planconfig.py)."""
    fn = (
        expand_inline_grouped_pallas
        if use_slotmap_pallas()
        else expand_inline_grouped
    )
    return fn(metap, ov_chunks, rows, capc, pcap)


@partial(jax.jit, static_argnames=("capc",))
def expand_inline_seg(
    metap: jnp.ndarray,
    ov_chunks: jnp.ndarray,
    rows: jnp.ndarray,
    capc: int,
):
    """expand_inline + per-overflow-chunk owner indices, for consumers
    that must know which input row produced each slot (the fused chain's
    uid-matrix reconstruction; inline slots' owner is their row position,
    so only the overflow side needs a computed seg).

    Returns (inline[B, INLINE], ov[capc, 8], total, ovseg[capc]) where
    ovseg[j] = index into ``rows`` owning overflow chunk j, -1 on padding.
    Rows: ascending-distinct over valid entries, -1 skips anywhere."""
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
