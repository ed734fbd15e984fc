"""MXU-native join tier: blocked boolean matmul over predicate adjacency.

Every traversal in ops/sets.py and ops/batch.py is GATHER-shaped — CSR
expansion plus sort-based set algebra — which leaves the TPU's dominant
compute unit, the MXU, completely idle.  EmptyHeaded (PAPERS.md) shows
that worst-case-optimal generic-join plans beat pairwise expansion by
orders of magnitude on cyclic (triangle/clique) subqueries; RedisGraph
shows the whole traversal algebra runs as GraphBLAS boolean matrix
multiplies — exactly the shape XLA compiles onto the MXU.  This module
is that tier for dgraph-tpu:

- **`PredTiles`**: a predicate's adjacency as BLOCKED boolean tiles —
  only blocks containing at least one edge are materialized, dense
  ``float32[T, T]`` each (T = MXU-native 128 by default), stacked into
  one ``[K, T, T]`` tensor with block coordinates ``(bi, bj)``.  Built
  lazily from the CSR host mirrors under a byte budget
  (``DGRAPH_TPU_TILE_BUDGET``) and cached per-arena
  (models/arena.py::CSRArena.tiles), dying with the arena like every
  other derived layout.
- **`expand_mask`**: frontier-bitmap × adjacency in one program.  The
  frontier is a ``float32[M]`` 0/1 mask over the T-blocked uid space;
  per stored tile the owning block-row of the mask multiplies the tile
  (``einsum('kt,ktu->ku')`` — a batched MXU matvec), and contributions
  combine into block-columns via a one-hot matmul instead of a
  scatter-add (XLA scatter ≈ 100ns/update on CPU and serializes on TPU;
  a ``[K, NB] @ [K, T]`` product rides the MXU).  Output counts > 0 is
  the next frontier — expansion AND dedup in one pass, no sort.
- **`intersect_masks`** / **`intersect_stack`**: k-way intersection.
  Masks intersect as a stacked tile product (ones-row matmul summing
  the stack, == k where all agree); padded uid SETS intersect in ONE
  program via k-1 parallel membership probes against the first set plus
  a single compacting sort — the per-op path dispatches k-1 separate
  sort+probe programs.
- **`run_mask_chain`**: the generic-join driver — a whole multi-level
  uid chain (each level optionally intersected with a keep mask, e.g. a
  fused ``@filter`` or a cycle-closing set) as ONE jitted program; masks
  stay device-resident between levels, per-level edge totals come from
  a degree-vector dot.
- **`triangle_mask`**: the fused cycle-closing kernel — expand two legs
  and intersect against the CLOSING predicate's tiles (reverse
  adjacency from the roots) in one program:
  ``z = ((x·A)·B) ∧ (x·C_rev)``.

Program-cache bounding: every shape entering jit is bucketed (tile
count, mask length = bucket(NB)·T, frontier pads) so a steady workload
compiles a handful of programs and then reuses them — the PR-4 compile
budget hook stays green, and a second same-shape query adds ZERO
programs (tests/test_spgemm.py pins this).

Route choice between this tier and pairwise expansion lives in
query/joinplan.py; docs/deploy.md ("Join tier") covers the knobs.
Every kernel here carries a device-program contract
(analysis/programs.py: the f32 tile discipline, callback/transfer
freedom, mask_lanes bucket soundness, golden fingerprints) — re-bless
with --update-programs after an intentional structural change.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from dgraph_tpu.ops.sets import SENT, bucket, member_mask, sort_desc_free
from dgraph_tpu.utils import planconfig


def tile_size() -> int:
    """Tile edge length (uids per block side).  128 is MXU-native; tests
    may shrink it via DGRAPH_TPU_TILE to exercise multi-block layouts on
    small fixtures.  (Knob read: utils/planconfig.py.)"""
    return planconfig.tile_size()


def tile_budget() -> int:
    """Per-arena tile byte budget (DGRAPH_TPU_TILE_BUDGET, default
    256MB).  Arenas whose non-empty-block count would exceed it refuse
    to densify and the join planner falls back to pairwise expansion."""
    return planconfig.tile_budget()


def mask_lanes(universe: int, t: Optional[int] = None) -> int:
    """Mask length covering ``universe`` uids: bucket the block count so
    program shapes stay bounded as the graph grows."""
    t = t or tile_size()
    nb = max(1, -(-int(universe) // t))
    return bucket(nb) * t


@dataclass
class PredTiles:
    """One predicate's adjacency as stacked dense boolean tiles."""

    bi: jnp.ndarray       # int32[Kb] block-row of each stored tile
    bj: jnp.ndarray       # int32[Kb] block-col of each stored tile
    tiles: jnp.ndarray    # float32[Kb, T, T]; zero pad tiles beyond n_tiles
    degs: jnp.ndarray     # int32[NBown*T] out-degree per uid (edge totals)
    t: int                # tile edge length
    nb: int               # block count covering this arena's own uids
    n_tiles: int          # true (non-pad) tile count
    universe: int         # max uid + 1 over this arena's src ∪ dst

    def device_bytes(self) -> int:
        return sum(
            a.size * a.dtype.itemsize
            for a in (self.bi, self.bj, self.tiles, self.degs)
        )


def count_tile_blocks(
    h_src: np.ndarray, h_offsets: np.ndarray, h_dst: np.ndarray, t: int
) -> Tuple[int, int]:
    """(non-empty block count, universe) for a CSR without building the
    tiles — the planner's byte estimate (K·T·T·4) must be computable
    BEFORE committing to a build."""
    E = len(h_dst)
    if E == 0:
        return 0, 0
    deg = h_offsets[1:] - h_offsets[:-1]
    u = np.repeat(np.asarray(h_src, dtype=np.int64), deg)
    v = np.asarray(h_dst, dtype=np.int64)
    universe = int(max(u.max(), v.max())) + 1
    nb = -(-universe // t)
    keys = (u // t) * nb + (v // t)
    return int(len(np.unique(keys))), universe


def est_tile_bytes(n_blocks: int, t: int) -> int:
    """Device bytes a tile set of ``n_blocks`` stored blocks costs."""
    kb = bucket(max(1, n_blocks))
    return kb * t * t * 4 + 2 * kb * 4


def build_tiles(
    h_src: np.ndarray,
    h_offsets: np.ndarray,
    h_dst: np.ndarray,
    t: Optional[int] = None,
    budget_bytes: Optional[int] = None,
) -> Optional[PredTiles]:
    """Densify a CSR's non-empty blocks into a PredTiles, or None when
    the estimated footprint exceeds the byte budget (the caller then
    stays on the gather tier).  Host-side, vectorized — one lexsort-free
    pass over the edge list."""
    t = t or tile_size()
    budget = tile_budget() if budget_bytes is None else budget_bytes
    E = len(h_dst)
    deg = (h_offsets[1:] - h_offsets[:-1]).astype(np.int64)
    if E == 0:
        return None
    n_blocks, universe = count_tile_blocks(h_src, h_offsets, h_dst, t)
    if est_tile_bytes(n_blocks, t) > budget:
        return None
    nb = -(-universe // t)
    u = np.repeat(np.asarray(h_src, dtype=np.int64), deg)
    v = np.asarray(h_dst, dtype=np.int64)
    keys = (u // t) * nb + (v // t)
    uniq, tid = np.unique(keys, return_inverse=True)
    K = len(uniq)
    Kb = bucket(max(1, K))
    tiles = np.zeros((Kb, t, t), dtype=np.float32)
    tiles[tid, u % t, v % t] = 1.0
    bi = np.zeros(Kb, dtype=np.int32)
    bj = np.zeros(Kb, dtype=np.int32)
    bi[:K] = (uniq // nb).astype(np.int32)
    bj[:K] = (uniq % nb).astype(np.int32)
    degv = np.zeros(nb * t, dtype=np.int32)
    # universe spans edge ENDPOINTS; degree-0 rows beyond it (dense
    # arenas carry them) have no edges to account for — skip, don't index
    hs = np.asarray(h_src, dtype=np.int64)
    sel = hs < nb * t
    degv[hs[sel]] = deg[sel].astype(np.int32)
    return PredTiles(
        bi=jnp.asarray(bi),
        bj=jnp.asarray(bj),
        tiles=jnp.asarray(tiles),
        degs=jnp.asarray(degv),
        t=t,
        nb=nb,
        n_tiles=K,
        universe=universe,
    )


def apply_tile_delta(
    pt: PredTiles, adds: np.ndarray, dels: np.ndarray
) -> Optional[PredTiles]:
    """IVM delta repair (dgraph_tpu/ivm/): apply (src, dst) uid-edge
    deltas to the stored blocks in place — a tile delta is ONE batched
    scatter on the [K, T, T] stack (set 1.0 for adds, 0.0 for dels)
    plus a degree-vector adjustment, instead of dropping the whole
    densified layout and paying a full rebuild on the next join.

    Returns the repaired PredTiles (same object, tensors replaced), or
    None when repair is structurally impossible and the caller must
    fall back to a rebuild: an edge lands outside the block grid (the
    universe grew) or an ADD lands in a block that was never
    materialized (densifying new blocks IS the rebuild).  A delete that
    empties a block keeps the zero block resident — it contributes
    nothing to any product, and the next full rebuild reclaims it.

    Semantic parity with a fresh build (pinned by tests/test_ivm.py):
    the densified adjacency matrix and the degree vector match
    ``build_tiles`` over the post-delta CSR exactly; only the block
    LIST may differ by such empty blocks."""
    t, nb = pt.t, pt.nb
    parts = []
    for arr, val in ((adds, 1.0), (dels, 0.0)):
        if len(arr):
            a = np.asarray(arr, dtype=np.int64).reshape(-1, 2)
            parts.append((a[:, 0], a[:, 1], np.full(len(a), val, np.float32)))
    if not parts:
        return pt
    u = np.concatenate([p[0] for p in parts])
    v = np.concatenate([p[1] for p in parts])
    vals = np.concatenate([p[2] for p in parts])
    if int(u.max()) >= nb * t or int(v.max()) >= nb * t or u.min() < 0 or v.min() < 0:
        return None  # universe grew past the block grid
    keys = (u // t) * nb + (v // t)
    hbi = np.asarray(pt.bi)[: pt.n_tiles].astype(np.int64)
    hbj = np.asarray(pt.bj)[: pt.n_tiles].astype(np.int64)
    skeys = hbi * nb + hbj  # np.unique build order: ascending
    pos = np.searchsorted(skeys, keys)
    pos = np.clip(pos, 0, max(0, len(skeys) - 1))
    if len(skeys) == 0 or not bool(np.all(skeys[pos] == keys)):
        return None  # some edge's block was never materialized
    pt.tiles = pt.tiles.at[pos, u % t, v % t].set(jnp.asarray(vals))
    n_degs = pt.degs.shape[0]
    deg_delta = np.zeros(n_degs, dtype=np.int32)
    for arr, sign in ((adds, 1), (dels, -1)):
        if len(arr):
            a = np.asarray(arr, dtype=np.int64).reshape(-1, 2)
            np.add.at(deg_delta, a[:, 0], sign)
    pt.degs = pt.degs + jnp.asarray(deg_delta)
    pt.universe = max(pt.universe, int(u.max()) + 1, int(v.max()) + 1)
    return pt


# -- mask algebra -------------------------------------------------------------


def _tile_counts(bi, bj, tiles, x):
    """Blocked boolean SpMV: path counts per target uid.

    ``x.reshape(-1, T)[bi] @ tiles`` produces each stored tile's
    contribution on the MXU; contributions combine into block-columns
    via a one-hot matmul (``[K, NB] @ [K, T]``) rather than a
    scatter-add — scatters serialize where matmuls saturate.  The
    combine costs K·NB·T MACs AND materializes the dense [K, NB] f32
    one-hot operand; the join planner both charges the MACs in its
    cost model and structurally rejects (even under force) shapes
    whose operand would exceed the tile byte budget, so huge-universe
    × many-block shapes route pairwise instead of landing here."""
    t = tiles.shape[1]
    xb = x.reshape(-1, t)
    contrib = jnp.einsum("kt,ktu->ku", xb[bi], tiles)
    oh = jax.nn.one_hot(bj, xb.shape[0], dtype=x.dtype)
    return jnp.einsum("kj,kt->jt", oh, contrib).reshape(-1)


@jax.jit
def expand_counts(bi, bj, tiles, x):
    """Path counts per uid for a frontier mask (the SpGEMM row)."""
    return _tile_counts(bi, bj, tiles, x)


@jax.jit
def expand_mask(bi, bj, tiles, x):
    """Next-frontier mask for frontier mask ``x``: expansion and dedup in
    one pass (counts > 0)."""
    return (_tile_counts(bi, bj, tiles, x) > 0).astype(x.dtype)


expand_mask_batch = jax.jit(
    jax.vmap(
        lambda bi, bj, tiles, x: (_tile_counts(bi, bj, tiles, x) > 0).astype(
            x.dtype
        ),
        in_axes=(None, None, None, 0),
    )
)
"""[B, M] batch of frontier masks expanded in ONE dispatch."""


@partial(jax.jit, static_argnames=("m",))
def uids_to_mask(uids: jnp.ndarray, m: int) -> jnp.ndarray:
    """Padded uid vector → float32 0/1 mask of length ``m`` (uids ≥ m and
    padding drop — callers size m from mask_lanes of the shared
    universe, so only row-less strays fall off)."""
    ok = (uids != SENT) & (uids >= 0) & (uids < m)
    slot = jnp.where(ok, uids, m)
    return jnp.zeros((m + 1,), jnp.float32).at[slot].set(1.0)[:m]


def _intersect_masks(stack):
    """AND of k stacked masks as one stacked tile product: a ones-row
    matmul sums the stack on the MXU; lanes where every mask fired sum
    to k."""
    k = stack.shape[0]
    sums = (jnp.ones((1, k), stack.dtype) @ stack)[0]
    return (sums >= k).astype(stack.dtype)


intersect_masks = jax.jit(_intersect_masks)


def _intersect_stack(mat):
    """k-way intersection of the rows of a [K, L] sorted-unique-padded
    matrix in ONE program: the first row is probed against every other
    row with independent (hence parallel) binary searches, and a single
    sort compacts the survivors.  The per-op equivalent dispatches K-1
    sort+probe programs, each re-sorting its shrinking accumulator."""
    a0 = mat[0]
    keep = a0 != SENT
    for i in range(1, mat.shape[0]):
        keep &= member_mask(a0, mat[i])
    return sort_desc_free(jnp.where(keep, a0, SENT))


intersect_stack = jax.jit(_intersect_stack)
intersect_stack_batch = jax.jit(jax.vmap(_intersect_stack))
"""[B, K, L] → [B, L]: B independent k-way intersections, one dispatch."""


# -- fused multi-level chain (generic join) -----------------------------------


@jax.jit
def run_mask_chain(tile_ops, keeps, degvs, x0):
    """A whole uid chain as ONE program over device-resident masks.

    tile_ops: tuple of per-level (bi, bj, tiles).
    keeps:    tuple of per-level keep masks (float32[M]) or None — a
              fused ``@filter`` keep-set or a cycle-closing set, applied
              right after the level's expansion (the generic-join
              intersection step).
    degvs:    tuple of per-level int32 degree vectors (arena-sized; the
              entering mask's prefix dots with it for the level's TRUE
              edge total — the accounting the gather tier reports as
              len(out_flat)).
    x0:       float32[M] root frontier mask.

    Returns (masks float32[L, M] — post-filter frontier per level —,
    totals int32[L]).  Tuple structure (level count, None pattern) is
    static per trace; shapes are bucketed, so the program cache stays
    bounded per (arena set, filter shape).
    """
    x = x0
    masks = []
    totals = []
    for (bi, bj, tiles), keep, dg in zip(tile_ops, keeps, degvs):
        nd = dg.shape[0]
        totals.append(
            jnp.sum(jnp.where(x[:nd] > 0, dg, 0)).astype(jnp.int32)
        )
        y = (_tile_counts(bi, bj, tiles, x) > 0).astype(x.dtype)
        if keep is not None:
            y = y * keep
        masks.append(y)
        x = y
    return jnp.stack(masks), jnp.stack(totals)


# -- fused triangle / cycle closing -------------------------------------------


def _triangle(bi1, bj1, t1, bi2, bj2, t2, bic, bjc, tc, x):
    """Expand two legs from root mask ``x`` and intersect against the
    closing predicate's tiles in one program: legs ``y = x·A`` and
    ``z = y·B``, closing set ``w = x·C`` where C is the CLOSING
    predicate's REVERSE adjacency (w = uids with a closing edge into
    some root).  Returns the mask of leaf uids that close a cycle."""
    y = (_tile_counts(bi1, bj1, t1, x) > 0).astype(x.dtype)
    z = (_tile_counts(bi2, bj2, t2, y) > 0).astype(x.dtype)
    w = (_tile_counts(bic, bjc, tc, x) > 0).astype(x.dtype)
    return z * w


triangle_mask = jax.jit(_triangle)
triangle_mask_batch = jax.jit(
    jax.vmap(_triangle, in_axes=(None,) * 9 + (0,))
)
"""[B, M] root masks → [B, M] closing masks, one dispatch for the batch."""


# -- host conversions ---------------------------------------------------------


def mask_to_uids(mask: np.ndarray) -> np.ndarray:
    """Host boundary: 0/1 mask → ascending int64 uid vector (the sorted-
    unique contract every set consumer expects)."""
    return np.flatnonzero(np.asarray(mask) > 0).astype(np.int64)
