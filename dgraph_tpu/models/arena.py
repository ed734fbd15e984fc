"""Device-resident posting-list arenas.

The query-time representation of the graph: per predicate, immutable CSR
tensors on device —

- **data arena**: sorted source uids + offsets + packed sorted target uids
  (uid predicates) — replaces the reference's per-key badger lookups +
  posting-list iteration (posting/list.go PIterator, worker/task.go:287).
- **reverse arena**: the inverted edge set (@reverse, posting/index.go:152).
- **index arenas**: one per tokenizer — host-side sorted token table +
  device CSR token-row → uid list (posting/index.go addIndexMutation:108).
  Inequalities become contiguous token-row ranges (sortable tokenizers).
- **value arena**: sorted uids + float32 numerics for device order-by /
  aggregation / math; exact typed values stay on the host store.
- count queries need no extra arena: degree = offsets diff (the reference
  maintains a separate count index, x/keys.go:101 — dense CSR gives it
  for free).

Arenas are rebuilt per dirty predicate from the host store (the analog of
the gentle-commit + lcache refresh cycle, posting/lists.go:109-215).
"""

from __future__ import annotations

import bisect
import math
import threading
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from dgraph_tpu import obs, ops
from dgraph_tpu.obs import ledger as _ledger
from dgraph_tpu.utils.metrics import (
    ARENA_EVICTIONS,
    ARENA_LAYOUT_UPDATES,
    ARENA_MIRROR_UPDATES,
    ARENA_REFRESH_H2D_BYTES,
    PATH_LAYOUT_H2D_BYTES,
    PATH_LAYOUT_UPDATES,
    RESIDENT_EPOCHS,
)
from dgraph_tpu.ops.sets import SENT
from dgraph_tpu import tok as tokmod
from dgraph_tpu.models.store import PostingStore
from dgraph_tpu.models.types import TypeID, TypedValue, numeric


# Shared lock for lazy per-arena derived-structure builds (ensure_device,
# inline_layout, lut).  Struck once per build, never on warm reads — the
# warm paths double-check their cached field before locking.  A single module
# lock (vs per-arena) keeps CSRArena a plain dataclass; contention is
# limited to cold-cache bursts.
_BUILD_LOCK = threading.RLock()


def _book_h2d(arrays) -> None:
    """Bytes just put on the device (a lazily built layout, a re-upload,
    a resident seed or delta), booked to the request on whose behalf
    they crossed (none active: an embedded engine, a boot)."""
    led = _ledger.current()
    if led is not None:
        led.bytes_h2d += sum(int(a.nbytes) for a in arrays)


# What an in-place layout update may rewrite: rows of ``metap`` and chunks of
# ``ov``.  Past either the layout is built anew — derived from sizes, as the
# IVM gate is: a rewrite of this many 32-byte rows is 2 MB put on the device,
# a fortieth of the smallest chain arena's layout.
_LAYOUT_ROWS_MAX = 4096
_LAYOUT_CHUNKS_MAX = 65536
_SCATTER_MIN = 64   # index vectors are padded to this (one program a table)


def _chunks_of(deg: np.ndarray) -> np.ndarray:
    """Overflow chunks a row of each degree holds: its targets past the
    first INLINE, ``ops.CHUNK`` a chunk."""
    return (np.maximum(deg - ops.INLINE, 0) + ops.CHUNK - 1) // ops.CHUNK


def _ov_capacity(n_chunks: int) -> int:
    """Rows of an overflow-chunk table that holds ``n_chunks`` and can take
    writes: a sixteenth of room (at least 1,024 chunks), rounded up to an
    eighth-step of a power of two.  The table's length is a static shape of
    every compiled chain program, so it must not follow the graph chunk by
    chunk (``ops.expand_inline_seg`` reads it in a ``clip`` only)."""
    return ops.bucket_fine(n_chunks + max(1024, n_chunks >> 4))


@jax.jit
def _scatter_rows(table, idx, rows):
    """``table`` with ``rows`` written at ``idx`` (an index past the end is
    dropped: the padding).  NOT donated: a reader that holds the old table —
    an embedded engine, a clustered refresh, a dispatch in flight — keeps a
    whole snapshot, as ``ResidentArena``'s shadow epoch does; the price is a
    device-side copy of the table (67 MB at the film graph's largest: 0.2 ms
    of the chip's bandwidth)."""
    return table.at[idx].set(rows, mode="drop")


def _book_refresh_h2d(arrays) -> None:
    """``_book_h2d`` for what a write put on the device (a layout's delta or
    its rebuild): the writer's account and the refresh counter."""
    _book_h2d(arrays)
    ARENA_REFRESH_H2D_BYTES.add(sum(int(a.nbytes) for a in arrays))


def _book_path_h2d(arrays) -> None:
    """``_book_h2d`` for a path search's merged layout (``PathLayout``), built
    or given a write's delta: the request's account and the layout's counter."""
    _book_h2d(arrays)
    PATH_LAYOUT_H2D_BYTES.add(sum(int(a.nbytes) for a in arrays))


def _put_scatter(table, idx: np.ndarray, rows: np.ndarray, book=_book_refresh_h2d):
    """Pads (idx, rows) to a bucketed length and scatters them into the
    device ``table``; ``book`` books the bytes that crossed."""
    k = ops.bucket(max(_SCATTER_MIN, len(idx)))
    pad_idx = np.full(k, table.shape[0], dtype=np.int32)
    pad_idx[: len(idx)] = idx
    pad_rows = np.zeros((k,) + tuple(table.shape[1:]), dtype=np.int32)
    pad_rows[: len(idx)] = rows
    di, dr = jnp.asarray(pad_idx), jnp.asarray(pad_rows)
    book((di, dr))
    return _scatter_rows(table, di, dr)


def _topm_replace(cs: np.ndarray, old: np.ndarray, new: np.ndarray) -> np.ndarray:
    """``cs`` — [0, cumsum of the descending-sorted positive values] — after
    the values ``old`` left the multiset and ``new`` joined it (zeros are
    not held).  Exact: a bound that drifted by one a write would walk a
    chain program's capacity across a power of two."""
    asc = np.diff(cs)[::-1]
    old, new = np.sort(old[old > 0]), np.sort(new[new > 0])
    if len(old):
        # the k-th of several equal values leaves the k-th place of its run
        nth = np.arange(len(old)) - np.searchsorted(old, old, side="left")
        asc = np.delete(asc, np.searchsorted(asc, old, side="left") + nth)
    if len(new):
        asc = np.insert(asc, np.searchsorted(asc, new), new)
    return np.concatenate([[0], np.cumsum(asc[::-1])])


def _rows_of_uids(h_src: np.ndarray, uids: np.ndarray) -> np.ndarray:
    """Row of each uid in the sorted ``h_src``, -1 where it has none."""
    if not len(h_src):
        return np.full(len(uids), -1, dtype=np.int64)
    pos = np.minimum(np.searchsorted(h_src, uids), len(h_src) - 1)
    return np.where(h_src[pos] == uids, pos, -1)


def _capacity(n: int) -> int:
    """Entries of the buffer behind a host mirror of ``n``: a sixteenth of
    room, at least 1,024 (``_ov_capacity``'s rule; a buffer outgrown is
    copied once into one a seventeenth longer, so an entry appended is
    copied sixteen times over the arena's life at most)."""
    return n + max(1024, n >> 4)


def _roomy(arr: np.ndarray, n: int) -> np.ndarray:
    """A copy of ``arr`` at the start of a NEW buffer that holds ``n``
    entries and room (``_capacity``): the view of the copy, the buffer its
    ``base``."""
    buf = np.empty(_capacity(n), dtype=arr.dtype)
    buf[: len(arr)] = arr
    return buf[: len(arr)]


def _spliced(arr: np.ndarray, pos: np.ndarray, vals) -> np.ndarray:
    """``np.insert(arr, pos, vals)`` (``pos`` non-decreasing) as the first
    entries of a NEW buffer with room (``_capacity``): the view returned has
    the buffer as its ``base``.  ``arr`` is not written."""
    n = len(arr) + len(pos)
    out = np.empty(_capacity(n), dtype=arr.dtype)[:n]
    at = pos + np.arange(len(pos))
    old = np.ones(n, dtype=bool)
    old[at] = False
    out[at] = vals
    out[old] = arr
    return out


def _with_new_rows(h_src, h_offsets, srcs):
    """(h_src, h_offsets) with a degree-0 row for every uid of ``srcs``
    that has none, h_src kept sorted."""
    u = np.unique(srcs)
    newsrc = u[_rows_of_uids(h_src, u) < 0]
    if len(newsrc):
        at = np.searchsorted(h_src, newsrc)
        h_src = _spliced(h_src, at, newsrc)
        h_offsets = _spliced(h_offsets, at + 1, h_offsets[at])
    return h_src, h_offsets


def _shift_offsets(h_offsets: np.ndarray, rows: np.ndarray, sign: int) -> None:
    """``h_offsets`` (the caller's own copy) after one edge joined
    (``sign`` +1) or left (-1) each row of ``rows``: only the offsets past
    the first touched row move, so rows appended at the end cost nothing."""
    lo = int(rows.min())
    cnt = np.bincount(rows - lo, minlength=len(h_offsets) - 1 - lo)
    h_offsets[lo + 1:] += sign * np.cumsum(cnt)


def _merge(h_src, h_offsets, h_dst, adds, dels):
    """The host mirrors after edges left and joined anywhere: each edge's
    place is a search in its own row (a journal window holds 65,536 at
    most), and every array that changes is copied once, into a new buffer
    with room at its end (``_spliced``) — O(rows + edges), no pass over the
    arena's edges but the copies themselves.  The arrays given are not
    written: whoever holds them keeps a whole snapshot."""
    theirs = h_offsets          # the published array: never written in place
    for arr, sign in ((dels, -1), (adds, +1)):
        if not len(arr):
            continue
        if sign > 0:
            h_src, h_offsets = _with_new_rows(h_src, h_offsets, arr[:, 0])
        order = np.lexsort((arr[:, 1], arr[:, 0]))
        srcs, dsts = arr[order, 0], arr[order, 1]
        rows = np.searchsorted(h_src, srcs)
        lo, hi = h_offsets[rows], h_offsets[rows + 1]
        pos = np.fromiter(
            (a + np.searchsorted(h_dst[a:b], d)
             for a, b, d in zip(lo.tolist(), hi.tolist(), dsts.tolist())),
            dtype=np.int64, count=len(rows),
        )
        if sign > 0:
            h_dst = _spliced(h_dst, pos, dsts)
        else:
            stay = np.ones(len(h_dst), dtype=bool)
            stay[pos] = False
            n = len(h_dst) - len(pos)
            h_dst = np.compress(stay, h_dst, out=np.empty(_capacity(n), h_dst.dtype)[:n])
        if h_offsets is theirs:
            h_offsets = _roomy(h_offsets, len(h_offsets))
        _shift_offsets(h_offsets, rows, sign)
    return h_src, h_offsets, h_dst


@dataclass
class CSRArena:
    """One CSR posting structure on device, with host mirrors for planning."""

    src: Optional[jnp.ndarray]      # int32[Sb] sorted row-key uids; None if rows are implicit
    offsets: jnp.ndarray            # int32[Sb+1]; padded rows have degree 0
    dst: jnp.ndarray                # int32[Eb], SENT-padded
    h_src: np.ndarray               # int64[S] (exact, unpadded)
    h_offsets: np.ndarray           # int64[S+1]
    n_rows: int
    n_edges: int

    def degree_of_rows(self, rows: np.ndarray) -> np.ndarray:
        """Host-side degree lookup for capacity planning."""
        rows = np.asarray(rows)
        ok = rows >= 0
        r = np.where(ok, rows, 0)
        return np.where(ok, self.h_offsets[r + 1] - self.h_offsets[r], 0)

    @property
    def avg_degree(self) -> float:
        """Mean out-degree — the O(1) fan-out estimate the cohort hop
        merger uses to predict device routing before paying for exact
        per-row degrees (query/engine.py DeviceExpander.expand)."""
        return self.n_edges / max(1, self.n_rows)

    _h_dst: Optional[np.ndarray] = None
    _n_distinct_dst: Optional[int] = None

    def host_dst(self) -> np.ndarray:
        """Host mirror of the packed dst column (lazy, cached; one device
        fetch).  Serves the small-expansion numpy fast path and the lazy
        layout builds."""
        if self._h_dst is None:
            self._h_dst = np.asarray(self.dst)[: self.n_edges]
        return self._h_dst

    def n_distinct_dst(self) -> int:
        """Number of distinct target uids (lazy).  Bounds the unique
        frontier any expansion over this arena can produce — unlike the
        source-uid universe, which says nothing about row-less leaves."""
        if self._n_distinct_dst is None:
            self._n_distinct_dst = (
                int(len(np.unique(self.host_dst()))) if self.n_edges else 0
            )
        return self._n_distinct_dst

    _max_uid: Optional[int] = None

    def max_uid(self) -> int:
        """The largest uid this arena holds, as a row or as a target (lazy):
        how far a table over the uid space would have to reach."""
        if self._max_uid is None:
            top = int(self.h_src[-1]) if len(self.h_src) else 0
            self._max_uid = max(top, int(self.host_dst().max()) if self.n_edges else 0)
        return self._max_uid

    def expand_host(self, rows: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        """Vectorized numpy CSR expansion over the host mirror: returns
        (out, seg_ptr) in the engine's layout — out grouped by input row
        (ascending within each group), seg_ptr[i]:seg_ptr[i+1] slicing row
        i's targets.  Rows < 0 skip (degree 0).  The single host gather
        shared by the engine's and the resolver's small-expansion paths."""
        rows = np.asarray(rows)
        n = len(rows)
        ok = rows >= 0
        r = np.where(ok, rows, 0)
        degs = np.where(ok, self.h_offsets[r + 1] - self.h_offsets[r], 0)
        total = int(degs.sum())
        seg_ptr = np.zeros(n + 1, dtype=np.int64)
        np.cumsum(degs, out=seg_ptr[1:])
        if total == 0:
            return np.empty(0, dtype=np.int64), seg_ptr
        starts = np.where(ok, self.h_offsets[r], 0)
        within = np.arange(total) - np.repeat(seg_ptr[:-1], degs)
        out = self.host_dst()[np.repeat(starts, degs) + within].astype(np.int64)
        return out, seg_ptr

    def device_bytes(self) -> int:
        """HBM footprint of this arena's device tensors (incl. built lazy
        layouts) — the residency manager's accounting unit."""
        n = 0
        for t in (self.src, self.offsets, self.dst, self._lut):
            if t is not None:
                n += t.size * t.dtype.itemsize
        if self._inline is not None:
            n += sum(t.size * t.dtype.itemsize for t in self._inline)
        if self._tiles is not None:
            # MXU join tier (ops/spgemm.py): densified adjacency blocks
            # ride the same HBM budget/eviction as every other layout
            n += self._tiles.device_bytes()
        if self._resident is not None:
            # resident Pallas tier: live epoch buffers AND the shadow
            # (previous epoch, pinned through the flip window) — each
            # counted exactly once (ResidentArena.device_bytes)
            n += self._resident.device_bytes()
        return n

    _inline: Optional[tuple] = None  # lazy (metap, ov_chunks)
    _ov_coff: Optional[np.ndarray] = None  # int64[S+1]: a row's first chunk

    def inline_layout(self) -> tuple:
        """Inline-head layout for ops.expand_inline_seg, built lazily.

        Returns (metap, ov_chunks): int32[Sb, 8] per-row rows with
        lane0 = overflow chunk start, lane1 = degree, lanes 2..7 = the
        first INLINE targets (SENT pad); int32[cap, 8] overflow chunks
        (targets INLINE.. of each row, a row's chunks side by side, rows
        in order), ``cap`` = ``_ov_capacity`` of the chunks in use: SENT
        rows past them, which a write fills.  One row gather serves
        metadata AND short posting lists (docs/ROOFLINE.md round 4).
        Kept true by ``_apply_delta_locked``: always what a build from the
        host mirrors would give, up to the capacity."""
        if self._inline is not None:
            return self._inline
        # stage h2d: built and put on first use — the request that meets
        # the layout missing pays for it (or waits out another's build)
        with obs.stage(None, "h2d_ms"), _BUILD_LOCK:
            if self._inline is None:
                self._build_inline()
                _book_h2d(self._inline)
            return self._inline

    def _inline_rows(self, rows: np.ndarray, coff_rows: np.ndarray):
        """(metap rows, chunk positions, chunk rows) of ``rows`` (ascending
        row indices) from the host mirrors, ``coff_rows`` being each row's
        first chunk: the layout's arithmetic, for a build and for a delta."""
        INL = ops.INLINE
        starts = self.h_offsets[rows]
        deg = self.h_offsets[rows + 1] - starts
        h_dst = self.host_dst() if self.n_edges else np.zeros(0, np.int32)
        mrows = np.full((len(rows), 8), SENT, dtype=np.int32)
        mrows[:, 0] = coff_rows
        mrows[:, 1] = deg
        for j in range(INL):
            sel = deg > j
            mrows[sel, 2 + j] = h_dst[starts[sel] + j]
        od = np.maximum(deg - INL, 0)
        cd = _chunks_of(deg)
        n_chunks = int(cd.sum())
        cpos = np.repeat(coff_rows, cd) + (
            np.arange(n_chunks, dtype=np.int64) - np.repeat(np.cumsum(cd) - cd, cd)
        )
        crows = np.full((n_chunks, 8), SENT, dtype=np.int32)
        if n_chunks:
            # vectorized tail-edge index set (no per-row arange loop):
            # within = 0..od-1 per row via the repeat/cumsum trick
            big = np.nonzero(od)[0]
            odb = od[big]
            ends = np.cumsum(odb)
            within = np.arange(int(ends[-1]), dtype=np.int64) - np.repeat(ends - odb, odb)
            which = np.repeat(big, odb)
            first = (np.cumsum(cd) - cd)[which]      # the row's first chunk, here
            crows[first + (within >> 3), within & 7] = h_dst[
                starts[which] + INL + within
            ]
        return mrows, cpos, crows

    def _build_inline(self) -> None:
        """The whole layout from the host mirrors, put on the device (the
        caller holds ``_BUILD_LOCK`` and books the bytes)."""
        S = self.n_rows
        coff = np.zeros(S + 1, dtype=np.int64)
        np.cumsum(_chunks_of(np.diff(self.h_offsets)), out=coff[1:])
        mrows, cpos, crows = self._inline_rows(np.arange(S, dtype=np.int64), coff[:-1])
        metap = np.full((ops.bucket(max(1, S)), 8), SENT, dtype=np.int32)
        metap[:, :2] = 0
        metap[:S] = mrows
        ov = np.full((_ov_capacity(int(coff[-1])), 8), SENT, dtype=np.int32)
        ov[cpos] = crows
        self._ov_coff = coff
        self._bufs.pop("_ov_coff", None)
        self._inline = (jnp.asarray(metap), jnp.asarray(ov))

    def ov_chunk_degree_of_rows(self, rows: np.ndarray) -> np.ndarray:
        """Host overflow-chunk-count lookup for inline_layout planning."""
        return _chunks_of(self.degree_of_rows(rows))

    # -- MXU join tier (ops/spgemm.py) --------------------------------------

    _tiles: Optional[object] = None  # lazy PredTiles (blocked adjacency)

    def tile_blocks(self) -> Tuple[int, int]:
        """(non-empty adjacency block count, universe) at the current
        tile size — the join planner's byte estimate, computable WITHOUT
        building the tiles (one O(E) unique pass, cached; invalidated
        with the other derived layouts on apply_delta)."""
        from dgraph_tpu.ops import spgemm

        t = spgemm.tile_size()
        cached = getattr(self, "_tile_blocks", None)
        if cached is not None and cached[0] == t:
            return cached[1], cached[2]
        if self.n_edges == 0:
            k, uni = 0, 0
        else:
            k, uni = spgemm.count_tile_blocks(
                self.h_src, self.h_offsets, self.host_dst(), t
            )
        self._tile_blocks = (t, k, uni)
        return k, uni

    def tiles(self):
        """Blocked boolean adjacency tiles for the MXU join tier, built
        lazily from the CSR host mirrors and cached on the arena (they
        die with it, like every derived layout; device_bytes() accounts
        them, so the ArenaManager HBM budget governs their residency).
        Returns None — without caching a negative — when the estimated
        footprint exceeds DGRAPH_TPU_TILE_BUDGET or the arena is
        edgeless; the planner then stays on the gather tier."""
        from dgraph_tpu.ops import spgemm
        from dgraph_tpu.utils.metrics import JOIN_TILE_BUILDS, JOIN_TILE_BYTES

        pt = self._tiles
        t = spgemm.tile_size()
        if pt is not None and pt.t == t:
            return pt
        if self.n_edges == 0:
            return None
        k, _uni = self.tile_blocks()
        if spgemm.est_tile_bytes(k, t) > spgemm.tile_budget():
            return None
        with _BUILD_LOCK:
            pt = self._tiles
            if pt is not None and pt.t == t:
                return pt
            pt = spgemm.build_tiles(
                self.h_src, self.h_offsets, self.host_dst(), t=t
            )
            if pt is not None:
                self._tiles = pt
                JOIN_TILE_BUILDS.add()
                JOIN_TILE_BYTES.add(pt.device_bytes())
            return pt

    def degree_histogram(self) -> np.ndarray:
        """Log2-bucketed out-degree histogram: slot c counts rows with
        ⌈log2(degree)⌉ == c (degree ≥ 1; slot 0 holds degree-1 rows).
        Cached; the join planner reads it to spot heavy-tailed
        predicates, where the dense-tile pass is immune to the skew
        that serializes gather capacity planning."""
        h = getattr(self, "_deg_hist", None)
        if h is None:
            deg = (self.h_offsets[1:] - self.h_offsets[:-1]).astype(np.int64)
            deg = deg[deg > 0]
            if len(deg):
                c = np.ceil(np.log2(deg, where=deg > 1, out=np.zeros(len(deg))))
                h = np.bincount(c.astype(np.int64), minlength=32)
            else:
                h = np.zeros(32, dtype=np.int64)
            self._deg_hist = h
        return h

    _lut: Optional[jnp.ndarray] = None

    def lut(self, universe: int) -> jnp.ndarray:
        """Dense uid→row lookup table on device: int32[bucket(universe+1)],
        -1 where the uid has no row.  One elementwise gather replaces a
        device binary search (searchsorted costs log(S) gather rounds —
        measured ~20× slower at engine scales).  ~4 bytes/uid of HBM."""
        need = ops.bucket(max(1, universe + 1))
        if self._lut is not None and self._lut.shape[0] >= need:
            return self._lut
        with obs.stage(None, "h2d_ms"), _BUILD_LOCK:  # as inline_layout
            cur = self._lut
            if cur is None or cur.shape[0] < need:
                self._build_lut(need)
                _book_h2d((self._lut,))
            return self._lut

    def _build_lut(self, size: int) -> None:
        t = np.full(size, -1, dtype=np.int32)
        if self.n_rows:
            keys = self.h_src[self.h_src < size]
            t[keys] = np.arange(len(keys), dtype=np.int32)
        self._lut = jnp.asarray(t)

    def rows_for_uids_host(self, uids: np.ndarray) -> np.ndarray:
        return _rows_of_uids(self.h_src, np.asarray(uids))

    # -- device-resident tier (PR 16: ops/pallas_gather.py) -----------------

    _resident: Optional[object] = None  # lazy ResidentArena
    epoch: int = 0  # bumped once per applied delta; hop-cache key element
    #                 (cache/hop.py key_for index 3): a pre-delta entry
    #                 can never match a post-delta probe by key equality

    def resident(self) -> "ResidentArena":
        """Device-pinned CSR view for the Pallas gather tier, built
        lazily from the host mirrors and kept fresh by ``apply_delta``
        (device-side merge, or a reseed on structural change) — never by
        per-query re-staging: after the first seed, mutations cross the
        host→device boundary as delta pairs only.  Counted in
        ``device_bytes()``, so the ArenaManager HBM budget/LRU governs
        its residency like every other derived layout."""
        ra = self._resident
        if ra is not None:
            return ra
        with _BUILD_LOCK:
            if self._resident is None:
                self._resident = ResidentArena.seed(
                    self.h_offsets, self.host_dst(), self.n_rows,
                    self.n_edges,
                )
            return self._resident

    # -- incremental refresh (gentle-commit analog) -------------------------

    _device_stale: bool = False

    def apply_delta(self, adds: np.ndarray, dels: np.ndarray) -> None:
        """Apply a small mutation batch to the HOST mirrors in place of a
        full rebuild (the O(E log E) lexsort + dict flatten of
        csr_from_edges) — the incremental counterpart of the reference's
        mutation layer merge (posting/list.go:321-410).  What the delta
        costs follows where it lies (``_take_delta_host``): rows and edges
        past everything the arena holds — a new film, its performances, its
        newcomers, whose uids are freshly assigned — are written into the
        room at the end of the mirrors' buffers, O(delta); a delete, or an
        edge of a row that is there, copies the mirrors once (``_merge``),
        O(rows + edges) memcpy.  Device tensors go stale and re-upload
        lazily on the next device-path use (ensure_device) — host-routed
        queries after a point mutation never touch the device at all.

        adds/dels: int64[n, 2] (src, dst) arrays; adds must not already
        exist, dels must exist (the store journal guarantees both).

        Runs under _BUILD_LOCK: in clustered mode refresh() applies
        deltas while readers run (ClusterStore drains dirty marks inside
        peek), so mirror mutation must be mutually exclusive with the
        lazy derived-layout builds (inline_layout/lut also take this
        lock) — otherwise a build that sampled the mirrors pre-delta
        could cache a torn layout AFTER the invalidation below.
        """
        with _BUILD_LOCK:
            self._apply_delta_locked(adds, dels)

    def _apply_delta_locked(self, adds: np.ndarray, dels: np.ndarray) -> None:
        pre_rows = self.n_rows
        old_last = int(self.h_src[-1]) if pre_rows else -1
        n_delta = len(adds) + len(dels)
        # the rows a delta touches, by uid, with their degrees before it:
        # the degree histogram, the top-m chunk sums and the device layout
        # are REPAIRED from them below, not dropped — a point write must
        # not cost the next reader a pass over the arena
        touched = np.unique(np.concatenate([
            np.asarray(a[:, 0], dtype=np.int64) for a in (adds, dels)
        ])) if n_delta else np.empty(0, np.int64)
        old_degs = self._degrees_of_uids(touched)
        if n_delta:
            ARENA_MIRROR_UPDATES.add(self._take_delta_host(adds, dels))
        new_degs = self._degrees_of_uids(touched)
        # bounds, kept as bounds: every added target may be new, every
        # deleted one may have twins (an exact count is one np.unique over
        # the arena, which the next whole build takes)
        if self._n_distinct_dst is not None and len(adds):
            self._n_distinct_dst += len(np.unique(adds[:, 1]))
        if self._max_uid is not None and len(adds):
            self._max_uid = max(self._max_uid, int(adds.max()))
        tb = getattr(self, "_tile_blocks", None)
        if tb is not None and len(adds):
            self._tile_blocks = (tb[0], tb[1] + len(adds),
                                 max(tb[2], int(adds.max()) + 1))
        if hasattr(self, "_topm_deg"):
            del self._topm_deg   # read by the scan chain, recurse, the mesh
        cs = getattr(self, "_topm_ovdeg", None)
        if cs is not None:
            ocd, ncd = _chunks_of(old_degs), _chunks_of(new_degs)
            moved = ocd != ncd
            if moved.any():
                self._topm_ovdeg = _topm_replace(cs, ocd[moved], ncd[moved])
        if getattr(self, "_deg_hist", None) is not None:
            # move each affected row between its old and new log2 bucket
            for od, nd in zip(old_degs.tolist(), new_degs.tolist()):
                if od != nd:
                    self._hist_move(od, nd)
        if n_delta and (self._inline is not None or self._lut is not None):
            self._layouts_take_delta(pre_rows, old_last, touched)
        # MXU tile repair (dgraph_tpu/ivm/): a small delta scatters onto
        # the stored T×T blocks instead of dropping the densified layout
        # wholesale — structurally-impossible repairs (new block, grown
        # universe) and disabled modes fall back to the drop
        pt = self._tiles
        if pt is not None:
            repaired = None
            if len(adds) + len(dels) > 0 and _ivm_repair_gate(
                len(adds) + len(dels), self.n_edges
            ):
                from dgraph_tpu.ops import spgemm as _spgemm
                from dgraph_tpu.utils.metrics import (
                    IVM_REPAIR_EDGES,
                    IVM_REPAIRS,
                )

                repaired = _spgemm.apply_tile_delta(pt, adds, dels)
                IVM_REPAIRS.add(
                    ("tile", "repaired" if repaired is not None
                     else "rebuild")
                )
                if repaired is not None:
                    IVM_REPAIR_EDGES.add(len(adds) + len(dels))
                    led = _ledger.current()
                    if led is not None:
                        led.repairs += 1
            self._tiles = repaired
        if len(adds) or len(dels):
            # arena EPOCH flip: probes formed after this point can never
            # match entries filled before it (cache/hop.py key_for)
            self.epoch += 1
            ra = self._resident
            if ra is not None:
                if self.n_rows != pre_rows or self.n_edges + 128 > ra.ecap:
                    # structural change (new source rows renumber every
                    # row) or the gather kernel's 128-lane slack tile
                    # would be breached: fresh upload becomes the next
                    # epoch, old buffers become the shadow (honest h2d
                    # charge inside seed)
                    nra = ResidentArena.seed(
                        self.h_offsets, self._h_dst, self.n_rows,
                        self.n_edges,
                    )
                    nra._prev = (ra.off, ra.dst)
                    self._resident = nra
                    RESIDENT_EPOCHS.add("reseed")
                else:
                    # device-side delta application: only the (row, dst)
                    # delta pairs cross host→device; the merge program
                    # produces the next epoch's buffers off the current
                    # ones, and the reference flip inside apply_delta is
                    # the atomic epoch swap
                    def _pack(arr):
                        rows = np.searchsorted(self.h_src, arr[:, 0])
                        b = ops.bucket(max(1, len(arr)))
                        return (
                            jnp.asarray(
                                ops.pad_to(rows.astype(np.int32), b)
                            ),
                            jnp.asarray(
                                ops.pad_to(arr[:, 1].astype(np.int32), b)
                            ),
                        )

                    ar, ad = _pack(adds)
                    dr, dd = _pack(dels)
                    ra.apply_delta(ar, ad, dr, dd, self.n_edges)
                    RESIDENT_EPOCHS.add("merge")
        self._device_stale = True

    # the buffers behind the host mirrors, by mirror name, once a delta has
    # needed room: the mirror is then ``buf[:n]``.  Empty on an arena that
    # took no delta: its mirrors are the arrays the build made
    _bufs: dict = field(default_factory=dict)

    def _extended(self, name: str, arr: np.ndarray, tail) -> Tuple[np.ndarray, bool]:
        """The mirror ``name``, ``arr`` followed by ``tail``, and whether a
        buffer was made for it.  Where ``arr`` is the start of its buffer
        and the buffer has the room, ``tail`` is written there and the view
        returned is longer: nothing ``arr`` covers is written, so whoever
        holds ``arr`` holds what it held.  Else ``arr`` is copied once to
        the start of a new buffer with room (``_capacity``)."""
        if not len(tail):
            return arr, False
        n = len(arr) + len(tail)
        buf = self._bufs.get(name)
        made = buf is None or arr.base is not buf or len(buf) < n
        if made:
            buf = self._bufs[name] = _roomy(arr, n).base
        buf[len(arr): n] = tail
        return buf[:n], made

    def _take_delta_host(self, adds: np.ndarray, dels: np.ndarray) -> str:
        """The host mirrors after a delta, and how they took it (the label of
        ``dgraph_arena_mirror_updates_total``).  The delta is split at the
        arena's last row: what lies at or before it — a delete, an edge of a
        row that is there (the last one too: its end is ``h_offsets[-1]``,
        which the published view holds), a row in the middle — goes through
        ``_merge``, which copies each mirror it changes into a new buffer
        with room (``copy``); rows past it with their edges are then written
        into the room at the end (``_extended``: ``append``, or ``grow``
        where a buffer had to be made or had run out).  Nothing a published
        view covers is ever written, so a reader that holds the old views —
        an embedded engine's host expansion, a clustered refresh, which hold
        no lock against this writer — keeps a whole snapshot; the new views
        are published back to back, so that it does not meet new offsets
        beside old targets while they are made."""
        last = int(self.h_src[-1]) if self.n_rows else -1
        past = adds[:, 0] > last
        mid, end = adds[~past], adds[past]
        src, off, dst = self.h_src, self.h_offsets, self.host_dst()
        how = "append"
        if len(mid) or len(dels):
            how = "copy"
            merged = _merge(src, off, dst, mid, dels)
            for name, was, now in zip(("h_src", "h_offsets", "_h_dst"), (src, off, dst), merged):
                if now is not was:
                    self._bufs[name] = now.base
            src, off, dst = merged
        end = end[np.lexsort((end[:, 1], end[:, 0]))]
        uids, counts = np.unique(end[:, 0], return_counts=True)
        src, made_s = self._extended("h_src", src, uids)
        off, made_o = self._extended("h_offsets", off, len(dst) + np.cumsum(counts))
        dst, made_d = self._extended("_h_dst", dst, end[:, 1])
        if how == "append" and (made_s or made_o or made_d):
            how = "grow"
        self.h_src, self.h_offsets, self._h_dst = src, off, dst
        self.n_rows, self.n_edges = len(src), len(dst)
        return how

    def _layouts_take_delta(self, pre_rows: int, old_last: int,
                            touched: np.ndarray) -> None:
        """The device inline layout and LUT after a delta the host mirrors
        have taken: the touched rows of ``metap``, the chunks that moved or
        are new and the new rows' LUT entries are scattered into the tables
        that are there, so that they read as a build from the mirrors would
        (tests hold them to it).  Built anew — here, on the writer's
        account, never by the next reader — where rows were renumbered, a
        table's capacity is outgrown or the rewrite would pass
        ``_LAYOUT_ROWS_MAX`` / ``_LAYOUT_CHUNKS_MAX``; a LUT the uid space
        has outgrown is dropped (its size is the caller's to say)."""
        S = self.n_rows
        # new rows all lie past the old ones: no old row was renumbered
        appended = S == pre_rows or pre_rows == 0 or int(
            self.h_src[pre_rows - 1]) == old_last
        how = "delta"
        if self._lut is not None:
            size = int(self._lut.shape[0])
            if S and int(self.h_src[-1]) >= size:
                self._lut = None
                how = "rebuild"
            elif not appended:
                self._build_lut(size)
                _book_refresh_h2d((self._lut,))
                how = "rebuild"
            elif S > pre_rows:
                self._lut = _put_scatter(
                    self._lut, self.h_src[pre_rows:].astype(np.int32),
                    np.arange(pre_rows, S, dtype=np.int32))
        if self._inline is not None and not (
            appended and self._inline_take_delta(pre_rows, touched)
        ):
            self._build_inline()
            _book_refresh_h2d(self._inline)
            how = "rebuild"
        ARENA_LAYOUT_UPDATES.add(how)

    def _inline_take_delta(self, pre_rows: int, touched: np.ndarray) -> bool:
        """False where the inline layout cannot take the delta in place.
        New rows lie past the old ones (the caller saw to it).  A row whose
        chunk count changed moves the chunks of every row after it, so the
        layout is rewritten from the first such row on — which is cheap
        where that row is near the end (a new film's cast), and a rebuild
        where it is not."""
        metap, ov = self._inline
        S, coff = self.n_rows, self._ov_coff
        if S > metap.shape[0]:
            return False
        t_rows = self.rows_for_uids_host(touched)
        t_rows = t_rows[(t_rows >= 0) & (t_rows < pre_rows)]
        cd = _chunks_of(self.h_offsets[t_rows + 1] - self.h_offsets[t_rows])
        moved = t_rows[cd != coff[t_rows + 1] - coff[t_rows]]
        r0 = int(moved.min()) if len(moved) else pre_rows
        tail = np.arange(r0, S, dtype=np.int64)
        tcoff = coff[r0] + np.concatenate([[0], np.cumsum(_chunks_of(
            self.h_offsets[tail + 1] - self.h_offsets[tail]))])
        used, was = int(tcoff[-1]), int(coff[-1])
        head = t_rows[t_rows < r0]
        if len(tail) + len(head) > _LAYOUT_ROWS_MAX or used > ov.shape[0]:
            return False
        rows = np.concatenate([head, tail])
        mrows, cpos, crows = self._inline_rows(
            rows, np.concatenate([coff[head], tcoff[:-1]]))
        if len(cpos) + max(0, was - used) > _LAYOUT_CHUNKS_MAX:
            return False
        if was > used:      # chunks given up read as a build leaves them
            cpos = np.concatenate([cpos, np.arange(used, was)])
            crows = np.concatenate(
                [crows, np.full((was - used, 8), SENT, dtype=np.int32)])
        if len(rows):
            metap = _put_scatter(metap, rows.astype(np.int32), mrows)
        if len(cpos):
            ov = _put_scatter(ov, cpos.astype(np.int32), crows)
        # read under _BUILD_LOCK only, so a row in the middle that moved
        # may be rewritten where it lies
        self._ov_coff, _ = self._extended("_ov_coff", coff[: r0 + 1], tcoff[1:])
        self._inline = (metap, ov)
        return True

    def _degrees_of_uids(self, uids: np.ndarray) -> np.ndarray:
        """Out-degree per ROW-KEY uid (0 where the uid has no row) — a
        delta's before/after probe."""
        return self.degree_of_rows(self.rows_for_uids_host(uids)).astype(np.int64)

    def _hist_move(self, old_deg: int, new_deg: int) -> None:
        """Shift one row between log2 degree buckets (bucket definition
        mirrors degree_histogram: slot ⌈log2(deg)⌉, degree-1 rows in
        slot 0; degree-0 rows are uncounted)."""
        h = self._deg_hist
        for deg, step in ((old_deg, -1), (new_deg, +1)):
            if deg <= 0:
                continue
            b = (int(deg) - 1).bit_length()
            if b >= len(h):
                h = self._deg_hist = np.concatenate(
                    [h, np.zeros(b + 1 - len(h), dtype=h.dtype)]
                )
            h[b] += step

    def insert_empty_rows(self, at: np.ndarray) -> None:
        """Degree-0 rows before the rows ``at`` (ascending, positions in the
        rows as they are) of an arena whose row keys are the row numbers —
        an index arena taking new tokens.  Every later row is renumbered,
        so what was derived from the rows is dropped; ``h_offsets`` is
        copied once (into a buffer with room), the row numbers grow at
        their end."""
        with _BUILD_LOCK:
            h_offsets = _spliced(self.h_offsets, at + 1, self.h_offsets[at])
            self._bufs["h_offsets"] = h_offsets.base
            n = len(h_offsets) - 1
            self.h_src, _ = self._extended(
                "h_src", self.h_src, np.arange(self.n_rows, n, dtype=np.int64))
            self.h_offsets, self.n_rows = h_offsets, n
            self._inline = self._ov_coff = self._lut = None
            self._bufs.pop("_ov_coff", None)
            self._resident = self._tiles = None
            for attr in ("_topm_ovdeg", "_topm_deg", "_tile_blocks", "_deg_hist"):
                if hasattr(self, attr):
                    delattr(self, attr)
            self._device_stale = True

    def ensure_device(self) -> None:
        """Re-upload device tensors from the host mirrors if a delta made
        them stale (one upload amortizes a burst of point mutations).

        Thread-safe under concurrent readers: the rebuild updates several
        fields, so it runs under the shared build lock with a re-check;
        the staleness flag clears LAST, so lock-free fast-path readers
        only skip once every field is fresh (mutations themselves are
        excluded by the server's write lock — see utils/rwlock.py)."""
        if not self._device_stale:
            return
        with _BUILD_LOCK:
            if not self._device_stale:
                return
            fresh = _csr_from_arrays(self.h_src, self.h_offsets, self._h_dst)
            self.src = fresh.src
            self.offsets = fresh.offsets
            self.dst = fresh.dst
            self._device_stale = False
            # the re-upload is this request's staging cost: the CSR
            # triple just crossed host→device on its behalf
            _book_h2d((self.src, self.offsets, self.dst))


def _ivm_repair_gate(n_delta: int, entry_edges: float) -> bool:
    """The repair-vs-rebuild decision for one derived view (IVM): off
    when the IVM gate is, else the planner's cost call
    (query/planner.py::repair_route — recorded like every other route
    decision, visible at /debug/planner)."""
    from dgraph_tpu.ivm import ivm_enabled

    if not ivm_enabled():
        return False
    from dgraph_tpu.query import planner

    ok, dec = planner.repair_route(n_delta, entry_edges)
    if dec is not None:
        planner.record(None, dec)
    return ok


def _resident_cap(n_edges: int) -> int:
    """Capacity of the resident dst buffer: live edges plus growth
    headroom (~1/8th, floor 1024) so point-mutation bursts merge on
    device instead of reseeding, rounded to whole (8, 128) int32 tiles
    PLUS one slack tile group — the layout contract of
    ops/pallas_gather.py (the buffer bitcasts to [NT, 128] with NT % 8
    == 0, and every 16-row window a live span can need lies inside it)."""
    head = max(n_edges // 8, 1024)
    return ((n_edges + head + 1023) // 1024) * 1024 + 1024


@jax.jit
def _resident_merge(off, dst, add_r, add_d, del_r, del_d):
    """Jitted segment-scatter: produce the NEXT epoch's (offsets, dst)
    from the live buffers plus padded (row, dst) delta pairs — the
    device-side twin of ``CSRArena._apply_delta_locked``'s host merge,
    with sorts in place of np.insert/np.delete (no int64 composite keys:
    x64 is disabled, so the (row, dst, tag) triple rides ``lexsort``).

    Correctness leans on the store-journal contract the host merge
    already relies on: adds must not already exist, dels must exist, and
    ``_try_apply_delta`` nets the journal so no key is both — hence a
    del's (row, dst) twin is exactly one live edge, and with ``tag`` as
    the last sort key it lands IMMEDIATELY after that twin.  Delta pads
    carry (SENT, SENT) and sort past every live row.  Registered as
    "resident.merge" in the device-program contract registry."""
    sb1 = off.shape[0]              # Sb + 1 (static)
    big = jnp.int32(sb1)            # > any live row index
    ecap = dst.shape[0]
    idx = jnp.arange(ecap, dtype=jnp.int32)
    # row of each packed edge slot; off[-1] == E by the pad contract
    er = jnp.searchsorted(off[1:], idx, side="right").astype(jnp.int32)
    live = idx < off[-1]
    rows0 = jnp.where(live, er, big)
    dst0 = jnp.where(live, dst, SENT)
    rows_c = jnp.concatenate([rows0, add_r, del_r])
    dst_c = jnp.concatenate([dst0, add_d, del_d])
    tag = jnp.concatenate([
        jnp.zeros(ecap + add_r.shape[0], jnp.int32),
        jnp.ones(del_r.shape[0], jnp.int32),
    ])
    o = jnp.lexsort((tag, dst_c, rows_c))
    r_s, d_s, t_s = rows_c[o], dst_c[o], tag[o]
    nxt_del = jnp.concatenate([t_s[1:] == 1, jnp.zeros(1, bool)])
    same = jnp.concatenate([
        (r_s[1:] == r_s[:-1]) & (d_s[1:] == d_s[:-1]),
        jnp.zeros(1, bool),
    ])
    remove = (t_s == 1) | (nxt_del & same)
    r_f = jnp.where(remove, big, r_s)
    d_f = jnp.where(remove, SENT, d_s)
    o2 = jnp.lexsort((d_f, r_f))
    r_f = r_f[o2][:ecap]
    d_f = d_f[o2][:ecap]
    # new offsets by rank: matches _csr_from_arrays pad semantics
    # (off[r] == E' for every padding row r > S, dst SENT-padded)
    new_off = jnp.searchsorted(
        r_f, jnp.arange(sb1, dtype=jnp.int32), side="left"
    ).astype(jnp.int32)
    return new_off, d_f


class ResidentArena:
    """Device-pinned CSR (offsets + packed dst) for the Pallas gather
    tier: the buffers ``ops.gather_pallas`` walks directly in HBM — the
    "store format IS the kernel format" endpoint (PAPERS.md RedisGraph/
    GraphBLAS line).  Unlike ``CSRArena.ensure_device`` — which re-stages
    the full CSR triple after every mutation — a resident arena absorbs
    deltas ON DEVICE (``_resident_merge``) under double-buffered epochs:
    the merge produces the next epoch's buffers, the reference flip in
    ``apply_delta`` is the atomic swap, and the previous epoch's buffers
    stay pinned as the shadow so in-flight expansions holding them read
    a consistent snapshot.  ``device_bytes()`` counts live AND shadow,
    each exactly once — the constant-across-flips total the ArenaManager
    budget accountant sees (no transient double-count in the flip
    window)."""

    def __init__(self, off: jnp.ndarray, dst: jnp.ndarray, n_edges: int):
        self.off = off              # int32[Sb+1], live epoch
        self.dst = dst              # int32[Ecap], SENT slack-padded
        self.n_edges = int(n_edges)
        self._prev: Optional[tuple] = None  # shadow: previous epoch

    @property
    def ecap(self) -> int:
        return int(self.dst.shape[0])

    @classmethod
    def seed(cls, h_offsets, h_dst, n_rows: int, n_edges: int):
        """Initial (or reseed) upload from the host mirrors — the ONE
        sanctioned full staging of a resident arena, charged h2d."""
        Sb = ops.bucket(max(1, n_rows))
        E = int(n_edges)
        off = np.full(Sb + 1, E, dtype=np.int32)
        off[: n_rows + 1] = h_offsets.astype(np.int32)
        dstp = np.full(_resident_cap(E), SENT, dtype=np.int32)
        if E:
            dstp[:E] = np.asarray(h_dst[:E], dtype=np.int32)
        ra = cls(jnp.asarray(off), jnp.asarray(dstp), E)
        _book_h2d((ra.off, ra.dst))
        return ra

    def apply_delta(self, add_r, add_d, del_r, del_d, n_edges: int) -> None:
        """Merge padded device delta pairs into the NEXT epoch's buffers
        and flip.  Only the delta pairs cross the boundary (charged h2d);
        the merge inputs and outputs never leave the device."""
        new_off, new_dst = _resident_merge(
            self.off, self.dst, add_r, add_d, del_r, del_d
        )
        _book_h2d((add_r, add_d, del_r, del_d))
        # the flip: previous epoch's buffers become the shadow (readers
        # holding them stay consistent; the NEXT flip releases them)
        self._prev = (self.off, self.dst)
        self.off = new_off
        self.dst = new_dst
        self.n_edges = int(n_edges)

    def expand_packed(
        self, rows: jnp.ndarray, cap: int, interpret: bool = False
    ) -> jnp.ndarray:
        """Packed frontier expansion against the LIVE epoch buffers:
        device-in, device-out, concat([out, seg]) like the engine's
        ``_packed_expand_csr`` — the transfer-free hop core (the engine
        fetches the result and charges the ledger itself)."""
        return ops.gather_pallas_packed(
            self.off, self.dst, rows, cap, interpret=interpret
        )

    def device_bytes(self) -> int:
        n = int(self.off.nbytes + self.dst.nbytes)
        if self._prev is not None:
            n += int(sum(t.nbytes for t in self._prev))
        return n


class PathLayout:
    """The arenas of a path search's listed predicates merged into ONE CSR
    over the uid space (``ops/bfs.py``): row = uid, so a frontier uid is
    its own row and a table over the uids is indexed by what the edges hold; a
    uid's edges lie in the order the predicates were listed, each
    predicate's ascending.  ``off`` is int32[ub, 2]: a uid's first and
    past-the-last edge slot side by side, so that one row gather reads both
    (a uid that holds no edge reads two equal slots, WHICH is not said: a
    build leaves the running count there, a delta what was there).
    ``esrc`` is the source uid of every edge slot (0 on padding: no uid),
    for the level done as a sweep.  Built on the
    host from the arenas' mirrors on first use and kept by the
    ``ArenaManager``, which gives it every delta its arenas take
    (``take_delta``).  Its tables are DENSE
    over the uid space, as a search's level and parent tables are:
    ``planner.path_route`` sends a block to the host where that space is
    wider than the arenas hold (``path_extent``)."""

    def __init__(self, arenas: List[CSRArena]):
        srcs = [np.repeat(a.h_src, np.diff(a.h_offsets)) for a in arenas]
        dsts = [a.host_dst()[: a.n_edges].astype(np.int64) for a in arenas]
        src = np.concatenate(srcs) if srcs else np.empty(0, np.int64)
        dst = np.concatenate(dsts) if dsts else np.empty(0, np.int64)
        self.n_edges = int(len(src))
        self.top = int(src.max()) if self.n_edges else 0   # the last uid that holds an edge
        self.universe = int(max(self.top, dst.max())) if self.n_edges else 0
        self.ub = ops.bucket_fine(self.universe + 2)
        order = np.argsort(src, kind="stable")
        counts = np.bincount(src, minlength=self.ub)
        self.max_degree = int(counts.max()) if self.n_edges else 0
        off = np.zeros(self.ub + 1, dtype=np.int32)
        np.cumsum(counts, out=off[1:])
        eb = ops.bucket(max(1, self.n_edges))
        self.off = jnp.asarray(np.stack([off[:-1], off[1:]], axis=1))
        self.dst = jnp.asarray(ops.pad_to(dst[order], eb))
        self.esrc = jnp.asarray(ops.pad_to(src[order], eb, fill=0))
        self.key = tuple((id(a), a.epoch) for a in arenas)
        _book_path_h2d((self.off, self.dst, self.esrc))

    def kpath_tables(self) -> "KPathTables":
        """This layout's pairs as a k-path search walks them
        (``ops/kbfs.py``), built from it on first use and kept with it: a
        layout is never changed in place (``take_delta`` makes a new one)."""
        kt = self.__dict__.get("_kpath")
        if kt is None:
            with _BUILD_LOCK:
                kt = self.__dict__.get("_kpath")
                if kt is None:
                    kt = self.__dict__["_kpath"] = KPathTables(self)
        return kt

    def take_delta(self, arenas: List[CSRArena], took: list) -> Optional["PathLayout"]:
        """The layout after ``arenas`` (the ones it was built from, in the
        order listed) took ``took`` — an (adds, dels) pair of (src, dst)
        arrays an arena, None where it took nothing: a NEW layout whose tables
        are this one's with the new uids' ``off`` rows and edge slots
        scattered in, array for array what a build from the arenas gives
        (tests hold it to that), at the same shapes, so that ``ops/bfs.py``
        compiles nothing.  None where it cannot be had that way and the
        caller builds anew: an edge deleted, a source that holds an edge
        already or lies under one that does (its slots are in the MIDDLE of
        ``dst``: every later uid's would move), the uid space or the edge
        slots outgrown, a degree past the widest there is (``bfs.capacities``
        sizes a chunk by it), or an arena that is not one epoch on from the
        one merged.  What a new film brings — itself, its performances, its
        newcomers, uids handed out past all others — passes."""
        adds = []
        for a, (was_id, was_epoch), t in zip(arenas, self.key, took):
            if id(a) != was_id or a.epoch != was_epoch + (t is not None):
                return None
            if t is not None:
                if len(t[1]):
                    return None
                adds.append(t[0][np.lexsort((t[0][:, 1], t[0][:, 0]))])
        new = np.concatenate(adds) if adds else np.zeros((0, 2), np.int64)
        new = new[np.argsort(new[:, 0], kind="stable")]   # listed order within a uid
        n = self.n_edges + len(new)
        if not len(new) or int(new[:, 0].min()) <= self.top \
                or int(new.max()) + 2 > self.ub or n > self.dst.shape[0]:
            return None
        uids, counts = np.unique(new[:, 0], return_counts=True)
        if int(counts.max()) > self.max_degree:
            return None
        ends = self.n_edges + np.cumsum(counts)
        slots = np.arange(self.n_edges, n, dtype=np.int32)
        lay = object.__new__(PathLayout)
        lay.off = _put_scatter(self.off, uids.astype(np.int32),
                               np.stack([ends - counts, ends], axis=1), _book_path_h2d)
        lay.dst = _put_scatter(self.dst, slots, new[:, 1], _book_path_h2d)
        lay.esrc = _put_scatter(self.esrc, slots, new[:, 0], _book_path_h2d)
        lay.n_edges, lay.top = n, int(uids[-1])
        lay.universe = max(self.universe, int(new.max()))
        lay.ub, lay.max_degree = self.ub, self.max_degree
        lay.key = tuple((id(a), a.epoch) for a in arenas)
        return lay


class KPathTables:
    """A ``PathLayout``'s pairs for a k-path search (``ops/kbfs.py``): each
    uid's DISTINCT targets, ascending (``dst``, a pair under two listed
    predicates once), its first pair slot (``first``) and pair count
    (``deg``), int32 over the uid space, and its out-degree under the listed
    predicates (``odeg``: the work a search books, a pair under two
    predicates twice).  ``targets`` uids are a pair's target — a search can
    take no other — and the widest uid holds ``max_degree`` pairs.  Built on
    the host from the layout's own tables."""

    def __init__(self, lay: PathLayout):
        n = lay.n_edges
        src = np.asarray(lay.esrc[:n]).astype(np.int64)
        pairs = np.unique((src << 32) | np.asarray(lay.dst[:n]).astype(np.int64))
        deg = np.bincount(pairs >> 32, minlength=lay.ub).astype(np.int32)
        off = np.asarray(lay.off)
        self.targets = len(np.unique(pairs & 0xFFFFFFFF))
        self.max_degree = int(deg.max()) if n else 0
        self.dst = jnp.asarray(ops.pad_to((pairs & 0xFFFFFFFF).astype(np.int32),
                                          ops.bucket(max(1, len(pairs)))))
        self.first = jnp.asarray((np.cumsum(deg) - deg).astype(np.int32))
        self.deg = jnp.asarray(deg)
        self.odeg = jnp.asarray((off[:, 1] - off[:, 0]).astype(np.int32))
        _book_h2d((self.dst, self.first, self.deg, self.odeg))

    def arrays(self) -> tuple:
        return self.dst, self.first, self.deg, self.odeg


def _build_csr(rows_to_dsts: Dict[int, np.ndarray]) -> CSRArena:
    """Build a CSR arena from {row_key: array-of-dst} (host)."""
    keys = np.array(sorted(rows_to_dsts.keys()), dtype=np.int64)
    S = len(keys)
    degs = np.array([len(rows_to_dsts[k]) for k in keys], dtype=np.int64)
    offsets = np.zeros(S + 1, dtype=np.int64)
    np.cumsum(degs, out=offsets[1:])
    E = int(offsets[-1])
    dst = np.empty(E, dtype=np.int32)
    for i, k in enumerate(keys):
        d = np.sort(np.asarray(list(rows_to_dsts[k]), dtype=np.int32))
        dst[offsets[i] : offsets[i + 1]] = d
    return _csr_from_arrays(keys, offsets, dst)


def _edges_columnar(edges: Dict[int, set]) -> Tuple[np.ndarray, np.ndarray]:
    """Flatten a dict-of-sets edge map into parallel (src, dst) arrays in
    ONE pass — per-row work is two C-speed slice assignments, so the
    million-row predicates of a 21M-quad graph extract in seconds (the
    per-row _build_csr path took a python sort per row)."""
    n = sum(len(s) for s in edges.values())
    src = np.empty(n, dtype=np.int64)
    dst = np.empty(n, dtype=np.int64)
    i = 0
    for u, s in edges.items():
        k = len(s)
        src[i : i + k] = u
        dst[i : i + k] = list(s)
        i += k
    return src, dst


def _sorted_unique_edges(src: np.ndarray, dst: np.ndarray):
    """Sort edge pairs by (src, dst) and drop duplicates (vectorized)."""
    src = np.asarray(src, dtype=np.int64)
    dst = np.asarray(dst, dtype=np.int64)
    order = np.lexsort((dst, src))
    s, d = src[order], dst[order]
    if len(s):
        keep = np.ones(len(s), dtype=bool)
        keep[1:] = (s[1:] != s[:-1]) | (d[1:] != d[:-1])
        s, d = s[keep], d[keep]
    return s, d


def csr_from_edges(
    src: np.ndarray, dst: np.ndarray, row_universe: Optional[np.ndarray] = None
) -> CSRArena:
    """Vectorized bulk CSR construction from parallel edge arrays — no
    per-row python loops (one global lexsort).  ``row_universe`` adds
    degree-0 rows for uids beyond the edge sources (the has()/_predicate_
    arena needs rows for uids that only carry values)."""
    s, d = _sorted_unique_edges(src, dst)
    ekeys, counts = np.unique(s, return_counts=True)
    if row_universe is not None and len(row_universe):
        keys = np.union1d(ekeys, np.asarray(row_universe, dtype=np.int64))
        full = np.zeros(len(keys), dtype=np.int64)
        full[np.searchsorted(keys, ekeys)] = counts
        counts = full
    else:
        keys = ekeys
    offsets = np.zeros(len(keys) + 1, dtype=np.int64)
    np.cumsum(counts, out=offsets[1:])
    return _csr_from_arrays(keys, offsets, d.astype(np.int32))


def csr_dense_from_edges(src: np.ndarray, dst: np.ndarray, n_nodes: int) -> CSRArena:
    """Dense CSR: one row per uid in [0, n_nodes] (degree 0 where absent),
    so frontier uids ARE row indices — no searchsorted on the query path.
    The layout of choice for whole-graph predicates at bench scale."""
    s, d = _sorted_unique_edges(src, dst)
    counts = np.bincount(s, minlength=n_nodes + 1)
    offsets = np.zeros(n_nodes + 2, dtype=np.int64)
    np.cumsum(counts, out=offsets[1:])
    keys = np.arange(n_nodes + 1, dtype=np.int64)
    return _csr_from_arrays(keys, offsets, d.astype(np.int32))


def _csr_from_arrays(keys: np.ndarray, offsets: np.ndarray, dst: np.ndarray) -> CSRArena:
    S, E = len(keys), len(dst)
    Sb = ops.bucket(max(1, S))
    Eb = ops.bucket(max(1, E))
    src_pad = np.full(Sb, SENT, dtype=np.int32)
    src_pad[:S] = keys.astype(np.int32)
    off_pad = np.full(Sb + 1, offsets[-1] if S else 0, dtype=np.int32)
    off_pad[: S + 1] = offsets.astype(np.int32)
    dst_pad = np.full(Eb, SENT, dtype=np.int32)
    dst_pad[:E] = dst
    return CSRArena(
        src=jnp.asarray(src_pad),
        offsets=jnp.asarray(off_pad),
        dst=jnp.asarray(dst_pad),
        h_src=keys,
        h_offsets=offsets,
        n_rows=S,
        n_edges=E,
    )


@dataclass
class IndexArena:
    """Secondary index: host token table + device token-row → uids CSR."""

    tokenizer: str
    tokens: list                    # sorted token keys (host)
    csr: CSRArena                   # rows aligned with ``tokens``
    lossy: bool

    def row_of(self, token) -> int:
        i = bisect.bisect_left(self.tokens, token)
        if i < len(self.tokens) and self.tokens[i] == token:
            return i
        return -1

    def device_bytes(self) -> int:
        return self.csr.device_bytes()

    def take_values(self, items) -> None:
        """The index after (uid, value) pairs were set on uids that held no
        value (``PostingStore.value_delta``): a token that is new gets a
        row at its place, each uid joins its tokens' rows — what a build
        from the store would give, without the walk over every value.

        ``tokens`` takes a new token where it lies (one ``list.insert``, a
        shift of references and no copy of the table); the CSR's offsets and
        targets are copied once each, into buffers with room, and its row
        numbers grow at their end.  In a server the writer holds the
        exclusive side, and no reader runs.  A reader that holds no lock
        against this writer (an embedded engine, a clustered refresh) may
        call ``row_of`` / ``row_range`` meanwhile: each sees the table before
        or after a token went in (``list.insert`` and ``bisect`` are atomic
        under the GIL), and the CSR arrays it holds stay whole (nothing a
        published view covers is written); but tokens and rows are not
        published together, and never were, so between the two a row number
        may be one of the other numbering."""
        pairs = set()
        for uid, val in items:
            try:
                toks = tokmod.tokens_for_value_lang(self.tokenizer, val, "")
            except (ValueError, TypeError, OverflowError):
                continue  # unindexable value, as at the build
            pairs.update((t, int(uid)) for t in toks)
        if not pairs:
            return
        with _BUILD_LOCK:
            new = sorted({t for t, _ in pairs if self.row_of(t) < 0})
            if new:
                at = np.array([bisect.bisect_left(self.tokens, t) for t in new],
                              dtype=np.int64)
                self.csr.insert_empty_rows(at)
                for t in new:
                    bisect.insort(self.tokens, t)
            adds = np.array(sorted((self.row_of(t), u) for t, u in pairs),
                            dtype=np.int64).reshape(-1, 2)
            self.csr.apply_delta(adds, np.zeros((0, 2), dtype=np.int64))

    def row_range(self, lo=None, hi=None, lo_open=False, hi_open=False) -> Tuple[int, int]:
        """Token rows t with lo <=(<) t <=(<) hi, as [start, end)."""
        start = 0
        end = len(self.tokens)
        if lo is not None:
            start = (
                bisect.bisect_right(self.tokens, lo)
                if lo_open
                else bisect.bisect_left(self.tokens, lo)
            )
        if hi is not None:
            end = (
                bisect.bisect_left(self.tokens, hi)
                if hi_open
                else bisect.bisect_right(self.tokens, hi)
            )
        return start, max(start, end)


@dataclass
class ValueArena:
    """Numeric values on device for order-by/aggregation/math."""

    src: jnp.ndarray                # int32[Sb] sorted uids, SENT-padded
    vals: jnp.ndarray               # float32[Sb]; padding slots hold NaN
    ranks: jnp.ndarray              # int32[Sb] dense rank of the EXACT
                                    # float64 value (device ordering by
                                    # rank is exact; float32 vals are not);
                                    # padding slots hold -1
    h_src: np.ndarray               # int64[S]
    h_vals: np.ndarray              # float64[S]
    h_ranks: np.ndarray             # int32[S] host mirror of ranks (exact)
    n: int
    langless: bool = True           # no lang-tagged values existed for the
                                    # predicate — untagged host lookup and
                                    # this arena agree uid-for-uid
    wallclock: bool = True          # no datetime carries a non-zero UTC
                                    # offset: a datetime tokenizer's fields
                                    # order as these values do
    whole: bool = True              # every value of the predicate has a
                                    # finite number here, as its index
                                    # holds a token: a uid missing from
                                    # the column holds no value

    def device_bytes(self) -> int:
        return sum(
            t.size * t.dtype.itemsize for t in (self.src, self.vals, self.ranks)
        )


def _cache_locked(fn):
    """Run an ArenaManager accessor under its cache lock (see __init__)."""
    import functools

    @functools.wraps(fn)
    def wrapper(self, *a, **k):
        with self._cache_lock:
            return fn(self, *a, **k)

    return wrapper


class ArenaManager:
    """Builds and caches arenas; invalidates on store dirty marks.

    The analog of posting's lcache + periodicCommit (posting/lists.go):
    arenas for clean predicates stay resident on device between queries.
    Accessors are thread-safe for concurrent read queries: the cache lock
    guards dict lookups and dirty-refresh only; heavy builds run outside
    it under per-key build locks (_get_or_build), so a cold predicate
    stalls only readers of that same predicate.
    """

    # graftcheck tier 3: the LRU accounting and the full-store-clear
    # generation are bumped from every query thread — the witness holds
    # them to the _cache_lock discipline the docstring above promises.
    # expand_device_min is deliberately NOT listed: it is a GIL-atomic
    # planner knob (engine setter rebinds an int; readers take either
    # value and both are valid plans).
    __race_fields__ = frozenset({"_lru_total", "_inval_gen_star"})

    def __init__(
        self,
        store: PostingStore,
        mesh=None,
        shard_threshold: int = 4096,
        budget_bytes: Optional[int] = None,
    ):
        self.store = store
        # device mesh for uid-range row sharding of big predicates (the
        # intra-predicate sharding the reference lacks, SURVEY.md §5);
        # None = single-device execution.  ``self.mesh`` is a property:
        # with the elastic fault domain active it reads the CURRENT
        # surviving sub-mesh, so every consumer (sharded_csr width,
        # executor dispatch, scheduler concurrency) follows a re-shard
        # through one swap.
        self._mesh = mesh
        self.shard_threshold = shard_threshold
        # mesh serving plane (PR 17): predicate→shard placement so
        # co-resident predicates don't all pile shard 0 (their densest
        # uid range) on the same chip, plus the memoized serving-path
        # executor the engine/chain dispatch through
        self.mesh_plan = None
        self._mesh_exec = None
        # elastic mesh fault domain (mesh/fault.py): per-chip health +
        # epoch-fenced sub-mesh re-sharding.  Only meaningful when there
        # is more than one chip to lose; DGRAPH_TPU_MESH_ELASTIC=0
        # restores the PR 17 monolithic plane exactly.
        self.mesh_fault = None
        if mesh is not None:
            from dgraph_tpu.mesh.plan import MeshPlan

            self.mesh_plan = MeshPlan.load(int(mesh.shape["model"]))
            if int(mesh.shape["model"]) > 1:
                from dgraph_tpu.mesh import fault as _mesh_fault

                if _mesh_fault.elastic_enabled():
                    self.mesh_fault = _mesh_fault.MeshFaultDomain(
                        self, mesh
                    )
        # single source of truth for host-vs-device expansion routing
        # (engine and FuncResolver both read it; engine may retune at
        # runtime) — see QueryEngine.__init__ for the rationale.  While
        # it sits at the planconfig default, the adaptive planner
        # (query/planner.py) substitutes its calibrated break-even;
        # assigning it (or pinning the env knob) restores the static gate
        from dgraph_tpu.utils import planconfig as _planconfig

        self.expand_device_min = _planconfig.expand_device_min()
        self._data: Dict[str, CSRArena] = {}
        self._reverse: Dict[str, CSRArena] = {}
        self._index: Dict[Tuple[str, str], IndexArena] = {}
        self._values: Dict[str, ValueArena] = {}
        self._sharded: Dict[Tuple[str, bool], tuple] = {}
        # merged layouts of path searches (PathLayout), by the listed
        # predicates: a handful at most, dropped whole under HBM pressure
        self._path_layouts: Dict[tuple, PathLayout] = {}
        # id(arena) -> the (adds, dels) it took in the refresh under way
        self._took: Dict[int, tuple] = {}
        # protects the cache dicts + refresh bookkeeping ONLY — heavy
        # arena builds run outside it under per-key build locks
        # (_get_or_build), so one cold predicate never stalls readers of
        # warm ones.  RLock because accessors nest (has_rows → data).
        self._cache_lock = threading.RLock()
        self._build_locks: Dict[tuple, threading.Lock] = {}
        # journal-consumption generations: refresh() bumps a predicate's
        # counter whenever it consumes that predicate's journal window
        # (delta applied in place OR caches dropped for rebuild).  A
        # build snapshots the counter before peeking the store and
        # retries if it moved — otherwise a writer's refresh can pop the
        # journal while a cold build holds a pre-write peek, and the
        # build then caches an arena the consumed delta never reaches
        # (the write is lost with no dirty mark left to repair it).
        self._inval_gen: Dict[str, int] = {}
        self._inval_gen_star = 0  # bumped by the "*" full-store clear
        # HBM residency budget (bytes): the analog of the reference's
        # memory-watermark-sized posting LRU (posting/lru.go:57,
        # posting/lists.go:191).  0 = unlimited.  Cold arenas evict
        # WHOLLY from the cache (host store keeps the truth; the next
        # access rebuilds), touched arenas move to the LRU tail.
        from collections import OrderedDict as _OD

        import os as _os

        self.budget_bytes = int(
            budget_bytes
            if budget_bytes is not None
            else _os.environ.get("DGRAPH_TPU_ARENA_BUDGET", 0)
        )
        self._lru: "_OD[tuple, int]" = _OD()  # (cache id, key) -> bytes
        self._lru_total = 0  # running sum of _lru values (O(1) touches)
        # tier-1 hop-expansion cache (dgraph_tpu/cache/hop.py): expansion
        # results are arena-snapshot state, so the cache lives and dies
        # with this manager and must hear about arena evictions below
        # (id-keyed entries may never outlive the arena object).  None
        # when DGRAPH_TPU_CACHE=0 — the expander then skips every probe.
        from dgraph_tpu.cache import HopCache, cache_enabled

        self.hop_cache = HopCache() if cache_enabled() else None
        self._caches_by_id = {
            id(self._data): self._data,
            id(self._reverse): self._reverse,
            id(self._index): self._index,
            id(self._values): self._values,
            id(self._sharded): self._sharded,
        }
        self.evictions = 0

    def _get_or_build(self, cache, key, build, valid=None, gen_key=None):
        """cache[key], building OUTSIDE the cache lock under a per-key
        build lock: concurrent readers of other keys proceed; concurrent
        readers of the same key wait for one build instead of duplicating
        it (the pattern of ClusterStore._remote_peek's fetch locks).
        ``valid`` optionally rejects a cached entry (sharded_csr checks
        its source-arena identity).  The build-lock entry is dropped even
        when the build raises, so a failed build can't wedge the key.

        ``gen_key`` (the predicate the build peeks) closes the
        build-vs-journal race: refresh() consuming a journal window
        between our peek and our cache commit means the consumed delta
        can neither reach the arena we are building (it isn't cached
        yet) nor survive for a later refresh — so the build must retry
        on a fresh peek.  The commit and the generation check share the
        cache lock with refresh, so a window consumed after the check
        necessarily sees (and repairs) the entry we just cached."""
        lkey = (id(cache), key)
        with self._cache_lock:
            a = cache.get(key)
            if a is not None and (valid is None or valid(a)):
                self._touch(lkey, a)
                return a
            bl = self._build_locks.setdefault(lkey, threading.Lock())
        with bl:
            with self._cache_lock:
                a = cache.get(key)
                if a is not None and (valid is None or valid(a)):
                    self._touch(lkey, a)
                    return a
            try:
                while True:
                    with self._cache_lock:
                        g0 = (
                            self._inval_gen.get(gen_key, 0),
                            self._inval_gen_star,
                        )
                    a = build()
                    with self._cache_lock:
                        if gen_key is not None and g0 != (
                            self._inval_gen.get(gen_key, 0),
                            self._inval_gen_star,
                        ):
                            continue  # journal consumed mid-build: re-peek
                        cache[key] = a
                        self._touch(lkey, a)
                        self._evict_over_budget(protect=lkey)
                        return a
            finally:
                with self._cache_lock:
                    self._build_locks.pop(lkey, None)

    def _touch(self, lkey: tuple, obj) -> None:
        """LRU bookkeeping under _cache_lock: refresh recency + size (lazy
        device layouts — lut/inline/tiles — built after caching grow the
        footprint, so warm touches also re-check the budget)."""
        if lkey[0] == id(self._sharded):
            obj = obj[1]  # (_sharded caches (source arena, ShardedArena))
        db = getattr(obj, "device_bytes", None)
        if db is None:
            return
        new = db()
        self._lru_total += new - self._lru.get(lkey, 0)
        self._lru[lkey] = new
        self._lru.move_to_end(lkey)
        self._evict_over_budget(protect=lkey)

    def _lru_drop(self, cache, key) -> None:
        """Remove a cache entry's budget accounting (refresh invalidation
        path) — phantom bytes would otherwise shrink the budget forever."""
        b = self._lru.pop((id(cache), key), None)
        if b is not None:
            self._lru_total -= b

    def _evict_over_budget(self, protect: tuple) -> None:
        """Drop least-recently-used arenas until within budget (never the
        entry just touched).  Evicting a data/reverse arena also drops its
        mesh-sharded view — the view holds a reference that would pin the
        arena's HBM alive.  Concurrent readers holding a popped arena keep
        using their reference safely — the object only leaves the cache,
        and the momentary overshoot ends with their request."""
        if not self.budget_bytes:
            return
        while self._lru_total > self.budget_bytes and len(self._lru) > 1:
            if not self._pop_lru_victim(protect):
                break

    def _pop_lru_victim(self, protect: Optional[tuple] = None) -> bool:
        """Evict the least-recently-used entry (never ``protect``);
        returns whether one was dropped.  Caller holds _cache_lock."""
        if not self._lru:
            return False
        victim, vbytes = next(iter(self._lru.items()))
        if victim == protect:
            return False
        self._lru.pop(victim)
        self._lru_total -= vbytes
        cache = self._caches_by_id.get(victim[0])
        gone = cache.pop(victim[1], None) if cache is not None else None
        if gone is not None and self.hop_cache is not None:
            # tier-1 entries are keyed by id(arena): drop them NOW,
            # while the object is still alive, or a later allocation
            # recycling the id could alias a dead entry's key
            self.hop_cache.drop_arena(id(gone))
        if cache is self._data or cache is self._reverse:
            skey = (victim[1], cache is self._reverse)
            if skey in self._sharded:
                self._sharded.pop(skey, None)
                self._lru_drop(self._sharded, skey)
        self.evictions += 1
        ARENA_EVICTIONS.add(1)
        return True

    def evict_for_oom(self, n: int = 2) -> int:
        """HBM-pressure valve (utils/devguard.py): a device dispatch
        just failed RESOURCE_EXHAUSTED, so drop up to ``n`` LRU entries
        REGARDLESS of the configured budget (the budget is an estimate;
        the allocator's verdict is ground truth) to give the one retry
        headroom.  Returns how many entries were dropped — zero means
        there is nothing left to free and the caller should fall
        straight to the host route.  In-flight expansions holding a
        dropped arena keep using their reference safely, exactly like
        budget eviction; the device copy is freed when the last
        reference dies."""
        with self._cache_lock:
            dropped = len(self._path_layouts)
            self._path_layouts.clear()
            while dropped < n and len(self._lru) > 1:
                if not self._pop_lru_victim():
                    break
                dropped += 1
            return dropped

    def residency(self) -> dict:
        """HBM-residency + program-cache snapshot (obs/device.py's data
        source).  ``resident_bytes`` is the budget accountant's running
        total — the same number eviction decisions are made on — so the
        telemetry can never disagree with the enforcement.  Program
        counts walk the cached data/reverse arenas' lazily-attached
        expanders/tile sets; the walk is O(cached predicates), debug-
        endpoint cost, never hot-path."""
        with self._cache_lock:
            resident = self._lru_total
            entries = len(self._lru)
            evictions = self.evictions
            arenas = list(self._data.values()) + list(
                self._reverse.values()
            )
        tile_bytes = 0
        tile_sets = 0
        for a in arenas:
            pt = getattr(a, "_tiles", None)
            if pt is not None:
                tile_bytes += pt.device_bytes()
                tile_sets += 1
        return {
            "resident_bytes": resident,
            "budget_bytes": self.budget_bytes,
            "headroom_bytes": (
                max(0, self.budget_bytes - resident)
                if self.budget_bytes else None
            ),
            "entries": entries,
            "evictions": evictions,
            "tile_bytes": tile_bytes,
            "program_caches": {"tile_sets": tile_sets},
        }

    @_cache_locked
    def refresh(self):
        """Drop or incrementally update cached arenas for predicates
        mutated since last refresh.  Small uid-edge deltas (the store's
        bounded journal) update cached data/reverse arenas in place —
        the gentle-commit amortization (posting/lists.go:109-215) — and
        with them their device layouts (``_layouts_take_delta``); values
        set on uids that held none go into the predicate's index arenas
        in place (``IndexArena.take_values``).  A value overwritten or
        deleted, a bulk load and a journal overflow fall back to the full
        rebuild.  Every cached ``PathLayout`` one of whose arenas was written
        is then brought up to them (``_path_layouts_take``): by whoever
        refreshes — in a server the writer, under the exclusive side."""
        dirty = self.store.dirty
        if not dirty:
            return
        self._refresh_dirty(dirty)
        took, self._took = self._took, {}
        self._path_layouts_take(took)

    def _refresh_dirty(self, dirty) -> None:
        # Never blanket-clear the dirty set: concurrent readers (admitted
        # by the server's RW lock) may add marks between our snapshot and
        # the clear (ClusterStore._drain_dirty runs inside peek); only
        # remove marks we actually processed, so a racing mark survives
        # for the next refresh.
        if "*" in dirty:  # full-store replacement (snapshot restore)
            self._inval_gen_star += 1  # in-flight builds must re-peek
            if self.hop_cache is not None:
                self.hop_cache.clear()
            self._data.clear()
            self._reverse.clear()
            self._values.clear()
            self._index.clear()
            self._sharded.clear()
            self._lru.clear()
            self._lru_total = 0
            dirty.discard("*")
            # remaining per-predicate marks fall through to the loop:
            # their caches are already gone, so it just consumes deltas
        deltas = getattr(self.store, "delta", {})
        vdeltas = getattr(self.store, "value_delta", {})
        bases = getattr(self.store, "delta_base", {})
        for p in list(dirty):
            delta = deltas.pop(p, None)
            vdelta = vdeltas.pop(p, None)
            # the journal window's repair base (models/store.py) is
            # consumed WITH the journal — a stale base must never
            # re-key a later window's entries
            base = bases.pop(p, None)
            # consuming this window invalidates any build mid-peek for
            # the predicate: the delta can't reach an arena that isn't
            # cached yet, so the builder must re-peek (_get_or_build)
            self._inval_gen[p] = self._inval_gen.get(p, 0) + 1
            if delta is not None and self._take_journal(p, delta, vdelta, base):
                dirty.discard(p)
                continue
            for key in [k for k in self._data if k == p or k.startswith(p + "\x00")]:
                gone = self._data.pop(key, None)
                if gone is not None and self.hop_cache is not None:
                    self.hop_cache.drop_arena(id(gone))
                self._lru_drop(self._data, key)
            gone = self._reverse.pop(p, None)
            if gone is not None and self.hop_cache is not None:
                self.hop_cache.drop_arena(id(gone))
            self._lru_drop(self._reverse, p)
            self._values.pop(p, None)
            self._lru_drop(self._values, p)
            for sk in ((p, False), (p, True)):
                self._sharded.pop(sk, None)
                self._lru_drop(self._sharded, sk)
            for key in [k for k in self._index if k[0] == p]:
                self._index.pop(key, None)
                self._lru_drop(self._index, key)
            dirty.discard(p)

    def _path_layouts_take(self, took: Dict[int, tuple]) -> None:
        """Every cached ``PathLayout`` one of whose arenas this refresh wrote
        or dropped, brought up to them: the delta scattered into its tables
        (``PathLayout.take_delta``) or — counted, never silent — the layout
        built anew from the arenas here, on the refresher's account, so that
        the next search pays for nothing; one whose arena left the cache goes
        with it (the arena is the next reader's to build, and the layout
        after it).  Stage ``path_layout``, inside ``refresh``."""
        for preds, lay in list(self._path_layouts.items()):
            arenas = [(self._reverse if rev else self._data).get(a) for a, rev in preds]
            if lay.key == tuple(None if a is None else (id(a), a.epoch) for a in arenas):
                continue
            with obs.stage(None, "path_layout_ms"), _BUILD_LOCK:
                cached = all(a is not None for a in arenas)
                new = lay.take_delta(arenas, [took.get(id(a)) for a in arenas]) if cached else None
                PATH_LAYOUT_UPDATES.add("rebuild" if new is None else "delta")
                if cached:
                    self._path_layouts[preds] = new or PathLayout(arenas)
                else:
                    del self._path_layouts[preds]

    def _take_journal(self, pred: str, delta: list, vdelta, base) -> bool:
        """The cached arenas of ``pred`` after its journal window: uid
        edges into the data and reverse arenas (``_try_apply_delta``),
        values set on uids that held none into its index arenas.  False
        where something cached cannot take it, and the caller drops all."""
        if not vdelta:
            return self._try_apply_delta(pred, delta, base)
        # a value write.  A predicate that carries uid edges as well, or
        # whose has() rows are cached, is dropped whole, as it always was
        if (delta or pred in self._data or pred in self._reverse
                or (pred + "\x00has") in self._data):
            return False
        for key in [k for k in self._index if k[0] == pred]:
            self._index[key].take_values(vdelta)
            self._touch((id(self._index), key), self._index[key])
        self._values.pop(pred, None)     # numeric ranks shift: rebuilt on use
        self._lru_drop(self._values, pred)
        return True

    def _try_apply_delta(self, pred: str, delta: list, base=None) -> bool:
        """Incrementally update the cached data (and reverse) arena for
        ``pred``.  Returns False when no cached arena exists (nothing to
        update — the next access builds fresh anyway) or a has-rows
        variant is cached (its row universe can shift: full rebuild).

        IVM (dgraph_tpu/ivm/): after the arena mirrors absorb the
        delta, the predicate's cached hop expansions absorb it too —
        repaired IN PLACE and re-keyed from ``base`` (the pred version
        every live entry carries, recorded when the journal window
        opened) to the predicate's post-mutation version, behind the
        planner's repair-vs-rebuild gate.  Entries a repair cannot fix
        simply stay stale-keyed and die by sweep, exactly as before."""
        a = self._data.get(pred)
        if a is None or (pred + "\x00has") in self._data:
            return False
        if (pred, False) in self._sharded or (pred, True) in self._sharded:
            return False  # mesh-sharded copies rebuild wholesale
        _E = np.zeros((0, 2), dtype=np.int64)
        if not delta:
            # facet-only touches: arenas unaffected, and the cached
            # expansions are still EXACT — a zero-delta repair merely
            # re-keys them to the new pred version (facet edits live in
            # the host store, never in (out, seg_ptr))
            self._repair_hop_entries(pred, a, _E, _E, base, gate=True)
            return True
        net: Dict[Tuple[int, int], int] = {}
        for s, d, sign in delta:
            net[(s, d)] = net.get((s, d), 0) + sign
        # row-garbage bound: repeated delete churn leaves degree-0 rows
        # that only a full rebuild reclaims; rebuild once they dominate
        # (a window without a delete adds none: no pass over the rows)
        if any(v < 0 for v in net.values()) and int(
            np.count_nonzero(np.diff(a.h_offsets) == 0)
        ) > max(4096, a.n_rows // 4):
            return False
        adds = np.array(
            [k for k, v in net.items() if v > 0], dtype=np.int64
        ).reshape(-1, 2)
        dels = np.array(
            [k for k, v in net.items() if v < 0], dtype=np.int64
        ).reshape(-1, 2)
        a.apply_delta(adds, dels)
        r = self._reverse.get(pred)
        if r is not None:
            r.apply_delta(adds[:, ::-1], dels[:, ::-1])
        n_delta = len(adds) + len(dels)
        if n_delta:     # what the merged path layouts over them have to take
            self._took[id(a)] = (adds, dels)
            if r is not None:
                self._took[id(r)] = (adds[:, ::-1], dels[:, ::-1])
        self._repair_hop_entries(
            pred, a, adds, dels, base,
            # the cost prior prices a typical warm entry as a ~32-row
            # frontier at this arena's mean fan-out (the tiers cap huge
            # entries anyway, so the prior errs small → errs toward
            # rebuild, the safe side)
            gate=(n_delta > 0 and _ivm_repair_gate(
                n_delta, max(1.0, a.avg_degree) * 32.0
            )),
        )
        # post-delta epoch sweep (the delta-driven twin of the PR 15
        # eviction race): entries the repair pass did not carry to the
        # new epoch describe a snapshot that no longer exists — drop
        # them now rather than letting them squat until their sweep
        if self.hop_cache is not None and n_delta > 0:
            self.hop_cache.drop_stale_epoch(id(a), a.epoch)
            if r is not None:
                self.hop_cache.drop_stale_epoch(id(r), r.epoch)
        return True

    def _repair_hop_entries(
        self, pred: str, a: CSRArena, adds, dels, base, gate: bool
    ) -> None:
        """Repair (or zero-delta re-key) the tier-1 entries for ``pred``
        on both directions' arenas.  Skips entirely when: the gate said
        rebuild, IVM is off (entries are keyed on the global version —
        nothing here could re-key them safely), the journal window
        carried no base, or a non-scopeable change (floor) landed
        inside the window (a repaired entry must never claim freshness
        across a schema epoch)."""
        if self.hop_cache is None or not gate or base is None:
            return
        from dgraph_tpu import ivm
        from dgraph_tpu.utils.metrics import IVM_REPAIR_EDGES, IVM_REPAIRS

        if not ivm.ivm_enabled():
            return
        pv = getattr(self.store, "pred_versions", None)
        if pv is None:
            return
        new_v = pv.get(pred, 0)
        floor = getattr(self.store, "pred_floor", 0)
        if new_v <= base or floor > base:
            return
        from dgraph_tpu import obs

        repaired = dropped = 0
        with obs.child("ivm.repair") as sp:
            for arena, rev, ad, dl in (
                (a, False, adds, dels),
                (self._reverse.get(pred), True,
                 adds[:, ::-1], dels[:, ::-1]),
            ):
                if arena is None:
                    continue
                # the delta that drives this repair bumped the arena
                # epoch exactly once (zero-delta re-keys bump nothing)
                ne = getattr(arena, "epoch", 0)
                oe = ne - 1 if (len(adds) or len(dels)) else ne
                rep, drop = self.hop_cache.repair_pred(
                    id(arena), pred, rev, ad, dl, base, new_v,
                    old_epoch=oe, new_epoch=ne,
                )
                repaired += rep
                dropped += drop
            sp.set_attr("pred", pred)
            sp.set_attr("delta", len(adds) + len(dels))
            sp.set_attr("repaired", repaired)
            sp.set_attr("dropped", dropped)
        if repaired:
            IVM_REPAIRS.add(("hop", "repaired"))
            IVM_REPAIR_EDGES.add((len(adds) + len(dels)) * repaired)
            led = _ledger.current()
            if led is not None:
                # attributed to the request whose refresh drove the
                # repair (usually the mutation; sometimes the first
                # reader after it — same attribution rule as spans)
                led.repairs += repaired
        if dropped:
            IVM_REPAIRS.add(("hop", "rebuild"))

    # -- mesh sharding -------------------------------------------------------

    @property
    def mesh(self):
        """The CURRENT serving mesh: the boot mesh, or — when the
        elastic fault domain has evicted a chip — the surviving
        sub-mesh it re-sharded onto.  None = unsharded execution."""
        if self.mesh_fault is not None:
            return self.mesh_fault.mesh
        return self._mesh

    @mesh.setter
    def mesh(self, m):
        self._mesh = m

    def sharded_csr(self, pred: str, reverse: bool = False):
        """Row-sharded view of a predicate's CSR over the mesh's 'model'
        axis, cached against the source arena's identity (rebuilds follow
        the same dirty invalidation as the arena itself) AND the
        MeshPlan offset it was placed under — a ``rebalance()`` moves a
        predicate's offset, so its next read rebuilds under the new
        placement instead of serving the old roll."""
        from dgraph_tpu.parallel.mesh import shard_arena_rows

        a = self.reverse(pred) if reverse else self.data(pred)
        pkey = ("~" + pred) if reverse else pred

        def build():
            sa = shard_arena_rows(
                a.h_src, a.h_offsets, a.host_dst(), self.mesh
            )
            off = 0
            if self.mesh_plan is not None:
                sa = self.mesh_plan.placed(pkey, sa)
                off = self.mesh_plan.placement.get(pkey, 0)
            return (a, sa, off)

        def valid(e):
            if e[0] is not a:
                return False
            # an elastic re-shard changed the model-axis width: the old
            # width's rolls are unservable on the new sub-mesh
            if e[1].n_shards != int(self.mesh.shape["model"]):
                return False
            if self.mesh_plan is None:
                return True
            return self.mesh_plan.placement.get(pkey, 0) == e[2]

        return self._get_or_build(
            self._sharded, (pred, reverse), build, valid=valid,
            gen_key=pred,
        )[1]

    def sharded_bytes_by_device(self) -> Dict[str, int]:
        """Bytes of the cached mesh-sharded views each device holds, read
        off the arrays' addressable shards (device id → bytes) — the
        placement check behind /debug/device: a row-sharded arena must
        sit a 1/width share on every chip, not whole on the first."""
        with self._cache_lock:
            views = [e[1] for e in self._sharded.values()]
        out: Dict[str, int] = {}
        for sa in views:
            for t in (sa.src, sa.offsets, sa.dst):
                for sh in t.addressable_shards:
                    key = str(sh.device.id)
                    out[key] = out.get(key, 0) + int(sh.data.nbytes)
        return out

    def mesh_executor(self):
        """The memoized serving-path executor (dgraph_tpu/mesh) over
        this manager's mesh — None when unsharded."""
        if self.mesh is None:
            return None
        if self._mesh_exec is None:
            from dgraph_tpu.mesh.executor import MeshExecutor

            self._mesh_exec = MeshExecutor(self)
        return self._mesh_exec

    def use_mesh_for(self, arena: CSRArena) -> bool:
        """Route this arena's expansions through the row-sharded mesh?

        Two policies (``shard_policy`` attr, default "rows"):
          "rows"  — shard at/above shard_threshold rows (explicit operator
                    knob; the mode every virtual-mesh test pins).
          "model" — consult the ICI crossover cost model
                    (parallel/crossover.py): shard when the model predicts
                    sharded wins for a typical query against this arena's
                    physical size, or when the arena cannot fit one
                    chip's HBM at all.  The threshold still floors it.
        """
        if self.mesh is None or arena.n_rows < self.shard_threshold:
            return False
        if getattr(self, "shard_policy", "rows") == "model":
            from dgraph_tpu.parallel.crossover import should_shard

            n_model = self.mesh.shape["model"]
            arena_bytes = 32 * arena.n_rows + 4 * arena.n_edges
            avg_deg = arena.n_edges / max(1, arena.n_rows)
            return should_shard(arena_bytes, arena.n_rows, avg_deg, n_model)
        return True

    def drop_sharded(self) -> None:
        """Drop every mesh-sharded view — the elastic re-shard's cache
        surgery: the evicted width's rolls are dead weight on the new
        sub-mesh, and survivors re-seed lazily through sharded_csr
        under the same HBM budget/LRU (this IS the re-seeding
        mechanism; no bulk re-upload)."""
        with self._cache_lock:
            for key in list(self._sharded):
                self._sharded.pop(key, None)
                self._lru_drop(self._sharded, key)

    def warm_sharded(self, mesh):
        """Pre-build sharded views at a rejoin CANDIDATE mesh's width —
        the warm half of warm-then-cutover, run on the fault domain's
        probe thread while live traffic keeps serving the current
        sub-mesh.  Offsets come from the plan's ``preview`` of the
        candidate width so the post-cutover ``rebalance`` finds the
        adopted entries already valid.  Build failures propagate: an
        unprovable warm means no cutover (the chip re-latches)."""
        from dgraph_tpu.mesh.fault import StagedShards
        from dgraph_tpu.mesh.plan import MeshPlan
        from dgraph_tpu.parallel.mesh import shard_arena_rows

        n_model = int(mesh.shape["model"])
        staged = StagedShards(n_model)
        with self._cache_lock:
            keys = list(self._sharded)
        preview = (
            self.mesh_plan.preview(n_model)
            if self.mesh_plan is not None
            else {}
        )
        for pred, reverse in keys:
            a = self.reverse(pred) if reverse else self.data(pred)
            pkey = ("~" + pred) if reverse else pred
            sa = shard_arena_rows(
                a.h_src, a.h_offsets, a.host_dst(), mesh
            )
            off = preview.get(pkey, 0) % n_model
            staged.views[(pred, reverse)] = (
                a, MeshPlan.rolled(sa, off), off,
            )
        return staged

    def adopt_sharded(self, staged) -> None:
        """Cutover half of warm-then-cutover: install the staged views
        built by :meth:`warm_sharded`, with LRU/budget accounting as if
        each had just been built (a stage whose width no longer matches
        the live mesh is the caller's to discard)."""
        if self.mesh is None or int(self.mesh.shape["model"]) != staged.width:
            return
        with self._cache_lock:
            for key, entry in staged.views.items():
                self._sharded[key] = entry
                self._touch((id(self._sharded), key), entry)

    # -- data / reverse ----------------------------------------------------

    def data(self, pred: str) -> CSRArena:
        self.refresh()
        return self._get_or_build(
            self._data, pred, lambda: self._build_data(pred), gen_key=pred
        )

    def _build_data(self, pred: str) -> CSRArena:
        pd = self.store.peek(pred)
        if pd is not None and pd.edges:
            return csr_from_edges(*_edges_columnar(pd.edges))
        return _build_csr({})

    def has_rows(self, pred: str) -> CSRArena:
        """Arena whose rows are every uid with *any* posting (edge or value)
        for the predicate — serves has(pred) and _predicate_ expansion.
        Realized as the data arena for uid preds; for value preds a CSR of
        degree-0 rows whose row set is what matters."""
        self.refresh()
        pd = self.store.peek(pred)
        if pd is None or not pd.values:
            return self.data(pred)
        return self._get_or_build(
            self._data, pred + "\x00has", lambda: self._build_has(pred),
            gen_key=pred,
        )

    def _build_has(self, pred: str) -> CSRArena:
        pd = self.store.peek(pred)
        universe = np.fromiter(pd.uids_with_data(), dtype=np.int64)
        src, dst = _edges_columnar(pd.edges)
        return csr_from_edges(src, dst, row_universe=universe)

    def reverse(self, pred: str) -> CSRArena:
        self.refresh()
        return self._get_or_build(
            self._reverse, pred, lambda: self._build_reverse(pred),
            gen_key=pred,
        )

    def _build_reverse(self, pred: str) -> CSRArena:
        pd = self.store.peek(pred)
        if pd is not None and pd.edges:
            src, dst = _edges_columnar(pd.edges)
            return csr_from_edges(dst, src)  # inverted: one lexsort, no
            # per-target python append loop (posting/index.go:152)
        return _build_csr({})

    def path_extent(self, preds: tuple) -> Tuple[int, int]:
        """(the largest uid the arenas of ``preds`` hold, the rows and edges
        they hold): what a search's dense tables would span against what
        the store already keeps — ``planner.path_route`` weighs them."""
        arenas = [self.reverse(a) if rev else self.data(a) for a, rev in preds]
        return (max((a.max_uid() for a in arenas), default=0),
                sum(a.n_rows + a.n_edges for a in arenas))

    def path_layout(self, preds: tuple) -> PathLayout:
        """The merged layout of ``preds`` — ((attr, reverse), ...) in the
        order listed — for ``ops/bfs.py``.  Valid while every arena it
        was built from is the cached one at the same epoch (a delta bumps
        the epoch, a rebuild replaces the object): ``refresh`` keeps it so
        through writes, and what is built here is what was never built,
        was evicted (``evict_for_oom``) or went with a dropped arena."""
        arenas = [self.reverse(a) if rev else self.data(a) for a, rev in preds]
        key = tuple((id(a), a.epoch) for a in arenas)
        with self._cache_lock:
            lay = self._path_layouts.get(preds)
        if lay is not None and lay.key == key:
            return lay
        with obs.stage(None, "h2d_ms"), _BUILD_LOCK:  # as CSRArena.lut
            with self._cache_lock:
                lay = self._path_layouts.get(preds)
            if lay is None or lay.key != key:
                lay = PathLayout(arenas)
                with self._cache_lock:
                    while len(self._path_layouts) >= 4:
                        self._path_layouts.pop(next(iter(self._path_layouts)))
                    self._path_layouts[preds] = lay
        return lay

    # -- secondary indexes ---------------------------------------------------

    def index(self, pred: str, tokenizer: str) -> IndexArena:
        self.refresh()
        return self._get_or_build(
            self._index,
            (pred, tokenizer),
            lambda: self._build_index(pred, tokenizer),
            gen_key=pred,
        )

    def _build_index(self, pred: str, tokenizer: str) -> IndexArena:
        tk = tokmod.get_tokenizer(tokenizer)
        pd = self.store.peek(pred)
        buckets: Dict[object, set] = {}
        if pd is not None:
            for (uid, _lang), val in pd.values.items():
                try:
                    # fulltext analyzes under the VALUE's language tag
                    # (per-language stemmer+stopwords, tok/fts.go:46-142)
                    toks = tokmod.tokens_for_value_lang(tk.name, val, _lang)
                except (ValueError, TypeError, OverflowError):
                    continue  # unindexable value (wrong type, inf, ...)
                for t in toks:
                    buckets.setdefault(t, set()).add(uid)
        tokens = sorted(buckets.keys())
        rows = {
            i: np.fromiter(buckets[t], dtype=np.int64, count=len(buckets[t]))
            for i, t in enumerate(tokens)
        }
        csr = _build_csr(rows)
        # implicit rows: row i of the CSR == tokens[i]
        csr2 = CSRArena(
            src=None,
            offsets=csr.offsets,
            dst=csr.dst,
            h_src=csr.h_src,
            h_offsets=csr.h_offsets,
            n_rows=csr.n_rows,
            n_edges=csr.n_edges,
        )
        return IndexArena(tokenizer=tokenizer, tokens=tokens, csr=csr2, lossy=tk.lossy)

    # -- numeric values ------------------------------------------------------

    def values(self, pred: str) -> ValueArena:
        self.refresh()
        return self._get_or_build(
            self._values, pred, lambda: self._build_values(pred),
            gen_key=pred,
        )

    def _build_values(self, pred: str) -> ValueArena:
        pd = self.store.peek(pred)
        pairs: Dict[int, float] = {}
        langless = wallclock = whole = True
        if pd is not None:
            # Deterministic lang choice: untagged value wins, else the
            # lexicographically first language (stable across ingest
            # order, unlike dict iteration).
            for (uid, lang) in sorted(pd.values.keys(), key=lambda k: (k[0], k[1] != "", k[1])):
                if lang:
                    langless = False
                if uid in pairs:
                    continue
                v = pd.values[(uid, lang)]
                x = numeric(v)
                if x is None:
                    whole = False
                else:
                    pairs[uid] = x
                    whole = whole and math.isfinite(x)   # no token holds ±inf, nan
                    off = getattr(v.value, "utcoffset", None)
                    if off is not None and off():
                        wallclock = False
        uids = np.array(sorted(pairs.keys()), dtype=np.int64)
        vals = np.array([pairs[u] for u in uids], dtype=np.float64)
        S = len(uids)
        Sb = ops.bucket(max(1, S))
        su = np.full(Sb, SENT, dtype=np.int32)
        su[:S] = uids.astype(np.int32)
        vv = np.full(Sb, np.nan, dtype=np.float32)
        vv[:S] = vals.astype(np.float32)
        # dense rank of the exact float64 value: device order-by sorts
        # by rank, immune to float32 rounding collisions
        rk = np.full(Sb, -1, dtype=np.int32)
        if S:
            rk[:S] = np.searchsorted(np.unique(vals), vals).astype(np.int32)
        a = ValueArena(
            src=jnp.asarray(su),
            vals=jnp.asarray(vv),
            ranks=jnp.asarray(rk),
            h_src=uids,
            h_vals=vals,
            h_ranks=rk[:S].copy(),
            n=S,
            langless=langless,
            wallclock=wallclock,
            whole=whole,
        )
        return a
