"""Batched, fused frontier execution: one device program per hop.

The per-op engine path (ops/sets.py consumed one `jax.jit` dispatch at a
time from query/engine.py) pays one device round trip per *set
operation*: a 2-hop traversal with multi-predicate filters dispatches
O(predicates × levels × queries) programs.  EmptyHeaded (PAPERS.md)
compiles whole multi-way join plans into one fused kernel instead of
composing pairwise ops; RedisGraph/GraphBLAS batches traversal into
single matrix-style operations.  This module is that shape for the
dgraph-tpu ops layer:

- **Batched set ops** (`intersect_batch`, `union_many_batch`,
  `difference_batch`, `member_mask_batch`, `sort_unique_batch`): the
  ``[B, L]`` vmapped variants of the scalar sorted-unique-padded kernels
  — one dispatch for a whole batch of frontiers instead of B.
- **`expand_ascending`**: dense CSR expansion for ASCENDING-DISTINCT row
  vectors via the telescoped slot map (one scatter + one prefix sum —
  the scalar analog of ops.expand_inline_seg's chunk map).  Output is
  densely packed (valid prefix, SENT tail), which makes the follow-up
  dedup sort as narrow as it can be.
- **`expand_filter_compact`**: gather → k-way merge → multi-predicate
  intersect → compact in ONE jitted program (plus its vmapped batch
  form).  The per-op path for the same hop is ≥ (2 + n_predicates)
  dispatches.
- **`multi_hop`**: a `lax.scan` multi-hop driver that keeps the
  frontier (and optionally the visited set) device-resident across
  hops, with donated carry buffers — no host round trip between levels.

Layout contract: everything here speaks the sorted-unique-padded dialect
of ops/sets.py (see docs/sets-contract.md).  The batch axis is always
leading: a ``[B, L]`` matrix is B independent uid sets, padded with SENT
to the shared capacity L.
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from dgraph_tpu.ops.sets import (
    SENT,
    frontier_rows,
    member_mask,
    sort_desc_free,
    sort_unique,
)


# -- batched set ops ---------------------------------------------------------
# vmapped at module level so the jit cache holds ONE program per (B, L)
# bucket, not one per call site.

intersect_batch = jax.jit(jax.vmap(lambda a, b: sort_desc_free(
    jnp.where(member_mask(a, b), a, SENT))))
"""[B, L] ∩ [B, L] rowwise (result shaped like ``a``): one dispatch."""

difference_batch = jax.jit(jax.vmap(lambda a, b: sort_desc_free(
    jnp.where((~member_mask(a, b)) & (a != SENT), a, SENT))))
"""[B, L] \\ [B, L] rowwise: one dispatch."""

union_many_batch = jax.jit(
    jax.vmap(lambda mat: sort_unique(mat.reshape(-1)))
)
"""[B, K, L] → [B, K*L]: K-way union per batch row, one dispatch."""

member_mask_batch = jax.jit(jax.vmap(member_mask))
"""[B, L] probed against [B, Ls] rowwise: one dispatch."""

sort_unique_batch = jax.jit(jax.vmap(sort_unique))
"""Rowwise sort + dedup of a [B, L] batch: one dispatch."""


# -- dense ascending-row expansion ------------------------------------------


@partial(jax.jit, static_argnames=("cap",))
def expand_ascending(
    offsets: jnp.ndarray, dst: jnp.ndarray, rows: jnp.ndarray, cap: int
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """CSR expansion of an ASCENDING-DISTINCT row vector (-1 skips
    anywhere) into a densely packed target vector.

    The slot→edge map telescopes exactly like ops.expand_inline_seg's
    chunk map: scatter ``o0_j - prev_end_j`` at each productive row's
    output start, prefix-sum, add the slot iota — one scatter + one
    O(cap) prefix sum, then a single dst gather per slot.  (Ascending
    rows make the productive ends monotone, which is what lets cummax
    stand in for "previous productive row's end".)

    Returns (out int32[cap] — valid prefix then SENT tail — and the
    valid count).  Unlike expand_csr the output carries no per-slot
    owner; callers that need the uid matrix keep expand_csr /
    expand_inline_seg.
    """
    valid = rows >= 0
    r = jnp.where(valid, rows, 0)
    o0 = offsets[r]
    deg = jnp.where(valid, offsets[r + 1] - o0, 0)
    o0 = jnp.where(valid, o0, 0)
    cum = jnp.cumsum(deg)
    out_start = cum - deg
    productive = deg > 0
    end = jnp.where(productive, o0 + deg, 0)
    pe = jnp.concatenate(
        [jnp.zeros((1,), end.dtype), jax.lax.cummax(end)[:-1]]
    )
    slot = jnp.where(productive, out_start, cap)
    dvec = (
        jnp.zeros((cap,), jnp.int32)
        .at[slot]
        .set(jnp.where(productive, o0 - pe, 0).astype(jnp.int32), mode="drop")
    )
    i = jnp.arange(cap, dtype=jnp.int32)
    edge = jnp.cumsum(dvec) + i
    ok = i < cum[-1]
    out = jnp.where(ok, dst[jnp.clip(edge, 0, dst.shape[0] - 1)], SENT)
    return out, cum[-1].astype(jnp.int32)


@partial(jax.jit, static_argnames=("cap", "cap_out"))
def expand_filter_compact(
    offsets: jnp.ndarray,
    dst: jnp.ndarray,
    rows: jnp.ndarray,
    cap: int,
    keeps: Tuple[jnp.ndarray, ...] = (),
    cap_out: Optional[int] = None,
):
    """One fused program for a whole hop: CSR gather → k-way merge →
    multi-predicate intersect → compact.

    ``keeps`` is a tuple of sorted-unique-padded uid keep-sets (one per
    fused filter predicate), applied as member_mask's before the merge
    so masked lanes never survive into the dedup sort.  The per-op
    equivalent is (2 + len(keeps)) separate dispatches: expand, one
    intersect per keep, then sort_unique.

    Returns (frontier int32[cap_out or cap] sorted-unique-padded,
    total int32 — raw edge count BEFORE filtering, the traversal work).
    """
    out, total = expand_ascending(offsets, dst, rows, cap)
    for k in keeps:
        out = jnp.where(member_mask(out, k), out, SENT)
    u = sort_unique(out)
    if cap_out is not None:
        u = u[:cap_out]
    return u, total


def expand_filter_compact_batch(
    offsets: jnp.ndarray,
    dst: jnp.ndarray,
    rows: jnp.ndarray,
    cap: int,
    keeps: Tuple[jnp.ndarray, ...] = (),
    cap_out: Optional[int] = None,
):
    """[B, R] batched expand_filter_compact — ONE dispatch for the whole
    batch of frontiers (keeps broadcast across the batch)."""
    return _effc_batch(offsets, dst, rows, cap, keeps, cap_out)


@partial(jax.jit, static_argnames=("cap", "cap_out"))
def _effc_batch(offsets, dst, rows, cap, keeps, cap_out):
    def one(r):
        return expand_filter_compact(offsets, dst, r, cap, keeps, cap_out)

    return jax.vmap(one)(rows)


# -- multi-hop scan driver ---------------------------------------------------


def multi_hop(
    offsets: jnp.ndarray,
    dst: jnp.ndarray,
    frontier: jnp.ndarray,
    visited: jnp.ndarray,
    n_hops: int,
    cap: int,
    track_visited: bool = False,
    lut: Optional[jnp.ndarray] = None,
):
    """lax.scan multi-hop driver: the frontier stays device-resident
    across hops; the (frontier, visited) carry buffers are DONATED so
    XLA reuses them in place instead of allocating per hop.

    Every hop shares one capacity ``cap`` (both the expansion width and
    the frontier width — lax.scan requires a uniform carry shape), so
    callers plan cap from the worst level.  Rows are frontier uids
    themselves (dense arenas: row i == uid i) unless ``lut`` maps
    uid → arena row (-1 for rowless uids, arena.lut layout).

    With ``track_visited`` the walk is level-synchronous BFS: each hop's
    output drops already-visited uids (the reachMap dedup of
    query/recurse.go:110-145) and joins the visited set.

    frontier: int32[cap] sorted-unique-padded; visited: int32[cap]
    (ignored unless track_visited).  Returns (frontiers int32[n_hops,
    cap] — the post-dedup frontier ENTERING hop i+1 —, edge counts
    int32[n_hops], final visited int32[cap]).
    """
    from dgraph_tpu import obs
    from dgraph_tpu.utils import devguard
    from dgraph_tpu.utils.failpoints import fail
    from dgraph_tpu.utils.jaxdiag import expected_unusable_donation

    # sampled requests record the whole fused scan as ONE span (it IS
    # one device program): hop count + capacity say what the chain/
    # recurse planner committed to, device_sync_ms splits compute from
    # the caller's later fetch.  Unsampled: no span, dispatch stays
    # fully async.
    sp = obs.current_span()
    ms = obs.NOOP if sp is None else sp.child("multi_hop")

    # one [cap]-shaped output means only ONE of the two donated carries
    # can alias; the visited buffer's fallback is contract-checked
    # (analysis/programs.py batch.multi_hop, donate_unused_ok) and
    # counted (dgraph_donation_fallback_total) instead of blanket-hidden
    def _dispatch():
        fail.point("device.multi_hop")
        with expected_unusable_donation("ops.batch.multi_hop"), ms:
            res = _multi_hop_jit(
                offsets, dst, frontier, visited, n_hops, cap,
                track_visited, lut,
            )
            if sp is not None:
                ms.set_attr("hops", int(n_hops))
                ms.set_attr("cap", int(cap))
                ms.set_attr("track_visited", bool(track_visited))
                ms.set_attr(
                    "device_sync_ms", round(obs.block_ready_ms(res), 3)
                )
            elif devguard.enabled():
                # under the guard the SYNC POINT must sit inside the
                # watchdog bracket — a wedged scan times out here on the
                # guard's worker instead of at the caller's later fetch
                obs.block_ready_ms(res)
            return res

    # devguard.run is a passthrough under DGRAPH_TPU_DEVGUARD=0 (fully
    # async dispatch, faults propagate raw — the legacy path); callers
    # (query/chain.py, query/recurse.py) catch DeviceFaultError and
    # fall back to per-level execution
    from dgraph_tpu.sched import segments

    k = segments.plan(n_hops, cap, "multi_hop")
    if k <= 0 or k >= n_hops:
        return devguard.get().run("device.multi_hop", _dispatch)

    # segmented dataflow (PR 18): k hops per dispatched program, the
    # donated (frontier, visited) carry threaded between segments, a
    # scheduler yield point (cancellation / preemption) at every seam.
    # Per-hop math is untouched — the stacked per-segment outputs
    # concatenate to the monolithic result byte-identically.  The
    # program cache stays bounded: fixed k compiles at most two
    # executables (the k-hop body and one remainder).
    def _dispatch_segment(f, vis, hops):
        fail.point("device.multi_hop")
        seg_ms = obs.NOOP if sp is None else sp.child("multi_hop_seg")
        with expected_unusable_donation("ops.batch.multi_hop"), seg_ms:
            res = _multi_hop_jit(
                offsets, dst, f, vis, hops, cap, track_visited, lut
            )
            if sp is not None:
                seg_ms.set_attr("hops", int(hops))
                seg_ms.set_attr("cap", int(cap))
                seg_ms.set_attr(
                    "device_sync_ms", round(obs.block_ready_ms(res), 3)
                )
            elif devguard.enabled():
                obs.block_ready_ms(res)
            return res

    fs_parts, tot_parts = [], []
    f, vis = frontier, visited
    done = 0
    while done < n_hops:
        if done:
            segments.seam("multi_hop")
        hops = min(k, n_hops - done)
        seg_fs, seg_tot, vis = devguard.get().run(
            "device.multi_hop",
            lambda f=f, vis=vis, hops=hops: _dispatch_segment(f, vis, hops),
        )
        fs_parts.append(seg_fs)
        tot_parts.append(seg_tot)
        done += hops
        if done < n_hops:
            f = seg_fs[-1]
            if bool(f[0] == SENT):
                # drained frontier: every remaining hop would expand
                # nothing — synthesize the all-SENT rows / zero totals
                # the monolithic scan would have produced and stop
                # dispatching (the carry-accumulation early exit)
                segments.early_exit("multi_hop")
                r = n_hops - done
                fs_parts.append(jnp.full((r, cap), SENT, seg_fs.dtype))
                tot_parts.append(jnp.zeros((r,), seg_tot.dtype))
                break
    return jnp.concatenate(fs_parts), jnp.concatenate(tot_parts), vis


@partial(
    jax.jit,
    static_argnames=("n_hops", "cap", "track_visited"),
    donate_argnums=(2, 3),
)
def _multi_hop_jit(
    offsets, dst, frontier, visited, n_hops, cap, track_visited, lut
):
    def body(carry, _):
        f, vis = carry
        if lut is None:
            rows = frontier_rows(f)
        else:
            rows = jnp.where(
                (f >= 0) & (f < lut.shape[0]) & (f != SENT),
                lut[jnp.clip(f, 0, lut.shape[0] - 1)],
                -1,
            )
        out, total = expand_ascending(offsets, dst, rows, cap)
        nxt = sort_unique(out)
        if track_visited:
            nxt = sort_desc_free(
                jnp.where(member_mask(nxt, vis), SENT, nxt)
            )
            vis = sort_unique(jnp.concatenate([vis, nxt]))[:cap]
        return (nxt, vis), (nxt, total)

    (f, vis), (fs, totals) = jax.lax.scan(
        body, (frontier, visited), None, length=n_hops
    )
    return fs, totals, vis
