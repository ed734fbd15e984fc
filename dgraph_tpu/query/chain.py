"""Fused multi-level uid-chain execution: the engine's device fast path.

The per-level engine (`QueryEngine._expand`) pays one device dispatch and
one host round trip per (level × predicate) — the host↔device ping-pong
the reference pays as per-key badger lookups (worker/task.go:287-440) and
that VERDICT r2 flagged as the engine's bottleneck.  This module fuses a
maximal chain of uid expansions into ONE jitted program: the frontier
stays device-resident between levels (rows via a dense uid→row LUT,
expansion via ops.expand_inline_seg, dedup via sort), and only the final
per-level result matrices transfer to the host for filtering-free levels'
JSON encoding.

Eligibility (per level): uid expansion without count/facets/groupby/
var-funcs.  Round 4 extends fusion to the two most common decorations of
the reference's hot film queries (wiki/content/performance/index.md:32):

- **@filter** whose tree resolves WITHOUT the frontier (index funcs,
  uid literals, boolean combinations — not val()/count()/uid_in): the
  keep-set resolves once on the host, rides to the device, and applies
  as one member_mask inside the fused program.
- **orderasc/orderdesc + first/offset** on a ValueArena-backed attribute
  (numeric/datetime, no @lang, no var): per-parent segmented rank sort +
  windowing run inside the program (ops/order.py kernels), so "top-N by
  date per parent" truncates the device-resident frontier directly.

Anything else falls back to the per-level path, which remains the
general-correctness implementation.

Capacity planning is overflow-free: level-0 caps are exact (host degree
lookup on the root frontier); deeper caps use the arena's top-m chunk
degree cumsum (the sum of the m largest rows bounds any m-row frontier).
If a planned cap exceeds CHAIN_MAX_CAPC the chain is abandoned before
compile (memory guard), never mid-query.
"""

from __future__ import annotations

from functools import partial
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from dgraph_tpu import obs, ops
from dgraph_tpu.obs import ledger as _ledger
from dgraph_tpu.ops.sets import SENT
from dgraph_tpu.utils import planconfig
from dgraph_tpu.utils.failpoints import fail

# minimum estimated fan-out before fusing pays for itself (STATIC
# fallback; the default route decision is the calibrated cost compare in
# query/planner.py::chain_route, which prices one fused program against
# per-level execution from measured per-kernel rates).  This threshold
# governs when the planner is off (DGRAPH_TPU_PLANNER=0), the env knob
# is pinned, or a caller assigned engine.chain_threshold directly.
# Knob table + provenance: utils/planconfig.py.  bench21m records
# `chain_reject` with the estimate whenever a chain is declined, so
# either gate is auditable against real workloads.
CHAIN_THRESHOLD = planconfig.chain_threshold()
# abandon plans whose per-level output would exceed this many chunks.
# Full-mode chains transfer their matrices, so the cap is transfer-sized;
# light-mode (var-block) chains keep matrices on device and only ship
# frontiers — they can afford much larger device buffers (a 2^23-chunk
# level is 256MB of HBM but ~2MB on the wire).
CHAIN_MAX_CAPC = planconfig.chain_max_capc()
CHAIN_MAX_CAPC_LIGHT = planconfig.chain_max_capc_light()


def _filter_fusable(ft) -> bool:
    """Can this filter tree resolve to a uid keep-set WITHOUT the
    frontier?  val()/count()/uid_in leaves depend on per-candidate state;
    everything else (index funcs, has, regexp, geo, uid literals, and/or/
    not combinations) resolves globally once."""
    if ft.func is not None:
        f = ft.func
        return not (
            f.is_val_var
            or f.is_count
            or f.needs_vars
            # uid_in inspects each candidate's edges; checkpwd verifies
            # per-candidate values — both are frontier-dependent
            or f.name in ("uid_in", "checkpwd")
        )
    if ft.op == "not":
        # complementing needs the candidate universe (the engine's normal
        # path complements against the level's dest set)
        return False
    return all(_filter_fusable(c) for c in ft.children)


def _order_fusable(engine, sg) -> bool:
    """Per-parent order (+ first/offset windowing) fuses under EXACTLY the
    engine device-order preconditions (_device_order_perm): rank-sortable
    type, lang-less value arena, not a var; negative ``first`` ("last N")
    stays on the host path."""
    p = sg.params
    if p.after:
        return False  # 'after' interleaves with ordering; host path owns it
    if (p.first or 0) < 0 or (p.offset or 0) < 0:
        return False  # negative window = take-from-tail, host semantics
    if not (p.order_attr or p.first or p.offset):
        return True  # nothing to do
    if not p.order_attr:
        return True  # pure windowing in matrix order
    if p.order_is_var or p.order_langs:
        return False
    tid = engine.store.schema.type_of(p.order_attr)
    if tid not in type(engine)._DEVICE_ORDER_TIDS:
        return False
    va = engine.arenas.values(p.order_attr)
    return va.langless and va.n > 0


def eligible_level(engine, sg) -> bool:
    """Is this SubGraph a fusable uid expansion (plain, filtered and/or
    ordered — see module docstring)?"""
    p = sg.params
    if sg.attr in ("", "_uid_", "uid", "val", "math", "_predicate_"):
        return False
    if sg.func is not None:
        return False
    if sg.filter is not None and not _filter_fusable(sg.filter):
        return False
    if p.do_count or p.is_groupby or p.expand:
        return False
    if p.facets is not None or p.facets_filter is not None:
        return False
    if not _order_fusable(engine, sg):
        return False
    tid = engine.store.schema.type_of(sg.attr)
    from dgraph_tpu.models.types import TypeID

    pd = engine.store.peek(sg.attr)
    is_uid = tid == TypeID.UID or (pd is not None and bool(pd.edges))
    return bool(is_uid)


def collect_chain(engine, child) -> List:
    """Maximal fusable chain starting at ``child`` (itself eligible)."""
    levels = [child]
    node = child
    while True:
        nxt = [c for c in node.children if eligible_level(engine, c)]
        if len(nxt) != 1:
            break
        levels.append(nxt[0])
        node = nxt[0]
    return levels


@partial(jax.jit, static_argnames=("caps", "light", "carry"))
def _run_fused(
    root_vec, metas, ovs, luts, keeps, orders, caps, light=False, carry=False
):
    """One program for the whole chain, ONE packed output buffer.

    Round 4: levels expand through the INLINE-HEAD layout
    (ops.expand_inline_seg) — one 32B row gather serves metadata and the
    first INLINE targets; only degree>INLINE rows touch overflow chunks.
    Gather-index count per level is roughly half that of a layout whose
    rows hold metadata only (docs/ROOFLINE.md).

    root_vec: int32[B0] sorted-unique uids, SENT-padded.
    metas/ovs/luts: tuples of per-level inline-layout arrays.
    keeps: per level, a sorted-unique-padded keep-set (fused @filter) or
      None — applied as one member_mask over the level's output.
    orders: per level, None or (val_src, val_ranks) for the in-program
      per-parent rank sort (static spec rides in caps).
    caps: static tuple of (B_i, capc_i, cap_u_i, need_dest_i,
      decorated_i, order_static_i): B_i = row-vector length, capc_i =
      overflow-chunk capacity, cap_u_i bounds the deduped frontier fed to
      level i+1; order_static_i = None | (desc, offset, first, has_vals).
    light: var-block mode — only edge counts (and consumed frontiers)
      transfer.
    carry: segmented execution (PR 18) — append the FINAL level's deduped
      frontier as one extra trailing ``cap_u`` array so the next k-level
      segment can consume it as its root_vec without a host round trip
      (light mode drops ``nxt`` from the packed output when nothing on
      the host needs it; the carry still must thread).

    Packed layout per level:
      full undecorated: [inline.ravel | ov.ravel | ovseg | nxt | total]
      full decorated:   [flat | segf | nxt | total]   (slot-aligned)
      light:            [nxt?] [total]
    """
    from dgraph_tpu.ops.order import gather_ranks, segmented_sort_perm

    u = root_vec
    parts = []
    for i in range(len(metas)):
        B, capc, cap_u, need_dest, decorated, order_static = caps[i]
        lut = luts[i]
        rows = jnp.where(
            (u >= 0) & (u < lut.shape[0]) & (u != SENT),
            lut[jnp.clip(u, 0, lut.shape[0] - 1)],
            -1,
        )
        inline, ov, total, ovseg = ops.expand_inline_seg(
            metas[i], ovs[i], rows, capc
        )
        if decorated:
            # slot-aligned flat matrix + per-slot owners: inline slots'
            # owner is their row position, overflow slots' owner is ovseg
            iown = jnp.where(
                inline != SENT,
                jnp.arange(B, dtype=jnp.int32)[:, None],
                -1,
            ).reshape(-1)
            oown = jnp.where(
                ov != SENT,
                jnp.broadcast_to(ovseg[:, None], (capc, ops.CHUNK)),
                -1,
            ).reshape(-1)
            flat = jnp.concatenate([inline.reshape(-1), ov.reshape(-1)])
            segf = jnp.concatenate([iown, oown])
            if keeps[i] is not None:
                keep = ops.member_mask(flat, keeps[i])
                flat = jnp.where(keep, flat, SENT)
                segf = jnp.where(keep, segf, -1)
            if order_static is not None:
                desc, off, first, has_vals = order_static
                if has_vals:
                    vsrc, vranks = orders[i]
                    ranks = gather_ranks(vsrc, vranks, flat)
                    perm = segmented_sort_perm(segf, ranks, desc)
                else:
                    # pure windowing: group by parent, keep matrix order
                    # (inline-then-overflow == ascending per parent)
                    perm = segmented_sort_perm(
                        segf, jnp.zeros_like(flat), False
                    )
                flat = flat[perm]
                segf = segf[perm]
                n = flat.shape[0]
                iota = jnp.arange(n, dtype=jnp.int32)
                is_first = jnp.concatenate(
                    [jnp.ones((1,), bool), segf[1:] != segf[:-1]]
                )
                start = jax.lax.cummax(jnp.where(is_first, iota, 0))
                pos = iota - start
                w = (segf >= 0) & (pos >= off)
                if first:
                    w &= pos < off + first
                flat = jnp.where(w, flat, SENT)
                segf = jnp.where(w, segf, -1)
            nxt = ops.sort_unique(flat)[:cap_u]
            if not light:
                parts += [flat, segf, nxt, total.reshape(1)]
            elif need_dest:
                parts += [nxt, total.reshape(1)]
            else:
                parts += [total.reshape(1)]
        else:
            nxt = ops.sort_unique(
                jnp.concatenate([inline.reshape(-1), ov.reshape(-1)])
            )[:cap_u]
            if not light:
                parts += [
                    inline.reshape(-1), ov.reshape(-1), ovseg, nxt,
                    total.reshape(1),
                ]
            elif need_dest:
                parts += [nxt, total.reshape(1)]
            else:
                parts += [total.reshape(1)]
        u = nxt
    if carry:
        parts.append(u)
    return jnp.concatenate(parts)


def packed_inline_to_matrix(packed, B, capov, n_src):
    """Unpack the device's [inline.ravel | ov.ravel | ovseg] buffer and
    assemble the uid matrix (single owner of the packed layout — the
    engine's per-level path and the chain's conversion both route here
    via inline_to_matrix)."""
    inline = packed[: B * ops.INLINE].reshape(B, ops.INLINE)
    ovflat = packed[B * ops.INLINE : B * ops.INLINE + capov * ops.CHUNK]
    ovseg = packed[B * ops.INLINE + capov * ops.CHUNK :]
    return inline_to_matrix(inline, ovflat, ovseg, n_src)


def inline_to_matrix(inline, ovflat, ovseg, n_src):
    """Host assembly of the engine uid-matrix from an inline-head
    expansion: per row, inline heads (the FIRST min(deg, INLINE) targets,
    ascending) then overflow tails (also ascending) — concatenation
    preserves per-row ascending order.  Shared by the fused chain's
    full-mode conversion and the engine's per-level device path.

    inline: int32[B, INLINE]; ovflat: int32[capc*CHUNK]; ovseg: int32[capc]
    (owner row per overflow chunk, -1 pad); n_src: true row count (<= B).
    Returns (out_flat int64[total], seg_ptr int64[n_src+1])."""
    iv = inline[:n_src] != SENT
    ci = iv.sum(axis=1)
    ow = np.repeat(ovseg, ops.CHUNK)
    ovalid = (ovflat != SENT) & (ow >= 0) & (ow < n_src)
    ovals = ovflat[ovalid].astype(np.int64)
    ow = ow[ovalid]
    co = np.bincount(ow, minlength=n_src)[:n_src]
    counts = ci + co
    seg_ptr = np.zeros(n_src + 1, dtype=np.int64)
    np.cumsum(counts, out=seg_ptr[1:])
    out_flat = np.empty(int(seg_ptr[-1]), dtype=np.int64)
    within_i = np.cumsum(iv, axis=1) - iv
    dest_i = seg_ptr[:n_src, None] + within_i
    out_flat[dest_i[iv]] = inline[:n_src][iv].astype(np.int64)
    if len(ovals):
        idx = np.arange(len(ow))
        first = np.r_[True, ow[1:] != ow[:-1]]
        run_start = idx[first][np.cumsum(first) - 1]
        dest_o = seg_ptr[ow] + ci[ow] + (idx - run_start)
        out_flat[dest_o] = ovals
    return out_flat, seg_ptr


def try_run_chain(engine, child, src: np.ndarray, resolver=None) -> bool:
    """Attempt fused execution of the chain rooted at ``child`` with
    frontier ``src``.  On success, stages (out_flat, seg_ptr) on every
    chain level (chain_stash) and returns True; on ineligibility returns
    False and the caller uses the per-level path."""
    def reject(reason: str) -> bool:
        # surfaced in the per-query debug stats: silent non-engagement at
        # benchmark scale was VERDICT r4 weak #2 — the WHY must be visible
        rj = engine.stats["chain_reject"]
        if len(rj) < 8:
            rj.append(reason)
        return False

    from dgraph_tpu.utils import devguard

    with obs.stage(engine.stats, "plan_ms"):  # eligibility: host, every child
        if len(src) == 0 or not eligible_level(engine, child):
            return reject(
                "root level not fusable" if len(src) else "empty frontier"
            )
        if not devguard.get().allowed():
            # device fault domain latched sick: every fused route below is
            # a device program, so decline the whole chain up front — the
            # per-level path then rides the host mirrors until the
            # half-open probe re-admits the backend (the planner's cost
            # factor makes the same call when it is armed; this is the
            # static-path seam)
            return reject("device sick: per-level host execution (devguard)")
        src = np.asarray(src)
        if not np.all(src[1:] > src[:-1]):
            # expand_inline_seg's slot map requires an ascending-distinct
            # frontier; an order-by at the root permutes dest_uids, so
            # fusing would corrupt the matrices — fall back
            return reject("frontier not ascending-distinct")
    # MXU join tier (query/joinplan.py): light chains — including the
    # cyclic triangle shape (two legs + a globally-resolvable closing
    # @filter the gather chain below can't fuse) — may run as ONE
    # blocked-boolean-matmul program when the per-query cost model picks
    # generic join over pairwise expansion.  Declines fall through to
    # the gather paths below; every decision lands in
    # engine.stats["join_routes"].
    from dgraph_tpu.query.joinplan import try_mxu_route

    if try_mxu_route(engine, child, src, resolver):
        return True
    with obs.stage(engine.stats, "plan_ms"):
        levels = collect_chain(engine, child)
    if len(levels) < 2:
        return reject("chain shorter than 2 levels")
    # --- fused mesh multi-hop (dgraph_tpu/mesh) ---
    # Shard-eligible arenas truncate the staged chain below (their
    # levels then re-plan one hop at a time over the mesh, a host round
    # trip per level).  A light same-predicate undecorated chain on such
    # an arena instead runs as ONE compiled mesh program whose
    # cross-chip frontier exchange happens between scan levels on the
    # interconnect (mesh/programs.py) — the sharded twin of the
    # _try_chain_scan path.
    got = _try_mesh_chain(engine, levels, src, reject)
    if got is not None:
        return got
    # stage plan: arenas, the fan-out estimate, the route decision and the
    # fused filters' keep sets and order specs — all host work, before any
    # byte moves (the keep sets are put on the device after it, stage h2d)
    with obs.stage(engine.stats, "plan_ms"):
        arenas = []
        universe = 0
        for sg in levels:
            a = (
                engine.arenas.reverse(sg.attr)
                if sg.reverse
                else engine.arenas.data(sg.attr)
            )
            if a.n_edges == 0 or engine.arenas.use_mesh_for(a):
                break  # truncate the chain here; the tail runs per-level
            arenas.append(a)
            if a.n_rows:
                # any uid owning a row in some chain arena is ≤ this bound, so
                # LUT misses beyond it are exactly the row-less uids
                universe = max(universe, int(a.h_src[-1]))
        levels = levels[: len(arenas)]
        if len(levels) < 2:
            return reject("chain truncated below 2 levels (empty/mesh arena)")

        # --- capacity planning (overflow-free) ---
        rows0 = arenas[0].rows_for_uids_host(src)
        est_edges = int(arenas[0].degree_of_rows(rows0).sum())
        # whole-chain fan-out estimate: propagate by average out-degree so a
        # modest first level doesn't hide a multi-million-edge tail
        est_total = est_u = est_edges
        for a in arenas[1:]:
            est_u = min(est_u, a.n_rows)
            lvl = int(est_u * (a.n_edges / max(1, a.n_rows)))
            est_total += lvl
            est_u = lvl
        # route decision: calibrated cost compare by default, the static
        # threshold when the planner is off or the knob is pinned
        # (query/planner.py::chain_route; plan_dec is None on the static
        # path so the legacy reject message stays byte-identical)
        from dgraph_tpu.query import planner

        fuse, plan_dec = planner.chain_route(engine, est_total, len(levels))
        if not fuse:
            if plan_dec is not None:
                # the per-level verdict is final — record it now
                planner.record(engine.stats, plan_dec)
                return reject(
                    f"fan-out estimate {est_total}: calibrated model favors "
                    f"per-level ({plan_dec['est_other_us']}us fused vs "
                    f"{plan_dec['est_chosen_us']}us per-level)"
                )
            return reject(
                f"fan-out estimate {est_total} below threshold "
                f"{engine.chain_threshold}"
            )
        # a fuse=True decision is recorded only at the SUCCESS sites below:
        # a structural reject past this point (unresolvable filter, capacity
        # over cap) falls back to per-level execution, and the ring/metric
        # must not claim a fused chain that never ran (chain_reject already
        # explains those falls)
        # var blocks encode nothing, so result matrices never leave the device
        # (unless a level participates in @cascade, which prunes matrices)
        light = bool(
            getattr(engine, "_cur_block_internal", False)
            and not any(sg.params.cascade for sg in levels)
        )
        max_capc = CHAIN_MAX_CAPC_LIGHT if light else CHAIN_MAX_CAPC
        # pre-resolve fused filters to keep-sets + order specs (host, once).
        # Resolution happens only after the fan-out threshold check above, so
        # small queries never pay it.
        from dgraph_tpu.query.functions import QueryError

        keeps: List = []
        orders: List = []
        order_statics: List = []
        for sg in levels:
            keep = None
            if sg.filter is not None:
                if resolver is None:
                    return reject("filtered level without a resolver")
                try:
                    kset = _resolve_filter_global(engine, sg.filter, resolver)
                except QueryError:
                    return reject("filter keep-set resolution failed")
                keep = ops.pad_to(
                    np.asarray(kset), ops.bucket(max(1, len(kset)))
                )
            keeps.append(keep)
            p = sg.params
            if p.order_attr or p.first or p.offset:
                has_vals = bool(p.order_attr)
                order_statics.append(
                    (bool(p.order_desc), int(p.offset or 0), int(p.first or 0), has_vals)
                )
                if has_vals:
                    va = engine.arenas.values(p.order_attr)
                    orders.append((va.src, va.ranks))
                else:
                    orders.append(None)
            else:
                order_statics.append(None)
                orders.append(None)

    # --- lax.scan multi-hop fast path (ops/batch.py) ---
    # Light, same-arena, undecorated chains (the `v as x { friend {
    # friend } }` reachability shape) ride the donated-carry scan
    # driver: the frontier never leaves the device between hops and the
    # per-level packed-output staging disappears entirely.  Decorated or
    # mixed-arena chains keep the staged program below.
    undecorated = all(k is None for k in keeps) and all(
        o is None for o in order_statics
    )
    if (
        light
        and undecorated
        and all(a is arenas[0] for a in arenas)
        and _try_chain_scan(engine, levels, arenas[0], src, est_edges, universe)
    ):
        # the chain RAN: record the decision and hand it to the engine's
        # chain_ms bracket for the post-hoc mispredict check
        if plan_dec is not None:
            planner.record(engine.stats, plan_dec)
        engine._pending_chain_dec = plan_dec
        return True

    with obs.stage(engine.stats, "plan_ms"):
        caps: List[Tuple[int, int, int, bool, bool, Optional[tuple]]] = []
        B = ops.bucket(max(1, len(src)))  # row-vector length entering level i
        m = len(src)  # bound on the unique frontier entering each level
        for i, a in enumerate(arenas):
            if i == 0:
                capc = int(arenas[0].ov_chunk_degree_of_rows(rows0).sum())
            else:
                capc = int(_topm_ov_chunk_sum(a, m))
            capc = ops.bucket(max(1, capc))
            if capc > max_capc:
                return reject(
                    f"level {i} overflow capacity {capc} exceeds "
                    f"{'light' if light else 'full'} cap {max_capc}"
                )
            # unique next-frontier ≤ total output slots, ≤ the arena's distinct
            # target count (NOT the source-uid universe: row-less leaf uids
            # exceed it, and truncating them would corrupt light-mode dest
            # sets and var bindings)
            slots = B * ops.INLINE + capc * ops.CHUNK
            nd = max(1, a.n_distinct_dst())
            # clamp to the actual slot count: slots is no longer a power of
            # two, and a cap_u above it would make the device's [:cap_u]
            # slice SHORTER than the host parser reads (buffer misalignment)
            cap_u = min(ops.bucket(max(1, min(slots, nd))), slots)
            sg = levels[i]
            # does anything on the host consume this level's dest set?
            need_dest = (
                bool(sg.params.var)
                or len(sg.children) > 1
                or i == len(levels) - 1
            )
            decorated = keeps[i] is not None or order_statics[i] is not None
            caps.append((B, capc, cap_u, need_dest, decorated, order_statics[i]))
            m = min(slots, nd)
            B = cap_u

    h2d = 0
    if not undecorated:
        # stage h2d, part one: the keep sets of the fused filters
        with obs.stage(engine.stats, "h2d_ms"):
            for i, k in enumerate(keeps):
                if k is not None:
                    keeps[i] = jnp.asarray(k)
                    h2d += int(keeps[i].nbytes)

    # The closures below run on the device guard's worker thread: their
    # stages (h2d, dispatch, fetch) go into the shell's own stats dict
    # and they return plain data — the ledger's bytes are booked on the
    # caller's thread, after the guard has handed the result back.
    st = engine.stats

    def _layouts(lo, hi):
        # an arena builds and puts its inline layout / LUT on first use,
        # under its own h2d bracket (models/arena.py)
        metas, ovs, luts = [], [], []
        for a in arenas[lo:hi]:
            mp, ov = a.inline_layout()
            metas.append(mp)
            ovs.append(ov)
            luts.append(a.lut(universe))
        return tuple(metas), tuple(ovs), tuple(luts)

    def _put_root():
        with obs.stage(st, "h2d_ms"):
            return jnp.asarray(ops.pad_to(src, caps[0][0]))

    def _dispatch():
        # staging + dispatch + the ONE fetch, all inside the device
        # guard's watchdog bracket: an HBM OOM uploading a layout
        # classifies like a dispatch OOM, a wedged program times out
        # here instead of blocking the flush worker
        fail.point("device.chain")
        metas, ovs, luts = _layouts(0, len(arenas))
        root_vec = _put_root()
        with obs.stage(st, "dispatch_ms"):
            dev = _run_fused(
                root_vec, metas, ovs, luts,
                tuple(keeps), tuple(orders), tuple(caps),
                light=light,
            )
        with obs.stage(st, "fetch_ms"):
            # ONE device round trip for the whole chain
            return np.asarray(dev), int(root_vec.nbytes)

    # segmented dataflow (PR 18): k consecutive levels per dispatched
    # program, the final level's deduped frontier threaded (device-
    # resident, via the carry tail) as the next segment's root_vec, a
    # scheduler yield point between dispatches.  Per-level math and the
    # packed layout are untouched — the concatenated per-segment host
    # buffers ARE the monolithic packed buffer, so the conversion loop
    # below never learns segmentation happened.
    from dgraph_tpu.sched import segments

    seg_k = segments.plan(
        len(levels), max(1, est_edges // max(1, len(levels))), "chain"
    )

    def _dispatch_segment(root_vec, lo, hi, want_carry):
        fail.point("device.chain")
        metas, ovs, luts = _layouts(lo, hi)
        with obs.stage(st, "dispatch_ms"):
            return _run_fused(
                root_vec, metas, ovs, luts,
                tuple(keeps[lo:hi]), tuple(orders[lo:hi]),
                tuple(caps[lo:hi]), light=light, carry=want_carry,
            )

    try:
        if seg_k <= 0 or seg_k >= len(levels):
            packed, put = devguard.get().run("device.chain", _dispatch)
            h2d += put
            d2h = int(packed.nbytes)
        else:
            host_parts = []
            root_vec = _put_root()
            h2d += int(root_vec.nbytes)
            d2h = 0
            lo = 0
            while lo < len(levels):
                if lo:
                    segments.seam("chain")
                hi = min(lo + seg_k, len(levels))
                want_carry = hi < len(levels)
                dev = devguard.get().run(
                    "device.chain",
                    lambda rv=root_vec, lo=lo, hi=hi, wc=want_carry: (
                        _dispatch_segment(rv, lo, hi, wc)
                    ),
                )
                with obs.stage(st, "fetch_ms"):
                    # the whole buffer crosses, the carry tail included
                    host = np.asarray(dev)
                d2h += int(host.nbytes)
                if want_carry:
                    tail = caps[hi - 1][2]  # cap_u of the segment-final level
                    root_vec = dev[-tail:]  # stays device-resident
                    host = host[:-tail]
                host_parts.append(host)
                lo = hi
            with obs.stage(st, "convert_ms"):
                packed = np.concatenate(host_parts)
    except devguard.DeviceFaultError:
        return reject("device fault: chain fell back to per-level")
    led = _ledger.current()
    if led is not None:
        # what THIS request moved: keep sets and root vector up (a layout
        # built on first use booked itself), the packed buffer(s) down —
        # capacity-sized, whatever the answer holds
        led.bytes_h2d += h2d
        led.bytes_d2h += d2h

    # --- host conversion: packed buffer → engine results per level ---
    with obs.stage(st, "convert_ms"):
        src_list = np.asarray(src, dtype=np.int64)
        pos = 0
        for sg, (B, capc, cap_u, need_dest, decorated, _ostat) in zip(levels, caps):
            # the fused program already applied these; the engine must not
            # re-apply them to the stashed matrices
            sg.chain_filtered = decorated and sg.filter is not None
            sg.chain_ordered = decorated and _ostat is not None
            if light:
                dest = None
                if need_dest:
                    nxt = packed[pos : pos + cap_u]
                    pos += cap_u
                    dest = nxt[nxt != SENT].astype(np.int64)
                total = int(packed[pos])
                pos += 1
                # src_list None = "trusted": the previous level's dest stayed
                # on device, so the consumer skips the alignment check
                sg.chain_stash = ("light", dest, src_list, total)
                src_list = dest
                continue
            n_src = len(src_list)
            if decorated:
                flat_len = B * ops.INLINE + capc * ops.CHUNK
                flat = packed[pos : pos + flat_len]
                pos += flat_len
                owner = packed[pos : pos + flat_len]
                pos += flat_len
                valid = flat != SENT
                out_flat = flat[valid].astype(np.int64)
                owner = owner[valid]
                counts = np.bincount(owner, minlength=n_src)[:n_src]
                # per-parent order survives, but slots of one parent may be
                # interleaved with SENT gaps: regroup stably by owner
                grp = np.argsort(owner, kind="stable")
                out_flat = out_flat[grp]
            else:
                inline = packed[pos : pos + B * ops.INLINE].reshape(B, ops.INLINE)
                pos += B * ops.INLINE
                ovflat = packed[pos : pos + capc * ops.CHUNK]
                pos += capc * ops.CHUNK
                ovseg = packed[pos : pos + capc]
                pos += capc
                out_flat, seg_ptr0 = inline_to_matrix(inline, ovflat, ovseg, n_src)
            nxt = packed[pos : pos + cap_u]
            pos += cap_u
            pos += 1  # total (unused in full mode: lengths say it)
            if decorated:
                seg_ptr = np.zeros(n_src + 1, dtype=np.int64)
                np.cumsum(counts, out=seg_ptr[1:])
            else:
                seg_ptr = seg_ptr0
            sg.chain_stash = ("full", out_flat, seg_ptr, src_list)
            src_list = nxt[nxt != SENT].astype(np.int64)
    if plan_dec is not None:
        planner.record(engine.stats, plan_dec)
    engine._pending_chain_dec = plan_dec
    return True


def _resolve_filter_global(engine, ft, resolver) -> np.ndarray:
    """Resolve a fused filter tree to ONE sorted uid keep-set without the
    frontier (leaves and ops pre-checked by _filter_fusable; 'not' is
    excluded there — it needs the candidate universe)."""
    if ft.func is not None:
        return np.asarray(resolver.resolve(ft.func, None), dtype=np.int64)
    if ft.op == "and":
        # k-way fold routed host-or-device by size (query/joinplan.py):
        # candidates that came off-device no longer force k-1 host
        # np.intersect1d passes — above the gate ONE batched device
        # program intersects the whole stack
        from dgraph_tpu.query.joinplan import kway_intersect

        parts = [
            _resolve_filter_global(engine, c, resolver) for c in ft.children
        ]
        if not parts:
            return np.empty(0, np.int64)
        return kway_intersect(parts, stats=engine.stats)
    if ft.op == "or":
        parts = [_resolve_filter_global(engine, c, resolver) for c in ft.children]
        out = parts[0]
        for s in parts[1:]:
            out = np.union1d(out, s)
        return out
    # 'not' cannot complement without a universe; signal ineligible
    from dgraph_tpu.query.functions import QueryError

    raise QueryError("not-filter is not chain-fusable")


def _topm_deg_sum(arena, m: int) -> int:
    """Upper bound on the RAW degree sum of ANY m distinct rows (cumsum
    of descending-sorted degrees, cached) — the expand_ascending
    counterpart of _topm_ov_chunk_sum."""
    cs = getattr(arena, "_topm_deg", None)
    if cs is None:
        deg = np.sort(arena.h_offsets[1:] - arena.h_offsets[:-1])[::-1]
        cs = np.concatenate([[0], np.cumsum(deg)])
        arena._topm_deg = cs
    return int(cs[min(m, len(cs) - 1)])


def _try_chain_scan(engine, levels, arena, src, est_edges, universe) -> bool:
    """Run a light same-arena undecorated chain through the lax.scan
    multi-hop driver (ops.multi_hop): one scan program, frontier
    device-resident, carry donated.  Returns False when the uniform
    carry capacity (scan requires one shape for every hop) would blow
    the light memory budget — the staged per-level program then runs."""
    caps = [est_edges]
    m = min(est_edges, max(1, arena.n_distinct_dst()))
    for _ in levels[1:]:
        e = _topm_deg_sum(arena, m)
        caps.append(e)
        m = min(e, max(1, arena.n_distinct_dst()))
    cap = ops.bucket(max(max(caps), len(src), 1))
    if cap > CHAIN_MAX_CAPC_LIGHT * ops.CHUNK:
        return False
    from dgraph_tpu.utils import devguard

    try:
        arena.ensure_device()
        lut = arena.lut(universe)
        f = jnp.asarray(ops.pad_to(np.asarray(src, dtype=np.int64), cap))
        vis = jnp.full((cap,), SENT, dtype=jnp.int32)
        # the scan driver is guard-bracketed inside ops.multi_hop: a
        # wedged/sick/OOM dispatch surfaces here as DeviceFaultError
        fs, totals, _vis = ops.multi_hop(
            arena.offsets, arena.dst, f, vis, len(levels), cap, lut=lut
        )
        fs = np.asarray(fs)
        totals = np.asarray(totals)
    except devguard.DeviceFaultError:
        # hot failover: decline the scan — the staged path (or, with
        # the domain now sick, the per-level host path) takes over
        return False
    src_list = np.asarray(src, dtype=np.int64)
    for i, sg in enumerate(levels):
        sg.chain_filtered = False
        sg.chain_ordered = False
        dest = fs[i][fs[i] != SENT].astype(np.int64)
        sg.chain_stash = ("light", dest, src_list, int(totals[i]))
        src_list = dest
    return True


def _try_mesh_chain(engine, levels, src, reject):
    """Fused multi-hop over the mesh serving plane (dgraph_tpu/mesh)
    for light same-predicate undecorated chains on a SHARD-ELIGIBLE
    arena — the sharded twin of ``_try_chain_scan``.

    Tri-state return: ``True`` the chain ran and every level is
    stashed; ``False`` the planner's calibrated verdict was per-level
    (recorded + rejected, the caller stops fusing); ``None`` this chain
    is not mesh-fusable (decorated, mixed-predicate, capacity blown, or
    a chip fault hot-declined) — the caller falls through to the staged
    path, whose arena loop truncates at the mesh arena and re-plans
    those levels one hop at a time."""
    ex = engine.arenas.mesh_executor()
    if ex is None:
        return None
    first = levels[0]
    attr, rev = first.attr, bool(first.reverse)
    if any(
        sg.attr != attr or bool(sg.reverse) != rev for sg in levels
    ):
        return None
    if any(sg.filter is not None for sg in levels):
        return None
    if any(
        sg.params.cascade
        or sg.params.order_attr
        or sg.params.first
        or sg.params.offset
        for sg in levels
    ):
        return None
    # var blocks only (result matrices never leave the device), exactly
    # like the unsharded scan gate
    if not getattr(engine, "_cur_block_internal", False):
        return None
    a = engine.arenas.reverse(attr) if rev else engine.arenas.data(attr)
    if a.n_edges == 0 or not engine.arenas.use_mesh_for(a):
        return None
    if not ex.allowed():
        return None
    src = np.asarray(src)
    # capacity planning: one uniform carry shape for every hop, planned
    # from the worst level (the _try_chain_scan discipline)
    rows0 = a.rows_for_uids_host(src)
    est_edges = int(a.degree_of_rows(rows0).sum())
    caps = [est_edges]
    m = min(est_edges, max(1, a.n_distinct_dst()))
    for _ in levels[1:]:
        e = _topm_deg_sum(a, m)
        caps.append(e)
        m = min(e, max(1, a.n_distinct_dst()))
    cap = ops.bucket(max(max(caps), len(src), 1))
    if cap > CHAIN_MAX_CAPC_LIGHT * ops.CHUNK:
        return None
    # the calibrated fuse-vs-per-level verdict (same gate as the staged
    # path; est_total propagates by average out-degree, lines above)
    est_total = est_u = est_edges
    for _ in levels[1:]:
        est_u = min(est_u, a.n_rows)
        lvl = int(est_u * (a.n_edges / max(1, a.n_rows)))
        est_total += lvl
        est_u = lvl
    from dgraph_tpu.query import planner

    fuse, plan_dec = planner.chain_route(engine, est_total, len(levels))
    if not fuse:
        if plan_dec is not None:
            planner.record(engine.stats, plan_dec)
            return reject(
                f"fan-out estimate {est_total}: calibrated model favors "
                f"per-level ({plan_dec['est_other_us']}us fused vs "
                f"{plan_dec['est_chosen_us']}us per-level)"
            )
        return reject(
            f"fan-out estimate {est_total} below threshold "
            f"{engine.chain_threshold}"
        )
    from dgraph_tpu.utils import devguard

    try:
        fs, totals = ex.multi_hop(
            attr, rev, src, len(levels), cap, engine.stats
        )
    except devguard.DeviceFaultError:
        # chip loss / wedged collective: hot-decline the fused program —
        # the staged path truncates at this arena and its levels re-plan
        # unsharded (the PR 15 degrade path, now on the chain too)
        return None
    src_list = np.asarray(src, dtype=np.int64)
    for i, sg in enumerate(levels):
        sg.chain_filtered = False
        sg.chain_ordered = False
        dest = fs[i][fs[i] != SENT].astype(np.int64)
        sg.chain_stash = ("light", dest, src_list, int(totals[i]))
        src_list = dest
    if plan_dec is not None:
        planner.record(engine.stats, plan_dec)
    engine._pending_chain_dec = plan_dec
    return True


def _topm_ov_chunk_sum(arena, m: int) -> int:
    """Upper bound on the OVERFLOW-chunk sum of ANY m distinct rows: the
    cumsum of the descending-sorted per-row overflow chunk degrees
    (cached; inline-head layout stores the first INLINE targets in the
    metadata row, so only degree>INLINE rows have chunks)."""
    cs = getattr(arena, "_topm_ovdeg", None)
    if cs is None:
        cdeg = arena.ov_chunk_degree_of_rows(np.arange(arena.n_rows))
        # the rows that have chunks alone: the others add nothing to a sum,
        # and a write repairs this array (models/arena.py _topm_replace)
        cdeg = np.sort(cdeg[cdeg > 0])[::-1]
        cs = np.concatenate([[0], np.cumsum(cdeg)])
        arena._topm_ovdeg = cs
    return int(cs[min(m, len(cs) - 1)])
