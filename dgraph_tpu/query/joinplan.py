"""Worst-case-optimal join-route choice: MXU tiles vs pairwise expansion.

EmptyHeaded (PAPERS.md) picks, per query, between a generic-join plan
(attribute-at-a-time intersection — here the blocked boolean matmul tier
of ops/spgemm.py) and the classic pairwise expansion pipeline, using
relation statistics.  This module is that chooser for dgraph-tpu:

- **`try_mxu_route`** — the pattern entry: a light (var-block) uid chain
  whose levels are plain expansions or globally-resolvable ``@filter``
  levels (index funcs, ``uid(var)`` cycle-closing sets) may run as ONE
  fused mask program over predicate adjacency tiles
  (ops.run_mask_chain).  Triangle/cycle-shaped subqueries — two legs
  plus a closing keep-set — are exactly this shape.  The route is costed
  from arena degree statistics (``CSRArena.avg_degree``,
  ``degree_histogram``) against the gather tier's per-level dispatch +
  per-edge cost; tiles must fit ``DGRAPH_TPU_TILE_BUDGET`` and the mask
  must fit ``DGRAPH_TPU_MXU_MASK_MAX``.
- **`kway_intersect`** — the k-way set-intersection router: host
  ``np.intersect1d`` folds below the size gate
  (``DGRAPH_TPU_KWAY_DEVICE_MIN``), one batched device program
  (ops.intersect_stack) above it.  query/engine.py's ``@filter`` AND
  evaluation, query/chain.py's fused-filter resolution and the
  functions.py token/trigram folds all route through here.
- **decision recording** — every route choice lands in the per-request
  ``engine.stats["join_routes"]`` (the ``chain_reject`` explainability
  discipline) AND a process-level ring surfaced at ``/debug/store``
  plus ``dgraph_join_route_total`` / ``dgraph_kway_intersect_total``
  counters, so bench runs explain every routing decision.

Gate: ``DGRAPH_TPU_MXU_JOIN`` — ``0`` disables the tier entirely
(byte-identical legacy paths), ``1`` (default) arms it behind the cost
model, ``force`` skips the cost comparison (structural eligibility still
applies; tests and benches pin routes with it).
"""

from __future__ import annotations

import threading
from collections import deque
from typing import List, Optional

import numpy as np

from dgraph_tpu import obs, ops
from dgraph_tpu.ops.sets import SENT
from dgraph_tpu.utils import devguard, planconfig
from dgraph_tpu.utils.failpoints import fail
from dgraph_tpu.utils.metrics import JOIN_ROUTES, KWAY_ELEMENTS, KWAY_INTERSECTS

_EMPTY = np.empty(0, dtype=np.int64)

# Cost rates (µs) come from the planner's calibrated model
# (query/planner.py::rates — priors in utils/calibrate.py, refined by
# the startup micro-calibration pass and online from per-hop timings).
# The decision only has to be RIGHT about which side of a ~100× shape
# gap a query sits on, and every decision is recorded with both
# estimates so a mis-calibration is visible in the stats.  With the
# planner OFF (DGRAPH_TPU_PLANNER=0) the original PR-9 constants below
# drive the compare verbatim, so the kill switch restores the legacy
# route choice exactly.
_PR9_RATES = {
    "dispatch_us": 120.0,
    "tile_mac_us": 1.2e-4,
    "combine_us_per_mac": 2e-5,
    "tile_build_us_per_lane": 1.8e-4,
    "tile_build_amortize": 8.0,
}


def mxu_mode() -> str:
    """DGRAPH_TPU_MXU_JOIN: '0' off, '1' auto (default), 'force' always
    (structural eligibility permitting).  Read per call so serving tests
    flip it without rebooting."""
    return planconfig.mxu_mode()


def kway_device_min() -> int:
    """Total candidate elements below which a k-way intersection stays
    on the host fold (STATIC fallback — the planner prices the fold
    against the batched device program instead when it is armed)."""
    return planconfig.kway_device_min()


def mask_max_lanes() -> int:
    """Largest frontier-mask length the mxu chain route may allocate
    (float32 lanes; 1<<22 ≈ 16MB per mask)."""
    return planconfig.mask_max_lanes()


# -- decision recording -------------------------------------------------------

_ROUTE_LOCK = threading.Lock()
_RECENT: "deque[dict]" = deque(maxlen=16)
_COUNTS = {"mxu": 0, "pairwise": 0, "kway_device": 0, "kway_host": 0, "kway_probe": 0}


def record_route(stats: Optional[dict], decision: dict) -> None:
    """Log one join-route decision everywhere it must be visible: the
    per-request engine stats (bounded, like chain_reject), the process
    ring behind /debug/store, and the prometheus counter."""
    route = decision["route"]
    JOIN_ROUTES.add(route)
    with _ROUTE_LOCK:
        _RECENT.append(decision)
        _COUNTS[route] = _COUNTS.get(route, 0) + 1
    if stats is not None:
        rj = stats.setdefault("join_routes", [])
        if len(rj) < 8:
            rj.append(decision)


def debug_summary() -> dict:
    """Process-level routing summary for /debug/store."""
    with _ROUTE_LOCK:
        return {"counts": dict(_COUNTS), "recent": list(_RECENT)}


def _reset_for_tests() -> None:
    with _ROUTE_LOCK:
        _RECENT.clear()
        for k in list(_COUNTS):
            _COUNTS[k] = 0


# -- k-way set intersection ---------------------------------------------------


def kway_intersect(
    sets: List[np.ndarray], stats: Optional[dict] = None
) -> np.ndarray:
    """Intersection of k sorted-unique uid sets, size-routed: one
    batched device program above the gate, the numpy fold below it.
    Byte-identical to the ``np.intersect1d`` fold by construction
    (sorted-unique int64 either way)."""
    from dgraph_tpu.query import planner

    sets = [np.asarray(s, dtype=np.int64) for s in sets]
    if not sets:
        return _EMPTY
    if len(sets) == 1:
        return sets[0]
    if min(len(s) for s in sets) == 0:
        return _EMPTY
    total = sum(len(s) for s in sets)
    k = len(sets)
    mode = mxu_mode()
    dec = None
    if mode == "0" or k > 16:
        use_device = False
    elif mode == "force":
        use_device = True
    else:
        # calibrated fold-vs-device break-even; static size gate when
        # the planner is off or DGRAPH_TPU_KWAY_DEVICE_MIN is pinned
        use_device, dec = planner.kway_route(total, k)
        if use_device is None:
            use_device = total >= kway_device_min()
    if dec is not None:
        planner.record(stats, dec)
    k0 = stats.get("kway_ms", 0.0) if stats is not None else 0.0
    if use_device and not devguard.get().allowed():
        # sick device on the static/forced path (the armed planner's
        # cost factor already priced the device branch out above); the
        # reroute is disclosed like every other failover
        use_device = False
        dec = None  # the fold below is not a sample for either route
        devguard.count_failover("host", stats)
    if use_device:
        import jax.numpy as jnp

        def _dispatch():
            fail.point("device.spgemm")
            with obs.stage(stats if stats is not None else {}, "kway_ms"):
                L = ops.bucket(max(len(s) for s in sets))
                mat = np.stack([ops.pad_to(s, L) for s in sets])
                out = np.asarray(ops.intersect_stack(jnp.asarray(mat)))
                return out[out != SENT].astype(np.int64)

        try:
            res = devguard.get().run("device.spgemm", _dispatch)
        except devguard.DeviceFaultError:
            # hot failover: the numpy fold below is byte-identical by
            # construction (sorted-unique int64 either way).  The
            # decision is dropped, not closed — the aborted attempt +
            # host fold is not a rate sample for the device route
            res = None
            dec = None
            devguard.count_failover("host", stats)
        if res is not None:
            KWAY_INTERSECTS.add("device")
            KWAY_ELEMENTS.add("device", total)
            with _ROUTE_LOCK:
                _COUNTS["kway_device"] += 1
            if stats is not None:
                stats["kway_device"] = stats.get("kway_device", 0) + 1
                planner.note_outcome(dec, (stats["kway_ms"] - k0) * 1e3)
            return res
    with obs.stage(stats if stats is not None else {}, "kway_ms"):
        out = sets[0]
        for s in sets[1:]:
            out = np.intersect1d(out, s)
    KWAY_INTERSECTS.add("host")
    KWAY_ELEMENTS.add("host", total)
    with _ROUTE_LOCK:
        _COUNTS["kway_host"] += 1
    if stats is not None:
        stats["kway_host"] = stats.get("kway_host", 0) + 1
        planner.note_outcome(dec, (stats["kway_ms"] - k0) * 1e3)
    return out


def note_probe(stats: Optional[dict], elements: int) -> None:
    """Book a filter leaf answered by looking its candidates up
    (query/functions.py ``FuncResolver.probe``) as a k-way of route
    ``probe``; its elements are the candidate slots it read."""
    KWAY_INTERSECTS.add("probe")
    KWAY_ELEMENTS.add("probe", elements)
    with _ROUTE_LOCK:
        _COUNTS["kway_probe"] += 1
    if stats is not None:
        stats["kway_probe"] = stats.get("kway_probe", 0) + 1


def filter_leaf_global(fn) -> bool:
    """Does this filter Function resolve to a uid set WITHOUT the
    candidate frontier?  The chain fast path's fusability rule
    (query/chain.py::_filter_fusable) plus ``uid(var)`` — a bound uid
    variable is a global set (the cycle-closing shape), it only looks
    frontier-dependent."""
    if fn.name == "uid":
        return True
    return not (
        fn.is_val_var
        or fn.is_count
        or fn.needs_vars
        or fn.name in ("uid_in", "checkpwd")
    )


def _mxu_filter_ok(ft) -> bool:
    """Filter tree resolvable to one global keep-set (no 'not': it needs
    the candidate universe)."""
    if ft.func is not None:
        return filter_leaf_global(ft.func)
    if ft.op == "not":
        return False
    return all(_mxu_filter_ok(c) for c in ft.children)


# -- the mxu chain / triangle route -------------------------------------------


def _mxu_level_ok(engine, sg) -> bool:
    """A chain level the mask tier can run: plain uid expansion, with at
    most a globally-resolvable @filter; no ordering/windowing/facets
    (those need the uid matrix the mask representation deliberately
    drops)."""
    p = sg.params
    if sg.attr in ("", "_uid_", "uid", "val", "math", "_predicate_"):
        return False
    if sg.func is not None:
        return False
    if p.do_count or p.is_groupby or p.expand:
        return False
    if p.facets is not None or p.facets_filter is not None:
        return False
    if p.order_attr or p.first or p.offset or p.after:
        return False
    if sg.filter is not None and not _mxu_filter_ok(sg.filter):
        return False
    from dgraph_tpu.models.types import TypeID

    tid = engine.store.schema.type_of(sg.attr)
    pd = engine.store.peek(sg.attr)
    return tid == TypeID.UID or (pd is not None and bool(pd.edges))


def _collect_mxu_chain(engine, child) -> List:
    levels = [child]
    node = child
    while True:
        nxt = [c for c in node.children if _mxu_level_ok(engine, c)]
        if len(nxt) != 1:
            break
        levels.append(nxt[0])
        node = nxt[0]
    return levels


def _resolve_keep(engine, ft, resolver) -> np.ndarray:
    """Resolve a global filter tree to ONE sorted keep-set (leaves
    pre-checked by _mxu_filter_ok; AND folds route through the k-way
    intersection router)."""
    if ft.func is not None:
        return np.asarray(resolver.resolve(ft.func, None), dtype=np.int64)
    if ft.op == "and":
        parts = [_resolve_keep(engine, c, resolver) for c in ft.children]
        return kway_intersect(parts, stats=engine.stats)
    if ft.op == "or":
        out = _resolve_keep(engine, ft.children[0], resolver)
        for c in ft.children[1:]:
            out = np.union1d(out, _resolve_keep(engine, c, resolver))
        return out
    raise ValueError(f"filter op {ft.op!r} is not globally resolvable")


def try_mxu_route(engine, child, src: np.ndarray, resolver) -> bool:
    """Attempt the MXU generic-join route for the chain rooted at
    ``child``: per-query plan choice between densified-tile execution
    and pairwise expansion, costed from arena degree statistics and
    recorded in engine.stats.  On success, stages light-mode chain
    stashes on every level (the same contract query/chain.py's scan
    driver produces) and returns True."""
    mode = mxu_mode()
    if mode == "0" or len(src) == 0:
        return False
    if not devguard.get().allowed():
        # device fault domain latched sick: the tile tier IS device
        # programs — decline before any tile build, the pairwise path's
        # expansions hot-fail to host (utils/devguard.py)
        return False
    # light (var-block) chains only: masks carry SETS, not uid matrices,
    # so any level whose results must be encoded cannot ride this tier
    if not getattr(engine, "_cur_block_internal", False):
        return False
    if not _mxu_level_ok(engine, child):
        return False
    levels = _collect_mxu_chain(engine, child)
    if any(sg.params.cascade for sg in levels):
        return False
    arenas = []
    for sg in levels:
        a = (
            engine.arenas.reverse(sg.attr)
            if sg.reverse
            else engine.arenas.data(sg.attr)
        )
        if a.n_edges == 0 or engine.arenas.use_mesh_for(a):
            break
        arenas.append(a)
    levels = levels[: len(arenas)]
    if len(levels) < 2:
        return False

    # --- fan-out estimate (the chain tier's own threshold discipline) ---
    rows0 = arenas[0].rows_for_uids_host(np.asarray(src))
    est_edges = int(arenas[0].degree_of_rows(rows0).sum())
    est_total = est_u = est_edges
    for a in arenas[1:]:
        est_u = min(est_u, a.n_rows)
        lvl = int(est_u * a.avg_degree)
        est_total += lvl
        est_u = lvl
    # fan-out admission shares the chain tier's calibrated break-even
    # (static threshold when the planner is off / the knob is pinned)
    from dgraph_tpu.query import planner

    if mode != "force" and not planner.mxu_fanout_ok(
        engine, est_total, len(levels)
    ):
        return False

    # --- structural feasibility: tiles + mask sizes ---
    from dgraph_tpu.ops import spgemm

    t = spgemm.tile_size()
    blocks = []
    universe = 0
    for a in arenas:
        k, uni = a.tile_blocks()
        if spgemm.est_tile_bytes(k, t) > spgemm.tile_budget():
            record_route(engine.stats, _decision(
                "pairwise", levels, est_total, 0.0, 0.0,
                reason=f"tile budget exceeded for {a.n_edges}-edge arena",
            ))
            return False
        blocks.append(k)
        universe = max(universe, uni)
    m = spgemm.mask_lanes(universe, t)
    if m > mask_max_lanes():
        record_route(engine.stats, _decision(
            "pairwise", levels, est_total, 0.0, 0.0,
            reason=f"mask {m} lanes over DGRAPH_TPU_MXU_MASK_MAX",
        ))
        return False
    # structural (not cost-model) bound on the one-hot combine operand —
    # a dense [K, NB] f32 the block-column matmul materializes per level.
    # Checked even under 'force': the cost model normally prices these
    # shapes out, but force skips the comparison, and a transient several
    # times the tile budget must never reach the device.
    for k in blocks:
        if ops.bucket(max(1, k)) * (m // t) * 4 > spgemm.tile_budget():
            record_route(engine.stats, _decision(
                "pairwise", levels, est_total, 0.0, 0.0,
                reason="one-hot combine operand over tile budget",
            ))
            return False

    # --- cost model: gather tier vs one fused tile pass ---
    # Degree-histogram skew term: the gather tier plans capacity from
    # top-m degree sums, so a heavy-tailed predicate (celebrity rows
    # many log2 classes above the bulk) pads its buckets far past the
    # real work; dense tiles are immune — a row's degree only changes
    # which lanes of an already-materialized block are 1.
    pad = 1.2
    for a in arenas:
        h = a.degree_histogram()
        nz = np.nonzero(h)[0]
        if len(nz) and h.sum():
            mean_cls = float((nz * h[nz]).sum()) / float(h.sum())
            if nz[-1] >= mean_cls + 4:
                pad = 2.0
                break
    # rate table: the planner's live (calibrated, online-refined) rates
    # when it is armed; the PR-9 constants VERBATIM when it is off, so
    # DGRAPH_TPU_PLANNER=0 restores the original mxu-vs-pairwise compare
    # exactly (gather_edge_us is the old GATHER_US_PER_EDGE — the gather
    # tier's per-edge cost including host conversion)
    planner_on = planner.enabled()
    if planner_on:
        r = planner.rates()
        # the gather tier's per-edge cost is device gather PLUS the
        # per-level host conversion/dedup — the same decomposition
        # chain_route charges, and the model's split of PR-9's flat
        # GATHER_US_PER_EDGE=0.02 (pricing it at device_edge alone
        # would under-admit the MXU tier relative to both)
        gather_edge_us = r["device_edge_us"] + r["host_touch_us"]
    else:
        r = _PR9_RATES
        gather_edge_us = 0.02
    est_pairwise = len(levels) * r["dispatch_us"] + est_total * (
        gather_edge_us * pad
    )
    nbm = m // t
    per_pass = sum(
        k * t * t * r["tile_mac_us"] + k * nbm * t * r["combine_us_per_mac"]
        for k in blocks
    )
    build = sum(
        k * t * t * r["tile_build_us_per_lane"]
        for a, k in zip(arenas, blocks)
        if a._tiles is None
    )
    est_mxu = r["dispatch_us"] + per_pass + build / r["tile_build_amortize"]
    if mode != "force" and est_mxu >= est_pairwise:
        record_route(engine.stats, _decision(
            "pairwise", levels, est_total, est_pairwise, est_mxu,
            reason="cost model favors gather tier",
        ))
        return False

    # --- resolve fused keep-sets (host, once) ---
    from dgraph_tpu.query.functions import QueryError

    keeps_np: List[Optional[np.ndarray]] = []
    try:
        for sg in levels:
            keeps_np.append(
                _resolve_keep(engine, sg.filter, resolver)
                if sg.filter is not None
                else None
            )
    except (QueryError, ValueError):
        record_route(engine.stats, _decision(
            "pairwise", levels, est_total, est_pairwise, est_mxu,
            reason="keep-set resolution failed",
        ))
        return False

    # --- build tiles (cached per arena) BEFORE recording the route: a
    # build can still refuse (a concurrent delta re-counted the blocks
    # over budget), and one query must log exactly ONE decision ---
    import jax.numpy as jnp

    with obs.stage(engine.stats, "tile_build_ms"):
        tiles = [a.tiles() for a in arenas]
    if any(pt is None for pt in tiles):
        record_route(engine.stats, _decision(
            "pairwise", levels, est_total, est_pairwise, est_mxu,
            reason="tile build refused (budget)",
        ))
        return False
    record_route(engine.stats, _decision(
        "mxu", levels, est_total, est_pairwise, est_mxu,
        reason="generic join over densified tiles",
    ))
    # twin entry in the unified planner ring (kind=mxu) so the post-hoc
    # mispredict check covers the tile tier too — only while the planner
    # is armed (=0 must leave /debug/planner counts and the mispredict
    # metric untouched; the join ring above keeps full PR-9 visibility)
    pdec = None
    if planner_on:
        pdec = {
            "kind": "mxu", "route": "mxu", "units": int(est_total),
            "est_chosen_us": round(float(est_mxu), 1),
            "est_other_us": round(float(est_pairwise), 1),
            "reason": "generic join over densified tiles",
        }
        planner.record(engine.stats, pdec)
    mxu_ms0 = engine.stats.get("mxu_join_ms", 0.0)

    sp = obs.current_span()
    hs = sp.child("hop") if sp is not None else obs.NOOP

    src32 = np.asarray(src, dtype=np.int64)

    def _dispatch():
        # mask staging + the whole tile-program chain + the fetch, all
        # inside the device guard's watchdog bracket
        fail.point("device.spgemm")
        x0 = spgemm.uids_to_mask(
            jnp.asarray(ops.pad_to(src32, ops.bucket(max(1, len(src32))))), m
        )
        keep_masks = []
        for ks in keeps_np:
            if ks is None:
                keep_masks.append(None)
            else:
                keep_masks.append(spgemm.uids_to_mask(
                    jnp.asarray(
                        ops.pad_to(ks, ops.bucket(max(1, len(ks))))
                    ),
                    m,
                ))
        masks_dev, totals_dev = spgemm.run_mask_chain(
            tuple((pt.bi, pt.bj, pt.tiles) for pt in tiles),
            tuple(keep_masks),
            tuple(pt.degs for pt in tiles),
            x0,
        )
        if sp is not None:
            hs.set_attr("route", "mxu")
            hs.set_attr("levels", len(levels))
            hs.set_attr("preds", [sg.attr for sg in levels])
            hs.set_attr("mask_lanes", int(m))
            hs.set_attr("tiles", [int(pt.n_tiles) for pt in tiles])
            hs.set_attr(
                "device_sync_ms",
                round(obs.block_ready_ms((masks_dev, totals_dev)), 3),
            )
        return np.asarray(masks_dev), np.asarray(totals_dev)

    # segmented dataflow (PR 18): k levels of the mask chain per
    # dispatched program, the post-filter frontier mask threaded
    # (device-resident) between segments, a scheduler yield point at
    # every seam.  Per-level math is untouched: the stacked per-segment
    # (masks, totals) concatenate to the monolithic result.
    from dgraph_tpu.sched import segments

    seg_k = segments.plan(
        len(levels), max(1, est_total // max(1, len(levels))), "mask_chain"
    )
    tile_ops = tuple((pt.bi, pt.bj, pt.tiles) for pt in tiles)
    degvs = tuple(pt.degs for pt in tiles)
    keep_masks: List = []

    def _dispatch_segment(x, lo, hi):
        fail.point("device.spgemm")
        if lo == 0:
            # first segment stages the root + keep masks (device-
            # resident across every later segment)
            x = spgemm.uids_to_mask(
                jnp.asarray(
                    ops.pad_to(src32, ops.bucket(max(1, len(src32))))
                ),
                m,
            )
            for ks in keeps_np:
                keep_masks.append(
                    None
                    if ks is None
                    else spgemm.uids_to_mask(
                        jnp.asarray(
                            ops.pad_to(ks, ops.bucket(max(1, len(ks))))
                        ),
                        m,
                    )
                )
        md, td = spgemm.run_mask_chain(
            tile_ops[lo:hi], tuple(keep_masks[lo:hi]), degvs[lo:hi], x
        )
        nxt = md[-1] if hi < len(levels) else None
        # the fetch stays inside the watchdog bracket, like _dispatch
        return np.asarray(md), np.asarray(td), nxt

    with hs, obs.stage(engine.stats, "mxu_join_ms"):
        try:
            if seg_k <= 0 or seg_k >= len(levels):
                masks, totals = devguard.get().run(
                    "device.spgemm", _dispatch
                )
            else:
                mask_parts, tot_parts = [], []
                x = None
                lo = 0
                while lo < len(levels):
                    if lo:
                        segments.seam("mask_chain")
                    hi = min(lo + seg_k, len(levels))
                    mseg, tseg, x = devguard.get().run(
                        "device.spgemm",
                        lambda x=x, lo=lo, hi=hi: _dispatch_segment(
                            x, lo, hi
                        ),
                    )
                    mask_parts.append(mseg)
                    tot_parts.append(tseg)
                    lo = hi
                    if lo < len(levels) and not mseg[-1].any():
                        # drained frontier mask: every remaining level
                        # is zero masks / zero totals — synthesize them
                        # and stop dispatching
                        segments.early_exit("mask_chain")
                        r = len(levels) - lo
                        mask_parts.append(
                            np.zeros((r,) + mseg.shape[1:], mseg.dtype)
                        )
                        tot_parts.append(np.zeros((r,), tseg.dtype))
                        break
                masks = np.concatenate(mask_parts)
                totals = np.concatenate(tot_parts)
                if sp is not None:
                    hs.set_attr("route", "mxu")
                    hs.set_attr("levels", len(levels))
                    hs.set_attr("preds", [sg.attr for sg in levels])
                    hs.set_attr("mask_lanes", int(m))
                    hs.set_attr("segments", -(-len(levels) // seg_k))
        except devguard.DeviceFaultError:
            # hot failover: decline the tile tier — the pairwise gather
            # chain (host-routed while the domain is sick) takes over;
            # the recorded mxu decision stands, the reroute is counted
            # (no note_outcome: a failed dispatch is not a rate sample)
            devguard.count_failover("host", engine.stats)
            return False
    planner.note_outcome(
        pdec, (engine.stats.get("mxu_join_ms", 0.0) - mxu_ms0) * 1e3
    )

    # --- stage light-mode stashes (the chain consumer's contract) ---
    src_list: Optional[np.ndarray] = src32
    for i, sg in enumerate(levels):
        need_dest = (
            bool(sg.params.var)
            or len(sg.children) > 1
            or i == len(levels) - 1
        )
        dest = spgemm.mask_to_uids(masks[i]) if need_dest else None
        sg.chain_filtered = sg.filter is not None
        sg.chain_ordered = False
        sg.chain_stash = ("light", dest, src_list, int(totals[i]))
        src_list = dest
    return True


def _decision(
    route: str, levels, est_total: int, est_pairwise: float,
    est_mxu: float, reason: str,
) -> dict:
    shape = "triangle" if (
        len(levels) == 2 and levels[-1].filter is not None
    ) else "chain"
    return {
        "route": route,
        "shape": shape,
        "levels": len(levels),
        "preds": [sg.attr for sg in levels],
        "est_edges": int(est_total),
        "est_pairwise_us": round(float(est_pairwise), 1),
        "est_mxu_us": round(float(est_mxu), 1),
        "reason": reason,
    }
