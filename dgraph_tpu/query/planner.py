"""Measured-cost adaptive planner: ONE calibrated model for every route.

The engine chooses between host and device at every level, and above
the level between the per-level loop and the fused routes (fused chain,
chain-scan, fused recurse, MXU tile join), plus the host-vs-device k-way
intersection.  A static threshold per gate costs real queries: BENCH21M
showed ``chain_reject: "fan-out estimate 168342 below threshold
262144"`` keeping the chain scan out of hot 3-hop queries it measurably
wins.  Banyan (PAPERS.md) frames graph serving as scoped dataflow with
per-scope scheduling choices; EmptyHeaded's cost-based plan choice
already drives PR 9's join tier.  This module generalizes that: every
route decision prices its candidates from MEASURED per-kernel
throughput and picks the cheaper one.

Structure:

- **Rates** come from ``utils/calibrate.py``: shipped priors → persisted
  calibration file → startup micro-calibration (``boot(measure=True)``),
  then refined ONLINE from the per-hop stage timings the engine already
  records — ``note_outcome`` folds each decision's actual latency back
  into an EWMA of the chosen route's per-unit rate.
- **Decisions** (``chain_route`` / ``expand_route`` / ``kway_route`` /
  ``merge_gate``) replace the static threshold compares in
  ``query/chain.py``, ``query/joinplan.py``, ``query/engine.py`` and the
  resolver path.  Each returns the chosen route WITH both cost
  estimates, recorded in the per-request ``engine.stats["planner"]``
  (the ``chain_reject`` explainability discipline), a process ring
  behind ``/debug/planner``, and
  ``dgraph_planner_decisions_total{kind,route}``.
- **Post-hoc mispredict check**: when the chosen route's measured
  latency lands above the REJECTED route's estimate (with margin) — or
  blows past its own estimate entirely — the decision is flagged and
  ``dgraph_planner_mispredict_total{kind}`` increments.  A rising
  mispredict rate is the operator's signal to re-run calibration.
- **Cohort feedback** (``CohortController``): the scheduler's cohort
  size and flush deadline adapt to measured queue-wait and cohort
  occupancy inside hard bounds, instead of fixed ``DGRAPH_TPU_SCHED``
  knobs.

Override discipline: ``DGRAPH_TPU_PLANNER=0`` restores every static
threshold byte-identically, and ANY explicitly pinned knob (env value
or runtime assignment like ``engine.chain_threshold = 0`` in tests)
wins over the model for that gate — calibration never overrules an
operator.
"""

from __future__ import annotations

import threading
from collections import deque
from typing import Optional, Tuple

from dgraph_tpu.utils import planconfig
from dgraph_tpu.utils.calibrate import PRIORS, Calibration, load, measure, save
from dgraph_tpu.utils.metrics import (
    PLANNER_CALIBRATIONS,
    PLANNER_DECISIONS,
    PLANNER_MISPREDICTS,
)

# decision units below which a measured latency is dispatch-dominated
# noise: no rate refinement, no mispredict verdict
_MIN_UNITS_FOR_RATE = 512
_EWMA_ALPHA = 0.2
# mispredict margins: wrong-side needs 1.5× past the rejected estimate,
# own-estimate blowout needs 8× — both loose enough that host noise on a
# 2-core CI box doesn't page anyone, tight enough that a stale
# calibration shows up within a bench round
_MISPREDICT_OTHER_MARGIN = 1.5
_MISPREDICT_SELF_MARGIN = 8.0
# observations past this multiple of the route's own estimate are cold
# compiles / host outliers, not routing evidence
_OUTLIER_FACTOR = 100.0


def _device_factor() -> float:
    """Device fault-domain pricing (utils/devguard.py): 1.0 while the
    backend may be dispatched to, a large price-out multiplier while it
    is latched sick — the planner then routes every decision host-side
    without any route growing a sick-device special case."""
    from dgraph_tpu.utils import devguard

    return devguard.cost_factor()

_LOCK = threading.Lock()
_RECENT: "deque[dict]" = deque(maxlen=64)
_COUNTS: dict = {}
_MISPREDICTS: dict = {}
_CAL: Calibration = PRIORS
_RATES: dict = PRIORS.rates()  # live copy the EWMA refines


def enabled() -> bool:
    return planconfig.planner_enabled()


# -- calibration lifecycle ---------------------------------------------------


def boot(measure_now: bool = False) -> Calibration:
    """Install the best available calibration.

    ``measure_now=False`` (every server construction): load a valid
    persisted file — the warm-boot path that skips the measurement pass.
    With no such file, a ``cpu`` backend keeps the current rates (the
    shipped priors ARE cpu-backend numbers); any other backend measures
    now and persists, because priors taken on a CPU must never price
    routes on a chip they were not taken on.

    ``measure_now=True`` (``DGRAPH_TPU_CALIBRATE=1`` boots, every
    bench.py round): RE-measure unconditionally and persist, replacing
    any existing file — this is the documented stale-calibration remedy,
    so it must never be short-circuited by the very file it is meant to
    refresh."""
    global _CAL
    import jax

    path = planconfig.calibration_file()
    backend = jax.default_backend()
    cal = None
    if path and not measure_now:
        # the backend gate is unconditional: a TPU calibration must
        # never price a CPU boot, nor the reverse
        cal = load(path, backend=backend)
    if cal is None and (measure_now or backend != "cpu"):
        cal = measure()
        PLANNER_CALIBRATIONS.add()
        if path:
            try:
                save(cal, path)
            except OSError:
                pass  # read-only disk: serve from the in-memory rates
    if cal is not None:
        with _LOCK:
            _CAL = cal
            _RATES.update(cal.rates())
    return _CAL


def install_calibration(cal: Calibration) -> None:
    """Adopt an explicit calibration (tests, operator tooling)."""
    global _CAL
    with _LOCK:
        _CAL = cal
        _RATES.update(cal.rates())


def rates() -> dict:
    """Snapshot of the live (online-refined) rate table, µs units."""
    with _LOCK:
        return dict(_RATES)


def calibration_info() -> dict:
    with _LOCK:
        return {
            "source": _CAL.source,
            "backend": _CAL.backend,
            "measured_at": _CAL.measured_at,
            "rates": dict(_RATES),
        }


# -- decision recording ------------------------------------------------------


def record(stats: Optional[dict], dec: dict) -> None:
    """Log one routing decision everywhere it must be visible: the
    bounded per-request stats list, the process ring behind
    /debug/planner, and the prometheus counter."""
    PLANNER_DECISIONS.add((dec["kind"], dec["route"]))
    with _LOCK:
        _RECENT.append(dec)
        k = (dec["kind"], dec["route"])
        _COUNTS[k] = _COUNTS.get(k, 0) + 1
    if stats is not None:
        lst = stats.setdefault("planner", [])
        if len(lst) < 8:
            lst.append(dec)


def note_outcome(dec: Optional[dict], actual_us: float) -> None:
    """Post-hoc check of one recorded decision: refine the chosen
    route's rate EWMA from the measured latency and flag a mispredict
    when the model picked the wrong side."""
    if dec is None or actual_us <= 0.0:
        return
    units = int(dec.get("units", 0))
    est_self = float(dec.get("est_chosen_us", 0.0))
    est_other = float(dec.get("est_other_us", 0.0))
    # a first-time shape's XLA compile (or a host page-fault storm)
    # dwarfs any honest execution estimate: recorded for the ring, but
    # it must neither poison the rate EWMA nor count as a mispredict —
    # decisions have to stay deterministic for a steady shape (the
    # zero-new-programs guard depends on it)
    outlier = est_self > 0 and actual_us > est_self * _OUTLIER_FACTOR
    wrong_side = est_other > 0 and actual_us > est_other * _MISPREDICT_OTHER_MARGIN
    blowout = est_self > 0 and actual_us > est_self * _MISPREDICT_SELF_MARGIN
    mispredict = (
        not outlier
        and units >= _MIN_UNITS_FOR_RATE  # dispatch-dominated: no verdict
        and (wrong_side or blowout)
    )
    # dec is already published to the process ring: mutate it ONLY under
    # the lock, and debug_summary snapshots per-entry copies under the
    # same lock — /debug/planner must never json.dumps a dict another
    # thread is growing
    with _LOCK:
        dec["actual_us"] = round(float(actual_us), 1)
        if outlier:
            dec["outlier"] = True
        if mispredict:
            dec["mispredict"] = True
            _MISPREDICTS[dec["kind"]] = _MISPREDICTS.get(dec["kind"], 0) + 1
    if mispredict:
        PLANNER_MISPREDICTS.add(dec["kind"])
    if not outlier:
        _refine(dec["kind"], dec["route"], units, actual_us)


# chain/mxu timings are composite (capacity planning + packing + the
# kernel) and deliberately refine nothing — only the leaf routes teach
# the model their per-unit rates
_RATE_KEY = {
    ("expand", "host"): ("host_edge_us", 0.0),
    ("expand", "device"): ("device_edge_us", 1.0),   # minus one dispatch
    ("expand", "resident"): ("resident_edge_us", 1.0),  # PR 16 Pallas tier
    ("expand", "mesh"): ("mesh_edge_us", 1.0),  # PR 17 sharded mesh plane
    ("kway", "host"): ("host_intersect_us", 0.0),
    ("kway", "device"): ("device_intersect_us", 1.0),
}


def _refine(kind: str, route: str, units: int, actual_us: float) -> None:
    """EWMA-refine the per-unit rate of the route that actually ran.
    Observed rates clamp to prior/64..prior×64 so one GC pause or page
    fault cannot poison the model."""
    key = _RATE_KEY.get((kind, route))
    if key is None or units < _MIN_UNITS_FOR_RATE:
        return
    field, dispatches = key
    with _LOCK:
        work_us = actual_us - dispatches * _RATES["dispatch_us"]
        if work_us <= 0:
            return
        obs = work_us / units
        prior = getattr(PRIORS, field)
        obs = min(max(obs, prior / 64.0), prior * 64.0)
        _RATES[field] = (1 - _EWMA_ALPHA) * _RATES[field] + _EWMA_ALPHA * obs


# -- route decisions ---------------------------------------------------------


def chain_route(
    engine, est_total: int, n_levels: int
) -> Tuple[bool, Optional[dict]]:
    """Fuse this chain into one device program, or run it per level?

    Static path (planner off, env-pinned threshold, or a runtime
    ``engine.chain_threshold`` assignment): the legacy
    ``est_total >= threshold`` compare, decision dict None so callers
    keep the legacy reject message byte-identically.

    Planner path: price the whole chain both ways —
      per-level = min(host numpy, per-level device dispatches + the
                      host conversion/dedup each level pays)
      chain     = one dispatch + capacity planning + device edge rate
    and fuse when the chain is cheaper.  The measured break-even sits
    around a few tens of thousands of edges on the CPU bench host —
    which is exactly why the BENCH21M 168342-edge 3-hop shape belongs on
    the chain scan that the static 262144 gate refused it."""
    if (
        not enabled()
        or planconfig.overridden("DGRAPH_TPU_CHAIN_THRESHOLD")
        or engine.chain_threshold != planconfig.CHAIN_THRESHOLD_DEFAULT
    ):
        return est_total >= engine.chain_threshold, None
    r = rates()
    # the device fault domain's pricing hook: a sick backend multiplies
    # every device-route cost (utils/devguard.py cost_factor) so it
    # loses each break-even instead of being special-cased per route
    df = _device_factor()
    host_c = n_levels * r["host_setup_us"] + est_total * r["host_edge_us"]
    dev_c = df * (
        n_levels * r["dispatch_us"]
        + est_total * (r["device_edge_us"] + r["host_touch_us"])
    )
    per_level = min(host_c, dev_c)
    chain_c = df * (
        r["dispatch_us"] + r["chain_plan_us"] + est_total * r["device_edge_us"]
    )
    fuse = chain_c < per_level
    dec = {
        "kind": "chain",
        "route": "chain" if fuse else "perlevel",
        "units": int(est_total),
        "levels": int(n_levels),
        "est_chosen_us": round(chain_c if fuse else per_level, 1),
        "est_other_us": round(per_level if fuse else chain_c, 1),
        "reason": (
            "calibrated break-even favors one fused program"
            if fuse
            else "calibrated break-even favors per-level execution"
        ),
    }
    return fuse, dec


def expand_route(
    total: int, configured_min: int, resident: bool = False
) -> Tuple[bool, Optional[dict]]:
    """Host numpy or one device dispatch for a single level's expansion?
    Returns (use_device, decision).  Static compare when the planner is
    off or the knob is pinned (env or runtime assignment).

    ``resident=True`` (PR 16): the engine's device dispatch for this
    arena is the device-resident Pallas gather (query/engine.py
    route:resident), so the device side is priced at
    ``resident_edge_us`` with a ZERO h2d staging term — no
    ``ensure_device`` re-upload ever rides this route, which is the
    whole point of the tier; the missing staging tax is what moves the
    break-even, not a faster kernel.  The decision's route string is
    "resident" so ``note_outcome`` refines the resident rate, never the
    staged one."""
    if (
        not enabled()
        or planconfig.overridden("DGRAPH_TPU_EXPAND_DEVICE_MIN")
        or configured_min != planconfig.EXPAND_DEVICE_MIN_DEFAULT
    ):
        return total >= configured_min, None
    r = rates()
    host_c = r["host_setup_us"] + total * r["host_edge_us"]
    edge = r["resident_edge_us"] if resident else r["device_edge_us"]
    dev_c = _device_factor() * (r["dispatch_us"] + total * edge)
    use_device = dev_c < host_c
    dev_route = "resident" if resident else "device"
    dec = {
        "kind": "expand",
        "route": dev_route if use_device else "host",
        "units": int(total),
        "est_chosen_us": round(dev_c if use_device else host_c, 1),
        "est_other_us": round(host_c if use_device else dev_c, 1),
        "reason": (
            "calibrated host/resident break-even (zero staging term)"
            if resident
            else "calibrated host/device break-even"
        ),
    }
    return use_device, dec


def mesh_route(total: int, width: int) -> Tuple[bool, Optional[dict]]:
    """Price one shard-eligible level's expansion over the mesh
    (dgraph_tpu/mesh — the route:mesh leaf).

    Eligibility is the OPERATOR'S verdict (ArenaManager.use_mesh_for:
    mesh present + shard_threshold/crossover policy) and the planner
    does not overrule it — a shard-eligible arena expands sharded
    exactly as it has since the mesh kernels landed, which is what
    keeps ``DGRAPH_TPU_MESH=0`` byte-identity a pure availability
    toggle with no planner interplay.  What the planner adds is the
    PRICE: the recorded decision carries the mesh estimate against the
    best unsharded alternative, ``note_outcome`` refines
    ``mesh_edge_us`` from the measured dispatch, and the mispredict
    counters surface arenas where sharding costs more than it saves
    (the operator's cue to raise the threshold or rebalance).

    Returns (True, dec); dec is None when the planner is off — the
    static path records nothing, matching every other route."""
    if not enabled():
        return True, None
    r = rates()
    host_c = r["host_setup_us"] + total * r["host_edge_us"]
    dev_c = _device_factor() * (r["dispatch_us"] + total * r["device_edge_us"])
    from dgraph_tpu.utils import devguard as _devguard

    mesh_c = _devguard.cost_factor("mesh") * (
        r["dispatch_us"] + total * r["mesh_edge_us"]
    )
    dec = {
        "kind": "expand",
        "route": "mesh",
        "units": int(total),
        "width": int(width),
        "est_chosen_us": round(mesh_c, 1),
        "est_other_us": round(min(host_c, dev_c), 1),
        "reason": "shard-eligible arena priced over the mesh",
    }
    return True, dec


def merge_gate(est_edges: float, configured_min: int) -> bool:
    """Should a cohort hop-merge rendezvous admit this expansion?
    Merging only amortizes when the union expansion device-routes, so
    the gate IS the expand decision on the estimated fan-out (no
    recording — the real expansion downstream records itself)."""
    if (
        not enabled()
        or planconfig.overridden("DGRAPH_TPU_EXPAND_DEVICE_MIN")
        or configured_min != planconfig.EXPAND_DEVICE_MIN_DEFAULT
    ):
        return est_edges >= configured_min
    r = rates()
    return (
        _device_factor() * (r["dispatch_us"] + est_edges * r["device_edge_us"])
        < r["host_setup_us"] + est_edges * r["host_edge_us"]
    )


def kway_route(total: int, k: int) -> Tuple[Optional[bool], Optional[dict]]:
    """Host ``np.intersect1d`` fold or one batched device program for a
    k-way intersection?  Returns (use_device, decision); (None, None)
    means static gate (caller compares against the configured min)."""
    if not enabled() or planconfig.overridden("DGRAPH_TPU_KWAY_DEVICE_MIN"):
        return None, None
    r = rates()
    host_c = k * r["host_setup_us"] + total * r["host_intersect_us"]
    dev_c = _device_factor() * (
        r["dispatch_us"] + total * r["device_intersect_us"]
    )
    use_device = dev_c < host_c
    dec = {
        "kind": "kway",
        "route": "device" if use_device else "host",
        "units": int(total),
        "k": int(k),
        "est_chosen_us": round(dev_c if use_device else host_c, 1),
        "est_other_us": round(host_c if use_device else dev_c, 1),
        "reason": "calibrated fold/device break-even",
    }
    return use_device, dec


def repair_route(
    n_delta: int, avg_entry_edges: float
) -> Tuple[bool, Optional[dict]]:
    """IVM delta repair (dgraph_tpu/ivm/): apply a mutation's edge
    deltas to a cached derived view IN PLACE, or drop it and let the
    next read rebuild?  Returns (repair, decision).

    Mode discipline (planconfig DGRAPH_TPU_IVM_REPAIR): '0' never,
    'force' always (the delta cap still bounds the work), '1' the cost
    compare below.  Static path (planner off / cap pinned): repair iff
    the delta fits the cap.

    Cost framing: repair is paid ONCE, now, on the refresh path — one
    memcpy-shaped pass over the entry plus the delta
    (``(E + D) × host_edge``).  Dropping defers to a refill the next
    hit-turned-miss pays in full — and an entry worth caching is read
    more than once (the zipf head is why the tiers exist), so the
    refill side is priced at TWO expected re-expansions of the entry,
    each at the cheaper of the host and device routes.  Small deltas
    against warm entries therefore repair; a delta rivaling the entry
    itself rebuilds."""
    mode = planconfig.ivm_repair_mode()
    if mode == "0":
        return False, None
    cap = planconfig.ivm_repair_max_delta()
    if mode == "force":
        return n_delta <= cap, None
    if n_delta > cap:
        return False, None
    if not enabled() or planconfig.overridden(
        "DGRAPH_TPU_IVM_REPAIR_MAX_DELTA"
    ):
        return True, None  # static gate: the cap IS the decision
    r = rates()
    e = max(float(avg_entry_edges), 1.0)
    repair_us = r["host_setup_us"] + (e + n_delta) * r["host_edge_us"]
    refill_us = 2.0 * min(
        r["host_setup_us"] + e * r["host_edge_us"],
        r["dispatch_us"] + e * r["device_edge_us"],
    )
    repair = repair_us < refill_us
    dec = {
        "kind": "repair",
        "route": "repair" if repair else "rebuild",
        "units": int(n_delta),
        "entry_edges": int(e),
        "est_chosen_us": round(repair_us if repair else refill_us, 1),
        "est_other_us": round(refill_us if repair else repair_us, 1),
        "reason": (
            "delta repair cheaper than the expected refills"
            if repair
            else "delta rivals the entry: drop and rebuild on demand"
        ),
    }
    return repair, dec


def segment_route(
    n_steps: int, est_step_units: int, driver: str
) -> Tuple[int, Optional[dict]]:
    """Segmented dataflow execution (PR 18): how many steps (hop levels /
    scan iterations / mask-chain levels) should one dispatched program
    segment cover?  Returns (k, decision); ``k == 0`` means monolithic —
    the caller runs the untouched pre-segmentation program.

    Mode discipline (planconfig DGRAPH_TPU_SEGMENT): '0' never segments
    (byte-identical legacy programs), 'force' always segments at the
    DGRAPH_TPU_SEGMENT_K knob, 'auto' prices it.  A pinned
    DGRAPH_TPU_SEGMENT_K is an operator override in auto mode too — the
    planner then only decides WHETHER to segment, never re-sizes k.

    Pricing: segmentation buys bounded yield latency (cancellation,
    preemption, ``first:`` early-exit all wait at most one segment) and
    pays ``ceil(n/k) - 1`` extra dispatches.  The model caps that
    overhead at 10% of the monolithic estimate: k is the smallest
    segment whose per-segment work dwarfs one dispatch by 10×, clamped
    to [1, n_steps].  When even k == n_steps-1 cannot amortize a second
    dispatch (tiny programs), the route stays monolithic — tiny
    programs already yield between themselves."""
    mode = planconfig.segment_mode()
    if mode == "0" or n_steps <= 1:
        return 0, None
    if mode == "force":
        return max(1, min(planconfig.segment_k(), n_steps)), None
    if not enabled():
        return 0, None
    r = rates()
    step_us = max(float(est_step_units), 1.0) * r["device_edge_us"]
    if planconfig.overridden("DGRAPH_TPU_SEGMENT_K"):
        k = max(1, min(planconfig.segment_k(), n_steps))
    else:
        # smallest k whose segment work is >= 10 dispatches of overhead
        k = int(-(-10.0 * r["dispatch_us"] // step_us))
        k = max(1, min(k, n_steps))
    n_segs = -(-n_steps // k)
    seg_c = n_segs * r["dispatch_us"] + n_steps * step_us
    mono_c = r["dispatch_us"] + n_steps * step_us
    if k >= n_steps:
        dec = {
            "kind": "segment",
            "route": "monolithic",
            "units": int(n_steps),
            "driver": driver,
            "k": 0,
            "est_chosen_us": round(mono_c, 1),
            "est_other_us": round(seg_c, 1),
            "reason": "program too small to amortize a second dispatch",
        }
        return 0, dec
    dec = {
        "kind": "segment",
        "route": "segmented",
        "units": int(n_steps),
        "driver": driver,
        "k": int(k),
        "est_chosen_us": round(seg_c, 1),
        "est_other_us": round(mono_c, 1),
        "reason": "bounded yield latency within 10% dispatch overhead",
    }
    return k, dec


def path_route(
    k: int, decorated: bool, faceted: bool, unusable: bool,
    universe: int = 0, held: int = 0,
) -> Tuple[bool, dict]:
    """A ``shortest`` block: the device's level-synchronous BFS
    (ops/bfs.py) or the host's Dijkstra (query/shortest.py)?  Decided by
    what the routes can DO, from what the block and the store show — no
    rate, no knob: the BFS answers one path at unit cost over plain
    predicates; k paths, a facet on a listed predicate (a weight, or one
    the hop has to render) and a decorated child (filter, pagination,
    order, count, ...) are the Dijkstra's.  So is a store whose uid space
    is wider than its arenas: the BFS keeps a parent and a frontier mark for EVERY
    uid up to the largest (``universe``), for each search in flight, and
    where that outgrows the rows and edges the listed arenas hold
    (``held``) — one uid near 2^30 in a store of a thousand edges — the
    tables would cost what no arena does; the Dijkstra touches only what
    it reaches.  How a level is done once on the device is chosen there,
    per level (ops/bfs.py)."""
    why = (
        "numpaths > 1: k paths are the Dijkstra's" if k > 1
        else "a listed predicate carries facets (weights, rendered facets)" if faceted
        else "a child is decorated (filter, pagination, ...)" if decorated
        else "device unavailable (sick, or an arena is sharded over the mesh)" if unusable
        else "the uid space is wider than the listed arenas hold" if universe > held
        else ""
    )
    dec = {
        "kind": "path",
        "route": "host" if why else "device",
        "units": int(k),
        "reason": why or "one path at unit cost over plain predicates",
    }
    return not why, dec


def mxu_fanout_ok(engine, est_total: int, n_levels: int) -> bool:
    """The MXU tier's fan-out admission: is this chain big enough to
    leave the host at all?  Shares chain_route's model (and its override
    discipline) without recording — joinplan records the full mxu-vs-
    pairwise decision itself."""
    ok, _dec = chain_route(engine, est_total, n_levels)
    return ok


# -- scheduler feedback ------------------------------------------------------


class CohortController:
    """Load-adaptive cohort admission: max_batch and the flush deadline
    move with MEASURED queue-wait and cohort occupancy, inside hard
    bounds, instead of sitting at fixed ``DGRAPH_TPU_SCHED`` knobs.

    Deterministic given the observation sequence (the seeded load-ramp
    test replays one), and bounded by construction:

      max_batch ∈ [base, min(8×base, 1024)]
      flush deadline ∈ [base/8, base]

    Rules per update (EWMA α=0.25 on occupancy and queue wait):
    - sustained occupancy ≥ 3/4 of the current batch cap → the cap
      doubles (arrivals are filling cohorts: batch harder);
    - occupancy back under 1/4 of BASE → the cap halves toward base
      (idle traffic must not wait for a giant cohort that never fills);
    - queue wait blowing past 4× the flush deadline → the deadline
      halves (drain faster under backlog);
    - queue wait under 1/4 of the deadline → the deadline relaxes back
      toward base.
    """

    def __init__(self, base_batch: int, base_flush_s: float, width: int = 1):
        self.base_batch = max(1, int(base_batch))
        # mesh serving plane (PR 17): a width-N mesh expands one merged
        # cohort frontier across N chips, so the adaptive CEILING scales
        # with the mesh width — the base (and thus the floor and the
        # idle behavior) stays put, width only raises how far sustained
        # load may push the cap before the 1024 clamp
        self.width = max(1, int(width))
        self._clamp_cap = 1024
        self.hi_batch = min(self.base_batch * 8 * self.width, self._clamp_cap)
        self.base_flush_s = float(base_flush_s)
        self.lo_flush_s = self.base_flush_s / 8.0
        self.max_batch = self.base_batch
        self.flush_s = self.base_flush_s
        self._occ = 0.0
        self._wait = 0.0
        self._service = 0.0
        self._updates = 0
        self._lock = threading.Lock()

    def set_width(self, width: int) -> None:
        """Re-target the batching ceiling at a NEW mesh width — the
        elastic fault domain (mesh/fault.py) shrinks/widens the serving
        sub-mesh at runtime, and the scheduler re-samples per flush.  A
        shrink also clamps the live cap immediately (a 7-chip sub-mesh
        must not keep draining cohorts sized for 8); growth lets the
        ordinary occupancy rule climb back on its own evidence."""
        width = max(1, int(width))
        with self._lock:
            if width == self.width:
                return
            self.width = width
            self.hi_batch = min(
                self.base_batch * 8 * width, self._clamp_cap
            )
            if self.max_batch > self.hi_batch:
                self.max_batch = self.hi_batch

    def update(
        self, occupancy: int, queue_wait_s: float, service_s: float = 0.0
    ) -> Tuple[int, float]:
        """Fold one flush's measurements in; returns the (possibly
        adjusted) (max_batch, flush_deadline_s)."""
        a = 0.25
        with self._lock:
            self._occ = (1 - a) * self._occ + a * float(occupancy)
            self._wait = (1 - a) * self._wait + a * float(queue_wait_s)
            self._service = (1 - a) * self._service + a * float(service_s)
            self._updates += 1
            if self._occ >= 0.75 * self.max_batch and self.max_batch < self.hi_batch:
                self.max_batch = min(self.max_batch * 2, self.hi_batch)
            elif self._occ <= 0.25 * self.base_batch and self.max_batch > self.base_batch:
                self.max_batch = max(self.max_batch // 2, self.base_batch)
            if self._wait > 4.0 * self.flush_s and self.flush_s > self.lo_flush_s:
                self.flush_s = max(self.flush_s * 0.5, self.lo_flush_s)
            elif self._wait < 0.25 * self.flush_s and self.flush_s < self.base_flush_s:
                self.flush_s = min(self.flush_s * 1.5, self.base_flush_s)
            return self.max_batch, self.flush_s

    def state(self) -> dict:
        with self._lock:
            return {
                "max_batch": self.max_batch,
                "flush_ms": round(self.flush_s * 1e3, 3),
                "base_batch": self.base_batch,
                "base_flush_ms": round(self.base_flush_s * 1e3, 3),
                "mesh_width": self.width,
                "hi_batch": self.hi_batch,
                "occupancy_ewma": round(self._occ, 2),
                "queue_wait_ms_ewma": round(self._wait * 1e3, 3),
                "service_ms_ewma": round(self._service * 1e3, 3),
                "updates": self._updates,
            }


# -- debug surface -----------------------------------------------------------


def debug_summary(scheduler=None) -> dict:
    """The unified /debug/planner view: calibration provenance, live
    rates, per-(kind,route) decision counts, mispredicts, the recent
    ring, the join tier's own ring (PR 9), and the scheduler's adaptive
    state when one is attached."""
    from dgraph_tpu.query import joinplan

    with _LOCK:
        counts = {f"{k}:{r}": v for (k, r), v in sorted(_COUNTS.items())}
        mis = dict(_MISPREDICTS)
        # per-entry copies: note_outcome mutates ring entries under this
        # lock, so the snapshot must not share the dict objects
        recent = [dict(d) for d in _RECENT]
    out = {
        "enabled": enabled(),
        "calibration": calibration_info(),
        "counts": counts,
        "mispredicts": mis,
        "mispredict_total": sum(mis.values()),
        "recent": recent,
        "join": joinplan.debug_summary(),
    }
    if scheduler is not None:
        ctl = getattr(scheduler, "_adaptive", None)
        out["sched"] = ctl.state() if ctl is not None else {
            "adaptive": False,
            "max_batch": scheduler.max_batch,
            "flush_ms": round(scheduler.flush_s * 1e3, 3),
        }
    return out


def mispredict_stats() -> dict:
    """(decision_total, mispredict_total, rate) — the bench headline's
    honesty row."""
    with _LOCK:
        total = sum(_COUNTS.values())
        mis = sum(_MISPREDICTS.values())
    return {
        "decisions": total,
        "mispredicts": mis,
        "mispredict_rate": round(mis / total, 4) if total else 0.0,
    }


def _reset_for_tests() -> None:
    global _CAL
    with _LOCK:
        _RECENT.clear()
        _COUNTS.clear()
        _MISPREDICTS.clear()
        _CAL = PRIORS
        _RATES.clear()
        _RATES.update(PRIORS.rates())
