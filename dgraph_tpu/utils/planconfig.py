"""Planner configuration: every execution-route gate knob in ONE module.

The routes above the per-level hop (fused chain, chain-scan, fused
recurse, MXU tile join), the per-level host-vs-device choice and the
host-vs-device k-way intersection read their gates here: one table of
documented defaults, one read path, and one override-detection helper
(the adaptive planner in ``query/planner.py`` only substitutes its
calibrated decision when the operator has NOT pinned the knob — an
explicit env value or runtime assignment always wins).  Which device
program expands a level is not a knob: ``query/engine.py``
``DeviceExpander`` resolves it from the platform.

The graftlint rule ``naked-route-threshold`` (analysis/rules.py) forbids
raw ``DGRAPH_TPU_*`` env reads and naked numeric route-gate comparisons
in ``query/`` and ``ops/`` — new thresholds land HERE, with a docstring,
or they don't land.

Knob table (env name → default → what it gates):

========================== ========= =====================================
DGRAPH_TPU_PLANNER            "1"    measured-cost adaptive planner gate;
                                     ``0`` restores every static threshold
                                     below byte-identically
DGRAPH_TPU_CHAIN_THRESHOLD  262144   min estimated chain fan-out before
                                     fusing into one device program
                                     (static fallback; the planner costs
                                     the break-even instead)
DGRAPH_TPU_EXPAND_DEVICE_MIN 262144  min per-level fan-out before an
                                     expansion leaves host numpy for a
                                     device dispatch (also gates cohort
                                     hop merging)
DGRAPH_TPU_KWAY_DEVICE_MIN  262144   min total candidate elements before
                                     a k-way intersection rides one
                                     batched device program
DGRAPH_TPU_CHAIN_MAX_CAPC   1<<21    full-mode chain per-level overflow
                                     chunk cap (transfer-sized)
DGRAPH_TPU_CHAIN_MAX_CAPC_LIGHT
                            1<<23    light-mode (var-block) chain cap
                                     (HBM-sized; frontiers only on wire)
DGRAPH_TPU_MXU_JOIN           "1"    MXU tile-join tier: 0 off / 1 cost-
                                     modeled / force (skip cost compare)
DGRAPH_TPU_MXU_MASK_MAX     1<<22    largest frontier-mask lane count the
                                     mxu chain route may allocate
DGRAPH_TPU_TILE               128    adjacency tile edge length (MXU-
                                     native 128; tests shrink it)
DGRAPH_TPU_TILE_BUDGET      1<<28    per-arena densified-tile byte budget
DGRAPH_TPU_CALIBRATION_FILE  scratch/planner_calib.json
                                     persisted micro-calibration (warm
                                     boots skip the measurement pass)
DGRAPH_TPU_CALIBRATE          "0"    "1" re-measures at server boot and
                                     re-persists (stale-calibration
                                     remedy); default boots load the file
DGRAPH_TPU_IVM_REPAIR         "1"    IVM delta repair of cached hop
                                     entries / tile blocks: 0 drop-only /
                                     1 cost-gated / force (skip the
                                     cost compare, cap still applies)
DGRAPH_TPU_IVM_REPAIR_MAX_DELTA 512  hard cap on the edge-delta size the
                                     repair path will apply in place;
                                     larger mutation batches drop the
                                     affected views (static fallback
                                     gate when the planner is off)
DGRAPH_TPU_SEGMENT          "auto"   segmented dataflow execution (PR 18):
                                     the fused drivers emit bounded
                                     k-step program segments with a
                                     scheduler yield point at every seam.
                                     "0" monolithic always (byte-identical
                                     pre-segmentation programs) / "auto"
                                     planner-priced segment size /
                                     "force" always segment at the k knob
DGRAPH_TPU_SEGMENT_K           4     steps (hop levels / scan iterations /
                                     mask-chain levels) per dispatched
                                     segment when segmentation engages;
                                     pinning it is an operator override —
                                     the planner then never re-sizes k
========================== ========= =====================================

Reads happen per call (not at import) so tests can flip knobs with
monkeypatch and a long-lived process picks up operator edits on the
next decision.
"""

from __future__ import annotations

import os

# -- documented defaults (the table above, machine-readable) -----------------

CHAIN_THRESHOLD_DEFAULT = 262144
EXPAND_DEVICE_MIN_DEFAULT = 262144
KWAY_DEVICE_MIN_DEFAULT = 262144
CHAIN_MAX_CAPC_DEFAULT = 1 << 21
CHAIN_MAX_CAPC_LIGHT_DEFAULT = 1 << 23
MXU_MASK_MAX_DEFAULT = 1 << 22
TILE_DEFAULT = 128
TILE_BUDGET_DEFAULT = 1 << 28
CALIBRATION_FILE_DEFAULT = "scratch/planner_calib.json"
IVM_REPAIR_MAX_DELTA_DEFAULT = 512
SEGMENT_K_DEFAULT = 4


def overridden(name: str) -> bool:
    """Is this knob explicitly pinned in the environment?  The adaptive
    planner treats a pinned knob as an operator override and falls back
    to the static comparison for that gate."""
    return name in os.environ


def _int(name: str, default: int) -> int:
    try:
        return int(float(os.environ.get(name, default)))
    except (ValueError, OverflowError):
        # a typo'd ("lots") or absurd ("inf") knob falls back instead of
        # crashing every decision that reads it
        return default


# -- gates -------------------------------------------------------------------


def planner_enabled() -> bool:
    """DGRAPH_TPU_PLANNER: the measured-cost planner gate (default ON).
    ``0`` restores every static threshold byte-identically."""
    return os.environ.get("DGRAPH_TPU_PLANNER", "1") != "0"


def chain_threshold() -> int:
    """Static min estimated fan-out before a chain fuses (the planner's
    fallback; see module table)."""
    return _int("DGRAPH_TPU_CHAIN_THRESHOLD", CHAIN_THRESHOLD_DEFAULT)


def expand_device_min() -> int:
    """Static min per-level fan-out before host numpy yields to a device
    dispatch (shared by the engine, the resolver and merge gating)."""
    return _int("DGRAPH_TPU_EXPAND_DEVICE_MIN", EXPAND_DEVICE_MIN_DEFAULT)


def kway_device_min() -> int:
    """Static min total candidate elements before a k-way intersection
    takes the batched device program over the host fold."""
    return _int("DGRAPH_TPU_KWAY_DEVICE_MIN", KWAY_DEVICE_MIN_DEFAULT)


def chain_max_capc() -> int:
    """Full-mode chain per-level overflow-chunk cap (transfer-sized)."""
    return _int("DGRAPH_TPU_CHAIN_MAX_CAPC", CHAIN_MAX_CAPC_DEFAULT)


def chain_max_capc_light() -> int:
    """Light-mode (var-block) chain cap — device-resident matrices can
    afford much larger buffers than transferring ones."""
    return _int(
        "DGRAPH_TPU_CHAIN_MAX_CAPC_LIGHT", CHAIN_MAX_CAPC_LIGHT_DEFAULT
    )


def mxu_mode() -> str:
    """DGRAPH_TPU_MXU_JOIN: '0' off, '1' cost-modeled (default), 'force'
    always (structural eligibility permitting)."""
    return os.environ.get("DGRAPH_TPU_MXU_JOIN", "1")


def mask_max_lanes() -> int:
    """Largest frontier-mask length the mxu chain route may allocate
    (float32 lanes; the default 1<<22 ≈ 16MB per mask)."""
    return _int("DGRAPH_TPU_MXU_MASK_MAX", MXU_MASK_MAX_DEFAULT)


def tile_size() -> int:
    """Adjacency tile edge length; 128 is MXU-native."""
    return _int("DGRAPH_TPU_TILE", TILE_DEFAULT)


def tile_budget() -> int:
    """Per-arena densified-tile byte budget."""
    return _int("DGRAPH_TPU_TILE_BUDGET", TILE_BUDGET_DEFAULT)


def calibration_file() -> str:
    """Path of the persisted micro-calibration JSON ('' disables
    persistence entirely)."""
    return os.environ.get(
        "DGRAPH_TPU_CALIBRATION_FILE", CALIBRATION_FILE_DEFAULT
    )


def ivm_repair_mode() -> str:
    """DGRAPH_TPU_IVM_REPAIR: '0' never repair (drop-only, the pre-IVM
    behavior for affected views), '1' cost-gated (default; the planner
    prices repair-now against refill-later), 'force' always repair when
    structurally possible (the cap below still bounds the work)."""
    return os.environ.get("DGRAPH_TPU_IVM_REPAIR", "1")


def ivm_repair_max_delta() -> int:
    """Hard edge-delta cap for in-place view repair — the static gate
    when the planner is off, and the work bound in every mode."""
    return _int(
        "DGRAPH_TPU_IVM_REPAIR_MAX_DELTA", IVM_REPAIR_MAX_DELTA_DEFAULT
    )


def segment_mode() -> str:
    """DGRAPH_TPU_SEGMENT: '0' monolithic always (byte-identical
    pre-segmentation programs), 'auto' (default; planner.segment_route
    prices the segment size from calibrated dispatch overhead), 'force'
    always segment at the DGRAPH_TPU_SEGMENT_K knob."""
    return os.environ.get("DGRAPH_TPU_SEGMENT", "auto")


def segment_k() -> int:
    """Steps per dispatched program segment when segmentation engages.
    Pinning it (env) is an operator override — auto mode then only
    decides WHETHER to segment, never re-sizes k."""
    return _int("DGRAPH_TPU_SEGMENT_K", SEGMENT_K_DEFAULT)


def calibrate_at_boot() -> bool:
    """DGRAPH_TPU_CALIBRATE=1: RE-run the micro-calibration pass at
    server boot and persist it, replacing any existing file — the
    stale-calibration remedy.  Default off: ordinary boots load the
    persisted file (warm path) or serve from priors; library and test
    constructions never pay a measurement pass."""
    return os.environ.get("DGRAPH_TPU_CALIBRATE", "0") == "1"
