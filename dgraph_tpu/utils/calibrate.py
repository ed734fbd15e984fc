"""Micro-calibration: MEASURED per-kernel throughput for the planner.

The adaptive planner (query/planner.py) costs every candidate execution
route from a handful of rates — fixed dispatch overhead, per-edge gather
throughput on each side of the host/device boundary, the host
``np.intersect1d`` fold rate, the per-MAC tile rate of the MXU join
tier.  Guessing those from datasheet numbers is how the old static
thresholds drifted (the 262144 twins); this module measures them on the
actual backend in a few hundred milliseconds and persists the result so
warm boots skip the pass entirely.

Three sources, in trust order:

- ``measured`` — ``measure()`` ran on this process's backend;
- ``file`` — a previous run's measurement loaded from
  ``DGRAPH_TPU_CALIBRATION_FILE`` (rejected when the backend or format
  version differs — a TPU calibration must never price a CPU boot);
- ``prior`` — shipped defaults distilled from the r4/r9 bench rounds
  (CPU-backend numbers, valid for the ``cpu`` backend only: a server on
  any other backend with no matching file measures at boot,
  query/planner.py ``boot``).

The calibration is a starting point, not the whole story: the planner
refines the edge/element rates ONLINE from the per-hop stage timings the
engine already records (utils/metrics.py histograms, PR 7 hop spans), so
a mis-measured cold pass converges toward the workload's real rates.

This module is the sanctioned home of the raw ``time.perf_counter``
loops (it lives in utils/, outside the naked-stage-timing rule's serving
dirs, by design — calibration is a measurement harness, not a serving
stage).
"""

from __future__ import annotations

import json
import os
import time
from dataclasses import asdict, dataclass, replace
from typing import Optional

CALIBRATION_VERSION = 1


@dataclass(frozen=True)
class Calibration:
    """Per-kernel rates (µs) the cost model prices routes from.

    Priors reflect the 2-core CPU bench host of rounds 4-9: fused device
    gather 55.5M edges/s (~0.018µs/edge), numpy baseline 1.79× slower
    (~0.032µs/edge), dispatch ~120µs; the tile rates are PR 9's
    joinplan constants unchanged."""

    dispatch_us: float = 120.0       # fixed cost of one device program
    device_edge_us: float = 0.018    # per-edge device gather rate
    resident_edge_us: float = 0.010  # per-edge rate of the resident
                                     # Pallas gather (PR 16): below
                                     # device_edge_us because the route
                                     # pays ZERO h2d staging — no
                                     # ensure_device re-upload rides the
                                     # dispatch (the prior encodes the
                                     # missing term, not a faster ALU;
                                     # online refinement converges it)
    mesh_edge_us: float = 0.008      # per-edge WALL rate of the
                                     # row-sharded mesh expansion
                                     # (dgraph_tpu/mesh): below
                                     # device_edge_us because N chips
                                     # split the gather, above the ideal
                                     # device_edge_us/N because the
                                     # cross-chip exchange rides every
                                     # hop; online refinement converges
                                     # it to the live mesh's reality
    host_edge_us: float = 0.032      # per-edge host numpy gather rate
    host_touch_us: float = 0.010     # per-edge host conversion/dedup the
                                     # per-level path pays that a fused
                                     # chain keeps on device
    host_setup_us: float = 4.0       # per-call host-path fixed cost
    chain_plan_us: float = 150.0     # chain capacity planning + packing
    host_intersect_us: float = 0.030   # per element, np.intersect1d fold
    device_intersect_us: float = 0.012  # per element, intersect_stack
    tile_mac_us: float = 1.2e-4      # per T·T MAC lane of a stored tile
    combine_us_per_mac: float = 2e-5   # one-hot block-column combine
    tile_build_us_per_lane: float = 1.8e-4  # host densify + upload
    tile_build_amortize: float = 8.0   # expected reuses of fresh tiles

    backend: str = ""                # jax backend the rates were taken on
    source: str = "prior"            # prior | file | measured
    measured_at: float = 0.0         # epoch seconds, stored only (never
                                     # interval math — wallclock rule)

    _RATE_FIELDS = (
        "dispatch_us", "device_edge_us", "resident_edge_us",
        "mesh_edge_us", "host_edge_us", "host_touch_us",
        "host_setup_us", "chain_plan_us", "host_intersect_us",
        "device_intersect_us", "tile_mac_us", "combine_us_per_mac",
        "tile_build_us_per_lane", "tile_build_amortize",
    )

    def rates(self) -> dict:
        d = asdict(self)
        return {k: d[k] for k in self._RATE_FIELDS}


PRIORS = Calibration()


def _median(xs) -> float:
    xs = sorted(xs)
    return xs[len(xs) // 2]


def measure(edges: int = 1 << 16, reps: int = 5) -> Calibration:
    """Run the micro-calibration pass on the current backend.

    Budgeted at a few hundred ms on a CPU host: one tiny jitted no-op
    for dispatch overhead, one synthetic-CSR gather each side of the
    host/device boundary for the edge rates, one ``np.intersect1d`` for
    the fold rate, one small einsum for the tile MAC rate.  Compiles a
    handful of throwaway programs — callers in test trees should prefer
    the priors or a saved file."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    backend = jax.default_backend()

    # dispatch overhead: pre-compiled elementwise no-op, blocked
    f = jax.jit(lambda x: x + 1)
    x = jnp.zeros(8, jnp.int32)
    f(x).block_until_ready()
    ts = []
    for _ in range(max(reps * 4, 16)):
        t0 = time.perf_counter()
        f(x).block_until_ready()
        ts.append((time.perf_counter() - t0) * 1e6)
    dispatch_us = max(_median(ts), 1.0)

    # synthetic CSR: S rows of uniform degree — representative of the
    # engine's gather shape without the planning machinery around it
    deg = 32
    S = max(edges // deg, 8)
    E = S * deg
    h_offsets = np.arange(S + 1, dtype=np.int64) * deg
    h_dst = np.arange(E, dtype=np.int32) % (S * 2)
    rows = np.arange(S, dtype=np.int32)

    # device edge rate: gather + dedup, the fused hop's core loop
    offsets_d = jnp.asarray(h_offsets.astype(np.int32))
    dst_d = jnp.asarray(h_dst)

    @jax.jit
    def gather(rws):
        o0 = offsets_d[rws]
        idx = o0[:, None] + jnp.arange(deg, dtype=jnp.int32)[None, :]
        return jnp.sort(dst_d[idx].reshape(-1))

    rd = jnp.asarray(rows)
    gather(rd).block_until_ready()
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        gather(rd).block_until_ready()
        ts.append((time.perf_counter() - t0) * 1e6)
    device_edge_us = max((_median(ts) - dispatch_us) / E, 1e-5)

    # host edge rate: the numpy twin of the same expansion (+ dedup,
    # which the host per-level path actually pays)
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        starts = h_offsets[:-1][rows]
        within = np.arange(E) - np.repeat(h_offsets[:-1][rows], deg)
        np.unique(h_dst[np.repeat(starts, deg) + within])
        ts.append((time.perf_counter() - t0) * 1e6)
    host_edge_us = max(_median(ts) / E, 1e-5)

    # host k-way fold rate: one np.intersect1d over sorted-unique sets
    a = np.arange(0, edges * 2, 2, dtype=np.int64)
    b = np.arange(0, edges * 3, 3, dtype=np.int64)
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        np.intersect1d(a, b, assume_unique=True)
        ts.append((time.perf_counter() - t0) * 1e6)
    host_intersect_us = max(_median(ts) / (len(a) + len(b)), 1e-5)

    # tile MAC rate: K stacked T×T f32 matmuls (the spgemm tile pass's
    # inner product), per MAC lane
    T, K = 128, 8
    tiles = jnp.ones((K, T, T), jnp.float32)
    vecs = jnp.ones((K, T), jnp.float32)

    @jax.jit
    def macs(m, v):
        return jnp.einsum("ktu,kt->ku", m, v)

    macs(tiles, vecs).block_until_ready()
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        macs(tiles, vecs).block_until_ready()
        ts.append((time.perf_counter() - t0) * 1e6)
    tile_mac_us = max((_median(ts) - dispatch_us) / (K * T * T), 1e-9)

    return replace(
        PRIORS,
        dispatch_us=dispatch_us,
        device_edge_us=device_edge_us,
        host_edge_us=host_edge_us,
        host_intersect_us=host_intersect_us,
        # the resident gather, the mesh expansion and the device fold
        # share the gather engine: scale their prior ratios to it until
        # the online refinement has seen each route run
        resident_edge_us=max(
            device_edge_us * (PRIORS.resident_edge_us / PRIORS.device_edge_us),
            1e-5,
        ),
        mesh_edge_us=max(
            device_edge_us * (PRIORS.mesh_edge_us / PRIORS.device_edge_us),
            1e-5,
        ),
        device_intersect_us=max(
            device_edge_us * (PRIORS.device_intersect_us / PRIORS.device_edge_us),
            1e-5,
        ),
        tile_mac_us=tile_mac_us,
        backend=backend,
        source="measured",
        measured_at=time.time(),
    )


def save(cal: Calibration, path: str) -> None:
    """Persist a calibration durably (atomic tmp+fsync+replace — the
    planner must never price routes from a torn file)."""
    from dgraph_tpu.utils.atomicio import atomic_write_file

    d = os.path.dirname(path)
    if d:
        os.makedirs(d, exist_ok=True)
    body = {
        "version": CALIBRATION_VERSION,
        "backend": cal.backend,
        "measured_at": cal.measured_at,
        "rates": cal.rates(),
    }
    atomic_write_file(path, json.dumps(body, indent=1).encode())


def load(path: str, backend: Optional[str] = None) -> Optional[Calibration]:
    """Load a persisted calibration; None when missing, unparsable, from
    another format version, or taken on a different backend."""
    try:
        with open(path, "rb") as f:
            body = json.loads(f.read().decode())
    except (OSError, ValueError):
        return None
    if body.get("version") != CALIBRATION_VERSION:
        return None
    if backend is not None and body.get("backend") != backend:
        return None
    rates = body.get("rates")
    if not isinstance(rates, dict):
        return None
    try:
        known = {k: float(v) for k, v in rates.items()
                 if k in Calibration._RATE_FIELDS}
        return replace(
            PRIORS,
            **known,
            backend=str(body.get("backend", "")),
            source="file",
            measured_at=float(body.get("measured_at", 0.0)),
        )
    except (TypeError, ValueError):
        # a hand-edited or partially-corrupt rate value must degrade to
        # priors, never refuse boot
        return None
