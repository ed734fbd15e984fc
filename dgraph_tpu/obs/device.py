"""Device/HBM telemetry: residency, program caches, compile events.

The ArenaManager already *enforces* an HBM budget (models/arena.py LRU
eviction) and the ops layer already *bounds* its per-arena spgemm tile
sets.  This module turns that enforcement bookkeeping into gauges and
one snapshot endpoint:

- **HBM residency** — resident bytes vs budget (headroom is the
  difference), dense join-tile bytes, cumulative arena evictions;
- **program caches** — tile-set counts
  (`dgraph_program_cache_entries{kind="tile_sets"}`), the occupancy side
  of the compile-budget guards tests already enforce;
- **XLA compile events** — every backend compilation via the same
  ``jax.monitoring`` event the per-test compile budgets count
  (`/jax/core/compile/backend_compile_duration`), as a process counter
  + duration histogram, and onto the active request's ledger so a
  compile-storm query is attributable.  That bracket closes round a
  program READ BACK from the persistent cache too, so
  `dgraph_xla_cache_reads_total` counts JAX's
  `/jax/compilation_cache/cache_hits` beside it: compiles less reads
  is what the backend compiled cold;
- **build identity** — `dgraph_build_info{version,backend,jax}` = 1,
  stamped once the backend is known.

Served at ``GET /debug/device`` and folded into ``GET /debug/bundle``
(serve/server.py) — the single-request postmortem JSON.
"""

from __future__ import annotations

import sys
import threading

from dgraph_tpu.obs import ledger as _ledger
from dgraph_tpu.utils import devguard
from dgraph_tpu.utils.metrics import (
    BUILD_INFO,
    HBM_BUDGET_BYTES,
    HBM_RESIDENT_BYTES,
    HBM_TILE_BYTES,
    PROGRAM_CACHE_ENTRIES,
    XLA_CACHE_READS,
    XLA_COMPILE_SECONDS,
    XLA_COMPILES,
)

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"

_install_lock = threading.Lock()
_installed = False
_longest_compile_s = 0.0  # guarded by _install_lock


def _on_compile_begin(name: str, value: float, **kw) -> None:
    # JAX records the start time of a bracketed stage as a scalar under
    # the stage's event name when it enters the bracket
    if name == _COMPILE_EVENT:
        devguard.note_compile_begin()


def _on_event(name: str, **kw) -> None:
    if name == _CACHE_HIT_EVENT:
        XLA_CACHE_READS.add(1)


def _on_event_duration(name: str, secs: float, **kw) -> None:
    global _longest_compile_s
    if name != _COMPILE_EVENT:
        return
    # the bracket reports its duration from __exit__, so a failed
    # compile's exception is the one being handled right now
    devguard.note_compile_end(secs, sys.exc_info()[1])
    XLA_COMPILES.add(1)
    XLA_COMPILE_SECONDS.observe(secs)
    with _install_lock:
        _longest_compile_s = max(_longest_compile_s, secs)
    led = _ledger.current()
    if led is not None:
        # compiles land on whichever request's thread triggered them —
        # per-request attribution, with the same caveat the per-test
        # compile budgets document for worker threads
        led.compiles += 1


def install_compile_listener() -> None:
    """Register the jax.monitoring compile listener (idempotent; safe
    to call from every server boot and every bench harness)."""
    global _installed
    with _install_lock:
        if _installed:
            return
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(
            _on_event_duration
        )
        jax.monitoring.register_scalar_listener(_on_compile_begin)
        jax.monitoring.register_event_listener(_on_event)
        _installed = True


def stamp_build_info() -> None:
    """Publish dgraph_build_info{version,backend,jax} = 1.  Reads the
    default backend, so call it AFTER jax platform selection settled
    (server start / harness boot)."""
    import jax

    from dgraph_tpu import __version__

    BUILD_INFO.set(
        (__version__, jax.default_backend(), jax.__version__), 1.0
    )


def _memory_of(dev):
    stats = dev.memory_stats()
    if not stats:
        return None
    return {
        k: int(stats[k])
        for k in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")
        if k in stats
    }


def snapshot(server=None) -> dict:
    """One device-telemetry snapshot (the /debug/device body), updating
    the gauges as a side effect so a scrape that never hits the debug
    endpoint still sees fresh residency numbers after any snapshot.

    ``server`` is a DgraphServer when called from the serving surface;
    None degrades to the process-wide (backend + compile) view."""
    import jax

    from dgraph_tpu.utils import jaxcache

    devs = jax.devices()
    with _install_lock:
        longest = _longest_compile_s
    out: dict = {
        "backend": jax.default_backend(),
        "device_kind": devs[0].device_kind,
        "devices": len(devs),
        "jax": jax.__version__,
        "compiles": {
            "total": XLA_COMPILES.value(),
            "cache_reads": XLA_CACHE_READS.value(),
            "seconds_sum": round(XLA_COMPILE_SECONDS.snapshot()[1], 3),
            "seconds_max": round(longest, 3),
        },
        "compile_cache": jaxcache.in_use(),
        # allocator's view per device (None where the backend reports
        # none, e.g. cpu): peak HBM is the number a deployment sizes to
        "memory": {
            str(d.id): _memory_of(d) for d in devs
        },
        # device fault domain (utils/devguard.py): state machine +
        # fault/failover/probe counters per domain
        "guard": {
            "enabled": devguard.enabled(),
            "domains": devguard.summary(),
        },
    }
    if server is None:
        return out
    arenas = getattr(server.engine, "arenas", None)
    if arenas is not None:
        res = arenas.residency()
        HBM_RESIDENT_BYTES.set(res["resident_bytes"])
        HBM_BUDGET_BYTES.set(res["budget_bytes"])
        HBM_TILE_BYTES.set(res["tile_bytes"])
        for kind, n in res["program_caches"].items():
            PROGRAM_CACHE_ENTRIES.set(kind, n)
        out["arenas"] = res
        if arenas.mesh is not None:
            out["mesh"] = {
                "width": int(arenas.mesh.shape["model"]),
                "sharded_bytes_by_device": arenas.sharded_bytes_by_device(),
            }
    return out
