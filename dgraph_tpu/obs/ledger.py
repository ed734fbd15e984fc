"""Per-query resource ledger: what did this request actually cost.

PR 7's flight recorder answers *where a sampled query's time went*; the
serving layer still had no per-request account of what every query
COSTS — edges traversed, hop dispatches by route, host vs device time,
bytes staged across the host↔device boundary, cache absorption, compile
events, IVM repairs.  This module supplies that account as one pooled
struct per request, threaded through scheduler, cache tiers, engine and
IVM the same way the span context propagates, and drained into bounded
Prometheus series at request end — which finally makes the BASELINE
north-star metric (`edges_traversed/sec`) a first-class live
per-tenant series (`dgraph_edges_traversed_total{tenant}`) instead of
a bench artifact.

Design constraints, in PR-7 discipline order:

1. **One pooled struct per request, zero further allocations.**
   `start()` pops a recycled :class:`Ledger` from a bounded free list;
   `finish()` drains it into the metric families, resets it and returns
   it.  `dgraph_ledger_structs_total` counts every ACTUAL construction
   (pool misses), so tests assert a zero delta across warm requests —
   the counter-proved twin of the span layer's zero-allocation guard.
2. **`DGRAPH_TPU_LEDGER=0` is byte-identical**: `start()` returns None,
   every instrumentation site branches on ``current() is None`` first,
   and responses carry no ledger key in any mode unless the caller
   explicitly asked (`?ledger=true` on /query).
3. **Attribution follows execution, not blame.**  A tier-2 result-cache
   hit or a singleflight follower records its cache/coalesced event and
   NO engine numbers — `dgraph_edges_traversed_total` counts work the
   engine actually did, once.  Hop-merged union expansions land on the
   leader (the same cohort-attribution caveat the debug stats and PR-7
   spans document).
4. **Bounded label spaces.**  Tenant goes through qos.metric_label
   (cardinality-capped), routes and stages are fixed small sets.

``stages`` is the request's time by catalogue stage (STAGES below):
every request, sampled or not, says how long it spent at each boundary
from admission to the encoder.  ``host_ms``/``device_ms`` are the
coarse parents: HOST wall-clock time inside host / device routes —
``device_ms`` is not device time.

``device_sync_ms`` is populated only on SAMPLED requests: the
unsampled path never blocks on device results by design (the fetch
overlaps host bookkeeping), so there is nothing to measure without
changing the execution it measures.

Env: ``DGRAPH_TPU_LEDGER`` (default on; read per-request so tests and
operators can flip it live).
"""

from __future__ import annotations

import contextvars
import os
import threading
from typing import Dict, Optional

from dgraph_tpu.utils.metrics import (
    EDGES_TRAVERSED,
    FILTER_PARTS,
    FILTER_STAGE_US,
    LEDGER_BYTES,
    LEDGER_HOP_EDGES,
    LEDGER_HOPS,
    LEDGER_STAGE_US,
    LEDGERS_CREATED,
    SCHED_WAIT_US,
)

_current: "contextvars.ContextVar[Optional[Ledger]]" = contextvars.ContextVar(
    "dgraph_tpu_ledger", default=None
)

# The stage catalogue (docs/deploy.md "Stage catalogue"): one name per
# boundary of a request's time, admission to the socket.  Each is
# bracketed ONCE, by ``obs.stage`` (or, for ``queue``, which crosses
# threads, by two monotonic stamps in SchedRequest.end_queue_wait), and
# no bracket nests in another, so a request's stages sum to no more than
# its wall time.  The same names label dgraph_ledger_stage_us_total and
# the ``dgraph.<stage>`` annotations on the profiler's host plane.
STAGES = (
    "parse", "result_cache", "queue", "merge_wait", "plan", "host_expand",
    "h2d", "dispatch", "fetch", "convert", "assemble", "encode", "handoff",
    "http_write",
    # the write path (serve/server.py, query/engine.py, models/wal.py)
    "write_lock", "write_apply", "write_wal", "refresh",
    # a path search's merged layout given a write (models/arena.py; inside refresh)
    "path_layout",
)
_STAGE_KEYS = tuple((s, s + "_ms") for s in STAGES)
# every label a scraper may diff is there at zero from boot: a family
# absent until first incremented reads as "no such metric" to the first
# scrape of a window
for _s in STAGES:
    LEDGER_STAGE_US.add(_s, 0)
for _d in ("h2d", "d2h"):
    LEDGER_BYTES.add(_d, 0)
# what stage ``queue`` waited for (sched/cohort.py ``SchedRequest``
# ``_book_waits``): a partition of it, not stages of their own —
# dgraph_sched_wait_us_total{cause}, docs/deploy.md "Stage catalogue"
WAIT_CAUSES = ("batch", "worker", "writer", "start", "twin")
for _c in WAIT_CAUSES:
    SCHED_WAIT_US.add(_c, 0)


def enabled() -> bool:
    """The DGRAPH_TPU_LEDGER gate (default ON)."""
    return os.environ.get("DGRAPH_TPU_LEDGER", "1") != "0"


def current() -> Optional["Ledger"]:
    """The calling thread's active ledger, or None (gate off / not in a
    request).  THE hot-path gate: every instrumentation site checks this
    before touching anything else."""
    return _current.get()


class Ledger:
    """One request's resource account.  Only ever constructed on a pool
    miss; every field is reset on release, so a recycled struct carries
    nothing across requests.

    Single-writer by construction: the handler thread owns it until the
    scheduler hands execution to a flush worker (the handler then blocks
    in ``req.wait()``), so plain ``+=`` needs no lock — the same
    hand-off argument SchedRequest.span relies on."""

    __slots__ = (
        "tenant", "edges", "hops", "hop_edges", "host_ms", "device_ms",
        "device_sync_ms", "bytes_h2d", "bytes_d2h", "compiles",
        "cache_hits", "cache_misses", "cache_hit_bytes", "repairs",
        "coalesced", "exchange_bytes", "mesh_ms", "mesh_chips", "stages",
        "waits", "_race_serial",
    )

    # graftcheck tier 3: the pooled ledger is the engine's flagship
    # single-writer hand-off — the lockset witness tracks every scalar
    # slot, and the arm-time wraps on activate()/SchedRequest.complete/
    # fail reset the epoch at exactly the happens-before edges this
    # class's contract names (handler -> flush worker -> handler).
    # ``hops``/``hop_edges``/``stages`` are dicts (item writes bypass
    # __setattr__) and are covered by the same epochs as the scalars they
    # travel with.
    # ``compiles`` is deliberately NOT listed: the jax.monitoring
    # compile listener (obs/device.py) increments it from whichever
    # engine-pool thread triggered the compile, concurrently with the
    # request thread — a lost increment costs one count in a per-
    # request diagnostic (the process-wide dgraph_xla_compiles_total
    # twin is locked), and any guard here would be an import-time lock
    # the witness cannot see.
    __race_fields__ = frozenset({
        "tenant", "edges", "host_ms", "device_ms", "device_sync_ms",
        "bytes_h2d", "bytes_d2h", "cache_hits",
        "cache_misses", "cache_hit_bytes", "repairs", "coalesced",
        "exchange_bytes", "mesh_ms", "mesh_chips",
    })

    def __init__(self):
        LEDGERS_CREATED.add(1)
        self.hops: Dict[str, int] = {}
        self.hop_edges: Dict[str, int] = {}
        self.stages: Dict[str, float] = {}
        self.waits: Dict[str, float] = {}
        self.reset()

    def reset(self) -> None:
        self.tenant = ""
        self.edges = 0
        self.hops.clear()
        self.hop_edges.clear()
        self.host_ms = 0.0
        self.device_ms = 0.0
        self.device_sync_ms = 0.0
        self.bytes_h2d = 0
        self.bytes_d2h = 0
        self.compiles = 0
        self.cache_hits = 0
        self.cache_misses = 0
        self.cache_hit_bytes = 0
        self.repairs = 0
        self.coalesced = 0
        # mesh serving plane (PR 17): wall time inside mesh programs,
        # the model-axis width those programs ran on (per-chip device
        # time = mesh_ms on EVERY chip under SPMD — aggregate chip-time
        # is mesh_ms × mesh_chips), and the estimated cross-chip
        # exchange payload (all_gather/psum traffic) they moved
        self.exchange_bytes = 0
        self.mesh_ms = 0.0
        self.mesh_chips = 0
        # catalogue stage milliseconds (STAGES): fixed keys, zeroed in
        # place — the pooled struct allocates nothing per request
        for s in STAGES:
            self.stages[s] = 0.0
        # stage ``queue`` by cause (WAIT_CAUSES), milliseconds
        for c in WAIT_CAUSES:
            self.waits[c] = 0.0

    # -- instrumentation sites (callers checked current() is not None) ------

    def note_hop(self, route: str, edges: int) -> None:
        self.hops[route] = self.hops.get(route, 0) + 1
        self.hop_edges[route] = self.hop_edges.get(route, 0) + edges

    def note_cache(self, tier: str, event: str, nbytes: int) -> None:
        """One cache-tier probe outcome (tier ∈ hop/result; event is the
        core cache's hit/miss/stale verdict)."""
        if event == "hit":
            self.cache_hits += 1
            self.cache_hit_bytes += int(nbytes)
        else:
            self.cache_misses += 1

    def merge_engine_stats(self, stats: dict) -> None:
        """Fold one engine shell's per-request stats in at completion —
        the single source for edges and stage time, so the ledger can
        never disagree with the debug=true engine breakdown it rides
        beside.  Chain levels and mxu join programs become hop routes
        here (they bypass the per-level expander entry)."""
        self.edges += int(stats.get("edges", 0))
        self.host_ms += (
            stats.get("host_expand_ms", 0.0)
            + stats.get("resolver_expand_ms", 0.0)
            + stats.get("recheck_ms", 0.0)
        )
        self.device_ms += (
            stats.get("device_expand_ms", 0.0)
            + stats.get("chain_ms", 0.0)
            + stats.get("device_order_ms", 0.0)
            + stats.get("kway_ms", 0.0)
            + stats.get("mxu_join_ms", 0.0)
            + stats.get("tile_build_ms", 0.0)
        )
        # the engine-side catalogue stages (plan, host_expand, h2d,
        # dispatch, fetch, convert, assemble, encode) were bracketed into
        # the shell's stats — on the request's thread or on a devguard
        # worker — and join the request's stages here, once
        for s, key in _STAGE_KEYS:
            ms = stats.get(key)
            if ms:
                self.stages[s] += ms
        for part in FILTER_PARTS:
            ms = stats.get(part + "_ms")
            if ms:
                FILTER_STAGE_US.add(part, int(ms * 1e3))
        lv = int(stats.get("chain_fused_levels", 0))
        if lv:
            self.hops["chain"] = self.hops.get("chain", 0) + lv
            self.hop_edges["chain"] = self.hop_edges.get("chain", 0) + int(
                stats.get("chain_edges", 0)
            )
        mxu = sum(
            1 for r in stats.get("join_routes", ())
            if isinstance(r, dict) and r.get("route") == "mxu"
        )
        if mxu:
            self.hops["mxu"] = self.hops.get("mxu", 0) + mxu

    # -- reporting -----------------------------------------------------------

    def to_dict(self) -> dict:
        """The response-extension / span-attr rendering (stable keys,
        ms rounded — this is an operator surface, not a wire format).
        Mesh attribution keys appear only when a mesh program actually
        ran this request — unsharded serving renders the PR-16 dict
        unchanged."""
        d = {
            "edges": self.edges,
            "hops": dict(self.hops),
            "hop_edges": dict(self.hop_edges),
            "host_ms": round(self.host_ms, 3),
            "device_ms": round(self.device_ms, 3),
            "device_sync_ms": round(self.device_sync_ms, 3),
            "bytes_h2d": self.bytes_h2d,
            "bytes_d2h": self.bytes_d2h,
            "compiles": self.compiles,
            "cache_hits": self.cache_hits,
            "cache_misses": self.cache_misses,
            "cache_hit_bytes": self.cache_hit_bytes,
            "repairs": self.repairs,
            "coalesced": self.coalesced,
        }
        if self.mesh_chips:
            d["mesh_ms"] = round(self.mesh_ms, 3)
            d["mesh_chips"] = self.mesh_chips
            d["exchange_bytes"] = self.exchange_bytes
        # the stages this request passed through (a result-cache hit
        # shows ``parse`` and ``result_cache`` alone); ``http_write`` runs
        # after the account is rendered and lives in the metric family
        # only
        d["stages"] = {
            s: round(ms, 3) for s, ms in self.stages.items() if ms
        }
        return d


# bounded free list: under the scheduler's worker model at most
# (handler threads in flight) ledgers are live at once; 256 recycled
# structs cover any sane concurrency and the bound keeps a burst from
# pinning memory forever
_POOL_CAP = 256
_pool: list = []
_pool_lock = threading.Lock()


def start(tenant: str = "") -> Optional[Ledger]:
    """Acquire the request's pooled ledger, or None when the gate is
    off.  The caller owns activation (``activate``/``deactivate``) and
    MUST pair with ``finish``."""
    if not enabled():
        return None
    with _pool_lock:
        led = _pool.pop() if _pool else None
    if led is None:
        led = Ledger()
    led.tenant = tenant
    return led


def note_stage(stage: str, ms: float) -> None:
    """``obs.stage``'s sink for a bracket with no engine stats in scope:
    the active request's account, or — no request active (the HTTP
    handler after run_query returned, an arena built at boot) — the
    metric family directly."""
    led = _current.get()
    if led is not None:
        led.stages[stage] += ms
    else:
        LEDGER_STAGE_US.add(stage, int(ms * 1e3))


def activate(led: Ledger):
    """Install ``led`` as the calling thread's ledger; returns the reset
    token.  The scheduler re-activates the same struct on its flush
    worker thread — one account per request, whatever thread runs it."""
    return _current.set(led)


def deactivate(token) -> None:
    _current.reset(token)


def finish(led: Ledger) -> dict:
    """Drain the ledger into the bounded metric families, recycle the
    struct, and return its final rendering (for span attrs / response
    extensions — taken here, before the reset).  The tenant label is
    cardinality-bounded by qos.metric_label; "" (QoS off) reads as the
    default tenant so the north-star series always has a home."""
    from dgraph_tpu.sched import qos as _qos

    out = led.to_dict()
    label = _qos.metric_label(led.tenant or _qos.DEFAULT_TENANT)
    if led.edges:
        EDGES_TRAVERSED.add(label, led.edges)
    for route, n in led.hops.items():
        LEDGER_HOPS.add(route, n)
    for route, n in led.hop_edges.items():
        if n:
            LEDGER_HOP_EDGES.add(route, n)
    if led.host_ms:
        LEDGER_STAGE_US.add("host", int(led.host_ms * 1e3))
    if led.device_ms:
        LEDGER_STAGE_US.add("device", int(led.device_ms * 1e3))
    if led.device_sync_ms:
        LEDGER_STAGE_US.add("device_sync", int(led.device_sync_ms * 1e3))
    if led.mesh_ms:
        # per-chip attribution: under SPMD every chip runs the program
        # for its full wall time, so "mesh" is the wall clock and
        # "mesh_chip" the aggregate chip-time (wall × width) — the
        # number capacity planning divides HBM-seconds by
        LEDGER_STAGE_US.add("mesh", int(led.mesh_ms * 1e3))
        LEDGER_STAGE_US.add(
            "mesh_chip", int(led.mesh_ms * 1e3) * max(1, led.mesh_chips)
        )
    for s, ms in led.stages.items():
        if ms:
            LEDGER_STAGE_US.add(s, int(ms * 1e3))
    _drain_waits(led)
    if led.bytes_h2d:
        LEDGER_BYTES.add("h2d", led.bytes_h2d)
    if led.bytes_d2h:
        LEDGER_BYTES.add("d2h", led.bytes_d2h)
    if led.cache_hit_bytes:
        LEDGER_BYTES.add("cache_hit", led.cache_hit_bytes)
    if led.exchange_bytes:
        LEDGER_BYTES.add("exchange", led.exchange_bytes)
    led.reset()
    with _pool_lock:
        if len(_pool) < _POOL_CAP:
            _pool.append(led)
    return out


def _drain_waits(led: Ledger) -> None:
    """The causes of stage ``queue`` into dgraph_sched_wait_us_total, in
    integer µs that sum to exactly the µs the stage itself drained: the
    few µs truncation takes off the parts go back to the largest."""
    queue_us = int(led.stages["queue"] * 1e3)
    if not queue_us:
        return
    us = {c: int(ms * 1e3) for c, ms in led.waits.items()}
    top = max(us, key=us.get)
    us[top] = max(0, us[top] + queue_us - sum(us.values()))
    for c, n in us.items():
        if n:
            SCHED_WAIT_US.add(c, n)


def aggregate_summary() -> dict:
    """The /debug/bundle "ledger" section: process-wide aggregates of
    every family the per-request drains feed."""
    return {
        "edges_by_tenant": EDGES_TRAVERSED.snapshot(),
        "hops_by_route": LEDGER_HOPS.snapshot(),
        "hop_edges_by_route": LEDGER_HOP_EDGES.snapshot(),
        "stage_us": LEDGER_STAGE_US.snapshot(),
        "bytes": LEDGER_BYTES.snapshot(),
        "structs_created": LEDGERS_CREATED.value(),
    }
