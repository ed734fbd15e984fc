"""Query execution.

Equivalent of the reference's query.ProcessQuery / ProcessGraph
(query/query.go:2182,1579) and worker/task.go's task serving, re-designed
level-batched: each (level × predicate) becomes ONE device CSR gather
over the arena (ops.expand_csr) instead of per-key posting-list loops,
filters combine uid sets with the device set kernels, and ordering uses
value arenas.  Host code orchestrates and handles string-shaped work
(JSON values, lossy re-checks) — the same host/device split the
reference draws at the ServeTask boundary (SURVEY.md §2c).
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from dgraph_tpu import gql, ivm, obs, ops
from dgraph_tpu.obs import ledger as _ledger
from dgraph_tpu.gql.ast import (
    FilterTree,
    Function,
    GraphQuery,
    MathTree,
    referenced_preds,
)
from dgraph_tpu.models.arena import ArenaManager
from dgraph_tpu.models.store import PostingStore
from dgraph_tpu.models.types import TypeID, TypedValue, numeric, sort_key
from dgraph_tpu.query.functions import FuncResolver, QueryError
from dgraph_tpu.query.subgraph import SubGraph, build_subgraph
from dgraph_tpu.query import outputnode, planner
from dgraph_tpu.utils import devguard, planconfig
from dgraph_tpu.utils.failpoints import fail
from dgraph_tpu.utils.metrics import DEVICE_FAILOVER

_EMPTY = np.empty(0, dtype=np.int64)


def _make_packed_expand():
    from functools import partial

    import jax
    import jax.numpy as jnp

    @partial(jax.jit, static_argnames=("cap",))
    def run(offsets, dst, rows, cap):
        out, seg, _t = ops.expand_csr(offsets, dst, rows, cap)
        return jnp.concatenate([out, seg])

    return run


# the non-TPU per-level program with out|seg concatenated on device: one
# host fetch instead of two (each fetch pays a full transport round trip).
# Module-level so the jit cache persists across queries.
_packed_expand_csr = _make_packed_expand()


def _unpack_out_seg(packed: np.ndarray, cap: int, total: int, n: int):
    """The packed ``out|seg`` buffer of the csr / resident programs →
    the engine's (out_flat, seg_ptr) uid matrix."""
    out = packed[:total].astype(np.int64)
    seg = packed[cap : cap + total].astype(np.int64)
    counts = np.bincount(seg, minlength=n)
    seg_ptr = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(counts, out=seg_ptr[1:])
    return out, seg_ptr


def _pallas_interpret() -> bool:
    """Interpret-mode flag for the resident Pallas program: only the CPU
    backend (the tests that pick the resident program by constructor
    argument) runs the kernel under the interpreter.  On a TPU the kernel
    compiles through Mosaic or the query fails — never the interpreter
    on the chip."""
    import jax

    return jax.default_backend() == "cpu"


def _resident_program(arena):
    """The TPU's per-level program: walk the CSR pinned in HBM
    (ops/pallas_gather.py over ResidentArena's epoch buffers).  No
    ``ensure_device`` restage rides the dispatch; only the frontier
    crosses h2d and only the packed result crosses d2h."""
    ra = arena.resident()
    interp = _pallas_interpret()
    return lambda rows_d, cap: ra.expand_packed(rows_d, cap, interpret=interp)


def _csr_program(arena):
    """Every other backend's per-level program: ops.expand_csr over the
    staged arena tensors."""
    arena.ensure_device()  # re-upload after host deltas
    return lambda rows_d, cap: _packed_expand_csr(
        arena.offsets, arena.dst, rows_d, cap
    )


# program name (also the hop's route label) → arena → (rows_d, cap) →
# packed out|seg device buffer.  Both take any frontier order.
_PROGRAMS = {"resident": _resident_program, "csr": _csr_program}


def _platform_program() -> str:
    import jax

    return "resident" if jax.default_backend() == "tpu" else "csr"


def _fresh_stats() -> dict:
    """Per-request engine stats: edges traversed + per-stage wall time
    (ms) — the per-query device/host breakdown the reference exposes
    through --trace + pprof (cmd/dgraph/main.go:181); surfaced in the
    latency map when the request carries debug=true."""
    return {
        "edges": 0,
        "chain_fused_levels": 0,
        "chain_edges": 0,  # edges those fused levels traversed
        # why fused-chain attempts fell back to per-level execution
        # (bounded list, one entry per rejected attempt; empty = fused or
        # never attempted) — the eligibility logic must be debuggable at
        # benchmark scale, not a silent no (VERDICT r4 weak #2)
        "chain_reject": [],
        # MXU join tier (query/joinplan.py): one entry per route decision
        # (mxu generic-join vs pairwise expansion, with the cost
        # estimates that drove it — the chain_reject discipline), plus
        # host-vs-device counts for size-gated k-way intersections
        "join_routes": [],
        "kway_device": 0,
        "kway_host": 0,
        "host_expand_ms": 0.0,
        "device_expand_ms": 0.0,
        "kway_ms": 0.0,
        "resolver_expand_ms": 0.0,
        "chain_ms": 0.0,
        "device_order_ms": 0.0,
        "tile_build_ms": 0.0,
        "mxu_join_ms": 0.0,
        # root-level `first: k` early termination (sched/qos.py gate):
        # number of root filters that stopped after enough survivors
        "first_early_exit": 0,
        # device fault domain (utils/devguard.py): dispatches this
        # request hot-failed over to a host route (wedged/sick/OOM
        # device) — nonzero stamps the response's degraded.device
        # annotation, the PR 5 stale-read disclosure device-flavored
        "device_failover": 0,
    }


class DeviceExpander:
    """Per-level expansion routing: ONE device program (or one host
    numpy pass) per (level × predicate).

    Routing order per call: mesh-sharded (big multi-device predicates) →
    host numpy (below the planner's break-even, transport-bound) → the
    platform's device program: ``resident`` on a TPU backend (the Pallas
    gather over the CSR pinned in HBM), ``csr`` anywhere else
    (ops.expand_csr over the staged arena).  Both accept any frontier
    order.  Two routes live ABOVE this per-level entry and take whole
    chains before it runs: ``chain`` (query/chain.py — consecutive
    levels fused into one device program) and the ``mxu`` join tier
    (query/joinplan.py + ops/spgemm.py — light chains, cyclic/triangle
    patterns included, as one blocked boolean-matmul program; its hop
    spans carry ``route:mxu`` with the tile-build vs matmul time split).

    ``program`` names the device program; the default is the platform's.
    Tests pass the other name to run the resident kernel (interpret
    mode) on the CPU backend.
    """

    def __init__(self, engine: "QueryEngine", program: Optional[str] = None):
        self.engine = engine
        self.program = program or _platform_program()
        # cross-session hop coalescing: the cohort scheduler
        # (sched/scheduler.py) installs one HopMerger per cohort so
        # same-(arena, predicate, direction) expansions from different
        # sessions sharing a snapshot merge into ONE dispatch
        self.hop_merger = None
        # flight-recorder state (obs/spans.py): _span is the SAMPLED
        # request's current hop span (None on the unsampled hot path —
        # the branch every trace hook takes first), _route names the
        # routing decision the last expansion took so the hop span can
        # say WHERE the time went, not just how much
        self._span = None
        self._route = ""
        # last host-vs-device decision made by the planner inside
        # _expand_one_inner; the _expand_one wrapper closes it with the
        # measured stage latency (post-hoc mispredict check + online
        # rate refinement)
        self._expand_dec = None

    def expand(
        self, arena, src: np.ndarray, attr: str = "", reverse: bool = False
    ) -> Tuple[np.ndarray, np.ndarray]:
        """Per-level expansion entry.  When the request is SAMPLED
        (obs/spans.py), each call records one ``hop`` span carrying the
        predicate, frontier size, edges traversed, the route the
        expansion took (cache/merged/mesh/host/resident/csr; the
        chain-level ``mxu`` route emits its own hop span upstream) and
        the device-time split; the unsampled path branches away before
        any span object exists.

        This call IS the hop-dispatch boundary: the cooperative
        CancelToken (sched/qos.py) is checkpointed here — a cancelled,
        deadline-lapsed or disconnected request stops BEFORE its next
        dispatch, never inside a jitted program — and the ``engine.hop``
        failpoint lets chaos tests stretch exactly this seam."""
        self.engine.checkpoint()
        fail.point("engine.hop")
        sp = obs.current_span()
        if sp is None:  # unsampled hot path: zero allocations, async dispatch
            out, seg_ptr = self._expand_cached(arena, src, attr, reverse)
            led = _ledger.current()
            if led is not None:
                # two dict bumps per hop on the pooled struct — the
                # ledger's whole unsampled footprint at this seam
                led.note_hop(self._route or "csr", len(out))
            return out, seg_ptr
        st = self.engine.stats
        e0, d0, h0 = st["edges"], st["device_expand_ms"], st["host_expand_ms"]
        self._route = ""
        with sp.child("hop") as hs:
            self._span = hs
            try:
                out, seg_ptr = self._expand_cached(arena, src, attr, reverse)
            finally:
                self._span = None
            hs.set_attr("pred", attr)
            if reverse:
                hs.set_attr("reverse", True)
            hs.set_attr("n_src", int(len(src)))
            hs.set_attr("edges", int(st["edges"] - e0))
            hs.set_attr("route", self._route)
            dm = st["device_expand_ms"] - d0
            hm = st["host_expand_ms"] - h0
            if dm:
                hs.set_attr("device_ms", round(dm, 3))
            if hm:
                hs.set_attr("host_ms", round(hm, 3))
        led = _ledger.current()
        if led is not None:
            led.note_hop(self._route or "csr", len(out))
        return out, seg_ptr

    def _expand_cached(
        self, arena, src: np.ndarray, attr: str = "", reverse: bool = False
    ) -> Tuple[np.ndarray, np.ndarray]:
        """Per-level expansion entry: tier-1 hop cache first (a repeat
        expansion over an unchanged store snapshot returns the memoized
        arrays — zero dispatch, zero transport, zero new programs, so
        the compile-count guards hold by construction), then the cohort
        hop merger when one is installed (cross-session dispatch
        coalescing) AND the expansion is big enough to be device-routed
        — merging a host-path numpy expansion costs more in union
        bookkeeping than the per-call overhead it saves, while a device
        dispatch (~100µs-1ms of fixed cost) amortizes beautifully."""
        hc = self.engine.arenas.hop_cache
        ver = hkey = None
        if hc is not None and attr and len(src):
            # pre-screen on the ESTIMATED result bytes: a frontier whose
            # expansion cannot be admitted (LFU-with-aging refuses
            # over-cap entries so one megaquery can't evict the hot
            # head) should not even pay for the digest
            est = (len(src) + len(src) * arena.avg_degree) * 8
            if est <= hc.max_entry_bytes:
                # predicate-scoped freshness (ivm/versions.py): the
                # entry keys on THIS predicate's last-mutation version,
                # so writes to other predicates leave it a hit — and
                # small deltas to this one REPAIR it in place
                # (ArenaManager._try_apply_delta) instead of killing it
                ver = ivm.hop_version(self.engine.store, attr)
        if ver is not None:
            # one digest per call: the miss path re-uses it for the fill
            hkey = hc.key_for(arena, attr, reverse, src)
            cached = hc.get(arena, attr, reverse, src, ver, key=hkey)
            if cached is not None:
                self.engine.stats["edges"] += len(cached[0])
                self._route = "cache"
                return cached
        if (
            self.hop_merger is not None
            and attr
            and len(src)
            # merge only where the union expansion would device-route:
            # calibrated break-even by default, the static
            # expand_device_min when the planner is off / knob pinned
            and planner.merge_gate(
                len(src) * arena.avg_degree, self.engine.expand_device_min
            )
        ):
            self._route = "merged"
            out, seg_ptr = self.submit_hop(arena, src, attr, reverse)
        else:
            out, seg_ptr = self._expand_one(
                arena, src, attr=attr, reverse=reverse
            )
        if ver is not None:
            # ``ver`` was read BEFORE the expansion: if a mutation raced
            # us (embedded engines without the server's read lock), the
            # entry lands under the older version and can never be hit
            # — stale-keyed, not stale-served
            hc.put(arena, attr, reverse, src, ver, out, seg_ptr, key=hkey)
        return out, seg_ptr

    def submit_hop(
        self, arena, src: np.ndarray, attr: str = "", reverse: bool = False
    ) -> Tuple[np.ndarray, np.ndarray]:
        """Rendezvous this level's expansion with concurrent cohort
        members: same-(arena, predicate, direction) submissions merge
        into one union-frontier dispatch, and each session gets its
        exact per-source segments back (sched/cohort.py::HopMerger —
        merging is deterministic-per-row, so results are byte-identical
        to solo expansion)."""
        key = (attr, bool(reverse), id(arena))
        return self.hop_merger.submit(
            key,
            src,
            lambda union: self._expand_one(
                arena, union, attr=attr, reverse=reverse
            ),
        )

    def _expand_one(
        self, arena, src: np.ndarray, attr: str = "", reverse: bool = False
    ) -> Tuple[np.ndarray, np.ndarray]:
        """Wrapper around the actual expansion: closes the planner's
        host-vs-device decision (made inside, where the exact fan-out is
        known) with the measured stage latency — the post-hoc mispredict
        check and the online rate refinement both feed off this."""
        st = self.engine.stats
        before = st["device_expand_ms"] + st["host_expand_ms"]
        self._expand_dec = None
        out, seg_ptr = self._expand_one_inner(
            arena, src, attr=attr, reverse=reverse
        )
        dec = self._expand_dec
        if dec is not None:
            self._expand_dec = None
            actual_ms = st["device_expand_ms"] + st["host_expand_ms"] - before
            planner.note_outcome(dec, actual_ms * 1e3)
        return out, seg_ptr

    # -- device fault domain (utils/devguard.py) ----------------------------

    def _count_failover(self, route: str) -> None:
        """One hot failover off the device plane: per-request stat (the
        response's degraded.device stamp) + the alertable series."""
        devguard.count_failover(route, self.engine.stats)

    def _run_guarded(self, op: str, fn):
        """Run one dispatch+fetch closure under the device guard.
        Returns the closure's result, or None after a classified device
        fault — the caller then takes the host route (byte-identical by
        the parity contracts).  HBM OOM gets ArenaManager LRU eviction
        plus ONE retry before giving up on the device; DGRAPH_TPU_
        DEVGUARD=0 calls the closure inline (legacy behavior, faults
        propagate)."""
        g = devguard.get()
        if not devguard.enabled():
            return fn()
        try:
            return g.run(op, fn)
        except devguard.DeviceFaultError as e:
            if e.kind == "oom" and self.engine.arenas.evict_for_oom():
                # pressure valve: the budget is an estimate, the
                # allocator's verdict is ground truth — free LRU arenas
                # and re-prove the dispatch once
                DEVICE_FAILOVER.add("evict_retry")
                try:
                    return g.run(op, fn)
                except devguard.DeviceFaultError:
                    pass
            # the planner's expand decision must NOT be closed with the
            # fallback's host latency — a failed dispatch is not a rate
            # sample for the device route
            self._expand_dec = None
            self._count_failover("host")
            return None

    def _host_fallback(self, arena, rows) -> Tuple[np.ndarray, np.ndarray]:
        """The hot-failover landing: serve this level off the host CSR
        mirror — the same vectorized numpy route small expansions take,
        byte-identical to every device route by the parity contracts."""
        eng = self.engine
        self._route = "host"
        with obs.stage(eng.stats, "host_expand_ms"):
            out, seg_ptr = arena.expand_host(rows)
        eng.stats["edges"] += len(out)
        return out, seg_ptr

    def _mesh_expand(self, arena, src, attr, reverse, cap, total):
        """Sharded expansion under the "mesh" fault domain, dispatched
        through the mesh serving plane (dgraph_tpu/mesh::MeshExecutor —
        the executor carries the ledger's per-chip/exchange attribution
        and the devguard bracket).  Returns (out, seg_ptr), or None
        when the mesh is latched sick or a chip fault/wedged collective
        was classified — the caller then re-plans this level unsharded
        (single-device or host), so a lost mesh chip degrades one
        route, not the node."""
        eng = self.engine
        ex = eng.arenas.mesh_executor()
        if ex is None or not ex.allowed():
            self._count_failover("unsharded")
            return None
        # route:mesh is planner-priced: the decision records the mesh
        # estimate vs the best unsharded alternative and note_outcome
        # (closed by _expand_one with the measured stage delta) refines
        # mesh_edge_us / flags mispredicts
        _, dec = planner.mesh_route(total, ex.width)
        if dec is not None:
            planner.record(eng.stats, dec)
            self._expand_dec = dec
        try:
            out, seg_ptr = ex.expand(attr, reverse, src, cap, eng.stats)
        except devguard.DeviceFaultError:
            # a failed dispatch is not a rate sample for the mesh route
            self._expand_dec = None
            self._count_failover("unsharded")
            return None
        self._route = "mesh"
        eng.stats["edges"] += len(out)
        return out, seg_ptr

    def _expand_one_inner(
        self, arena, src: np.ndarray, attr: str = "", reverse: bool = False
    ) -> Tuple[np.ndarray, np.ndarray]:
        """One batched device gather for a whole level (the TPU replacement
        for the reference's per-key loop, worker/task.go:287-440).  Big
        predicates on a multi-device mesh expand sharded: each device owns
        a uid range of rows, results merge via all_gather (SURVEY §2b —
        intra-predicate sharding the reference lacks).

        Every device dispatch+fetch below runs bracketed by the device
        guard (utils/devguard.py): a wedged dispatch times out on the
        watchdog instead of blocking this worker forever, a classified
        fault hot-fails the level over to ``_host_fallback``, and a
        sick backend is priced out by the planner (or shed at the seam
        on the static path) until the half-open probe re-admits it."""
        eng = self.engine
        n = len(src)
        if n == 0 or arena.n_edges == 0:
            self._route = "empty"
            return _EMPTY, np.zeros(n + 1, dtype=np.int64)
        rows = arena.rows_for_uids_host(src)
        total = int(arena.degree_of_rows(rows).sum())
        if total == 0:
            self._route = "empty"
            return _EMPTY, np.zeros(n + 1, dtype=np.int64)
        cap = ops.bucket(total)
        if attr and eng.arenas.use_mesh_for(arena):
            got = self._mesh_expand(arena, src, attr, reverse, cap, total)
            if got is not None:
                return got
            # mesh chip-loss / wedged collective: fall through — the
            # level re-plans unsharded onto the routes below
        # host-vs-device: calibrated break-even by default (the
        # size-adaptive routing the reference does per-intersection,
        # algo/uidlist.go:56-64, priced from MEASURED rates instead of a
        # magic number); static expand_device_min compare when the
        # planner is off or the knob is pinned
        use_device, dec = planner.expand_route(
            total, eng.expand_device_min,
            resident=self.program == "resident",
        )
        if dec is not None:
            planner.record(eng.stats, dec)
            self._expand_dec = dec
        if use_device and not devguard.get().allowed():
            # sick device on the STATIC path (planner off / knob
            # pinned — the armed planner already priced it out above)
            use_device = False
            self._count_failover("host")
        if not use_device:
            # small expansion: vectorized numpy over the host CSR mirror —
            # a device dispatch costs a transport round trip that dwarfs
            # the work
            return self._host_fallback(arena, rows)
        self._route = self.program

        def _dispatch():
            fail.point("device.hop")
            # staging inside the bracket: an HBM OOM uploading the arena
            # classifies like a dispatch OOM.  The closure returns plain
            # data — ledger/span writes happen on the CALLER thread
            # below, so an abandoned (wedged) worker waking up later can
            # never scribble on a pooled struct a newer request now owns
            st = eng.stats
            with obs.stage(st, "device_expand_ms"):
                run = _PROGRAMS[self.program](arena)
                with obs.stage(st, "h2d_ms"):
                    rows_d = jnp.asarray(ops.pad_rows(rows, ops.bucket(n)))
                with obs.stage(st, "dispatch_ms"):
                    dev = run(rows_d, cap)
                # sampled: split pure device time from the host fetch
                # (the unsampled path stays dispatch-async — asarray
                # overlaps compute with bookkeeping)
                sync_ms = (
                    obs.block_ready_ms(dev)
                    if self._span is not None else None
                )
                # one fetch: out|seg concatenated on device
                with obs.stage(st, "fetch_ms"):
                    return np.asarray(dev), sync_ms

        got = self._run_guarded("device.hop", _dispatch)
        if got is None:
            return self._host_fallback(arena, rows)
        packed, sync_ms = got
        led = _ledger.current()
        if sync_ms is not None and self._span is not None:
            self._span.set_attr("device_sync_ms", round(sync_ms, 3))
            if led is not None:
                led.device_sync_ms += sync_ms
        if led is not None:
            led.bytes_h2d += int(rows.nbytes)
            led.bytes_d2h += int(packed.nbytes)
        with obs.stage(eng.stats, "convert_ms"):
            out, seg_ptr = _unpack_out_seg(packed, cap, total, n)
        eng.stats["edges"] += len(out)
        return out, seg_ptr


class QueryEngine:
    """One engine instance per store; thread-unsafe by design (the serving
    layer serializes, as the reference does per-request goroutines over
    shared immutable posting state)."""

    def __init__(
        self,
        store: PostingStore,
        mesh=None,
        shard_threshold: int = 4096,
        arenas=None,
        arena_budget_bytes=None,
    ):
        self.store = store
        # ``arenas`` shares a warm ArenaManager between engine instances:
        # the serving layer creates one cheap engine per request (its own
        # stats/traversal state) over the process-wide arena cache, the
        # way the reference runs per-request goroutines over the shared
        # posting lcache (query/query.go:1684, posting/lists.go)
        self.arenas = (
            arenas
            if arenas is not None
            else ArenaManager(
                store,
                mesh=mesh,
                shard_threshold=shard_threshold,
                budget_bytes=arena_budget_bytes,
            )
        )
        # minimum estimated fan-out before chains fuse into one device
        # program (below it, per-level host orchestration wins on
        # latency).  The value is the STATIC gate: while it sits at the
        # planconfig default and DGRAPH_TPU_PLANNER is on, the
        # calibrated cost model (query/planner.py) makes the call
        # instead; assigning it (tests, bench A/B arms) pins the gate
        self.chain_threshold = planconfig.chain_threshold()
        # chain decision awaiting its post-hoc latency check (see
        # _exec_child's chain_ms bracket)
        self._pending_chain_dec = None
        # per-level expansion routing — see DeviceExpander
        self.expander = DeviceExpander(self)
        # below this fan-out an expansion runs as vectorized numpy on the
        # host CSR mirror: a device dispatch pays a fixed round trip
        # (calibrate.py measures it) that only amortizes on big gathers.
        # Same adaptive-by-size philosophy as
        # the reference's intersection-algorithm choice (uidlist.go:56-64).
        # Stored on the ArenaManager so FuncResolver shares the policy.
        # per-request execution stats (reset by run_parsed): edge traversal
        # counts + per-stage timings feed bench_engine and the debug
        # latency map
        self.stats = _fresh_stats()
        # --dumpsg support: when the serving layer sets dump_shapes, each
        # execute() stores the CHEAP execution-shape dicts (never the
        # result-bearing SubGraph trees — those would pin whole result
        # payloads on a long-lived engine) in last_dump, reset per request
        self.dump_shapes = False
        self.last_dump = None
        # cooperative cancellation (sched/qos.py): the scheduler installs
        # the request's CancelToken here; checkpoint() probes it at
        # hop-dispatch boundaries.  None (embedded engines, QoS off)
        # costs one attribute read per checkpoint.
        self.cancel = None

    def checkpoint(self) -> None:
        """Cooperative cancellation checkpoint: raises
        QueryCancelledError when this request's token flipped (deadline
        lapse, client disconnect, /admin/cancel).  Placed at
        hop-dispatch boundaries only — a dispatched device program
        always completes, so cancellation latency is bounded by one
        hop.  The graftlint rule ``unchecked-hop-loop`` enforces a
        checkpoint in every query/ loop that drives the expander.

        Segmented dataflow (PR 18): a checkpoint is also a scheduler
        yield point — after the token probe it offers the seam to a
        queued higher-priority cohort (sched/segments.py), so per-level
        hop loops preempt at hop boundaries exactly like the fused
        drivers preempt at segment seams."""
        tok = self.cancel
        if tok is not None:
            tok.check()
        from dgraph_tpu.sched import segments as _segments

        ctx = _segments.current()
        if ctx is not None and ctx.preempt is not None:
            ctx.preempt()

    @property
    def expand_device_min(self) -> int:
        return self.arenas.expand_device_min

    @expand_device_min.setter
    def expand_device_min(self, v: int) -> None:
        self.arenas.expand_device_min = v

    # -- public ------------------------------------------------------------

    def run(self, text: str, variables: Optional[Dict[str, str]] = None) -> dict:
        """Parse and execute a request; returns the JSON-able response dict
        (the analog of ProcessWithMutation + ToFastJSON)."""
        return self.run_parsed(gql.parse(text, variables))

    def run_parsed(self, parsed: "gql.ParsedResult") -> dict:
        """Execute an already-parsed request — the single request pipeline
        shared by the embedded path (run) and the HTTP server."""
        self.stats = _fresh_stats()
        self.last_dump = None
        # segmented dataflow (PR 18): arm the fused drivers' seams for
        # this request.  A scheduler-installed context contributes the
        # preempt hook (and the token it registered); with none active
        # (embedded engines, DGRAPH_TPU_SCHED=0) a token-only context
        # still bounds mid-chain cancellation to one segment.  Either
        # way the STATS binding is re-made here — the line above just
        # replaced the dict the outer context captured.
        from dgraph_tpu.sched import segments as _segments

        outer = _segments.current()
        prev = _segments.activate(_segments.SegmentContext(
            token=outer.token if outer is not None else self.cancel,
            preempt=outer.preempt if outer is not None else None,
            stats=self.stats,
        ))
        try:
            return self._run_parsed_inner(parsed)
        finally:
            _segments.deactivate(prev)

    def _run_parsed_inner(self, parsed: "gql.ParsedResult") -> dict:
        out: dict = {}
        if parsed.mutation is not None:
            from dgraph_tpu.serve.mutations import (
                apply_mutation,
                format_assigned_uids,
            )

            # stage write_apply: quads to edges, blank nodes to uids, the
            # store, its journals and dirty marks (the WAL's appends inside
            # it are stage write_wal, carved out: models/wal.py)
            with obs.stage(None, "write_apply_ms"):
                blanks = apply_mutation(self.store, parsed.mutation)
            if blanks:
                # assigned blank-node uids, as the reference's mutation
                # response carries (protos AssignedUids)
                out["uids"] = format_assigned_uids(blanks)
        if parsed.schema_request is not None:
            out["schema"] = self._schema_response(parsed.schema_request)
        if parsed.queries:
            out.update(self.execute(parsed))
            # graceful degradation (ClusterStore.degraded_info): when any
            # owner group's snapshots are being served from cache because
            # the owners are unreachable, the response says so — clients
            # see stale-but-correct data WITH a freshness disclosure
            # instead of an error page (JSON extension; gRPC mirrors it
            # as a dgraph-degraded trailer, serve/grpc_server.py).
            # Scoped to the predicates THIS query can read (None = not
            # statically knowable, e.g. expand(): node-wide view) so a
            # purely-local query is never branded stale.  Passed as a
            # thunk: the AST walk only runs when something IS degraded
            deg = getattr(self.store, "degraded_info", None)
            if deg is not None:
                info = deg(preds=lambda: referenced_preds(parsed.queries))
                if info:
                    out["degraded"] = info
            # device fault domain (utils/devguard.py): a request that
            # hot-failed device dispatches over to host routes says so —
            # the results are byte-identical (parity contracts), only
            # slower, and the client deserves the same freshness-style
            # disclosure stale reads carry.  Absent on every fault-free
            # request (and under DGRAPH_TPU_DEVGUARD=0), so the healthy
            # response stays byte-identical.
            if self.stats.get("device_failover"):
                out.setdefault("degraded", {})["device"] = {
                    "failovers": int(self.stats["device_failover"]),
                    "domains": {
                        d: {
                            "state": s["state"],
                            "last_fault": s["last_fault"],
                            "retry_after": s["cooldown_s"],
                        }
                        for d, s in devguard.summary().items()
                        if s["state"] != "healthy" or s["faults"]
                    },
                }
            # elastic mesh fault domain (mesh/fault.py): a request served
            # on a SURVIVING sub-mesh — or drained-and-resumed across an
            # epoch flip — carries the epoch + capacity disclosure.  The
            # results are byte-identical (placement invisibility +
            # program parity contracts); only capacity is degraded.
            # gRPC mirrors the epoch as a dgraph-mesh-epoch trailer.
            if self.stats.get("mesh_degraded"):
                out.setdefault("degraded", {})["mesh"] = dict(
                    self.stats["mesh_degraded"]
                )
        elif parsed.mutation is not None and "schema" not in out:
            out["code"] = "Success"
            out["message"] = "Done"
        return out

    def execute(self, parsed: gql.ParsedResult) -> dict:
        uid_vars: Dict[str, np.ndarray] = {}
        value_vars: Dict[str, Dict[int, TypedValue]] = {}
        with obs.stage(self.stats, "plan_ms"):
            blocks = [build_subgraph(q) for q in parsed.queries]
        deps = parsed.query_vars

        done = [False] * len(blocks)
        out: dict = {}
        for _round in range(len(blocks) + 1):
            progressed = False
            for i, sg in enumerate(blocks):
                if done[i]:
                    continue
                defines = deps[i][0] if i < len(deps) else []
                needs = deps[i][1] if i < len(deps) else []
                # a block may consume vars it defines itself (math over
                # sibling-defined vars); only external needs gate scheduling
                if any(
                    n not in uid_vars and n not in value_vars and n not in defines
                    for n in needs
                ):
                    continue
                self._exec_block(sg, uid_vars, value_vars)
                done[i] = True
                progressed = True
            if all(done):
                break
            if not progressed:
                raise QueryError("circular variable dependency between blocks")

        if self.dump_shapes:
            from dgraph_tpu.query.subgraph import dump_dict

            self.last_dump = [dump_dict(sg) for sg in blocks]
        with obs.stage(self.stats, "encode_ms"):
            for sg in blocks:
                if sg.params.is_internal:
                    continue
                name = sg.params.alias or "me"
                if sg.params.is_shortest:
                    outputnode.encode_path(self.store, sg, out)
                    continue
                out.setdefault(name, []).extend(
                    outputnode.encode_block(self.store, sg)
                )
        return out

    # -- block execution ---------------------------------------------------

    def _exec_block(self, sg: SubGraph, uid_vars, value_vars):
        resolver = FuncResolver(
            self.store, self.arenas, uid_vars, value_vars, stats=self.stats,
            cancel=self.cancel,
        )
        # var blocks are never encoded → chains under them may skip result
        # matrices entirely (light mode, query/chain.py)
        self._cur_block_internal = bool(sg.params.is_internal)
        if sg.params.is_shortest:
            from dgraph_tpu.query.shortest import shortest_path

            shortest_path(self, sg, resolver)
            self._collect_vars(sg, uid_vars, value_vars)
            return
        # stage plan: the block's root — function, filter, order — on
        # the host, before any child expands (a root function that
        # itself expands rides resolver_expand_ms / kway_ms inside it)
        with obs.stage(self.stats, "plan_ms"):
            dest = self._root_uids(sg, resolver)
            if sg.filter is not None:
                dest = self._apply_root_filter(sg, dest, resolver)
            dest = self._order_and_paginate_root(sg, dest, value_vars)
        sg.dest_uids = dest
        if sg.params.is_groupby:
            from dgraph_tpu.query.groupby import process_groupby

            process_groupby(self, sg, value_vars)  # root @groupby
        elif sg.params.is_recurse:
            from dgraph_tpu.query.recurse import recurse

            recurse(self, sg, resolver)
        else:
            self._exec_children(sg, resolver, uid_vars, value_vars)
        self._collect_vars(sg, uid_vars, value_vars)

    def _root_uids(self, sg: SubGraph, resolver: FuncResolver) -> np.ndarray:
        if sg.func is None:
            # func-less block: legal when every child is an aggregation /
            # math / val fetch (the reference's aggregation-only blocks,
            # e.g. `total() { s as sum(val(c)) }`)
            if sg.children and all(
                c.attr in ("val", "math") or c.params.agg_func for c in sg.children
            ):
                return _EMPTY
            raise QueryError(f"block {sg.params.alias!r} needs func: or id:")
        return resolver.resolve(sg.func)

    # -- children ----------------------------------------------------------

    def _exec_children(self, sg: SubGraph, resolver, uid_vars, value_vars):
        src = sg.dest_uids
        self._expand_expand_nodes(sg, value_vars)
        for child in sg.children:
            self.checkpoint()
            self._exec_child(child, src, resolver, uid_vars, value_vars)
        if sg.params.cascade and sg.children:
            self._cascade_prune(sg)

    def _cascade_prune(self, sg: SubGraph):
        """Execution-time @cascade: drop uids from dest_uids (and the uid
        matrix) that lack a result in ANY non-internal child — so vars
        bound under @cascade see the pruned set, not just the encoder
        (populateVarMap, query.go:1330-1350)."""
        dest = sg.dest_uids
        if not len(dest):
            return
        keep_mask = np.ones(len(dest), dtype=bool)
        for child in sg.children:
            if child.params.is_internal or child.attr in ("_uid_", "uid"):
                continue
            if child.counts is not None:
                continue  # counts exist for every src uid
            if child.values:
                # one vectorized membership probe per child instead of a
                # dict-lookup per (dest uid × child) — @cascade on a wide
                # result was O(U×V) python
                vk = np.fromiter(
                    child.values.keys(), dtype=np.int64, count=len(child.values)
                )
                has = np.isin(dest, vk)
            elif len(child.seg_ptr) > 1:
                # child expanded with dest as its src: row-degree > 0
                degs = np.diff(child.seg_ptr)
                has = (degs > 0) if len(degs) == len(dest) else np.zeros(
                    len(dest), dtype=bool
                )
            else:
                has = np.zeros(len(dest), dtype=bool)
            keep_mask &= has
            if not keep_mask.any():
                break
        if keep_mask.all():
            return
        sg.dest_uids = dest[keep_mask]
        if len(sg.out_flat):
            self._mask_matrix(sg, sg.dest_uids)

    def _expand_expand_nodes(self, sg: SubGraph, value_vars):
        """expand(_all_) / expand(val(v)) → concrete children
        (query/query.go:1780-1813)."""
        import copy

        if not any(c.params.expand for c in sg.children):
            return
        new_children: List[SubGraph] = []
        for c in sg.children:
            if not c.params.expand:
                new_children.append(c)
                continue
            if c.params.expand == "_all_":
                preds = [p for p in self.store.predicates() if not p.startswith("_")]
            else:
                vmap = value_vars.get(c.params.expand, {})
                names = set()
                for tv in vmap.values():
                    v = tv.value
                    names.update(v if isinstance(v, list) else [v])
                preds = sorted(names)
            for pr in preds:
                nc = SubGraph(attr=pr)
                nc.children = [copy.deepcopy(g) for g in c.children]
                new_children.append(nc)
        sg.children = new_children

    def _exec_child(self, child: SubGraph, src: np.ndarray, resolver, uid_vars, value_vars):
        self._exec_child_inner(child, src, resolver, uid_vars, value_vars)
        # bind vars immediately: later siblings (math, aggregations) and
        # later blocks read them (populateVarMap happens per-node in the
        # reference too, query/query.go:1755 assignVars)
        self._bind_var(child, uid_vars, value_vars)

    def _bind_var(self, sg: SubGraph, uid_vars, value_vars):
        p = sg.params
        if p.var:
            if sg.counts is not None:
                value_vars[p.var] = {
                    int(u): TypedValue(TypeID.INT, int(c))
                    for u, c in zip(sg.src_uids.tolist(), sg.counts.tolist())
                }
            elif sg.values:
                value_vars[p.var] = dict(sg.values)
            elif len(sg.dest_uids):
                uid_vars[p.var] = sg.dest_uids
            else:
                uid_vars.setdefault(p.var, _EMPTY)
        if p.facets and p.facets.aliases and sg.edge_facets:
            for key, var in p.facets.aliases.items():
                m = {}
                for (s, d), fs in sg.edge_facets.items():
                    if key in fs:
                        m[int(d)] = fs[key]
                value_vars[var] = m

    def _exec_leaf(self, child: SubGraph, src: np.ndarray, resolver, value_vars) -> bool:
        """The children that expand nothing: uid, val(), math(),
        _predicate_, checkpwd, count(pred) and value leaves.  True where
        ``child`` was one of them (and is done)."""
        attr = child.attr
        p = child.params
        if attr in ("_uid_", "uid", ""):
            child.src_uids = src
            return True
        if attr == "val":
            # val(x) fetch: values come from the variable map
            v = child.needs_var[0] if child.needs_var else ""
            vmap = value_vars.get(v, {})
            child.src_uids = src
            child.values = {int(u): vmap[int(u)] for u in src.tolist() if int(u) in vmap}
            if p.agg_func:
                self._aggregate(child, src, value_vars)
            return True
        if attr == "math":
            child.src_uids = src
            child.values = self._eval_math(child.math_exp, src, value_vars)
            return True
        if attr == "_predicate_":
            child.src_uids = src
            # one vectorized membership probe per predicate (cached sorted
            # mirror, store.uids_with_data_sorted) — remaining Python work
            # is proportional to the OUTPUT (uid, pred) pairs, not to
            # |preds| × |uids| (VERDICT r4 weak #4)
            src64 = np.asarray(src, dtype=np.int64)
            acc: List[List[str]] = [[] for _ in range(len(src64))]
            for pr in self.store.predicates():
                wd = self.store.pred(pr).uids_with_data_sorted()
                if not len(wd):
                    continue
                pos = np.searchsorted(wd, src64)
                hit = (pos < len(wd)) & (wd[np.minimum(pos, len(wd) - 1)] == src64)
                for i in np.nonzero(hit)[0]:
                    acc[i].append(pr)
            child.values = {
                int(u): TypedValue(TypeID.STRING, acc[i])
                for i, u in enumerate(src64)
            }
            return True
        if child.func is not None and child.func.name == "checkpwd":
            child.src_uids = src
            ok = resolver.resolve(child.func, src)
            okset = set(ok.tolist())
            child.values = {
                int(u): TypedValue(TypeID.BOOL, int(u) in okset) for u in src.tolist()
            }
            return True

        tid = self.store.schema.type_of(attr)
        is_uid_pred = tid == TypeID.UID or (
            self.store.peek(attr) is not None and bool(self.store.pred(attr).edges)
        )

        if p.do_count:
            arena = self.arenas.reverse(attr) if child.reverse else self.arenas.data(attr)
            rows = arena.rows_for_uids_host(src)
            child.src_uids = src
            child.counts = arena.degree_of_rows(rows).astype(np.int64)
            return True

        if not is_uid_pred:
            # value leaf: fetch typed values for each src uid — direct
            # dict probes on the predicate's value map (no store.value
            # call overhead on the hot loop)
            child.src_uids = src
            # reference v0.7 lang semantics (query_test.go TestLang*):
            # no @ → untagged only; @a:b → first EXACT match in chain
            # order, no implicit fallback; '.' → untagged else any lang
            langs = child.langs or [""]
            vals = {}
            pd = self.store.peek(attr)
            if pd is not None:
                pv = pd.values
                if langs == [""]:
                    # vectorized untagged fetch: one searchsorted over the
                    # predicate's sorted value mirror instead of a Python
                    # dict probe per uid (VERDICT r3 weak #6)
                    hit, pos, mv = pd.untagged_lookup(src)
                    if hit.any():
                        hs = src[hit].tolist()
                        hv = mv[pos[hit]].tolist()
                        vals = dict(zip(map(int, hs), hv))
                else:
                    any_map = _any_value_map(pd) if "." in langs else None
                    for u in src.tolist():
                        for l in langs:
                            tv = any_map.get(u) if l == "." else pv.get((u, l))
                            if tv is not None:
                                vals[u] = tv
                                break
            child.values = vals
            if pd is not None and pd.value_facets and child.params.facets:
                child.value_facets = {
                    int(u): pd.value_facets[int(u)]
                    for u in src.tolist()
                    if int(u) in pd.value_facets
                }
            return True
        return False

    def _exec_child_inner(self, child: SubGraph, src: np.ndarray, resolver, uid_vars, value_vars):
        attr = child.attr
        p = child.params
        # stage assemble: a level's host work that is not an expansion —
        # here the leaves (values, counts, val/math), below the level's
        # uniques, filter, facets and ordering
        with obs.stage(self.stats, "assemble_ms"):
            if self._exec_leaf(child, src, resolver, value_vars):
                return

        # uid expansion on device.  Big plain chains fuse into one device
        # program (query/chain.py) staged here and consumed level by level
        # as the recursion descends; everything else goes per-level.
        if child.chain_stash is None:
            from dgraph_tpu.query.chain import try_run_chain

            # failed attempts count too: planning cost must show up in
            # SOME bucket or the breakdown misleads
            c0 = self.stats["chain_ms"]
            with obs.stage(self.stats, "chain_ms"):
                try_run_chain(self, child, src, resolver)
            # close the planner's chain decision with the measured
            # latency (set only when a planner-routed chain actually ran)
            cdec = getattr(self, "_pending_chain_dec", None)
            if cdec is not None:
                self._pending_chain_dec = None
                planner.note_outcome(cdec, (self.stats["chain_ms"] - c0) * 1e3)
        if child.chain_stash is not None and child.chain_stash[0] == "light":
            _tag, dest, stash_src, n_edges = child.chain_stash
            child.chain_stash = None
            if stash_src is None or len(stash_src) == len(src):
                # var-block level: matrices stayed on device; only the
                # deduped frontier came back (and only where a var or a
                # sibling subtree consumes it — dest None otherwise)
                child.src_uids = src
                child.out_flat = _EMPTY
                child.seg_ptr = np.zeros(len(src) + 1, dtype=np.int64)
                child.dest_uids = dest if dest is not None else _EMPTY
                self.stats["edges"] += n_edges
                self.stats["chain_edges"] += n_edges
                self.stats["chain_fused_levels"] += 1
                self._exec_children(child, resolver, uid_vars, value_vars)
                return
            # misaligned light stash: the per-level re-expansion below
            # must re-apply filter/order — the fused flags are stale
            child.chain_filtered = False
            child.chain_ordered = False
        if child.chain_stash is not None:
            _tag, out_flat, seg_ptr, stash_src = child.chain_stash
            child.chain_stash = None
            if len(stash_src) != len(src):  # defensive: never mis-align
                child.chain_filtered = False
                child.chain_ordered = False
                arena = (
                    self.arenas.reverse(attr) if child.reverse else self.arenas.data(attr)
                )
                out_flat, seg_ptr = self._expand(
                    arena, src, attr=attr, reverse=child.reverse
                )
            else:
                self.stats["edges"] += len(out_flat)
                self.stats["chain_edges"] += len(out_flat)
                self.stats["chain_fused_levels"] += 1
        else:
            arena = self.arenas.reverse(attr) if child.reverse else self.arenas.data(attr)
            out_flat, seg_ptr = self._expand(arena, src, attr=attr, reverse=child.reverse)
        with obs.stage(self.stats, "assemble_ms"):
            child.src_uids = src
            child.out_flat = out_flat
            child.seg_ptr = seg_ptr
            dest = np.unique(out_flat)

            if child.filter is not None and not getattr(child, "chain_filtered", False):
                dest = self._apply_filter(child.filter, dest, resolver)
                self._mask_matrix(child, dest)
            self._load_edge_facets(child)
            if child.params.facets_filter is not None:
                self._apply_facet_filter(child)
            if not getattr(child, "chain_ordered", False):
                self._order_and_paginate_child(child, value_vars)
            child.dest_uids = np.unique(child.out_flat)

            if p.is_groupby:
                from dgraph_tpu.query.groupby import process_groupby

                process_groupby(self, child, value_vars)
                return
        self._exec_children(child, resolver, uid_vars, value_vars)

    def _expand(
        self, arena, src: np.ndarray, attr: str = "", reverse: bool = False
    ) -> Tuple[np.ndarray, np.ndarray]:
        """One batched device gather for a whole level — routing lives on
        the DeviceExpander (see class docstring)."""
        return self.expander.expand(arena, src, attr=attr, reverse=reverse)

    # -- filters -----------------------------------------------------------

    def _apply_root_filter(
        self, sg: SubGraph, dest: np.ndarray, resolver
    ) -> np.ndarray:
        """Root filter application with `first: k` early termination
        (the QoS PR's early-exit leg): when the block carries a positive
        ``first`` and no ordering, the final dest is the first
        ``offset+first`` (post-``after``) survivors in uid order — so
        the filter evaluates over ASCENDING CHUNKS of the candidate set
        and stops once enough survive, instead of paying per-candidate
        filter work (and, downstream, chain-scan / per-level expansion
        sizing) proportional to the whole candidate universe.

        Byte-identical by construction: filters are per-candidate
        membership tests (and/or/not over uid sets), so filtering
        commutes with chunking, chunks are consumed in ascending uid
        order, and the accumulated prefix feeds the SAME
        _order_and_paginate_root windowing.  Ineligible shapes (order,
        negative windows, unsorted candidates) and DGRAPH_TPU_QOS=0
        take the legacy whole-set path unchanged."""
        p = sg.params
        need = (p.first or 0) + max(p.offset or 0, 0)
        from dgraph_tpu.sched.qos import qos_enabled

        if (
            (p.first or 0) <= 0
            or p.order_attr
            or (p.offset or 0) < 0
            or not qos_enabled()
        ):
            return self._apply_filter(sg.filter, dest, resolver)
        # chunk floor: global filter leaves (index funcs) re-resolve per
        # chunk, so start big enough that doubling reaches the whole set
        # in a few rounds — the early exit must never turn one filter
        # pass into O(n/k) of them
        chunk = max(1024, 8 * need)
        if len(dest) <= chunk or not bool(np.all(dest[1:] > dest[:-1])):
            return self._apply_filter(sg.filter, dest, resolver)
        after = p.after or 0
        parts: List[np.ndarray] = []
        got = 0
        pos = 0
        while pos < len(dest):
            self.checkpoint()
            part = self._apply_filter(
                sg.filter, dest[pos : pos + chunk], resolver
            )
            parts.append(part)
            got += int((part > after).sum()) if after else len(part)
            pos += chunk
            if got >= need:
                if pos < len(dest):
                    self.stats["first_early_exit"] += 1
                break
            chunk *= 2
        return np.concatenate(parts)

    def _apply_filter(self, ft: FilterTree, candidates: np.ndarray, resolver) -> np.ndarray:
        if ft.func is not None:
            return resolver.resolve(ft.func, candidates)
        if ft.op == "and":
            # multi-predicate intersection (the MXU join tier's k-way
            # entry, query/joinplan.py): leaves that resolve WITHOUT the
            # frontier — index funcs, has(), uid sets — intersect with
            # the candidates as ONE k-way pass (size-routed host/device)
            # instead of k sequential narrowing passes.  AND children
            # are set filters, so the intersection commutes: frontier-
            # dependent leaves (val/count/uid_in/checkpwd) and nested
            # trees apply sequentially on the k-way result, and the
            # output is byte-identical to the legacy fold.  Each leaf
            # already resolved its full set before narrowing (resolve →
            # _bound), so the reorder adds no resolution work.
            from dgraph_tpu.query import joinplan

            if joinplan.mxu_mode() != "0":
                glob = [
                    c for c in ft.children
                    if c.func is not None
                    and joinplan.filter_leaf_global(c.func)
                ]
                if len(glob) >= 2:
                    sets = [resolver.resolve(c.func, None) for c in glob]
                    out = joinplan.kway_intersect(
                        [candidates] + sets, stats=self.stats
                    )
                    gids = {id(c) for c in glob}
                    for c in ft.children:
                        if id(c) not in gids:
                            out = self._apply_filter(c, out, resolver)
                    return out
            out = candidates
            for c in ft.children:
                out = self._apply_filter(c, out, resolver)
            return out
        if ft.op == "or":
            parts = [self._apply_filter(c, candidates, resolver) for c in ft.children]
            out = parts[0]
            for s in parts[1:]:
                out = np.union1d(out, s)
            return out
        if ft.op == "not":
            sub = self._apply_filter(ft.children[0], candidates, resolver)
            return np.setdiff1d(candidates, sub)
        raise QueryError(f"bad filter op {ft.op!r}")

    def _mask_matrix(self, sg: SubGraph, keep: np.ndarray):
        """Filter out_flat to uids in ``keep`` (updateUidMatrix analog)."""
        if len(sg.out_flat) == 0:
            return
        _apply_edge_mask(sg, np.isin(sg.out_flat, keep))

    # -- facets ------------------------------------------------------------

    def _load_edge_facets(self, sg: SubGraph):
        pd = self.store.peek(sg.attr)
        if pd is None or not pd.edge_facets:
            return
        if sg.params.facets is None and sg.params.facets_filter is None:
            return
        counts = np.diff(sg.seg_ptr)
        owner = np.repeat(np.arange(len(counts)), counts)
        srcs = sg.src_uids[owner]
        dsts = sg.out_flat
        ef = pd.edge_facets
        if pd._efmirror is None and len(dsts) * 8 < len(ef):
            # cold mirror + small result: direct dict probes beat paying
            # an O(F log F) mirror rebuild for a handful of edges (the
            # mirror amortizes across queries once built; any facet WRITE
            # invalidates it, so mutate-then-query workloads land here)
            for src, dst in zip(srcs.tolist(), dsts.tolist()):
                f = ef.get((dst, src) if sg.reverse else (src, dst))
                if f:
                    sg.edge_facets[(src, dst)] = f
            return
        # one vectorized probe over the predicate's sorted facet mirror
        # (the per-edge dict loop was the r3-flagged host bottleneck)
        if sg.reverse:
            hit, pos, mv = pd.edge_facets_lookup(dsts, srcs)
        else:
            hit, pos, mv = pd.edge_facets_lookup(srcs, dsts)
        if hit.any():
            hs = srcs[hit].tolist()
            hd = dsts[hit].tolist()
            hf = mv[pos[hit]].tolist()
            for src, dst, f in zip(hs, hd, hf):
                sg.edge_facets[(int(src), int(dst))] = f

    def _apply_facet_filter(self, sg: SubGraph):
        """@facets(eq(key, val)): keep edges whose facets satisfy the tree.

        Vectorized (VERDICT r4 weak #4): the tree is evaluated as boolean
        COLUMNS over the edge list, not a Python closure per edge.  Only
        facet-BEARING edges (sg.edge_facets, loaded by _load_edge_facets)
        are touched at all; each leaf gathers its facet column once,
        groups by value tid, converts the filter arg once per (leaf, tid),
        and compares the whole group with one numpy op.  and/or/not are
        mask algebra, so facetless edges cost nothing anywhere.
        """
        tree = sg.params.facets_filter
        from dgraph_tpu.models.types import compare_vals, convert

        E = len(sg.out_flat)
        counts = np.diff(sg.seg_ptr)
        owner = np.repeat(np.arange(len(counts)), counts)
        srcs = sg.src_uids[owner]
        ef = sg.edge_facets

        # flat-edge position of every facet-bearing edge: one searchsorted
        # over the (src<<32|dst) keys (edges are unique per (row, dst))
        if ef:
            keys = (srcs.astype(np.int64) << 32) | sg.out_flat.astype(np.int64)
            order = np.argsort(keys)
            skeys = keys[order]
            fkeys = np.fromiter(
                ((s << 32) | d for (s, d) in ef.keys()),
                dtype=np.int64,
                count=len(ef),
            )
            pos = np.clip(np.searchsorted(skeys, fkeys), 0, max(0, E - 1))
            # guard: a facet key whose edge is no longer in the list (an
            # earlier mask pruned it after loading) must be DROPPED, not
            # land on an arbitrary clipped position
            hit = skeys[pos] == fkeys if E else np.zeros(len(fkeys), bool)
            fpos = order[pos[hit]]
            fdicts = [
                f for f, h in zip(ef.values(), hit.tolist()) if h
            ]
        else:
            fpos = np.zeros(0, np.int64)
            fdicts = []

        conv_memo: Dict[tuple, Optional[TypedValue]] = {}

        def leaf_mask(ft: FilterTree) -> np.ndarray:
            out = np.zeros(E, dtype=bool)
            key = ft.func.attr
            # gather this leaf's facet column (facet-bearing edges only)
            groups: Dict[object, list] = {}
            for j, f in enumerate(fdicts):
                fv = f.get(key)
                if fv is not None:
                    groups.setdefault(fv.tid, []).append(j)
            for tid, js in groups.items():
                mk = (id(ft.func), tid)
                if mk not in conv_memo:
                    try:
                        conv_memo[mk] = convert(
                            TypedValue(TypeID.STRING, ft.func.args[0]), tid
                        )
                    except (ValueError, IndexError):
                        conv_memo[mk] = None
                target = conv_memo[mk]
                if target is None:
                    continue
                vals = [fdicts[j][key] for j in js]
                idx = fpos[np.asarray(js, dtype=np.int64)]
                if tid in (TypeID.INT, TypeID.FLOAT):
                    a = np.fromiter(
                        (float(v.value) for v in vals), np.float64, len(vals)
                    )
                    b = float(target.value)
                else:
                    a = np.empty(len(vals), dtype=object)
                    for i, v in enumerate(vals):
                        a[i] = v.value
                    b = target.value
                op = ft.func.name
                try:
                    if op == "eq":
                        m = a == b
                    elif op == "lt":
                        m = a < b
                    elif op == "le":
                        m = a <= b
                    elif op == "gt":
                        m = a > b
                    elif op == "ge":
                        m = a >= b
                    else:
                        raise ValueError(op)
                    m = np.asarray(m, dtype=bool)
                except (ValueError, TypeError):
                    # heterogenous values that defeat the columnar compare
                    # fall back to the scalar semantics, element by element
                    m = np.fromiter(
                        (_cmp_quiet(compare_vals, op, v, target) for v in vals),
                        dtype=bool,
                        count=len(vals),
                    )
                out[idx] = m
            return out

        def ev(ft: FilterTree) -> np.ndarray:
            if ft.func is not None:
                return leaf_mask(ft)
            if ft.op == "and":
                m = np.ones(E, dtype=bool)
                for c in ft.children:
                    m &= ev(c)
                return m
            if ft.op == "or":
                m = np.zeros(E, dtype=bool)
                for c in ft.children:
                    m |= ev(c)
                return m
            if ft.op == "not":
                return ~ev(ft.children[0])
            return np.zeros(E, dtype=bool)

        _apply_edge_mask(sg, ev(tree))

    # -- order & pagination --------------------------------------------------

    def _value_key_fn(self, attr: str, langs: List[str], value_vars, is_var: bool):
        if is_var:
            vmap = value_vars.get(attr, {})

            def key(u: int):
                v = vmap.get(u)
                return sort_key(v) if v is not None else (9,)

            return key

        def key(u: int):
            v = None
            for l in langs or [""]:
                v = (
                    self.store.any_value(attr, u)
                    if l == "."
                    else self.store.value(attr, u, l)
                )
                if v is not None:
                    break
            return sort_key(v) if v is not None else (9,)

        return key

    # device order-by eligibility: types whose host sort_key orders
    # identically to the ValueArena's exact-float64 value ranks
    _DEVICE_ORDER_TIDS = (
        TypeID.INT, TypeID.FLOAT, TypeID.DATETIME, TypeID.DATE, TypeID.BOOL,
    )

    def _device_order_perm(
        self, out: np.ndarray, owner: np.ndarray, attr: str, desc: bool
    ) -> Optional[np.ndarray]:
        """Segmented order-by on device (the TPU replacement for the
        reference's per-row types.Sort, worker/sort.go:123-149 + SURVEY
        §7.6 "segmented top-k"): gather value RANKS from the ValueArena
        with one batched binary search, then one stable lexsort over
        (segment, ±rank).  Returns the permutation, or None when the host
        path must handle it (string keys, lang-tagged values, value vars)."""
        tid = self.store.schema.type_of(attr)
        if tid not in self._DEVICE_ORDER_TIDS:
            return None
        va = self.arenas.values(attr)
        if not va.langless:
            return None
        n = len(out)
        if n == 0:
            return np.empty(0, dtype=np.int64)
        if n < self.expand_device_min:
            # small sorts: numpy lexsort over the host rank mirror beats a
            # device round trip (same size routing as _expand); missing
            # values sort last ascending / first descending, matching the
            # device kernel (ops/order.py segmented_sort_perm)
            miss = np.int64(1) << 40
            if va.n:
                pos = np.clip(np.searchsorted(va.h_src, out), 0, va.n - 1)
                hit = va.h_src[pos] == out
                key = np.where(hit, va.h_ranks[pos].astype(np.int64), miss)
            else:
                key = np.full(n, miss, dtype=np.int64)
            if desc:
                key = np.where(key == miss, -miss, -key)
            return np.lexsort((key, owner)).astype(np.int64)
        import jax.numpy as jnp

        with obs.stage(self.stats, "device_order_ms"):
            cap = ops.bucket(n)
            uids_pad = jnp.asarray(ops.pad_to(out, cap))
            seg_pad = np.full(cap, -1, dtype=np.int32)
            seg_pad[:n] = owner
            ranks = ops.gather_ranks(va.src, va.ranks, uids_pad)
            perm = np.asarray(
                ops.segmented_sort_perm(jnp.asarray(seg_pad), ranks, bool(desc))
            )
        return perm[:n].astype(np.int64)  # padding sorts to the tail

    def _host_order_perm(
        self, n_items: int, owner: np.ndarray, n_segs: int, key_at, desc: bool
    ) -> np.ndarray:
        """Per-segment stable python sort (string keys / vars / facet
        keys).  ``key_at(j)`` keys by flat item index; returns a
        permutation of range(n_items)."""
        perm = np.arange(n_items, dtype=np.int64)
        starts = np.zeros(n_segs + 1, dtype=np.int64)
        np.cumsum(np.bincount(owner, minlength=n_segs), out=starts[1:])
        for i in range(n_segs):
            lo, hi = int(starts[i]), int(starts[i + 1])
            if hi - lo > 1:
                perm[lo:hi] = sorted(range(lo, hi), key=key_at, reverse=desc)
        return perm

    def _order_and_paginate_root(self, sg: SubGraph, dest: np.ndarray, value_vars) -> np.ndarray:
        p = sg.params
        if p.after:
            dest = dest[dest > p.after]
        if p.order_attr:
            perm = None
            if not (p.order_is_var or p.order_langs):
                perm = self._device_order_perm(
                    dest, np.zeros(len(dest), dtype=np.int64), p.order_attr,
                    p.order_desc,
                )
            if perm is not None:
                dest = dest[perm]
            else:
                key = self._value_key_fn(p.order_attr, p.order_langs, value_vars, p.order_is_var)
                lst = sorted(dest.tolist(), key=key, reverse=p.order_desc)
                dest = np.array(lst, dtype=np.int64)
        dest = _paginate(dest, p.offset, p.first)
        return dest

    def _order_and_paginate_child(self, sg: SubGraph, value_vars):
        p = sg.params
        if not (p.first or p.offset or p.after or p.order_attr or
                (p.facets and p.facets.order_key)):
            return
        counts = np.diff(sg.seg_ptr)
        n_segs = len(counts)
        out = sg.out_flat
        owner = np.repeat(np.arange(n_segs), counts)

        # -- ordering (commutes with the 'after' uid filter) ----------------
        if p.facets and p.facets.order_key:
            fkey_name = p.facets.order_key

            def fkey_at(j: int):
                src = int(sg.src_uids[owner[j]])
                v = sg.edge_facets.get((src, int(out[j])), {}).get(fkey_name)
                return sort_key(v) if v is not None else (9,)

            perm = self._host_order_perm(
                len(out), owner, n_segs, fkey_at, p.facets.order_desc
            )
            out, owner = out[perm], owner[perm]
        elif p.order_attr:
            perm = None
            if not (p.order_is_var or p.order_langs):
                perm = self._device_order_perm(out, owner, p.order_attr, p.order_desc)
            if perm is None:
                key = self._value_key_fn(
                    p.order_attr, p.order_langs, value_vars, p.order_is_var
                )
                perm = self._host_order_perm(
                    len(out), owner, n_segs,
                    lambda j: key(int(out[j])), p.order_desc,
                )
            out, owner = out[perm], owner[perm]

        # -- after + per-segment windowing (vectorized, no python loop) -----
        if p.after:
            m = out > p.after
            out, owner = out[m], owner[m]
        out, owner = _window_segments(out, owner, n_segs, p.offset, p.first)
        sg.out_flat = out
        sg.seg_ptr = np.zeros(n_segs + 1, dtype=np.int64)
        np.cumsum(np.bincount(owner, minlength=n_segs), out=sg.seg_ptr[1:])

    # -- vars / aggregation / math -------------------------------------------

    def _collect_vars(self, sg: SubGraph, uid_vars, value_vars):
        self._bind_var(sg, uid_vars, value_vars)
        for c in sg.children:
            self._collect_vars(c, uid_vars, value_vars)

    def _aggregate(self, child: SubGraph, src: np.ndarray, value_vars):
        """min/max/sum/avg over a value variable (valueVarAggregation).
        min/max preserve the operand type (min of datetimes is a datetime,
        query/aggregator.go ApplyVal); sum/avg promote to numeric."""
        v = child.needs_var[0] if child.needs_var else ""
        vmap = value_vars.get(v, {})
        fn = child.params.agg_func
        if fn in ("min", "max"):
            vals = list(vmap.values())
            if not vals:
                child.values = {}
                return
            pick = min if fn == "min" else max
            tv = pick(vals, key=sort_key)
        else:
            nums = [numeric(tv) for tv in vmap.values()]
            nums = [x for x in nums if x is not None]
            if not nums:
                child.values = {}
                return
            r = sum(nums) if fn == "sum" else sum(nums) / len(nums)
            tv = TypedValue(TypeID.FLOAT, float(r))
        # one value for the block (reference emits it on the block root)
        child.values = {int(u): tv for u in src.tolist()} or {0: tv}
        if child.params.var:
            value_vars[child.params.var] = dict(child.values)

    def _eval_math(self, mt: MathTree, src: np.ndarray, value_vars) -> Dict[int, TypedValue]:
        """Evaluate math() over the value-variable environment
        (query/math.go evalMathTree) — vectorized: the whole expression
        tree runs elementwise over one uid-aligned float64 array instead
        of a python interpreter loop per uid.  Error semantics match the
        per-uid path: a uid is dropped when a variable is missing or the
        arithmetic is undefined there (div-zero/log-domain/overflow all
        surface as non-finite lanes)."""
        uids = set()
        self._math_uids(mt, value_vars, uids)
        if not uids:
            uids = {int(u) for u in src.tolist()}
        ua = np.array(sorted(uids), dtype=np.int64)
        with np.errstate(all="ignore"):
            vals, ok = _eval_math_vec(mt, ua, value_vars)
            ok = ok & np.isfinite(vals)
        return {
            int(u): TypedValue(TypeID.FLOAT, float(v))
            for u, v in zip(ua[ok].tolist(), vals[ok].tolist())
        }

    def _math_uids(self, mt: MathTree, value_vars, acc: set):
        if mt.var and mt.var in value_vars:
            acc.update(value_vars[mt.var].keys())
        for c in mt.children:
            self._math_uids(c, value_vars, acc)

    # -- schema introspection -------------------------------------------------

    def _schema_response(self, req) -> List[dict]:
        preds = req.predicates or self.store.schema.predicates()
        fields = req.fields or ["type"]
        out = []
        for pr in preds:
            s = self.store.schema.peek(pr)
            if s is None:
                continue
            item = {"predicate": pr}
            for f in fields:
                if f == "type":
                    item["type"] = s.tid.name.lower()
                elif f == "index":
                    item["index"] = bool(s.tokenizers)
                elif f == "tokenizer":
                    item["tokenizer"] = list(s.tokenizers)
                elif f == "reverse":
                    item["reverse"] = s.reverse
                elif f == "count":
                    item["count"] = s.count
            out.append(item)
        return out


def _cmp_quiet(compare_vals, op: str, a, b) -> bool:
    """compare_vals with the facet-filter's 'mismatch means False'."""
    try:
        return compare_vals(op, a, b)
    except (ValueError, TypeError):
        return False


def _apply_edge_mask(sg: SubGraph, mask: np.ndarray) -> None:
    """Apply a per-edge boolean mask to (out_flat, seg_ptr) keeping the
    segmented CSR consistent — the one shared place segment accounting
    happens after filtering."""
    counts = np.diff(sg.seg_ptr)
    owner = np.repeat(np.arange(len(counts)), counts)
    kept = np.bincount(owner[mask], minlength=len(counts))
    sg.out_flat = sg.out_flat[mask]
    sg.seg_ptr = np.zeros(len(counts) + 1, dtype=np.int64)
    np.cumsum(kept, out=sg.seg_ptr[1:])


def _any_value_map(pd) -> Dict[int, TypedValue]:
    """uid → value under '.' fallback: untagged wins, else the
    lexicographically-first language (deterministic; list.go:835)."""
    out: Dict[int, TypedValue] = {}
    for (u, l) in sorted(pd.values.keys(), key=lambda k: (k[0], k[1] != "", k[1])):
        if u not in out:
            out[u] = pd.values[(u, l)]
    return out


def _window_segments(
    out: np.ndarray, owner: np.ndarray, n_segs: int, offset: int, first: int
) -> Tuple[np.ndarray, np.ndarray]:
    """Apply _paginate's offset/first window to every segment at once:
    position-within-segment is computed vectorized, so pagination costs
    O(edges) numpy work regardless of segment count."""
    if not (offset or first) or len(out) == 0:
        return out, owner
    offset = max(offset, 0)  # _paginate ignores non-positive offsets
    counts = np.bincount(owner, minlength=n_segs)
    starts = np.zeros(n_segs + 1, dtype=np.int64)
    np.cumsum(counts, out=starts[1:])
    pos = np.arange(len(out), dtype=np.int64) - starts[owner]
    keep = np.ones(len(out), dtype=bool)
    if offset > 0:
        keep &= pos >= offset
    if first > 0:
        keep &= pos < offset + first
    elif first < 0:
        # negative first = last |first| entries of the post-offset slice
        eff = np.maximum(counts[owner] - max(offset, 0), 0)
        keep &= pos >= max(offset, 0) + np.maximum(eff + first, 0)
    return out[keep], owner[keep]


def _paginate(arr: np.ndarray, offset: int, first: int) -> np.ndarray:
    """first/offset windowing (x.PageRange analog: negative first = from
    the end)."""
    n = len(arr)
    if offset > 0:
        arr = arr[min(offset, n):]
    if first > 0:
        arr = arr[:first]
    elif first < 0:
        arr = arr[first:]
    return arr


_MATH_BIN = {
    "+": lambda a, b: a + b,
    "-": lambda a, b: a - b,
    "*": lambda a, b: a * b,
    "/": lambda a, b: a / b,
    "%": np.fmod,
    "<": lambda a, b: (a < b).astype(np.float64),
    ">": lambda a, b: (a > b).astype(np.float64),
    "<=": lambda a, b: (a <= b).astype(np.float64),
    ">=": lambda a, b: (a >= b).astype(np.float64),
    "==": lambda a, b: (a == b).astype(np.float64),
    "!=": lambda a, b: (a != b).astype(np.float64),
    "pow": lambda a, b: np.power(a, b),
    "logbase": lambda a, b: np.log(a) / np.log(b),
}

_MATH_UNARY = {
    "u-": np.negative,
    "exp": np.exp,
    "ln": np.log,
    "sqrt": np.sqrt,
    "floor": np.floor,
    "ceil": np.ceil,
}


def _eval_math_vec(mt: MathTree, ua: np.ndarray, value_vars):
    """Elementwise tree evaluation over uid-aligned arrays.  Returns
    (float64[n] values, bool[n] defined-mask); undefined lanes carry NaN.
    Boolean results are 1.0/0.0 (the per-uid path's float(bool))."""
    n = len(ua)
    if mt.var:
        vmap = value_vars.get(mt.var, {})
        vals = np.full(n, np.nan, dtype=np.float64)
        ok = np.zeros(n, dtype=bool)
        for i, u in enumerate(ua.tolist()):
            tv = vmap.get(u)
            if tv is None:
                continue
            x = numeric(tv)
            if x is not None:
                vals[i] = x
                ok[i] = True
        return vals, ok
    if mt.const is not None:
        return (
            np.full(n, float(mt.const), dtype=np.float64),
            np.ones(n, dtype=bool),
        )
    fn = mt.fn
    kid_vals = []
    ok = np.ones(n, dtype=bool)
    for c in mt.children:
        v, o = _eval_math_vec(c, ua, value_vars)
        kid_vals.append(v)
        # a non-finite lane in ANY subexpression drops the uid — the
        # per-uid path evaluated every child eagerly, so an undefined
        # untaken cond() branch also killed the uid there
        ok &= o & np.isfinite(v)
    if fn in _MATH_BIN and len(kid_vals) == 2:
        return _MATH_BIN[fn](kid_vals[0], kid_vals[1]), ok
    if fn in _MATH_UNARY and len(kid_vals) == 1:
        return _MATH_UNARY[fn](kid_vals[0]), ok
    if fn == "since":
        import time

        # since() is wall-clock BY DEFINITION: it subtracts a stored,
        # user-visible timestamp from "now" — monotonic time has no
        # relation to stored epochs.
        # graftlint: ignore[wallclock-duration]
        return time.time() - kid_vals[0], ok
    if fn == "max":
        return np.maximum.reduce(kid_vals), ok
    if fn == "min":
        return np.minimum.reduce(kid_vals), ok
    if fn == "cond":
        return np.where(kid_vals[0] != 0, kid_vals[1], kid_vals[2]), ok
    raise QueryError(f"unknown math fn {fn!r}")
