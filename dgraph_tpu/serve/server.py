"""HTTP serving surface.

Equivalent of cmd/dgraph/main.go's handler set (queryHandler:226,
shareHandler:391, exportHandler:499, shutdown:471, /health, /debug/store
main.go:641-652) + dgraph/server.go's request loop (Run:104: parse →
process → encode with latency map and 1-minute timeout).  The reference
multiplexes gRPC + HTTP on one port via cmux; here one threaded HTTP
server carries both the human JSON surface and the machine client
(dgraph_tpu.client speaks the same /query endpoint, as the reference's
HTTP clients do).  Engine execution is serialized by a lock — the arena
is shared device state, and the reference likewise funnels device work
through one ServeTask boundary per group (SURVEY.md §2c).
"""

from __future__ import annotations

import copy
import hashlib
import itertools
import json
import threading
import time
from collections.abc import MutableMapping
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Optional
from urllib.parse import parse_qs, urlparse

from dgraph_tpu import obs
from dgraph_tpu.cache import Answer
from dgraph_tpu.obs import device as _device
from dgraph_tpu.obs import ledger as _ledger
from dgraph_tpu.models.durability import ReadOnlyError, StorageFaultError
from dgraph_tpu.models.store import PostingStore
from dgraph_tpu.query.engine import QueryEngine
from dgraph_tpu.serve.export import export as export_rdf
from dgraph_tpu.utils import HealthGate, Latency
from dgraph_tpu.utils.rwlock import RWLock
from dgraph_tpu.utils.metrics import (
    ENGINE_LOCK_BARRED_US,
    NUM_QUERIES,
    PENDING_QUERIES,
    QUERY_CANCELLED,
    QUERY_LATENCY,
    TENANT_LATENCY,
    WRITES,
    metrics,
)
from dgraph_tpu.cluster.peerclient import StaleUnavailableError
from dgraph_tpu.sched import (
    QueryCancelledError,
    SchedDeadlineError,
    SchedOverloadError,
    SchedQuotaError,
    sched_enabled,
)
from dgraph_tpu.sched import qos as _qos

_CORS = {
    "Access-Control-Allow-Origin": "*",
    "Access-Control-Allow-Methods": "POST, GET, OPTIONS",
    "Access-Control-Allow-Headers": "Content-Type",
    # NOTE: no forced "Connection: close" — every _reply carries an
    # exact Content-Length, so HTTP/1.1 keep-alive is sound and a
    # high-QPS client fleet stops paying a TCP handshake per query.
    # Clients that send "Connection: close" (urllib does) still get
    # per-request connections; idle keep-alive sockets fall to the
    # handler's 60s read timeout.
}


class Response(MutableMapping):
    """``run_query(encoded=True)``'s return: the response — the answer's
    blocks, then the per-request tail — FROZEN.  The ``/query`` handler,
    which only sends it, ``encode()``s a frozen one by splicing the tail
    round the answer's encoded body (``cache.Answer.reply``): one
    serialisation for singleflight twins, cache and socket, and a
    result-cache hit builds no tree at all.  Whatever sits between
    ``run_query`` and the handler and treats the response as the dict
    ``run_query`` otherwise returns (a wrapper, a test's hook) thaws it
    into one, and what it leaves there is what gets sent."""

    __slots__ = ("_answer", "_tail", "_dict")

    def __init__(self, answer, tail: dict):
        self._answer = answer
        self._tail = tail
        self._dict: Optional[dict] = None

    def thaw(self) -> dict:
        d = self._dict
        if d is None:
            d = self._dict = {**self._answer.tree(), **self._tail}
        return d

    def encode(self) -> bytes:
        if self._dict is None:
            return self._answer.reply(self._tail)
        return json.dumps(self._dict).encode()

    def __getitem__(self, key):
        return self.thaw()[key]

    def __setitem__(self, key, value) -> None:
        self.thaw()[key] = value

    def __delitem__(self, key) -> None:
        del self.thaw()[key]

    def __iter__(self):
        return iter(self.thaw())

    def __len__(self) -> int:
        return len(self.thaw())

    def __deepcopy__(self, memo) -> dict:
        return copy.deepcopy(self.thaw(), memo)


class DgraphServer:
    """Owns the store + engine and serves the HTTP surface."""

    def __init__(
        self,
        store: PostingStore,
        port: int = 0,
        bind: str = "127.0.0.1",
        export_path: str = "export",
        expose_trace: bool = True,
        tls_cert: str = "",
        tls_key: str = "",
        cluster=None,
        profiler=None,
        arena_budget_mb: int = 0,
        dumpsg_path: str = "",
    ):
        # --dumpsg analog (cmd/dgraph/main.go:347-358): write each query's
        # execution-shape tree as timestamped JSON for offline inspection
        self.dumpsg_path = dumpsg_path
        self.cluster = cluster  # ClusterService when clustered, else None
        self.store = store
        # planner calibration lifecycle (query/planner.py): a valid
        # persisted calibration loads on every boot (warm boots skip the
        # measurement pass); the micro-calibration itself runs only when
        # DGRAPH_TPU_CALIBRATE=1 — a library/test construction must not
        # pay a measurement pass it didn't ask for.  Priors serve until
        # then, refined online from per-hop timings either way.
        from dgraph_tpu.query import planner as _planner
        from dgraph_tpu.utils import planconfig as _planconfig

        if _planner.enabled():
            try:
                _planner.boot(measure_now=_planconfig.calibrate_at_boot())
            except Exception as e:  # noqa: BLE001 — a wedged backend or
                # unwritable scratch dir must degrade to priors, never
                # refuse boot over a calibration nicety (counted, not
                # silent)
                from dgraph_tpu.utils.metrics import note_swallowed

                note_swallowed("server.planner_boot", e)
        import os as _os

        self.engine = QueryEngine(
            store,
            mesh=_auto_mesh(),
            # mesh placement/eligibility knob (docs/deploy.md "Mesh
            # serving"): rows at/above this shard over the model axis;
            # the default matches the engine's, so unset is unchanged
            shard_threshold=int(
                _os.environ.get("DGRAPH_TPU_MESH_SHARD_ROWS", "4096")
            ),
            arena_budget_bytes=(arena_budget_mb * (1 << 20)) or None,
        )
        self.health = HealthGate()
        self.export_path = export_path
        self.expose_trace = expose_trace
        # RW lock: read-only queries run CONCURRENTLY over the shared
        # immutable arenas; mutations/stop take the exclusive side (the
        # reference's per-request goroutines + posting RWMutex, see
        # utils/rwlock.py).  Kept under the old name so operators' mental
        # model ("the engine lock") still holds for the write side.
        self._engine_lock = RWLock()
        # the lock's busy time beside the readers' waits for it
        # (dgraph_sched_wait_us_total{writer}): the seconds new readers
        # were barred, a scrape counting an open interval up to itself
        ENGINE_LOCK_BARRED_US.bind(self._engine_lock.barred_s)
        self._stop_lock = threading.Lock()
        # exports write a minute-stamped file; two concurrent exports
        # would interleave gzip streams into one path — serialize them
        # (they still share the READ side of the engine lock with queries)
        self._export_lock = threading.Lock()
        self._stopped = False
        # bounded LRU: shares are a convenience surface, not durable state
        from collections import OrderedDict

        self._shares: "OrderedDict[str, str]" = OrderedDict()
        self._max_shares = 1024
        self._httpd: Optional[ThreadingHTTPServer] = None
        self._thread: Optional[threading.Thread] = None
        self._bind = bind
        self._port = port
        self._tls_cert = tls_cert
        self._tls_key = tls_key
        # shared cProfile enabled per-request under the engine lock when
        # the CLI passes --cpu (profiling must cover handler threads,
        # where all query execution happens — not just the main thread)
        self._profiler = profiler
        # cohort scheduler (sched/): coalesces concurrent read queries
        # into shape-bucketed cohorts riding the fused executor.  Gated
        # by DGRAPH_TPU_SCHED (default on); =0 restores the serial
        # per-request path byte-identically.  Profiled runs stay serial
        # (cProfile is not thread-safe), so no scheduler there either.
        self.scheduler = None
        if sched_enabled() and profiler is None:
            from dgraph_tpu.sched import CohortScheduler

            self.scheduler = CohortScheduler(self)
        # incremental view maintenance (dgraph_tpu/ivm/): attach the
        # mutation delta stream to the store and stand up the live-query
        # subscription registry (POST /subscribe).  Needs a store with
        # per-predicate version tracking (the PostingStore family);
        # duck-typed cluster stores keep global-version cache behavior
        # and serve no subscriptions.
        self.subs = None
        from dgraph_tpu import ivm as _ivm

        if (
            _ivm.ivm_enabled()
            and getattr(store, "pred_versions", None) is not None
            # ClusterStore exposes pred_versions for per-predicate
            # cache keying (PR 17) but has no local mutation path to
            # journal — it must not grow a delta stream or serve
            # subscriptions (supports_ivm_stream = False there)
            and getattr(store, "supports_ivm_stream", True)
        ):
            stream = _ivm.attach_stream(store)
            from dgraph_tpu.ivm import subs as _subs

            if _subs.subs_enabled():
                self.subs = _subs.SubscriptionRegistry(self, stream)
        # storage plane (models/wal.py + models/durability.py), for
        # stores that have one (DurableStore; ClusterStore's durability
        # lives in the raft logs instead):
        # - group commit: move the --sync fsync out of the exclusive
        #   write section into a shared post-lock barrier so concurrent
        #   writers amortize one fsync (DGRAPH_TPU_GROUP_COMMIT=0 keeps
        #   the legacy fsync-per-write inside the lock)
        # - snapshotter: the background seal/compact loop that finally
        #   CALLS DurableStore.snapshot machinery in the serving path,
        #   keeping the WAL bounded under sustained writes
        import os as _os

        if (
            hasattr(store, "enable_group_commit")
            and _os.environ.get("DGRAPH_TPU_GROUP_COMMIT", "1") != "0"
        ):
            store.enable_group_commit()
        self.snapshotter = None
        if (
            hasattr(store, "seal_segment")
            and _os.environ.get("DGRAPH_TPU_SNAPSHOTTER", "1") != "0"
        ):
            from dgraph_tpu.models.durability import Snapshotter

            self.snapshotter = Snapshotter(
                store, exclusive=self._engine_lock.write
            )

    # -- lifecycle ---------------------------------------------------------

    def start(self) -> None:
        handler = _make_handler(self)

        # deep accept backlog: the stdlib default (5) drops SYNs the
        # moment a few dozen clients connect at once (keep-alive helps,
        # but urllib-style clients still open a connection per request),
        # and the 1s TCP retransmit turns into a phantom 1000ms p50 —
        # the listen queue must absorb a burst of the whole client
        # fleet.  Subclassed so the stdlib class is left untouched.
        class _Server(ThreadingHTTPServer):
            request_queue_size = 128

        self._httpd = _Server((self._bind, self._port), handler)
        if self._tls_cert:
            # TLS termination (x/tls_helper.go analog): stdlib ssl, TLS1.2+.
            # do_handshake_on_connect=False moves the handshake off the
            # accept loop into the per-connection handler thread (with its
            # socket timeout) — a stalled client must not block accept()
            import ssl

            ctx = ssl.SSLContext(ssl.PROTOCOL_TLS_SERVER)
            ctx.minimum_version = ssl.TLSVersion.TLSv1_2
            ctx.load_cert_chain(self._tls_cert, self._tls_key or None)
            self._httpd.socket = ctx.wrap_socket(
                self._httpd.socket, server_side=True,
                do_handshake_on_connect=False,
            )
        self._port = self._httpd.server_address[1]
        self._thread = threading.Thread(
            target=self._httpd.serve_forever, name="dgraph-http", daemon=True
        )
        self._thread.start()
        if self.snapshotter is not None:
            self.snapshotter.start()
        if self.subs is not None:
            self.subs.start()
        # device telemetry (obs/device.py): compile-event listener +
        # build-identity stamp — by start() the jax platform is settled
        # (the engine's arenas forced backend selection in __init__)
        _device.install_compile_listener()
        _device.stamp_build_info()
        self.health.set_ok(True)

    @property
    def port(self) -> int:
        return self._port

    @property
    def addr(self) -> str:
        scheme = "https" if self._tls_cert else "http"
        return f"{scheme}://{self._bind}:{self._port}"

    def stop(self) -> None:
        # idempotent (admin endpoint + signal handler can both call it) and
        # serialized against in-flight mutations: the store is only closed
        # under the engine lock, after the listener stops accepting.  The
        # stop lock is held for the WHOLE teardown so a second caller
        # returning means teardown (incl. the WAL flush) has completed.
        if self._stopped:  # unlocked fast path: done means durably done
            return
        with self._stop_lock:
            if self._stopped:
                return
            self.health.set_ok(False)
            if self._httpd is not None:
                self._httpd.shutdown()
                self._httpd.server_close()
                self._httpd = None
            if self.subs is not None:
                # before the scheduler: the notifier's in-flight
                # re-evaluations ride the scheduler, which must still
                # be admitting while they drain
                self.subs.stop()
            if self.scheduler is not None:
                # before the write lock: queued cohorts must drain (fail
                # fast) or they would wait on a read lock that never comes
                self.scheduler.stop()
            if self.snapshotter is not None:
                # likewise before the write lock: a mid-seal snapshotter
                # holds it and must finish (or be told to stop) first
                self.snapshotter.stop()
            with self._engine_lock.write():
                if self.cluster is not None:
                    self.cluster.stop()
                if hasattr(self.store, "close"):
                    self.store.close()
            self._stopped = True

    # -- request execution -------------------------------------------------

    def run_query(
        self,
        text: str,
        variables: Optional[dict] = None,
        debug: bool = False,
        timeout_s: Optional[float] = None,
        trace_ctx=None,
        tenant: str = "",
        cancel_probe=None,
        ledger_out: bool = False,
        encoded: bool = False,
    ):
        """The ParseQueryAndMutation → ProcessWithMutation → encode path
        with the reference's latency breakdown (query/query.go:102).

        Returns the response dict.  ``encoded=True`` (the JSON-over-HTTP
        handler, which only sends it) returns it as a frozen
        ``Response``: the answer's blocks stay encoded — serialised once
        for twins, cache and socket — and a result-cache hit never
        builds a tree.

        ``timeout_s`` is the caller's remaining budget (gRPC deadline /
        X-Dgraph-Timeout header): a scheduled request past it sheds with
        SchedDeadlineError while queued, and — under QoS — its
        CancelToken stops execution at the next hop-dispatch checkpoint
        once the budget lapses mid-flight (504 either way; the engine
        stops burning time for a client that already gave up).

        ``tenant`` is the QoS scope (X-Dgraph-Tenant / gRPC metadata;
        absent = default tenant) and ``cancel_probe`` an optional
        transport-liveness callable (returns True when the client is
        GONE) that turns client disconnects into cooperative
        cancellation.  Both are inert under DGRAPH_TPU_QOS=0.

        ``trace_ctx`` (obs.TraceContext) is the caller's incoming W3C
        traceparent, if any: a sampled upstream makes this request's
        flight-recorder root join its trace.  When sampled, the legacy
        Latency stage marks are mirrored as ``parsing``/``processing``
        spans under the root — the response's latency map renders
        exactly as before, the trace just stops being flat."""
        from dgraph_tpu import gql

        NUM_QUERIES.add(1)
        PENDING_QUERIES.add(1)
        lat = Latency()
        t0 = time.monotonic()
        sched = self.scheduler
        qos_on = sched is not None and sched.qos is not None
        token = None
        if qos_on:
            tenant = _qos.resolve_tenant(tenant)
            token = _qos.CancelToken(timeout_s, tenant=tenant)
            if cancel_probe is not None:
                token.attach_probe(cancel_probe)
        else:
            tenant = ""
        root = obs.start_request("query", trace_ctx)
        if root is not None:
            root.set_attr("query", text[:200])
            if self.cluster is not None:
                root.set_attr("node", self.cluster.node_id)
            if qos_on:
                root.set_attr("tenant", tenant)
            root.__enter__()  # paired with __exit__ in the finally below
        # per-query resource ledger (obs/ledger.py): one pooled struct
        # for this request's whole serving path; None under
        # DGRAPH_TPU_LEDGER=0, and then every downstream site is a dead
        # None-check — the byte-identical off switch
        led = _ledger.start(tenant)
        ltoken = _ledger.activate(led) if led is not None else None
        parsed = None
        try:
            with obs.child("parsing"), obs.stage(None, "parse_ms"):
                parsed = gql.parse(text, variables)
            lat.record_parsing()
            if parsed.mutation is not None:
                # disk-fault read-only mode: shed mutations BEFORE they
                # queue on the write lock (reads keep flowing below);
                # the handler maps this to 503 + Retry-After
                ro = getattr(self.store, "storage_readonly", None)
                if ro is not None and ro():
                    st = self.store.health
                    raise ReadOnlyError(
                        "storage is in read-only mode "
                        f"({st.last_site}: {st.last_error}); "
                        "mutations shed until the re-arm probe clears",
                        retry_after=st.probe_interval_s,
                    )
            from dgraph_tpu.query import outputnode

            with obs.child("processing"):
                if self.scheduler is not None and parsed.mutation is None:
                    # read-only: ride a cohort (the scheduler's member
                    # thread sets DEBUG_UIDS for the encode; writes and
                    # profiled runs keep the exclusive path below,
                    # untouched).  The key makes equal requests
                    # singleflight-coalescible AND tier-2
                    # result-cacheable: a repeat of an executed key over
                    # the same store snapshot returns from the cache
                    # before admission (sched/scheduler.py,
                    # cache/result.py; DGRAPH_TPU_CACHE=0 restores
                    # today's path exactly).
                    vkey = (
                        json.dumps(variables, sort_keys=True)
                        if variables else ""
                    )
                    if token is not None and root is not None:
                        # the /admin/cancel?trace_id= hook: registered
                        # ONLY for scheduled reads — the inline
                        # mutation/profiled path has no checkpoints, and
                        # the endpoint must 404 rather than claim a
                        # cancel it cannot deliver.  Sampled requests
                        # are exactly the ones an operator can see (and
                        # therefore target) in /debug/traces.
                        _qos.REGISTRY.register(root.trace_id, token)
                    answer, stats = self.scheduler.run_answer(
                        parsed, debug=debug, timeout_s=timeout_s,
                        key=(text, vkey, debug),
                        tenant=tenant, cancel=token, tree=not encoded,
                    )
                else:
                    out: dict = {}
                    debug_token = outputnode.DEBUG_UIDS.set(debug)
                    try:
                        stats = self._run_locked(parsed, out)
                    finally:
                        outputnode.DEBUG_UIDS.reset(debug_token)
                    answer = Answer(out)
                    if parsed.mutation is not None:
                        # group-commit durability barrier, OUTSIDE the
                        # write lock: the mutation is applied and
                        # journaled; the ack (this response) waits for a
                        # shared fsync that concurrent writers amortize
                        # (no-op unless enable_group_commit ran — see
                        # __init__).  Stage write_wal, with the appends
                        # and the flush (models/wal.py)
                        barrier = getattr(self.store, "sync_barrier", None)
                        if barrier is not None:
                            with obs.stage(None, "write_wal_ms"):
                                barrier()
            lat.record_processing()
            # json encode happens in the handler; pre-record here so the
            # latency map is complete before attaching it
            lat.record_json()
            # the per-request tail, appended after the answer's blocks
            # (cache/result.py TAIL_KEYS)
            tail = {"server_latency": lat.to_map()}
            if ledger_out and led is not None:
                # explicit opt-in surface (?ledger=true): the account in
                # the response extensions, the Dgraph convention for
                # out-of-band response metadata.  Default responses (any
                # gate state) never carry the key.
                tail["extensions"] = {"ledger": led.to_dict()}
            if debug:
                # per-stage engine breakdown (device vs host vs fused
                # chain time + edges traversed) — the per-query profile
                # surface (reference: --trace + pprof, main.go:181).
                # ``stats`` comes from this request's own engine shell,
                # so concurrent queries can't clobber it.  Caveat under
                # the cohort scheduler: a hop MERGED across sessions
                # (HopMerger) attributes the whole union's edge count
                # and device time to the member that led the dispatch —
                # cohort-attributed, not per-request; DGRAPH_TPU_SCHED=0
                # restores exact per-request accounting.
                tail["server_latency"]["engine"] = {
                    k: (round(v, 3) if isinstance(v, float) else v)
                    for k, v in stats.items()
                }
            if parsed.mutation is not None:
                WRITES.add("ok")   # past the barrier: this return is the ack
            if encoded:
                return Response(answer, tail)
            return {**answer.tree(), **tail}
        except BaseException as e:
            if parsed is not None and parsed.mutation is not None:
                WRITES.add("error")
            if root is not None:
                root.set_attr("error", type(e).__name__)
                if isinstance(e, QueryCancelledError):
                    # the PR-7 span contract: a cancelled query's trace
                    # says so explicitly, not just via the error class
                    root.set_attr("outcome", "cancelled")
            if isinstance(e, QueryCancelledError):
                QUERY_CANCELLED.add(
                    (e.reason, _qos.metric_label(tenant or e.tenant))
                )
            raise
        finally:
            PENDING_QUERIES.add(-1)
            dur = time.monotonic() - t0
            if led is not None:
                # drain to the per-tenant/per-route series and recycle
                # the struct; a sampled trace carries the same account
                # as a root attr (before __exit__ publishes it)
                _ledger.deactivate(ltoken)
                summary = _ledger.finish(led)
                if root is not None:
                    root.set_attr("ledger", summary)
            trace_id = root.trace_id if root is not None else None
            if root is not None:
                if token is not None:
                    # identity-checked: a concurrent request sharing
                    # this trace id keeps ITS registration
                    _qos.REGISTRY.unregister(root.trace_id, token)
                root.__exit__(None, None, None)  # publish to the ring
            if qos_on:
                TENANT_LATENCY.observe(_qos.metric_label(tenant), dur)
            # slow-query tail sampling is independent of the head
            # sampler: an offender at ratio 0 still gets a structured
            # log line and a synthetic trace (obs/spans.py note_slow) —
            # run it BEFORE the histogram so the tail bucket's exemplar
            # can point at the synthetic trace too
            slow_tid = obs.get_recorder().note_slow(text, dur, trace_id)
            # the latency histogram carries the trace as an OpenMetrics
            # exemplar (utils/metrics.py): the bucket this request
            # landed in links straight to /debug/traces/<id>
            QUERY_LATENCY.observe(dur, trace_id=trace_id or slow_tid)

    _dump_seq = itertools.count()

    def _dump_subgraphs(self, dump) -> None:
        import datetime as _dt

        try:
            import os as _os

            _os.makedirs(self.dumpsg_path, exist_ok=True)
            # timestamp + process-wide sequence: concurrent queries in the
            # same microsecond must not overwrite each other's dump
            name = "%s.%06d.json" % (
                _dt.datetime.now().strftime("%Y%m%d.%H%M%S.%f"),
                next(self._dump_seq),
            )
            with open(_os.path.join(self.dumpsg_path, name), "w") as f:
                # default=str: a non-JSON-able value (e.g. a numpy scalar
                # in params) must degrade to its repr, not a TypeError
                json.dump(dump, f, indent=1, default=str)
        except (OSError, ValueError):  # dump failures must never fail the query
            pass

    def _run_locked(self, parsed, out: dict) -> dict:
        # Mutations (and the profiler, which is not thread-safe) need the
        # exclusive side; pure queries share the read side and execute
        # concurrently, each on its own engine shell over the shared
        # arena cache (query/query.go:1684-1714 runs per-request
        # goroutines the same way).
        is_write = parsed.mutation is not None or self._profiler is not None
        if is_write:
            # stage write_lock: a mutation's wait for the exclusive side —
            # every reader in flight runs out first
            with obs.stage(None, "write_lock_ms"):
                self._engine_lock.acquire_write()
            release = self._engine_lock.release_write
        else:
            self._engine_lock.acquire_read()
            release = self._engine_lock.release_read
        try:
            if self._profiler is not None:
                self._profiler.enable()
            try:
                if is_write:
                    eng = self.engine  # exclusive: run on the main engine
                else:
                    eng = QueryEngine(self.store, arenas=self.engine.arenas)
                    eng.chain_threshold = self.engine.chain_threshold
                eng.dump_shapes = bool(self.dumpsg_path)
                out.update(eng.run_parsed(parsed))
                if parsed.mutation is not None:
                    # stage refresh: the writer, which holds the exclusive
                    # side, takes its own journal into the cached arenas and
                    # their device layouts — once, while no reader runs; the
                    # reader that comes next finds nothing dirty and pays
                    # for no layout (an embedded engine or a clustered
                    # apply, which have no such lock, still refresh on the
                    # next read, inside that read's own stages)
                    with obs.stage(None, "refresh_ms"):
                        self.engine.arenas.refresh()
                led = _ledger.current()
                if led is not None:
                    led.merge_engine_stats(eng.stats)
                if self.dumpsg_path and eng.last_dump:
                    self._dump_subgraphs(eng.last_dump)
            finally:
                if self._profiler is not None:
                    self._profiler.disable()
            return dict(eng.stats)
        finally:
            release()


def _auto_mesh():
    """A ("data","model") mesh over all local devices; big predicates
    then expand row-sharded through the mesh serving plane
    (dgraph_tpu/mesh).

    ``DGRAPH_TPU_MESH`` tri-state (the env convention of planconfig):
      "0"/"off"       — never: unsharded serving, byte-identical to the
                        pre-mesh engine (the docs/deploy.md contract);
      "1"/"auto"/unset — on when more than one device is visible;
      "force"          — always, even single-device (a 1-wide mesh:
                        the mesh code paths run, results unchanged —
                        the CI byte-identity arm uses this with the
                        forced 8-device host platform)."""
    import os

    mode = os.environ.get("DGRAPH_TPU_MESH", "auto")
    if mode in ("0", "off"):
        return None
    import jax

    if mode != "force" and len(jax.devices()) < 2:
        return None
    from dgraph_tpu.parallel import make_mesh

    return make_mesh(len(jax.devices()), data=1)


def _make_handler(srv: DgraphServer):
    class Handler(BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"
        timeout = 60  # bounds reads AND the deferred TLS handshake below
        # TCP_NODELAY: the stdlib default leaves Nagle armed, and a
        # keep-alive request/response exchange then hits the classic
        # Nagle × delayed-ACK stall — measured 44ms PER REQUEST on this
        # host's loopback for a response a warm cache serves in 0.5ms.
        # A request/response server never benefits from coalescing its
        # last segment; responses are byte-identical, only un-delayed.
        disable_nagle_algorithm = True

        def setup(self):
            super().setup()
            # deferred TLS handshake, in this connection's thread and
            # under this connection's timeout
            import ssl

            if isinstance(self.request, ssl.SSLSocket):
                try:
                    self.request.do_handshake()
                except (ssl.SSLError, OSError):
                    self.close_connection = True
                    raise
        server_version = "dgraph-tpu/0.1"

        def log_message(self, *a):  # quiet
            pass

        def _reply(
            self,
            code: int,
            body: bytes,
            ctype: str = "application/json",
            extra_headers=None,
        ):
            self.send_response(code)
            self.send_header("Content-Type", ctype)
            self.send_header("Content-Length", str(len(body)))
            for k, v in _CORS.items():
                self.send_header(k, v)
            for k, v in (extra_headers or {}).items():
                self.send_header(k, v)
            self.end_headers()
            self.wfile.write(body)

        def _err(self, code: int, msg: str):
            self._reply(
                code,
                json.dumps({"code": "ErrorInvalidRequest", "message": msg}).encode(),
            )

        def do_OPTIONS(self):
            self._reply(200, b"")

        def do_GET(self):
            u = urlparse(self.path)
            path = u.path
            if path == "/health":
                qs = parse_qs(u.query)
                if qs.get("detail", ["0"])[0] in ("1", "true"):
                    # peer/breaker/raft-leader summary (resilience layer,
                    # cluster/peerclient.py).  The bare /health stays a
                    # plain OK/503 — load balancers and the dashboard
                    # only want the bit.
                    detail = {"ok": srv.health.ok()}
                    if srv.cluster is not None:
                        detail.update(srv.cluster.health_summary())
                    status = getattr(srv.store, "storage_status", None)
                    if status is not None:
                        # disk plane: read-only latch, WAL growth,
                        # snapshot age, last recovery (models/wal.py)
                        detail["storage"] = status()
                    # device fault domain (utils/devguard.py): per-domain
                    # health state machine, fault/failover counters, and
                    # the re-admission probe's score card
                    from dgraph_tpu.utils import devguard as _devguard

                    detail["device"] = {
                        "enabled": _devguard.enabled(),
                        "domains": _devguard.summary(),
                    }
                    # elastic mesh fault domain (mesh/fault.py): current
                    # epoch, per-chip guard states, placement summary
                    # and in-flight drain count — the operator's first
                    # stop in the "Mesh fault domain" runbook
                    dom = getattr(srv.engine.arenas, "mesh_fault", None)
                    if dom is not None:
                        detail["mesh"] = dom.status()
                    code = 200 if srv.health.ok() else 503
                    self._reply(code, json.dumps(detail).encode())
                elif srv.health.ok():
                    self._reply(200, b"OK", "text/plain")
                else:
                    self._reply(503, b"\"uninitialized\"")
            elif path == "/":
                from dgraph_tpu.serve.dashboard import DASHBOARD_HTML

                self._reply(200, DASHBOARD_HTML.encode(), "text/html")
            elif path == "/subscribe":
                # attach to a detached subscription's event stream
                if srv.subs is None:
                    return self._err(404, "subscriptions disabled")
                sid = parse_qs(u.query).get("id", [""])[0]
                sub = srv.subs.get(sid) if sid else None
                if sub is None:
                    return self._err(404, "no such subscription")
                self._sse_stream(sub)
            elif path == "/debug/store":
                from dgraph_tpu.query import joinplan

                with srv._engine_lock.read():
                    stats = _store_stats(srv.store)
                stats["qcache"] = _qcache_stats(srv)
                # IVM: per-pred version spread + delta-stream state +
                # live-subscription table (None when the gate is off)
                stats["ivm"] = _ivm_stats(srv)
                # multi-tenant QoS: tenant table + live queue/inflight
                # depths (None when DGRAPH_TPU_QOS=0 or scheduler off)
                stats["qos"] = (
                    srv.scheduler.qos_state()
                    if srv.scheduler is not None
                    else None
                )
                # MXU join tier: route counts + the recent decision ring
                # (mxu vs pairwise with the cost estimates that drove
                # each choice) — the chain_reject explainability,
                # process-wide
                stats["join"] = joinplan.debug_summary()
                self._reply(200, json.dumps(stats).encode())
            elif path == "/debug/device":
                # device/HBM telemetry snapshot (obs/device.py): backend
                # identity, HBM residency vs budget, program-cache
                # occupancy, compile-event totals — and the gauges
                # refresh as a side effect of the snapshot
                self._reply(200, json.dumps(_device.snapshot(srv)).encode())
            elif path == "/debug/bundle":
                # ONE postmortem JSON: everything an operator pastes
                # into an incident doc — traces ring + slow queries +
                # planner/join rings + qos + ivm + device + ledger
                # aggregates, snapshotted together so the pieces are
                # mutually consistent to within one scrape
                from dgraph_tpu.obs import ledger as _ledgermod
                from dgraph_tpu.query import planner as _planner

                rec = obs.get_recorder()
                bundle = {
                    "generated_unix": time.time(),
                    "traces": rec.traces() if srv.expose_trace else None,
                    "slow_queries": (
                        rec.slow_queries() if srv.expose_trace else None
                    ),
                    "planner": _planner.debug_summary(
                        scheduler=srv.scheduler
                    ),
                    "qos": (
                        srv.scheduler.qos_state()
                        if srv.scheduler is not None
                        else None
                    ),
                    "ivm": _ivm_stats(srv),
                    "qcache": _qcache_stats(srv),
                    "device": _device.snapshot(srv),
                    "ledger": _ledgermod.aggregate_summary(),
                }
                self._reply(200, json.dumps(bundle, default=str).encode())
            elif path == "/debug/planner":
                # the unified route-decision view (query/planner.py):
                # calibration provenance + live rates, per-(kind,route)
                # decision counts with mispredicts, the recent decision
                # ring (each entry carries both cost estimates and — when
                # the post-hoc check ran — the measured latency), PR 9's
                # join ring, and the scheduler's adaptive cohort state
                from dgraph_tpu.query import planner

                self._reply(
                    200,
                    json.dumps(
                        planner.debug_summary(scheduler=srv.scheduler)
                    ).encode(),
                )
            elif path in ("/metrics", "/debug/prometheus_metrics"):
                # /metrics is the standard scrape alias; the debug path
                # stays for existing scrape configs.  Content negotiation:
                # a scraper asking for OpenMetrics gets histogram bucket
                # EXEMPLARS (trace_id links into /debug/traces) + # EOF;
                # everyone else gets the classic format under its proper
                # versioned content type.
                accept = self.headers.get("Accept", "")
                if "application/openmetrics-text" in accept:
                    self._reply(
                        200,
                        metrics.openmetrics_text().encode(),
                        "application/openmetrics-text; version=1.0.0; "
                        "charset=utf-8",
                    )
                else:
                    self._reply(
                        200,
                        metrics.prometheus_text().encode(),
                        "text/plain; version=0.0.4; charset=utf-8",
                    )
            elif path == "/debug/traces" or path.startswith("/debug/traces/"):
                # the flight-recorder ring (obs/spans.py): listing, one
                # trace's merged span tree, or the Chrome trace_event
                # export (?format=chrome) for chrome://tracing / Perfetto
                if not srv.expose_trace:
                    return self._err(403, "tracing not exposed")
                rec = obs.get_recorder()
                if path == "/debug/traces":
                    self._reply(200, json.dumps(rec.traces()).encode())
                else:
                    tid = path.rsplit("/", 1)[1]
                    t = rec.trace(tid)
                    if t is None:
                        return self._err(404, "no such trace")
                    qs = parse_qs(u.query)
                    if qs.get("format", [""])[0] == "chrome":
                        t = obs.chrome_trace(t)
                    self._reply(200, json.dumps(t).encode())
            elif path == "/debug/slow_queries":
                if not srv.expose_trace:
                    return self._err(403, "tracing not exposed")
                self._reply(
                    200,
                    json.dumps(obs.get_recorder().slow_queries()).encode(),
                )
            elif path == "/admin/export":
                try:
                    with srv._export_lock, srv._engine_lock.read():
                        info = export_rdf(srv.store, srv.export_path)
                    self._reply(200, json.dumps(
                        {"code": "Success", "message": "Export completed.", **info}
                    ).encode())
                except Exception as e:  # pragma: no cover
                    self._err(500, str(e))
            elif path == "/admin/snapshot":
                # force a snapshot/compaction round now (the knob-driven
                # Snapshotter's manual trigger; ?wait=1 blocks until the
                # round completed).  Clustered servers compact every
                # group's raft log instead (same trigger machinery).
                qs = parse_qs(u.query)
                wait = qs.get("wait", ["0"])[0] in ("1", "true")
                if srv.cluster is not None:
                    srv.cluster.snapshot_all()
                    self._reply(200, json.dumps(
                        {"code": "Success",
                         "message": "Raft snapshot requested for all groups."}
                    ).encode())
                elif srv.snapshotter is not None:
                    ok = srv.snapshotter.trigger(wait=wait)
                    if ok:
                        self._reply(200, json.dumps(
                            {"code": "Success",
                             "message": "Snapshot completed."
                             if wait else "Snapshot triggered."}
                        ).encode())
                    else:
                        self._err(500, "snapshot failed; see /health?detail=1")
                else:
                    self._err(404, "store has no snapshotter")
            elif path == "/admin/cancel":
                # explicit cooperative cancellation: flip the live
                # CancelToken registered under this trace id (sampled
                # requests only — exactly the ones visible in
                # /debug/traces).  The query stops at its next
                # hop-dispatch checkpoint and answers 499/504; this
                # endpoint merely flips the flag.
                qs = parse_qs(u.query)
                tid = qs.get("trace_id", [""])[0]
                if not tid:
                    return self._err(400, "trace_id required")
                if _qos.REGISTRY.cancel(tid, reason="admin"):
                    self._reply(200, json.dumps({
                        "code": "Success",
                        "message": f"cancel requested for trace {tid}",
                    }).encode())
                else:
                    self._err(404, "no live query under that trace_id")
            elif path == "/admin/shutdown":
                self._reply(200, json.dumps(
                    {"code": "Success", "message": "Server is shutting down"}
                ).encode())
                threading.Thread(target=srv.stop, daemon=True).start()
            elif path.startswith("/share/"):
                sid = path.rsplit("/", 1)[1]
                q = srv._shares.get(sid)
                if q is None:
                    self._err(404, "no such share")
                else:
                    self._reply(200, json.dumps({"share": q}).encode())
            elif path == "/pred-snapshot":
                # cross-server read plane (ServeTask analog): versioned
                # predicate snapshot for groups other servers don't place
                if srv.cluster is None:
                    return self._err(404, "not clustered")
                if not self._cluster_authorized():
                    return self._err(403, "cluster secret required")
                qs = parse_qs(u.query)  # parse_qs already percent-decodes
                name = qs.get("name", [""])[0]
                since = int(qs.get("since", ["-1"])[0])
                # server half of the distributed trace: a sampled remote
                # reader's traceparent makes THIS node record its leg of
                # the snapshot serve under the same trace_id
                tctx = obs.parse_traceparent(self.headers.get("Traceparent"))
                with obs.server_span("peer.pred-snapshot", tctx) as ss:
                    ss.set_attr("node", srv.cluster.node_id)
                    ss.set_attr("pred", name)
                    gid = srv.cluster.conf.belongs_to(name)
                    g = srv.cluster.groups.get(gid)
                    if g is None:
                        return self._err(404, f"group {gid} not served here")
                    from dgraph_tpu.cluster.replica import pred_to_bytes

                    with g._lock:
                        ver = g.pred_version(name)
                        body = (
                            b"" if ver == since
                            else pred_to_bytes(g.store, name)
                        )
                    ss.set_attr("bytes", len(body))
                    self.send_response(204 if ver == since else 200)
                    self.send_header("X-Pred-Version", str(ver))
                    self.send_header("Content-Length", str(len(body)))
                    self.end_headers()
                    if ver != since:
                        self.wfile.write(body)
            elif path == "/predlist":
                if srv.cluster is None:
                    return self._err(404, "not clustered")
                if not self._cluster_authorized():
                    return self._err(403, "cluster secret required")
                gid = int(parse_qs(u.query).get("group", ["-1"])[0])
                g = srv.cluster.groups.get(gid)
                if g is None:
                    return self._err(404, f"group {gid} not served here")
                with g._lock:
                    names = sorted(g.store._preds.keys())
                self._reply(200, json.dumps(names).encode())
            else:
                self._err(404, "no such endpoint")

        def _sse_stream(self, sub):
            """Server-sent-events pump for one subscription: close-
            delimited HTTP/1.1 stream (no Content-Length), one ``event:``
            frame per pushed update, comment heartbeats while idle so a
            vanished client surfaces as a write error within a beat.
            The connection owns the subscription: a transport error
            cancels it (a live query with no listener is pure waste)."""
            self.send_response(200)
            self.send_header("Content-Type", "text/event-stream")
            self.send_header("Cache-Control", "no-cache")
            self.send_header("Connection", "close")
            self.end_headers()
            self.close_connection = True
            try:
                while True:
                    ev = sub.next_event(timeout=2.0)
                    if ev is None:
                        self.wfile.write(b": ping\n\n")
                        self.wfile.flush()
                        continue
                    frame = (
                        f"event: {ev.get('kind', 'update')}\n"
                        f"id: {ev.get('seq', 0)}\n"
                        f"data: {json.dumps(ev, default=str)}\n\n"
                    )
                    self.wfile.write(frame.encode())
                    self.wfile.flush()
                    if ev.get("kind") == "cancelled":
                        return
            except OSError:
                srv.subs.cancel(sub.id, reason="disconnect")

        def _disconnect_probe(self):
            """Transport-liveness probe for cooperative cancellation
            (None when QoS is off — zero overhead on the legacy path).
            Both transports route through the shared helper
            (sched/qos.py::socket_disconnect_probe): plain TCP peeks
            the socket for EOF without consuming pipelined bytes; TLS
            checks the SSL layer's buffered-pending first and peeks the
            RAW fd for the FIN (recv flags are rejected at the SSL
            layer), so a vanished HTTPS client cancels cooperatively
            too."""
            if srv.scheduler is None or srv.scheduler.qos is None:
                return None
            return _qos.socket_disconnect_probe(self.connection)

        def _cluster_authorized(self) -> bool:
            """Gate for the intra-cluster control plane (/raft*, /assign-uids):
            when the cluster is configured with a shared secret, every peer
            request must carry it — these endpoints share the public port
            (the reference isolates its raft plane on an internal gRPC
            port), and an unauthenticated one lets anyone with network
            reach inject forged raft frames or arbitrary proposals."""
            secret = getattr(srv.cluster.auth, "secret", "") if srv.cluster else ""
            if not secret:
                return True
            import hmac

            from dgraph_tpu.cluster.transport import SECRET_HEADER

            got = self.headers.get(SECRET_HEADER, "")
            # bytes, not str: compare_digest raises on non-ASCII strings
            return hmac.compare_digest(got.encode("utf-8"), secret.encode("utf-8"))

        def do_POST(self):
            u = urlparse(self.path)
            n = int(self.headers.get("Content-Length", 0))
            if u.path == "/assign-uids":
                # leader-only uid leasing (AssignUidsOverNetwork target)
                raw = self.rfile.read(n)
                if srv.cluster is None:
                    return self._err(404, "not clustered")
                if not self._cluster_authorized():
                    return self._err(403, "bad cluster secret")
                from dgraph_tpu.cluster.raft import NotLeaderError

                tctx = obs.parse_traceparent(self.headers.get("Traceparent"))
                with obs.server_span("peer.assign-uids", tctx) as ss:
                    ss.set_attr("node", srv.cluster.node_id)
                    try:
                        want = int(raw or b"1")
                        if want < 0:  # negative = reserve an explicit uid
                            start, end = srv.cluster.reserve_local(-want)
                        else:
                            start, end = srv.cluster.assign_local(want)
                    except NotLeaderError as e:
                        return self._reply(
                            409, (e.leader or "").encode(), "text/plain"
                        )
                    except Exception as e:
                        return self._err(400, str(e))
                    return self._reply(
                        200, json.dumps({"start": start, "end": end}).encode()
                    )
            if u.path == "/join":
                # runtime membership: a new server announces itself
                # (grpc JoinCluster analog, draft.go:1049)
                raw = self.rfile.read(n)
                if srv.cluster is None:
                    return self._err(404, "not clustered")
                if not self._cluster_authorized():
                    return self._err(403, "bad cluster secret")
                try:
                    body = json.loads(raw)
                    peers = srv.cluster.handle_join(
                        str(body["id"]), str(body["addr"])
                    )
                except Exception as e:
                    return self._err(400, str(e))
                return self._reply(200, json.dumps({"peers": peers}).encode())
            if u.path.startswith("/raft/") or u.path.startswith("/raft-propose/"):
                # raft plane: binary frames, no engine lock (RaftMessage /
                # proposeOrSend endpoints, draft.go:1017, mutation.go:319)
                raw = self.rfile.read(n)
                if srv.cluster is None:
                    return self._err(404, "not clustered")
                if not self._cluster_authorized():
                    return self._err(403, "bad cluster secret")
                try:
                    gid = int(u.path.rsplit("/", 1)[1])
                except ValueError:
                    return self._err(400, "bad group")
                if u.path.startswith("/raft/"):
                    try:
                        srv.cluster.deliver(gid, raw)
                    except Exception as e:
                        return self._err(400, str(e))
                    return self._reply(200, b"{}")
                from dgraph_tpu.cluster.raft import NotLeaderError

                # the forwarded-proposal leg of a distributed trace: a
                # sampled forwarder's traceparent lands this node's
                # commit work in the same trace
                tctx = obs.parse_traceparent(self.headers.get("Traceparent"))
                with obs.server_span("peer.raft-propose", tctx) as ss:
                    ss.set_attr("node", srv.cluster.node_id)
                    ss.set_attr("group", gid)
                    try:
                        srv.cluster.propose_local(gid, raw)
                    except NotLeaderError as e:
                        ss.set_attr("outcome", "not_leader")
                        return self._reply(
                            409, (e.leader or "").encode(), "text/plain"
                        )
                    except Exception as e:
                        return self._err(500, str(e))
                    return self._reply(200, b"{}")
            body = self.rfile.read(n).decode("utf-8", "replace")
            if u.path == "/subscribe":
                # live-query registration (dgraph_tpu/ivm/subs.py): the
                # body is a read-only DQL query, vars ride X-Dgraph-Vars
                # like /query.  An SSE-capable client (Accept:
                # text/event-stream or ?stream=1) gets the event stream
                # on THIS connection, starting with the snapshot;
                # otherwise the response is the subscription handle to
                # attach to via GET /subscribe?id=.
                if srv.subs is None:
                    return self._err(404, "subscriptions disabled "
                                          "(DGRAPH_TPU_IVM/DGRAPH_TPU_SUBS)")
                from dgraph_tpu.ivm.subs import SubQuotaError

                try:
                    vars_hdr = self.headers.get("X-Dgraph-Vars")
                    variables = json.loads(vars_hdr) if vars_hdr else None
                    sub = srv.subs.register(
                        body, variables,
                        tenant=self.headers.get("X-Dgraph-Tenant") or "",
                    )
                except SubQuotaError as e:
                    return self._reply(
                        429,
                        json.dumps({
                            "code": "ErrorServiceUnavailable",
                            "message": str(e),
                            "tenant": e.tenant,
                        }).encode(),
                        extra_headers={
                            "Retry-After": str(
                                max(1, int(round(e.retry_after)))
                            )
                        },
                    )
                except Exception as e:
                    return self._err(400, str(e))
                qs = parse_qs(u.query)
                stream = (
                    "text/event-stream" in self.headers.get("Accept", "")
                    or qs.get("stream", ["0"])[0] in ("1", "true")
                )
                if stream:
                    return self._sse_stream(sub)
                return self._reply(200, json.dumps({
                    "code": "Success",
                    "sub_id": sub.id,
                    "preds": (
                        sorted(sub.footprint)
                        if sub.footprint is not None else None
                    ),
                }).encode())
            if u.path == "/subscribe/cancel":
                if srv.subs is None:
                    return self._err(404, "subscriptions disabled")
                sid = parse_qs(u.query).get("id", [""])[0]
                if not sid:
                    return self._err(400, "id required")
                if srv.subs.cancel(sid):
                    return self._reply(200, json.dumps({
                        "code": "Success",
                        "message": f"subscription {sid} cancelled",
                    }).encode())
                return self._err(404, "no such subscription")
            if u.path == "/query":
                qs = parse_qs(u.query)
                debug = qs.get("debug", ["false"])[0] == "true"
                # ?ledger=true: return the per-query resource account in
                # the response extensions (obs/ledger.py; no-op when
                # DGRAPH_TPU_LEDGER=0)
                want_ledger = qs.get("ledger", ["false"])[0] == "true"
                try:
                    vars_hdr = self.headers.get("X-Dgraph-Vars")
                    variables = json.loads(vars_hdr) if vars_hdr else None
                    # request budget (seconds): ONE deadline resolution
                    # shared with the gRPC surface (sched/qos.py) —
                    # queued AND (under QoS) executing phases both honor
                    # it
                    timeout_s = _qos.parse_timeout(
                        self.headers.get("X-Dgraph-Timeout")
                    )
                    # a malformed traceparent parses to None — an
                    # attacker-controlled header must never 500 a query
                    tctx = obs.parse_traceparent(
                        self.headers.get("Traceparent")
                    )
                    accept = self.headers.get("Accept", "")
                    # binary client surface: protobuf wire-format
                    # Response (graphresponse.proto), hand-encoded from
                    # the tree — see serve/proto.py
                    as_proto = (
                        "application/protobuf" in accept
                        or "application/x-protobuf" in accept
                    )
                    out = srv.run_query(
                        body, variables, debug=debug, timeout_s=timeout_s,
                        trace_ctx=tctx,
                        tenant=self.headers.get("X-Dgraph-Tenant") or "",
                        cancel_probe=self._disconnect_probe(),
                        ledger_out=want_ledger,
                        encoded=not as_proto,
                    )
                    # stage http_write: run_query's clock has stopped and
                    # its ledger is drained; the answer's one
                    # serialisation (a miss; twins wait for it, a
                    # result-cache hit has the bytes), the cache's put of
                    # those bytes, the splice of the tail and the socket
                    # write are the server's last share of the client's
                    # latency
                    with obs.stage(None, "http_write_ms"):
                        if as_proto:
                            from dgraph_tpu.serve import proto as _proto

                            self._reply(
                                200, _proto.encode_response(out),
                                "application/protobuf",
                            )
                        elif isinstance(out, Response):
                            self._reply(200, out.encode())
                        else:  # a wrapper put a plain dict in its place
                            self._reply(200, json.dumps(out).encode())
                except SchedQuotaError as e:
                    # per-TENANT quota shed: still a 429, but with a
                    # Retry-After sized to that tenant's own backlog —
                    # the antagonist gets back-pressure scoped to itself
                    self._reply(
                        429,
                        json.dumps({
                            "code": "ErrorServiceUnavailable",
                            "message": str(e),
                            "tenant": e.tenant,
                        }).encode(),
                        extra_headers={
                            "Retry-After": str(
                                max(1, int(round(e.retry_after)))
                            )
                        },
                    )
                except SchedOverloadError as e:
                    # shed under overload: retriable, not a client error
                    self._reply(429, json.dumps(
                        {"code": "ErrorServiceUnavailable", "message": str(e)}
                    ).encode())
                except SchedDeadlineError as e:
                    self._reply(504, json.dumps(
                        {"code": "ErrorDeadlineExceeded", "message": str(e)}
                    ).encode())
                except QueryCancelledError as e:
                    # cooperative cancellation: a deadline that lapsed
                    # MID-EXECUTION reads exactly like the queued-shed
                    # 504; disconnect/admin cancels get 499 (the nginx
                    # client-closed-request convention).  The reply may
                    # race a vanished client — that write failing is the
                    # expected outcome, never an error to surface.
                    try:
                        if e.reason == "deadline":
                            self._reply(504, json.dumps({
                                "code": "ErrorDeadlineExceeded",
                                "message": str(e),
                            }).encode())
                        else:
                            self._reply(499, json.dumps({
                                "code": "ErrorQueryCancelled",
                                "message": str(e),
                            }).encode())
                    except OSError:
                        self.close_connection = True
                except StorageFaultError as e:
                    # disk fault / read-only mode: the mutation was NOT
                    # acknowledged; retriable once the re-arm probe
                    # clears, so say exactly that (503 + Retry-After
                    # sized to the probe interval)
                    self._reply(
                        503,
                        json.dumps({
                            "code": "ErrorServiceUnavailable",
                            "message": str(e),
                        }).encode(),
                        extra_headers={
                            "Retry-After": str(
                                max(1, int(round(e.retry_after)))
                            )
                        },
                    )
                except StaleUnavailableError as e:
                    # owner group unreachable AND no cached snapshot to
                    # degrade to: a retriable SERVICE condition, told as
                    # one — 503 + Retry-After sized to the breaker
                    # cooldown, not a raw 400/500
                    self._reply(
                        503,
                        json.dumps({
                            "code": "ErrorServiceUnavailable",
                            "message": str(e),
                        }).encode(),
                        extra_headers={
                            "Retry-After": str(
                                max(1, int(round(e.retry_after)))
                            )
                        },
                    )
                except Exception as e:
                    self._err(400, str(e))
            elif u.path == "/share":
                sid = hashlib.sha256(body.encode()).hexdigest()[:16]
                srv._shares[sid] = body
                srv._shares.move_to_end(sid)
                while len(srv._shares) > srv._max_shares:
                    srv._shares.popitem(last=False)
                self._reply(200, json.dumps({"code": "Success", "uids": {"share": sid}}).encode())
            else:
                self._err(404, "no such endpoint")

    return Handler


def _ivm_stats(srv: DgraphServer) -> Optional[dict]:
    """/debug/store "ivm" section: predicate-version spread (how much
    invalidation scoping is buying), delta-stream occupancy, and the
    subscription table.  None when IVM is off or the store predates
    per-predicate tracking."""
    from dgraph_tpu import ivm as _ivm

    store = srv.store
    pv = getattr(store, "pred_versions", None)
    if not _ivm.ivm_enabled() or pv is None:
        return None
    stream = getattr(store, "delta_stream", None)
    return {
        # debug introspection, not a cache key (the ivm/ helpers ARE
        # what this section reports on)
        # graftlint: ignore[naked-version-key]
        "version": getattr(store, "version", 0),
        "pred_floor": getattr(store, "pred_floor", 0),
        "tracked_preds": len(pv),
        "stream": stream.snapshot() if stream is not None else None,
        "subs": srv.subs.snapshot() if srv.subs is not None else None,
    }


def _qcache_stats(srv: DgraphServer) -> dict:
    """Two-tier query cache occupancy for /debug/store (the counters
    live on /debug/prometheus_metrics; this is the at-a-glance view).
    Both tiers are None under DGRAPH_TPU_CACHE=0."""
    hop = srv.engine.arenas.hop_cache
    rc = srv.scheduler.result_cache if srv.scheduler is not None else None
    return {
        "hop": (
            {"entries": len(hop), "bytes": hop.occupancy_bytes}
            if hop is not None
            else None
        ),
        "result": (
            {"entries": len(rc), "bytes": rc.occupancy_bytes}
            if rc is not None
            else None
        ),
    }


def _store_stats(store: PostingStore) -> dict:
    """/debug/store — the badger-stats analog (cmd/dgraph/main.go:448)."""
    preds = {}
    for p in store.predicates():
        pd = store.peek(p)
        if pd is None:
            continue
        preds[p] = {
            "edges": sum(len(s) for s in pd.edges.values()),
            "values": len(pd.values),
        }
    from dgraph_tpu import native

    return {
        "predicates": preds,
        "uids": len(store.uids),
        "max_uid": store.uids.max_uid,
        # which N-Quad scanner bulk loads ride (the native one is built
        # on demand and silently absent without a toolchain)
        "nquad_scanner": native.scanner_name(),
    }
