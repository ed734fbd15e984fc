"""Device fault domain: health state machine, dispatch watchdog, and
hot host failover for the XLA execution plane.

The engine's own bench history is the bug report: TPU bench rounds 4-5
ran on a WEDGED chip (183 failed probes), and until this module the
serving stack had zero defense — a hung XLA dispatch blocked a
scheduler flush worker forever, an HBM ``RESOURCE_EXHAUSTED`` killed
the query, and a lost mesh chip killed the process.  The storage plane
(models/durability.py StorageHealth) and the peer plane
(cluster/peerclient.py breakers) already scope failures to one resource
and re-prove it with a cooldown-first half-open probe; this is the same
discipline for the device:

- **Per-domain health state machine** — ``healthy → suspect → sick``.
  A transient fault (XlaRuntimeError, injected OSError) marks the
  domain suspect; ``DGRAPH_TPU_DEVICE_SICK_AFTER`` consecutive faults
  (default 3) — or ONE wedged dispatch — latch it sick.  Sick domains
  shed device work in microseconds (:class:`DeviceSickError`) and the
  calibrated planner (query/planner.py) prices them out of every route
  decision via :func:`cost_factor`, so the engine's existing host numpy
  routes take over (byte-identical by the PR 1/9/10 parity contracts).
  Two domains exist: ``"device"`` (the default backend's dispatch
  plane) and ``"mesh"`` (the multi-chip collective plane) — a lost mesh
  chip re-plans sharded expansion to unsharded without branding
  single-device dispatch sick.

- **Dispatch watchdog** — :meth:`DeviceGuard.run` executes the
  dispatch+fetch closure on a guard-owned worker thread and waits at
  most ``DGRAPH_TPU_DEVICE_HANG_MS`` (default 30s — generous enough for
  a cold dispatch; time the worker spends inside XLA compiles is not
  counted, since compiling is host work and a cold program at deployed
  widths compiles for longer than that).  On overrun
  the caller abandons the wedged worker (it keeps blocking — nothing
  can interrupt a stuck XLA call — but it is no longer anyone's
  problem), latches the domain SICK and raises
  :class:`DeviceHangError` so the seam hot-fails over to the host
  route.  The flush worker is never the thread that blocks.

- **Exception classifier** — :func:`classify` sorts a dispatch failure
  into ``oom`` (``RESOURCE_EXHAUSTED`` / out-of-memory markers, however
  jaxlib spells the class), ``transient`` (other XLA runtime errors and
  OSError — injected faults ride this lane, failpoints are OSError by
  contract) or ``None`` (NOT a device fault: shape bugs, ValueErrors —
  re-raised unwrapped so real bugs never hide behind a failover).  On
  the per-level expander seam (query/engine.py ``_run_guarded`` — the
  seam every query crosses), an OOM triggers ArenaManager LRU eviction
  plus ONE retry before the host fallback (models/arena.py
  ``evict_for_oom``); the fused-route seams (chain/multi_hop/mxu)
  decline their route on OOM and let the per-level retry machinery
  handle the re-expansion.

- **Cooldown-first re-admission** — a sick domain starts a
  :class:`CooldownProbeLoop` (utils/health.py — the shared
  StorageHealth/breaker discipline): wait ``DGRAPH_TPU_DEVICE_COOLDOWN_S``
  (default 2s), then re-prove the device with one trivial dispatch
  under the same watchdog, single-probe-at-a-time via
  :class:`HalfOpenGate`.  Success re-admits (healthy); failure re-opens
  the cooldown.

Gate: ``DGRAPH_TPU_DEVGUARD`` (default on).  ``0`` restores the legacy
dispatch path byte-identically — no worker threads, no state checks, no
classification; every seam calls its closure inline.

Observability: ``dgraph_device_state{domain}`` (0 healthy / 1 suspect /
2 sick), ``dgraph_device_faults_total{kind}``,
``dgraph_device_failover_total{route}``,
``dgraph_device_probes_total{outcome}``; ``/health?detail=1`` carries a
``device`` section and ``/debug/device`` embeds :func:`summary`.
Chaos: the ``hang(ms=)`` / ``xla_oom`` failpoint actions
(utils/failpoints.py) arm at the ``device.*`` dispatch sites; the
seeded suite lives in tests/test_devguard.py and docs/deploy.md
"Device fault tolerance" documents the knobs and runbook.
"""

from __future__ import annotations

import os
import queue
import re
import sys
import threading
import time
from typing import Callable, Dict, Optional

from dgraph_tpu.utils.env import env_float
from dgraph_tpu.utils.failpoints import fail
from dgraph_tpu.utils.health import CooldownProbeLoop, HalfOpenGate
from dgraph_tpu.utils.metrics import (
    DEVICE_FAULTS,
    DEVICE_PROBES,
    DEVICE_STATE,
)

HEALTHY, SUSPECT, SICK = "healthy", "suspect", "sick"
_STATE_GAUGE = {HEALTHY: 0, SUSPECT: 1, SICK: 2}


def enabled() -> bool:
    """The DGRAPH_TPU_DEVGUARD gate (default ON); ``0`` restores the
    legacy dispatch path byte-identically."""
    return os.environ.get("DGRAPH_TPU_DEVGUARD", "1") != "0"


class DeviceFaultError(RuntimeError):
    """A classified device-plane fault at a dispatch seam.  ``kind`` ∈
    {hang, oom, transient, sick}; seams catch this (and only this) to
    hot-fail over to the host route."""

    def __init__(self, domain: str, op: str, kind: str, detail: str = ""):
        self.domain = domain
        self.op = op
        self.kind = kind
        super().__init__(
            f"device fault [{domain}/{op}]: {kind}"
            + (f" ({detail})" if detail else "")
        )


class DeviceSickError(DeviceFaultError):
    """Shed without dispatch: the domain is latched sick and the
    half-open probe has not re-proved it yet."""

    def __init__(self, domain: str, op: str):
        super().__init__(domain, op, "sick", "awaiting re-admission probe")


class DeviceHangError(DeviceFaultError):
    """The watchdog deadline lapsed with the dispatch still in flight:
    the worker is abandoned, the domain latched sick."""

    def __init__(self, domain: str, op: str, hang_ms: float):
        super().__init__(
            domain, op, "hang", f"no completion within {hang_ms:g}ms"
        )


_OOM_MARKERS = ("RESOURCE_EXHAUSTED", "out of memory", "Out of memory")
# class names that mean "the XLA runtime itself failed" across jaxlib
# layouts (jaxlib.xla_extension.XlaRuntimeError, jax.errors aliases)
_XLA_CLASS_MARKERS = ("XlaRuntimeError", "JaxRuntimeError")
# a program the compiler refuses is a bug in the program, whatever class
# carries the refusal: Pallas lowering (NotImplementedError), Mosaic
# (MosaicError / VerificationError), XLA:TPU ("compile permanent error")
_COMPILER_CLASS_MARKERS = ("MosaicError", "VerificationError")
_COMPILER_TEXT_MARKERS = ("Mosaic failed to compile", "compile permanent error")
# set on an exception that left a backend compile (note_compile_end)
_COMPILE_FAILURE_ATTR = "_dgraph_compile_failure"


def classify(exc: BaseException) -> Optional[str]:
    """Sort a dispatch failure: "oom" / "transient" device faults, or
    None for everything that is NOT the device's fault (shape bugs,
    ValueErrors, programs the compiler refuses — whatever memory space
    or error class the refusal names) — those re-raise unwrapped, never
    masked by failover."""
    if isinstance(exc, DeviceFaultError):
        return exc.kind
    text = f"{type(exc).__name__}: {exc}"
    if (
        getattr(exc, _COMPILE_FAILURE_ATTR, False)
        or isinstance(exc, NotImplementedError)
        or any(m in type(exc).__name__ for m in _COMPILER_CLASS_MARKERS)
        or any(m in text for m in _COMPILER_TEXT_MARKERS)
    ):
        return None
    if any(m in text for m in _OOM_MARKERS):
        return "oom"
    if any(m in type(exc).__name__ for m in _XLA_CLASS_MARKERS):
        return "transient"
    if isinstance(exc, OSError):
        # injected faults are OSError by failpoint contract; a real
        # OSError inside a dispatch closure is transport-shaped too
        return "transient"
    return None


# -- XLA compile accounting -----------------------------------------------------
#
# Fed by obs/device.py's jax.monitoring listeners (installed at server
# boot).  Compiling is host work: it cannot wedge the device, and a cold
# program at deployed widths compiles for longer than the hang deadline
# (sandbox compiles for v5e at film-21M widths, PR 21: resident.merge
# 93 s, batch.multi_hop 23 s) — so the watchdog does not count it, and an
# exception that leaves a compile is marked as the compiler's refusal.

_tls = threading.local()


def note_compile_begin() -> None:
    """A backend compile started on this thread."""
    job = getattr(_tls, "job", None)
    if job is not None:
        job.compile_t0 = time.monotonic()


def note_compile_end(secs: float, exc: Optional[BaseException]) -> None:
    """The backend compile on this thread ended after ``secs``; ``exc``
    is the exception leaving it, if it failed."""
    if exc is not None:
        try:
            setattr(exc, _COMPILE_FAILURE_ATTR, True)
        except (AttributeError, TypeError):
            pass  # slotted foreign class: the text markers still apply
    job = getattr(_tls, "job", None)
    if job is not None:
        job.compile_t0 = None
        job.compile_s += secs


# chip attribution: XLA device errors sometimes name the failing device
# ("chip=3", and injected faults carry the same tag via the failpoint
# chip= selector).  When a fault names a chip, the elastic mesh fault
# domain (mesh/fault.py) evicts THAT chip and re-shards onto survivors
# instead of latching the whole collective plane.
_CHIP_RE = re.compile(r"\bchip=(\d+)\b")


def chip_of(exc: BaseException) -> Optional[int]:
    """The chip index a dispatch failure names, walking the exception
    chain (a DeviceFaultError wraps the raw XLA/failpoint error); None
    when the fault cannot be attributed to one chip — the caller must
    then treat it as a whole-plane fault (the PR 15 path)."""
    seen = set()
    e: Optional[BaseException] = exc
    while e is not None and id(e) not in seen:
        seen.add(id(e))
        m = _CHIP_RE.search(f"{type(e).__name__}: {e}")
        if m:
            return int(m.group(1))
        e = e.__cause__ or e.__context__
    return None


class _Job:
    __slots__ = (
        "fn", "done", "result", "exc", "abandoned", "lock",
        "compile_t0", "compile_s", "_race_serial",
    )

    # graftcheck tier 3: the dispatcher creates the job, ONE worker
    # thread writes result/exc exactly once before done.set(), and only
    # the dispatcher flips abandoned (under job.lock) — the lockset
    # witness's single-writer hand-off tolerance must keep this silent
    __race_fields__ = frozenset({"result", "exc", "abandoned"})

    def __init__(self, fn):
        self.fn = fn
        self.done = threading.Event()
        self.result = None
        self.exc: Optional[BaseException] = None
        self.abandoned = False
        self.lock = threading.Lock()
        # compile accounting: written by the worker (through the
        # jax.monitoring listeners), read by the waiting dispatcher
        self.compile_t0: Optional[float] = None  # in-flight compile start
        self.compile_s = 0.0                     # finished compile seconds

    def compile_seconds(self) -> float:
        t0 = self.compile_t0
        return self.compile_s + (time.monotonic() - t0 if t0 is not None else 0.0)


class DeviceGuard:
    """One fault domain's health state + watchdog + probe machinery."""

    # graftcheck tier 3: callers, the idle-worker watchdog, and the
    # cooldown probe loop all mutate the state machine — every write
    # must carry self._lock (directly or via the caller-holds helpers)
    __race_fields__ = frozenset({
        "state", "_consecutive", "failovers", "probes_ok",
        "probes_failed", "readmissions", "wedged_workers",
    })

    def __init__(
        self,
        domain: str = "device",
        hang_ms: Optional[float] = None,
        cooldown_s: Optional[float] = None,
        sick_after: Optional[int] = None,
        probe_fn: Optional[Callable[[], None]] = None,
        on_readmit: Optional[Callable[[], None]] = None,
    ):
        self.domain = domain
        # fault-attribution sink (elastic mesh, mesh/fault.py): consulted
        # in run() after classify(); returning True means a SUB-domain
        # (one chip's guard) owns the fault and this plane guard is not
        # charged — the DeviceFaultError still raises so the seam can
        # retry under the re-sharded plan.  Wired post-construction by
        # the owning fault domain; None = every fault charges this guard.
        self.fault_sink: Optional[
            Callable[[str, str, BaseException], bool]
        ] = None
        # fired (outside the state lock) after a successful half-open
        # probe re-admits the domain — the staged-rejoin trigger for
        # per-chip sub-domains
        self.on_readmit = on_readmit
        self.hang_ms = (
            hang_ms
            if hang_ms is not None
            else env_float("DGRAPH_TPU_DEVICE_HANG_MS", 30_000.0)
        )
        self.cooldown_s = (
            cooldown_s
            if cooldown_s is not None
            else env_float("DGRAPH_TPU_DEVICE_COOLDOWN_S", 2.0)
        )
        self.sick_after = int(
            sick_after
            if sick_after is not None
            else env_float("DGRAPH_TPU_DEVICE_SICK_AFTER", 3)
        )
        self._probe_fn = probe_fn or self._default_probe
        self._lock = threading.Lock()
        self.state = HEALTHY
        self._consecutive = 0
        self._gate = HalfOpenGate()
        self._probe_loop = CooldownProbeLoop(
            self.probe_now,
            self.cooldown_s,
            lambda: self.state == SICK,
            name=f"dgraph-devguard-{domain}",
        )
        # worker-pool: idle workers recycle; a wedged one is abandoned
        # (it exits on its own when — if — the stuck call returns)
        self._idle: "queue.SimpleQueue[_IdleWorker]" = queue.SimpleQueue()
        # counters (status surface; the prometheus series are global)
        self.faults: Dict[str, int] = {}
        self.failovers = 0
        self.probes_ok = 0
        self.probes_failed = 0
        self.readmissions = 0
        self.wedged_workers = 0
        self.last_fault = ""
        self.last_fault_op = ""
        self.last_fault_at = 0.0
        DEVICE_STATE.set(domain, 0)

    # -- state machine ------------------------------------------------------

    def allowed(self) -> bool:
        """May a seam dispatch to this domain right now?  Guard off =
        always yes (the legacy path); sick = no (host routes take over
        until the probe re-admits)."""
        return not enabled() or self.state != SICK

    def _set_state(self, state: str) -> None:
        # caller holds self._lock
        if self.state != state:
            self.state = state
            DEVICE_STATE.set(self.domain, _STATE_GAUGE[state])

    def note_fault(self, kind: str, op: str, exc=None) -> None:
        """Record a classified device fault; one wedged dispatch latches
        SICK immediately (re-proving a hang costs hang_ms every time —
        suspect grace would just stall more queries), other kinds walk
        healthy → suspect → sick over ``sick_after`` consecutive
        faults."""
        DEVICE_FAULTS.add(kind)
        start_probe = False
        with self._lock:
            self.faults[kind] = self.faults.get(kind, 0) + 1
            self._consecutive += 1
            self.last_fault = (
                f"{kind}: {type(exc).__name__}: {exc}" if exc is not None
                else kind
            )
            self.last_fault_op = op
            self.last_fault_at = time.monotonic()
            if kind == "hang" or self._consecutive >= self.sick_after:
                if self.state != SICK:
                    print(
                        f"# device fault domain [{self.domain}] latched "
                        f"SICK at {op} ({self.last_fault}); device work "
                        "fails over to host routes, re-admission probe "
                        f"every {self.cooldown_s:g}s",
                        file=sys.stderr,
                    )
                self._set_state(SICK)
                self._gate.open(time.monotonic())
                start_probe = True
            elif self.state == HEALTHY:
                self._set_state(SUSPECT)
        if start_probe:
            self._probe_loop.start()

    def note_ok(self) -> None:
        with self._lock:
            self._consecutive = 0
            if self.state == SUSPECT:
                self._set_state(HEALTHY)

    def note_failover(self) -> None:
        with self._lock:
            self.failovers += 1

    # -- the watchdog-bracketed dispatch ------------------------------------

    def run(self, op: str, fn: Callable[[], object]):
        """Execute a dispatch+fetch closure under this domain's guard.

        Guard off: ``fn()`` inline, byte-identical legacy behavior.
        Sick: :class:`DeviceSickError` without touching the device.
        Otherwise ``fn`` runs on a guard worker thread (request
        contextvars propagated, so span/ledger attribution survives the
        hop) with the watchdog deadline; overrun abandons the worker,
        latches sick and raises :class:`DeviceHangError`; a classified
        failure raises :class:`DeviceFaultError` (chained), an
        unclassified one re-raises as itself."""
        if not enabled():
            return fn()
        if self.state == SICK:
            raise DeviceSickError(self.domain, op)
        job = self._submit(fn)
        if not self._wait(job):
            with job.lock:
                if not job.done.is_set():
                    job.abandoned = True
                    with self._lock:
                        self.wedged_workers += 1
                    self.note_fault("hang", op)
                    raise DeviceHangError(self.domain, op, self.hang_ms)
            # completed inside the race window: fall through to results
        if job.exc is not None:
            kind = classify(job.exc)
            if kind is None:
                raise job.exc  # not a device fault — never masked
            sink = self.fault_sink
            if sink is not None and sink(kind, op, job.exc):
                # a sub-domain (one mesh chip) owns this fault: the
                # plane guard stays un-charged — N−1 healthy chips keep
                # their route — but the seam still hears about it
                raise DeviceFaultError(
                    self.domain, op, kind, str(job.exc)
                ) from job.exc
            self.note_fault(kind, op, job.exc)
            raise DeviceFaultError(
                self.domain, op, kind, str(job.exc)
            ) from job.exc
        self.note_ok()
        return job.result

    def _wait(self, job: _Job) -> bool:
        """Wait for ``job`` up to the hang deadline, not counting the
        time its worker spends inside XLA compiles (see "XLA compile
        accounting" above)."""
        limit = self.hang_ms / 1000.0
        t0 = time.monotonic()
        while True:
            left = limit + job.compile_seconds() - (time.monotonic() - t0)
            if left <= 0:
                return job.done.is_set()
            # a compile in flight keeps ``left`` constant: re-check at
            # least every 50 ms of it instead of spinning on a sliver
            if job.done.wait(max(left, 0.05)):
                return True

    def _submit(self, fn) -> _Job:
        import contextvars

        ctx = contextvars.copy_context()
        job = _Job(lambda: ctx.run(fn))
        while True:
            try:
                w = self._idle.get_nowait()
            except queue.Empty:
                w = _IdleWorker(self)
                break
            if w.alive():
                break
        w.inbox.put(job)
        return job

    def _worker_idle(self, w: "_IdleWorker") -> None:
        self._idle.put(w)

    # -- re-admission probe --------------------------------------------------

    def _default_probe(self) -> None:
        """One trivial dispatch that must round-trip the device: proves
        the runtime answers again after a wedge/OOM storm."""
        fail.point("devguard.probe")
        import jax
        import jax.numpy as jnp

        jax.block_until_ready(jnp.arange(8, dtype=jnp.int32).sum())

    def probe_now(self) -> bool:
        """One half-open re-admission probe (the loop calls this too;
        tests may call it directly).  Cooldown-first and single-probe
        via the shared HalfOpenGate; success re-admits the domain."""
        now = time.monotonic()
        with self._lock:
            if self.state != SICK:
                return True
            granted, _retry, token = self._gate.admit(
                now, self.cooldown_s, half_open=False
            )
        if not granted:
            return False
        ok = False
        try:
            job = self._submit(self._probe_fn)
            if job.done.wait(self.hang_ms / 1000.0):
                ok = job.exc is None
            else:
                with job.lock:
                    if not job.done.is_set():
                        job.abandoned = True
                        with self._lock:
                            self.wedged_workers += 1
                    else:
                        ok = job.exc is None
        finally:
            with self._lock:
                self._gate.release(token)
                if ok:
                    self.probes_ok += 1
                    self.readmissions += 1
                    self._consecutive = 0
                    self._set_state(HEALTHY)
                    print(
                        f"# device fault domain [{self.domain}] probe "
                        "succeeded; device RE-ADMITTED",
                        file=sys.stderr,
                    )
                else:
                    self.probes_failed += 1
                    self._gate.open(time.monotonic())
        DEVICE_PROBES.add("ok" if ok else "fail")
        if ok and self.on_readmit is not None:
            # outside self._lock: staged rejoin (mesh/fault.py) runs
            # warm dispatches and may re-latch this guard sick when the
            # candidate plan fails to prove itself
            try:
                self.on_readmit()
            except Exception as e:  # noqa: BLE001 — a failed rejoin
                # hook must not kill the probe loop; the domain simply
                # stays on the surviving sub-mesh until the next probe
                from dgraph_tpu.utils.metrics import note_swallowed

                note_swallowed("devguard.on_readmit", e)
            if self.state == SICK:
                # the hook re-latched (failed warm on a flapping chip):
                # report un-healed so the probe loop keeps running —
                # its start() during our own probe was a no-op
                return False
        return ok

    # -- surfaces ------------------------------------------------------------

    def status(self) -> dict:
        with self._lock:
            return {
                "state": self.state,
                "consecutive_faults": self._consecutive,
                "faults": dict(self.faults),
                "failovers": self.failovers,
                "probes_ok": self.probes_ok,
                "probes_failed": self.probes_failed,
                "readmissions": self.readmissions,
                "wedged_workers": self.wedged_workers,
                "last_fault": self.last_fault or None,
                "last_fault_op": self.last_fault_op or None,
                "last_fault_age_s": (
                    round(time.monotonic() - self.last_fault_at, 3)
                    if self.last_fault_at else None
                ),
                "hang_ms": self.hang_ms,
                "cooldown_s": self.cooldown_s,
                "sick_after": self.sick_after,
            }

    def degraded_info(self) -> dict:
        """The response annotation for device-failover serving (the
        PR 5 stale-read disclosure, device flavored): results are
        byte-identical host-route answers, only slower."""
        with self._lock:
            return {
                "domain": self.domain,
                "state": self.state,
                "reason": self.last_fault or "device fault",
                "retry_after": self.cooldown_s,
            }


class _IdleWorker:
    """One reusable dispatch thread.  After each job it returns itself
    to the guard's idle pool — unless the job was abandoned by the
    watchdog, in which case the thread exits when the stuck call
    finally returns (if ever) and is never reused."""

    __slots__ = ("inbox", "_thread", "_guard")

    def __init__(self, guard: DeviceGuard):
        self.inbox: "queue.SimpleQueue[_Job]" = queue.SimpleQueue()
        self._guard = guard
        self._thread = threading.Thread(
            target=self._loop,
            name=f"dgraph-devguard-{guard.domain}-worker",
            daemon=True,
        )
        self._thread.start()

    def alive(self) -> bool:
        return self._thread.is_alive()

    def _loop(self) -> None:
        while True:
            job = self.inbox.get()
            _tls.job = job
            try:
                job.result = job.fn()
            except BaseException as e:  # noqa: BLE001 — transported to
                # the waiting caller verbatim, classified there
                job.exc = e
            _tls.job = None
            with job.lock:
                job.done.set()
                abandoned = job.abandoned
            if abandoned:
                return  # wedged past the watchdog: never reused
            self._guard._worker_idle(self)


# -- process-wide registry -----------------------------------------------------

_guards_lock = threading.Lock()
_guards: Dict[str, DeviceGuard] = {}


def get(domain: str = "device") -> DeviceGuard:
    """The process-wide guard for one fault domain ("device" = the
    default backend's dispatch plane, "mesh" = the collective plane)."""
    with _guards_lock:
        g = _guards.get(domain)
        if g is None:
            g = _guards[domain] = DeviceGuard(domain)
        return g


def ensure(domain: str, **kwargs) -> DeviceGuard:
    """The registry constructor for guards that need non-default wiring
    (per-chip mesh sub-domains: ``sick_after=1``, a chip-targeted
    probe_fn, the staged-rejoin on_readmit hook).  First caller's kwargs
    win; later calls return the existing guard untouched — guards are
    long-lived state machines, not config carriers."""
    with _guards_lock:
        g = _guards.get(domain)
        if g is None:
            g = _guards[domain] = DeviceGuard(domain, **kwargs)
        return g


def count_failover(route: str, stats: Optional[dict] = None, domain: str = "device") -> None:
    """The ONE failover bookkeeping sequence every seam shares: the
    per-request stat (drives the response's degraded.device stamp), the
    alertable series, and the guard's own counter.  Hand-copying this
    at seams is how the disclosure contract drifts."""
    from dgraph_tpu.utils.metrics import DEVICE_FAILOVER

    if stats is not None:
        stats["device_failover"] = stats.get("device_failover", 0) + 1
    DEVICE_FAILOVER.add(route)
    get(domain).note_failover()


def cost_factor(domain: str = "device") -> float:
    """The planner's pricing hook (query/planner.py): multiply device
    route costs by this — 1.0 while the domain may be dispatched to, a
    price-out factor while it is sick, so sick backends lose every
    calibrated break-even instead of being special-cased per route.
    Large-finite rather than inf: estimates stay JSON-clean in
    /debug/planner."""
    with _guards_lock:
        g = _guards.get(domain)
    if g is None or g.allowed():
        return 1.0
    return 1e9


def summary() -> Dict[str, dict]:
    """Per-domain status for /health?detail=1 and /debug/device."""
    with _guards_lock:
        guards = list(_guards.values())
    return {g.domain: g.status() for g in guards}


def reset_for_tests() -> None:
    """Drop all guards (fresh state machines, fresh workers).  Wedged
    workers from a previous test keep sleeping harmlessly — they are
    daemon threads bound to abandoned jobs."""
    with _guards_lock:
        for g in _guards.values():
            DEVICE_STATE.set(g.domain, 0)
        _guards.clear()
