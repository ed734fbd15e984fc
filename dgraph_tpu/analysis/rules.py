"""The graftlint rule set — this repo's idioms, not generic style.

Each rule targets a bug class that has actually bitten (or nearly
bitten) this codebase and that the tier-1 suite cannot catch reliably
on a noisy 2-core CPU host:

- ``host-sync-in-jit``: a stray ``.item()`` / ``bool(tracer)`` /
  ``np.asarray`` inside a ``jit``/``scan``/``pallas_call`` body either
  fails at trace time in a rarely-hit branch or — worse — silently
  forces a device→host sync per call and ruins the one-dispatch-per-hop
  story (ops/batch.py).
- ``recompile-hazard``: ``jax.jit`` constructed inside a loop or
  invoked inline (``jax.jit(f)(x)``) defeats jit's weakref cache and
  recompiles per iteration/call; the budgets in
  ``analysis/budgets.json`` would catch the symptom at test time, this
  catches the cause at review time.
- ``wallclock-duration``: interval math on ``time.time()`` breaks under
  NTP slew/step — scheduler deadlines, raft election ticks and cache
  aging must use ``time.monotonic()``.  Wall clock stays legitimate
  where a *user-visible timestamp* is involved (``since()`` compares
  against stored dates; pragma those sites).
- ``swallowed-exception``: a broad ``except Exception: pass`` in
  cluster/raft/loader code turns partial outages into silent data
  gaps; narrow the type or count it via
  ``utils.metrics.note_swallowed`` so operators can see the drop rate.
- ``naked-peer-rpc``: a direct ``urlopen_peer`` (anywhere) or raw
  channel-RPC call (in the cluster peer plane) bypasses PeerClient's
  retry budget, circuit breaker and health ordering — exactly the
  one-shot brittleness PR 5 removed; route it through
  ``cluster/peerclient.py``.
- ``naked-atomic-write``: a direct ``os.replace``/``os.rename`` outside
  ``utils/atomicio.py`` — durable file replacement must go through
  ``atomic_write_file`` (tmp + fsync + replace + directory fsync) or a
  crash can observe a half-state or resurrect the old name.  The rare
  deliberate site (a rename of an already-fully-synced file, a build
  artifact) carries the pragma with a WHY comment.
- ``naked-stage-timing``: direct ``time.perf_counter*`` stage
  bracketing in ``serve/``, ``sched/``, ``query/`` or ``cache/`` —
  stage timing in the serving tree must go through the span API
  (``dgraph_tpu.obs``: hop spans, ``obs.stage``) so the number is
  attributable to a trace instead of vanishing into a local variable;
  ``obs/`` and ``utils/trace.py`` are the sanctioned homes of the raw
  clock reads.
- ``naked-route-threshold``: a raw big-number comparison or a
  ``DGRAPH_TPU_*`` env read in ``query/`` or ``ops/`` — route-gate
  thresholds grew as scattered magic numbers until two independent
  ``262144`` twins (chain.py / joinplan.py) kept the chain scan out of
  3-hop queries it wins (BENCH21M).  Every gate lives in
  ``utils/planconfig.py`` with a documented default, and the decision
  itself belongs to the calibrated planner (``query/planner.py``).
- ``naked-device-sync``: a bare ``.block_until_ready()`` /
  ``jax.block_until_ready`` / ``jax.device_get`` / no-arg ``.item()``
  sync point on the HOST orchestration path in ``query/``, ``ops/``,
  ``parallel/`` or ``sched/`` — a naked sync is exactly where a wedged
  chip blocks a flush worker forever (TPU bench rounds 4-5 ran on one).
  Device syncs in the serving tree go through the device guard's
  watchdog bracket (``utils/devguard.py`` — deadline + SICK latch +
  host failover) or ``obs.block_ready_ms`` (which also attributes the
  wait to the span); a deliberate host-value ``.item()`` carries the
  pragma with the WHY.
- ``unchecked-hop-loop``: a loop in ``query/`` that drives the
  expander/dispatch seam (``expand``/``submit_hop``/``_expand_rows``/
  ``_exec_child``/``multi_hop``) without a ``CancelToken`` checkpoint —
  cooperative cancellation (PR 11, sched/qos.py) only works if EVERY
  hop-dispatching loop checkpoints; one unchecked loop and a
  deadline-expired or disconnected query silently runs to completion
  again.  Call ``engine.checkpoint()`` / ``resolver.checkpoint()`` (or
  ``<token>.check()``) inside the loop, or pragma the site with the
  WHY.

- ``naked-resident-transfer``: a ``jax.device_put`` / ``np.asarray`` /
  ``jnp.asarray`` on a resident arena's device buffers outside
  ``models/arena.py`` — the resident tier's contract (PR 16) is that
  the pinned CSR never re-crosses the host/device boundary after
  seeding; ``ResidentArena.seed``/``apply_delta`` are the only
  sanctioned (and ledger-charged) stagings.

Suppress a deliberate site with ``# graftlint: ignore[rule-id]`` on the
line (or the line above).  docs/analysis.md has the full catalog and
the how-to-add-a-rule walkthrough.
"""

from __future__ import annotations

import ast
import re
from pathlib import Path
from typing import Dict, Iterator, List, Optional, Sequence, Set, Tuple

from dgraph_tpu.analysis.framework import FileContext, Finding, Rule


# -- shared AST helpers -----------------------------------------------------

def _dotted(node: ast.AST) -> str:
    """'jax.lax.scan' for nested Attribute/Name chains, '' otherwise."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return ""


def _is_jit_expr(node: ast.AST, jit_names: Set[str]) -> bool:
    """``jax.jit`` / imported ``jit`` / ``partial(jax.jit, ...)``."""
    d = _dotted(node)
    if d in jit_names:
        return True
    if isinstance(node, ast.Call):
        f = _dotted(node.func)
        if f in ("partial", "functools.partial") and node.args:
            return _is_jit_expr(node.args[0], jit_names)
        return f in jit_names  # jax.jit(fn) / jax.jit(fn, static_...)
    return False


def _jit_call_of(node: ast.AST, jit_names: Set[str]) -> Optional[ast.Call]:
    """The Call node carrying static_arg* keywords, if any."""
    if isinstance(node, ast.Call):
        f = _dotted(node.func)
        if f in jit_names:
            return node
        if f in ("partial", "functools.partial") and node.args:
            if _dotted(node.args[0]) in jit_names:
                return node
    return None


def _jit_aliases(tree: ast.AST) -> Set[str]:
    """Names that mean jax.jit / jax.pmap in this file."""
    names = {"jax.jit", "jax.pmap"}
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom) and node.module == "jax":
            for a in node.names:
                if a.name in ("jit", "pmap"):
                    names.add(a.asname or a.name)
    return names


def _static_params(fn: ast.FunctionDef, call: Optional[ast.Call]) -> Set[str]:
    """Parameter names declared static via static_argnames/static_argnums
    on the jit decorator — those are Python values inside the trace, so
    ``int(cap)``-style coercions on them are fine."""
    if call is None:
        return set()
    params = [a.arg for a in fn.args.posonlyargs + fn.args.args]
    out: Set[str] = set()
    for kw in call.keywords:
        v = kw.value
        if kw.arg == "static_argnames":
            if isinstance(v, ast.Constant) and isinstance(v.value, str):
                out.add(v.value)
            elif isinstance(v, (ast.Tuple, ast.List)):
                for e in v.elts:
                    if isinstance(e, ast.Constant) and isinstance(e.value, str):
                        out.add(e.value)
        elif kw.arg == "static_argnums":
            nums: List[int] = []
            if isinstance(v, ast.Constant) and isinstance(v.value, int):
                nums = [v.value]
            elif isinstance(v, (ast.Tuple, ast.List)):
                nums = [
                    e.value for e in v.elts
                    if isinstance(e, ast.Constant) and isinstance(e.value, int)
                ]
            for n in nums:
                if 0 <= n < len(params):
                    out.add(params[n])
    return out


# traced-callee POSITIONS per combinator: which positional args are
# functions whose bodies execute under the trace (None = all from that
# index on, for switch's branch list)
_TRACED_ARG_POS = {
    "scan": (0,),
    "while_loop": (0, 1),   # cond_fun AND body_fun both trace
    "fori_loop": (2,),      # (lower, upper, body_fun, init)
    "cond": (1, 2),         # (pred, true_fun, false_fun, *operands)
    "switch": (1,),         # (index, [branch_fns], *operands)
    "vmap": (0,),
    "checkpoint": (0,),
    "remat": (0,),
    "pallas_call": (0,),
}
_COMBINATOR_PREFIXES = ("", "lax.", "jax.", "jax.lax.", "pl.",
                        "jax.experimental.pallas.")


def _traced_functions(
    tree: ast.AST, jit_names: Set[str]
) -> List[Tuple[ast.FunctionDef, Set[str], str]]:
    """Every FunctionDef whose body executes under a trace:
    (node, static param names, why)."""
    out: List[Tuple[ast.FunctionDef, Set[str], str]] = []
    # names handed to scan/cond/fori_loop/pallas_call... as traced callees
    callee_names: Dict[str, str] = {}
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        f = _dotted(node.func)
        base = f.split(".")[-1]
        if base not in _TRACED_ARG_POS or not any(
            f == p + base for p in _COMBINATOR_PREFIXES
        ):
            continue
        for pos in _TRACED_ARG_POS[base]:
            if pos >= len(node.args):
                continue
            arg = node.args[pos]
            if isinstance(arg, ast.Name):
                callee_names[arg.id] = base
            elif isinstance(arg, (ast.List, ast.Tuple)):  # switch branches
                for e in arg.elts:
                    if isinstance(e, ast.Name):
                        callee_names[e.id] = base
    for node in ast.walk(tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        for dec in node.decorator_list:
            if _is_jit_expr(dec, jit_names):
                out.append(
                    (node, _static_params(node, _jit_call_of(dec, jit_names)),
                     "jit")
                )
                break
        else:
            if node.name in callee_names:
                out.append((node, set(), callee_names[node.name]))
    return out


# -- rule: host-sync-in-jit -------------------------------------------------

_NUMPY_ROOTS = {"np", "numpy", "onp"}
_NUMPY_SYNC_FNS = {"asarray", "array", "ascontiguousarray", "copy"}


class HostSyncInJit(Rule):
    id = "host-sync-in-jit"
    doc = (
        "no .item()/bool()/int()/float() on traced values, np.asarray, "
        "jax.device_get or .block_until_ready() inside jit/scan/"
        "pallas_call bodies"
    )

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        jit_names = _jit_aliases(ctx.tree)
        for fn, static, why in _traced_functions(ctx.tree, jit_names):
            params = {
                a.arg for a in fn.args.posonlyargs + fn.args.args
                + fn.args.kwonlyargs
            } - static
            # nested defs inherit tracedness; their params are traced too
            for inner in ast.walk(fn):
                if isinstance(inner, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    if inner is not fn:
                        params |= {
                            a.arg for a in inner.args.posonlyargs
                            + inner.args.args + inner.args.kwonlyargs
                        }
            yield from self._check_body(ctx, fn, params, why)

    def _check_body(
        self, ctx: FileContext, fn: ast.FunctionDef,
        traced_params: Set[str], why: str,
    ) -> Iterator[Finding]:
        where = f"inside {why} body `{fn.name}`"
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            if isinstance(f, ast.Attribute):
                if f.attr == "item" and not node.args:
                    yield ctx.finding(
                        self.id, node,
                        f".item() forces a device->host sync {where}; "
                        "keep the value on device or hoist the read out "
                        "of the traced region",
                    )
                    continue
                if f.attr == "block_until_ready":
                    yield ctx.finding(
                        self.id, node,
                        f".block_until_ready() {where} serializes the "
                        "trace against the device stream",
                    )
                    continue
                root = _dotted(f).split(".")[0]
                if root in _NUMPY_ROOTS and f.attr in _NUMPY_SYNC_FNS:
                    if node.args and not _const_like(node.args[0]):
                        yield ctx.finding(
                            self.id, node,
                            f"np.{f.attr}() {where} materializes the "
                            "operand on host every call; use jnp.* or "
                            "move the conversion outside the trace",
                        )
                    continue
            d = _dotted(f)
            if d in ("jax.device_get", "device_get"):
                yield ctx.finding(
                    self.id, node,
                    f"jax.device_get {where} is a host sync; return the "
                    "array and fetch after dispatch",
                )
                continue
            if (
                isinstance(f, ast.Name)
                and f.id in ("bool", "int", "float")
                and len(node.args) == 1
                and isinstance(node.args[0], ast.Name)
                and node.args[0].id in traced_params
            ):
                yield ctx.finding(
                    self.id, node,
                    f"{f.id}({node.args[0].id}) {where} concretizes a "
                    "traced value (TracerBoolConversionError at best, a "
                    "silent per-call sync at worst); mark the argument "
                    "static or keep the branch on device (lax.cond/"
                    "jnp.where)",
                )


def _const_like(node: ast.AST) -> bool:
    if isinstance(node, ast.Constant):
        return True
    if isinstance(node, (ast.Tuple, ast.List)):
        return all(_const_like(e) for e in node.elts)
    return False


# -- rule: recompile-hazard -------------------------------------------------

class RecompileHazard(Rule):
    id = "recompile-hazard"
    doc = (
        "jax.jit constructed inside a loop, or invoked inline "
        "(jax.jit(f)(x)) — both defeat the compile cache"
    )

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        jit_names = _jit_aliases(ctx.tree)
        loop_spans: List[Tuple[int, int]] = []
        for node in ast.walk(ctx.tree):
            if isinstance(node, (ast.For, ast.While, ast.AsyncFor)):
                end = getattr(node, "end_lineno", node.lineno)
                loop_spans.append((node.lineno, end))
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            # inline invocation: jax.jit(f)(x) — a fresh wrapper per call
            if isinstance(node.func, ast.Call):
                inner = _jit_call_of(node.func, jit_names)
                if inner is not None and inner.args:
                    yield ctx.finding(
                        self.id, node,
                        "jax.jit(f)(...) creates and traces a fresh "
                        "wrapper per call; bind the jitted function once "
                        "(module scope or a cached builder) and call that",
                    )
                    continue
            call = _jit_call_of(node, jit_names)
            if call is None or not call.args:
                continue
            # decorator position is handled by normal function defs
            if any(lo <= node.lineno <= hi for lo, hi in loop_spans):
                yield ctx.finding(
                    self.id, node,
                    "jax.jit constructed inside a loop recompiles every "
                    "iteration; hoist it out or cache it keyed on the "
                    "static arguments (see mesh/programs.py "
                    "mesh_multi_hop_step)",
                )


# -- rule: wallclock-duration -----------------------------------------------

def _is_walltime_call(node: ast.AST) -> bool:
    return (
        isinstance(node, ast.Call)
        and _dotted(node.func) in ("time.time", "datetime.datetime.now")
        and not node.args
    )


class WallClockDuration(Rule):
    id = "wallclock-duration"
    doc = (
        "interval math on time.time() — deadlines, tick loops and age "
        "computations must use time.monotonic()"
    )

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        # scopes: module + each function gets its own timeish-name set
        scopes: List[ast.AST] = [ctx.tree]
        for node in ast.walk(ctx.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                scopes.append(node)
        seen: Set[int] = set()
        for scope in scopes:
            timeish = self._timeish_names(scope)
            for node in self._walk_scope(scope):
                if id(node) in seen:
                    continue
                hit = None
                if isinstance(node, ast.BinOp) and isinstance(
                    node.op, (ast.Add, ast.Sub)
                ):
                    if (
                        _is_walltime_call(node.left)
                        or _is_walltime_call(node.right)
                        or self._timeish(node.left, timeish)
                        or self._timeish(node.right, timeish)
                    ):
                        hit = (
                            "duration/deadline arithmetic on time.time() "
                            "drifts under NTP slew and can go backwards "
                            "on clock steps; use time.monotonic() for "
                            "intervals (wall clock is for user-visible "
                            "timestamps only)"
                        )
                elif isinstance(node, ast.Compare):
                    sides = [node.left] + list(node.comparators)
                    if any(_is_walltime_call(s) for s in sides):
                        hit = (
                            "comparing time.time() against a deadline is "
                            "interval logic; use time.monotonic()"
                        )
                if hit is not None:
                    seen.add(id(node))
                    yield ctx.finding(self.id, node, hit)

    @classmethod
    def _timeish_names(cls, scope: ast.AST) -> Set[str]:
        # same scope boundary as the expression pass (_walk_scope):
        # nested defs keep their own timeish sets — a closure's
        # `ts = time.time()` must not taint the enclosing scope's `ts`
        names: Set[str] = set()
        for node in cls._walk_scope(scope):
            if isinstance(node, ast.Assign) and _is_walltime_call(node.value):
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        names.add(t.id)
                    elif isinstance(t, ast.Tuple):
                        names.update(
                            e.id for e in t.elts if isinstance(e, ast.Name)
                        )
            elif isinstance(node, ast.Assign) and isinstance(
                node.value, ast.Tuple
            ):
                # total, t0 = 0, time.time()
                for t in node.targets:
                    if isinstance(t, ast.Tuple) and len(t.elts) == len(
                        node.value.elts
                    ):
                        for tgt, val in zip(t.elts, node.value.elts):
                            if isinstance(tgt, ast.Name) and _is_walltime_call(
                                val
                            ):
                                names.add(tgt.id)
        return names

    @staticmethod
    def _timeish(node: ast.AST, timeish: Set[str]) -> bool:
        return isinstance(node, ast.Name) and node.id in timeish

    @staticmethod
    def _walk_scope(scope: ast.AST):
        """Walk a scope without descending into nested function defs
        (each gets its own pass with its own timeish set)."""
        stack = list(ast.iter_child_nodes(scope))
        while stack:
            node = stack.pop()
            yield node
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            stack.extend(ast.iter_child_nodes(node))


# -- rule: swallowed-exception ----------------------------------------------

_BROAD = {"Exception", "BaseException"}


def _broad_handler(h: ast.ExceptHandler) -> bool:
    t = h.type
    if t is None:
        return True
    if isinstance(t, (ast.Name, ast.Attribute)):
        return _dotted(t).split(".")[-1] in _BROAD
    if isinstance(t, ast.Tuple):
        return any(
            isinstance(e, (ast.Name, ast.Attribute))
            and _dotted(e).split(".")[-1] in _BROAD
            for e in t.elts
        )
    return False


def _silent_body(body: Sequence[ast.stmt]) -> bool:
    for stmt in body:
        if isinstance(stmt, (ast.Pass, ast.Continue, ast.Break)):
            continue
        if isinstance(stmt, ast.Expr) and isinstance(
            stmt.value, ast.Constant
        ):
            continue  # docstring / ellipsis
        return False
    return True


class SwallowedException(Rule):
    id = "swallowed-exception"
    doc = (
        "broad `except Exception: pass` hides partial outages; narrow "
        "the type or count it (utils.metrics.note_swallowed)"
    )

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.ExceptHandler):
                continue
            if _broad_handler(node) and _silent_body(node.body):
                yield ctx.finding(
                    self.id, node,
                    "broad exception swallowed silently — a downed peer, "
                    "a bad record and a typo all vanish here; catch the "
                    "narrow type you mean, or at minimum count the drop "
                    "via utils.metrics.note_swallowed(site, exc)",
                )


# -- rule: naked-peer-rpc ---------------------------------------------------

_CHANNEL_RPC_ATTRS = {
    "unary_unary", "unary_stream", "stream_unary", "stream_stream",
}


class NakedPeerRpc(Rule):
    id = "naked-peer-rpc"
    doc = (
        "direct urlopen_peer / channel-RPC call outside cluster/"
        "peerclient.py — peer RPCs must route through PeerClient "
        "(retry budget, per-peer circuit breaker, health ordering)"
    )

    # ``urlopen_peer`` is flagged EVERYWHERE (it exists only for peer
    # calls); raw gRPC multicallables are flagged only under cluster/ —
    # serve/ChannelPool and client/ are the PUBLIC API surface, where a
    # naked channel RPC is the client's own business.
    def check(self, ctx: FileContext) -> Iterator[Finding]:
        path = ctx.path.replace("\\", "/")
        if path.endswith("cluster/peerclient.py"):
            return  # the one legitimate home of both call forms
        in_cluster = "cluster/" in path
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            name = _dotted(f).split(".")[-1]
            if name == "urlopen_peer":
                yield ctx.finding(
                    self.id, node,
                    "one-shot urlopen_peer call bypasses PeerClient: no "
                    "retry/backoff budget, no circuit breaker, and a "
                    "down peer costs a full connect timeout here — use "
                    "ClusterService.peerclient.urlopen(...)",
                )
            elif (
                in_cluster
                and isinstance(f, ast.Attribute)
                and f.attr in _CHANNEL_RPC_ATTRS
            ):
                yield ctx.finding(
                    self.id, node,
                    f"raw channel.{f.attr}() in the cluster peer plane "
                    "bypasses PeerClient — use peerclient.grpc_unary(...) "
                    "so retries/breakers cover this RPC too",
                )


# -- rule: naked-atomic-write -----------------------------------------------

_RENAME_FNS = {"replace", "rename", "renames"}


def _os_rename_aliases(tree: ast.AST) -> Set[str]:
    """Bare names that mean os.replace/os.rename in this file
    (``from os import replace [as rp]``)."""
    out: Set[str] = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom) and node.module == "os":
            for a in node.names:
                if a.name in _RENAME_FNS:
                    out.add(a.asname or a.name)
    return out


class NakedAtomicWrite(Rule):
    id = "naked-atomic-write"
    doc = (
        "direct os.replace / os.rename outside utils/atomicio.py — "
        "durable file replacement must go through atomic_write_file "
        "(tmp + fsync + replace + dir fsync) or a crash can observe "
        "half-state"
    )

    # every step of the dance matters: a replace without the tmp-fsync
    # can install a file whose BLOCKS are not on disk yet (content
    # garbage after a crash); without the directory fsync the rename
    # itself can roll back and resurrect the old name.  The helper does
    # both; a naked call almost certainly skips at least one.
    def check(self, ctx: FileContext) -> Iterator[Finding]:
        path = ctx.path.replace("\\", "/")
        if path.endswith("utils/atomicio.py"):
            return  # the one legitimate home of the raw call
        aliases = _os_rename_aliases(ctx.tree)
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            d = _dotted(f)
            named = d in ("os.replace", "os.rename", "os.renames") or (
                isinstance(f, ast.Name) and f.id in aliases
            )
            if not named:
                continue
            fn = d.split(".")[-1] if d else f.id  # type: ignore[union-attr]
            yield ctx.finding(
                self.id, node,
                f"naked os.{fn}() skips the fsync'd tmp+replace+dir-sync "
                "dance — a crash here can install unsynced content or "
                "resurrect the old name; use utils.atomicio."
                "atomic_write_file (or pragma a rename of an "
                "already-fully-synced file, with the WHY)",
            )


# -- rule: naked-stage-timing -----------------------------------------------

def _is_perf_counter_call(node: ast.AST) -> bool:
    return (
        isinstance(node, ast.Call)
        and _dotted(node.func).split(".")[-1]
        in ("perf_counter", "perf_counter_ns")
        and not node.args
    )


class NakedStageTiming(Rule):
    id = "naked-stage-timing"
    doc = (
        "direct time.perf_counter* stage bracketing in serve/, sched/, "
        "query/ or cache/ — route stage timing through the span API "
        "(dgraph_tpu.obs: hop spans / obs.stage) so the number lands in "
        "traces, not a local variable"
    )

    # only the serving tree: these are the layers whose stage timings
    # the flight recorder exists to attribute.  obs/ and utils/trace.py
    # ARE the span API — the raw clock reads live there by design.
    _DIRS = ("serve/", "sched/", "query/", "cache/")

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        path = ctx.path.replace("\\", "/")
        if "obs/" in path or path.endswith("utils/trace.py"):
            return
        if not any(d in path for d in self._DIRS):
            return
        # same scope discipline as wallclock-duration: names assigned
        # from perf_counter in a scope taint only that scope
        scopes: List[ast.AST] = [ctx.tree]
        for node in ast.walk(ctx.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                scopes.append(node)
        seen: Set[int] = set()
        for scope in scopes:
            timers = self._timer_names(scope)
            for node in WallClockDuration._walk_scope(scope):
                if id(node) in seen:
                    continue
                if not (
                    isinstance(node, ast.BinOp)
                    and isinstance(node.op, (ast.Add, ast.Sub))
                ):
                    continue
                sides = (node.left, node.right)
                if any(_is_perf_counter_call(s) for s in sides) or any(
                    isinstance(s, ast.Name) and s.id in timers
                    for s in sides
                ):
                    seen.add(id(node))
                    yield ctx.finding(
                        self.id, node,
                        "perf_counter stage bracketing outside the span "
                        "API: this duration can never be attributed to a "
                        "trace — wrap the stage in obs.stage(stats, key) "
                        "or record it as a span attr (dgraph_tpu/obs/), "
                        "or pragma the site with the WHY",
                    )

    @staticmethod
    def _timer_names(scope: ast.AST) -> Set[str]:
        names: Set[str] = set()
        for node in WallClockDuration._walk_scope(scope):
            if isinstance(node, ast.Assign) and _is_perf_counter_call(
                node.value
            ):
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        names.add(t.id)
            elif isinstance(node, ast.Assign) and isinstance(
                node.value, ast.Tuple
            ):
                for t in node.targets:
                    if isinstance(t, ast.Tuple) and len(t.elts) == len(
                        node.value.elts
                    ):
                        for tgt, val in zip(t.elts, node.value.elts):
                            if isinstance(
                                tgt, ast.Name
                            ) and _is_perf_counter_call(val):
                                names.add(tgt.id)
        return names


# -- rule: naked-route-threshold --------------------------------------------

def _const_int(node: ast.AST) -> Optional[int]:
    """Fold an integer-literal expression: plain Constant, unary minus,
    and BinOps of constants (``1 << 21``, ``4 * 1024``) — the spellings
    magic thresholds actually use."""
    if isinstance(node, ast.Constant) and type(node.value) is int:
        return node.value
    if isinstance(node, ast.UnaryOp) and isinstance(node.op, ast.USub):
        v = _const_int(node.operand)
        return -v if v is not None else None
    if isinstance(node, ast.BinOp):
        l, r = _const_int(node.left), _const_int(node.right)
        if l is None or r is None:
            return None
        try:
            if isinstance(node.op, ast.LShift):
                return l << r
            if isinstance(node.op, ast.Mult):
                return l * r
            if isinstance(node.op, ast.Add):
                return l + r
            if isinstance(node.op, ast.Sub):
                return l - r
            if isinstance(node.op, ast.Pow):
                return l**r if 0 <= r <= 64 else None
        except (OverflowError, ValueError):
            return None
    return None


class NakedRouteThreshold(Rule):
    id = "naked-route-threshold"
    doc = (
        "raw numeric route-gate comparison or DGRAPH_TPU_* env read in "
        "query//ops/ — thresholds live in utils/planconfig.py (documented "
        "defaults, override detection) and decisions in query/planner.py "
        "(calibrated cost model)"
    )

    # query/ and ops/ are the layers where route gates live; the config
    # module itself sits in utils/ — outside the scanned dirs by design,
    # so it needs no exemption.  The literal floor (65536) is far above
    # any capacity/bucket constant but below every historical gate
    # (262144, 1<<21, 1<<22); disabling-style sentinels (1 << 60) are
    # exactly the pattern that belongs behind a planconfig name too.
    _DIRS = ("query/", "ops/")
    _FLOOR = 65536

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        path = ctx.path.replace("\\", "/")
        if not any(d in path for d in self._DIRS):
            return
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.Call):
                d = _dotted(node.func)
                knob = None
                if d in ("os.environ.get", "os.getenv") and node.args:
                    a0 = node.args[0]
                    if isinstance(a0, ast.Constant) and isinstance(
                        a0.value, str
                    ):
                        knob = a0.value
                if knob is not None and knob.startswith("DGRAPH_TPU_"):
                    yield ctx.finding(
                        self.id, node,
                        f"env read of {knob} in the routing layers: knob "
                        "reads belong in utils/planconfig.py (one table "
                        "of documented defaults the planner can treat as "
                        "overridable) — two independently-grown 262144 "
                        "twins is how we got here",
                    )
            elif isinstance(node, ast.Compare):
                operands = [node.left] + list(node.comparators)
                for op in operands:
                    v = _const_int(op)
                    if v is not None and abs(v) >= self._FLOOR:
                        yield ctx.finding(
                            self.id, node,
                            f"naked numeric gate ({v}) in a comparison: "
                            "name it in utils/planconfig.py (or derive it "
                            "from the calibrated model in "
                            "query/planner.py) so the threshold is "
                            "documented, overridable and auditable — or "
                            "pragma the site with the WHY",
                        )
                        break


# -- rule: naked-version-key --------------------------------------------------

def _storeish(node: ast.AST) -> bool:
    """Does this expression read like a store reference (``store``,
    ``self.store``, ``self._server.store``, ``self.engine.store``)?"""
    d = _dotted(node)
    return d == "store" or d.endswith(".store")


class NakedVersionKey(Rule):
    id = "naked-version-key"
    doc = (
        "bare store.version read in the cache-keying layers — "
        "predicate-scoped cache versions live in dgraph_tpu/ivm/"
        "versions.py (hop_version/result_version/version_for); a new "
        "view keyed on the GLOBAL version quietly regrows one-write-"
        "invalidates-everything"
    )

    # the layers that construct cache keys / freshness probes; the ivm/
    # package is the sanctioned home and sits outside them by design.
    # Both spellings are flagged: a plain ``<x>.store.version``
    # attribute read and the duck-typed ``getattr(<store>, "version")``.
    _DIRS = ("cache/", "query/", "sched/", "serve/")

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        path = ctx.path.replace("\\", "/")
        if not any(d in path for d in self._DIRS):
            return
        for node in ast.walk(ctx.tree):
            if (
                isinstance(node, ast.Attribute)
                and node.attr == "version"
                and _storeish(node.value)
            ):
                yield ctx.finding(
                    self.id, node,
                    "bare store.version read: key caches through "
                    "dgraph_tpu/ivm/versions.py (predicate-scoped "
                    "freshness) — or pragma the site with WHY it is "
                    "not a cache key",
                )
            elif isinstance(node, ast.Call):
                if (
                    _dotted(node.func) == "getattr"
                    and len(node.args) >= 2
                    and isinstance(node.args[1], ast.Constant)
                    and node.args[1].value == "version"
                    and _storeish(node.args[0])
                ):
                    yield ctx.finding(
                        self.id, node,
                        "bare getattr(store, \"version\") read: key "
                        "caches through dgraph_tpu/ivm/versions.py "
                        "(predicate-scoped freshness) — or pragma the "
                        "site with WHY it is not a cache key",
                    )


# -- rule: naked-device-sync --------------------------------------------------

class NakedDeviceSync(Rule):
    id = "naked-device-sync"
    doc = (
        "bare .block_until_ready()/jax.block_until_ready/jax.device_get/"
        ".item() sync point in query/, ops/, parallel/ or sched/ — device "
        "syncs in the serving tree go through the device guard "
        "(utils/devguard.py watchdog bracket) or obs.block_ready_ms, so a "
        "wedged chip can never block a flush worker forever"
    )

    # the serving layers whose host orchestration dispatches device
    # programs; utils/devguard.py (the watchdog's home) and obs/ (the
    # block_ready_ms wrapper) sit outside them by design.  In-jit sync
    # points are host-sync-in-jit's jurisdiction — this rule covers the
    # HOST side of the seam, so it skips traced bodies to keep one
    # finding per bug class.
    _DIRS = ("query/", "ops/", "parallel/", "sched/")

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        path = ctx.path.replace("\\", "/")
        if not any(d in path for d in self._DIRS):
            return
        jit_names = _jit_aliases(ctx.tree)
        traced_lines: Set[int] = set()
        for fn, _static, _why in _traced_functions(ctx.tree, jit_names):
            end = getattr(fn, "end_lineno", fn.lineno)
            traced_lines.update(range(fn.lineno, end + 1))
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            if node.lineno in traced_lines:
                continue  # host-sync-in-jit owns the traced bodies
            f = node.func
            hit = None
            if isinstance(f, ast.Attribute):
                if f.attr == "block_until_ready" or (
                    f.attr == "item" and not node.args
                ):
                    hit = f.attr
            d = _dotted(f)
            if d in ("jax.block_until_ready", "jax.device_get", "device_get"):
                hit = d
            if hit is None:
                continue
            yield ctx.finding(
                self.id, node,
                f"naked `{hit}` sync point on the host orchestration "
                "path: a wedged dispatch blocks this worker with no "
                "deadline and no failover — bracket the dispatch+fetch "
                "with the device guard (utils/devguard.py run()) or use "
                "obs.block_ready_ms so the wait is watchdogged and "
                "span-attributed, or pragma a deliberate host-value "
                ".item() with the WHY",
            )


# -- rule: unchecked-hop-loop -----------------------------------------------

# the expander/dispatch seam: calls that (directly or one wrapper deep)
# cost a hop dispatch per iteration.  ``expand`` as a BARE name covers
# the local-closure shape (query/shortest.py's lazy expander); the rest
# are the engine/resolver method names.
_HOP_SEAM_ATTRS = {
    "expand", "_expand", "_expand_rows", "_exec_child",
    "_exec_child_inner", "submit_hop", "multi_hop",
}
# segmented dataflow (PR 18): a host loop that re-dispatches a carry
# through a bounded program segment — by convention every segment
# driver names its per-segment dispatch helper `_dispatch_segment`
# (ops/batch.py, query/chain.py, query/joinplan.py, mesh/executor.py)
_SEG_SEAM_ATTRS = {"_dispatch_segment"}
_HOP_CHECK_ATTRS = {"checkpoint"}


def _is_seam_call(node: ast.AST) -> bool:
    if not isinstance(node, ast.Call):
        return False
    f = node.func
    if isinstance(f, ast.Attribute):
        return f.attr in _HOP_SEAM_ATTRS
    return isinstance(f, ast.Name) and f.id == "expand"


def _is_segment_dispatch_call(node: ast.AST) -> bool:
    if not isinstance(node, ast.Call):
        return False
    f = node.func
    if isinstance(f, ast.Attribute):
        return f.attr in _SEG_SEAM_ATTRS
    return isinstance(f, ast.Name) and f.id in _SEG_SEAM_ATTRS


def _is_checkpoint_call(node: ast.AST) -> bool:
    if not isinstance(node, ast.Call):
        return False
    f = node.func
    if isinstance(f, ast.Attribute):
        if f.attr in _HOP_CHECK_ATTRS:
            return True
        # the scheduler yield point between program segments
        # (sched/segments.py): segments.seam(...) probes the token AND
        # offers preemption — it IS the checkpoint of a segment loop
        if f.attr == "seam":
            return "segment" in _dotted(f).lower()
        # direct token probe: <something>cancel/token<something>.check()
        if f.attr == "check":
            root = _dotted(f).lower()
            return "cancel" in root or "token" in root
    return isinstance(f, ast.Name) and f.id in _HOP_CHECK_ATTRS


class UncheckedHopLoop(Rule):
    id = "unchecked-hop-loop"
    doc = (
        "loop driving the expander/dispatch seam (query/) or "
        "re-dispatching a segment carry (_dispatch_segment in "
        "query//ops//mesh/) without a CancelToken checkpoint or "
        "segments.seam() yield point — cooperative cancellation and "
        "segment preemption need a probe between EVERY pair of "
        "dispatches"
    )

    # query/ is the layer that drives hop dispatches in loops; ops/
    # loops run INSIDE jitted programs where a checkpoint is impossible
    # by design (the documented cancellation granularity is one
    # dispatched program), and sched/ owns the token itself.  The ONE
    # exception to the ops//mesh/ exemption is the segment driver
    # (PR 18): its `_dispatch_segment` loop is a HOST loop between
    # bounded programs — exactly where a yield point is possible and
    # required — so those calls are checked in all three layers.
    _DIRS = ("query/",)
    _SEG_DIRS = ("query/", "ops/", "mesh/")

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        path = ctx.path.replace("\\", "/")
        hop_layer = any(d in path for d in self._DIRS)
        seg_layer = any(d in path for d in self._SEG_DIRS)
        if not hop_layer and not seg_layer:
            return
        for node in ast.walk(ctx.tree):
            if not isinstance(node, (ast.For, ast.AsyncFor, ast.While)):
                continue
            has_seam = False
            has_seg = False
            has_check = False
            for sub in ast.walk(node):
                if hop_layer and _is_seam_call(sub):
                    has_seam = True
                elif seg_layer and _is_segment_dispatch_call(sub):
                    has_seg = True
                elif _is_checkpoint_call(sub):
                    has_check = True
            if (has_seam or has_seg) and not has_check:
                what = (
                    "re-dispatches a program-segment carry"
                    if has_seg
                    else "dispatches hop expansions"
                )
                yield ctx.finding(
                    self.id, node,
                    f"this loop {what} but never probes the request's "
                    "CancelToken or yield point: a deadline-expired, "
                    "disconnected, or preemptable query keeps burning "
                    "engine time here — call engine.checkpoint() / "
                    "segments.seam() / <token>.check() between "
                    "dispatches, or pragma the site with the WHY",
                )


# -- rule: unregistered-metric ------------------------------------------------

# the MetricsRegistry constructor methods (utils/metrics.py) — the only
# sanctioned way a dgraph_* series comes into existence
_METRIC_CTORS = {
    "counter", "gauge", "func_gauge", "labeled", "multilabeled",
    "labeled_gauge", "multilabeled_gauge", "histogram",
    "labeled_histogram",
}


class UnregisteredMetric(Rule):
    id = "unregistered-metric"
    doc = (
        "dgraph_* metric series constructed without a row in the "
        "docs/deploy.md metric catalog — every exported series must be "
        "documented where operators build dashboards and alerts, or it "
        "is dark data with a scrape cost"
    )

    # lazily-parsed catalog: the backticked dgraph_* names in deploy.md's
    # "### Metric catalog" section (scoped to the section so prose
    # elsewhere mentioning a series does not register it).  Tests
    # override ``catalog_override`` to pin the set.
    catalog_override: Optional[Set[str]] = None
    _catalog_cache: Optional[Set[str]] = None

    @classmethod
    def catalog(cls) -> Set[str]:
        if cls.catalog_override is not None:
            return cls.catalog_override
        if cls._catalog_cache is None:
            names: Set[str] = set()
            doc = (
                Path(__file__).resolve().parents[2]
                / "docs" / "deploy.md"
            )
            if doc.exists():
                in_section = False
                for line in doc.read_text(encoding="utf-8").splitlines():
                    if line.startswith("### Metric catalog"):
                        in_section = True
                        continue
                    if in_section and line.startswith("#"):
                        break
                    if in_section:
                        names.update(
                            re.findall(r"`(dgraph_[a-z0-9_]+)`", line)
                        )
            cls._catalog_cache = names
        return cls._catalog_cache

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        catalog = self.catalog()
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            if not (
                isinstance(f, ast.Attribute) and f.attr in _METRIC_CTORS
            ):
                continue
            # the series name is the first positional OR the name=
            # keyword — a kwarg spelling must not slip the gate
            a0 = node.args[0] if node.args else next(
                (kw.value for kw in node.keywords if kw.arg == "name"),
                None,
            )
            if a0 is None:
                continue
            if not (
                isinstance(a0, ast.Constant)
                and isinstance(a0.value, str)
                and a0.value.startswith("dgraph_")
            ):
                continue
            name = a0.value
            # histogram exposition appends _bucket/_sum/_count; the
            # catalog documents the family name, which is what is
            # constructed here — exact match is the contract
            if name not in catalog:
                yield ctx.finding(
                    self.id, node,
                    f"series {name!r} has no row in the docs/deploy.md "
                    "metric catalog (### Metric catalog): add one — "
                    "name, type, labels, one-line meaning — or pragma "
                    "the site with WHY the series is deliberately "
                    "undocumented",
                )


# -- rule: unregistered-program-factory --------------------------------------

# the compiled-program constructors: jax.jit / jax.pmap (via the shared
# alias helper) plus pallas_call in its import spellings
_PALLAS_NAMES = {
    "pallas_call", "pl.pallas_call", "pallas.pallas_call",
    "jax.experimental.pallas.pallas_call",
}


def _factory_names(tree: ast.AST) -> Set[str]:
    return _jit_aliases(tree) | _PALLAS_NAMES


def _is_factory_construction(node: ast.AST, names: Set[str]) -> bool:
    """A Call that actually BUILDS a compiled-program factory:
    ``jax.jit(fn)`` / ``pl.pallas_call(kernel, ...)`` /
    ``partial(jax.jit, static_argnames=...)(fn)`` with operands (a bare
    ``jax.jit`` reference constructs nothing)."""
    if not isinstance(node, ast.Call):
        return False
    if _dotted(node.func) in names and bool(node.args):
        return True
    # the curried spelling: partial(jax.jit, ...)(fn) — the inner
    # partial(...) Call is not itself a construction (so no double
    # count), the application to fn is
    f = node.func
    return (
        isinstance(f, ast.Call)
        and _dotted(f.func) in ("partial", "functools.partial")
        and bool(f.args)
        and _is_jit_expr(f.args[0], names)
        and bool(node.args)
    )


class UnregisteredProgramFactory(Rule):
    id = "unregistered-program-factory"
    doc = (
        "jax.jit / pl.pallas_call construction in dgraph_tpu/ whose "
        "factory site is not registered in the device-program contract "
        "registry (analysis/programs.py) — every compiled kernel "
        "carries a checked contract or an explicit exemption"
    )

    # tests pin the acceptance set; production reads the live registry
    coverage_override: Optional[Set[str]] = None

    @classmethod
    def coverage(cls) -> Set[str]:
        if cls.coverage_override is not None:
            return cls.coverage_override
        # lazy: programs.py imports nothing heavy at module level by
        # design, so the lint pass stays cheap
        from dgraph_tpu.analysis.programs import covered_sites

        return covered_sites()

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        path = ctx.path.replace("\\", "/")
        if not (
            path.startswith("dgraph_tpu/") or "/dgraph_tpu/" in path
        ) or "analysis/" in path:
            return
        names = _factory_names(ctx.tree)
        sites: List[Tuple[ast.AST, str]] = []
        self._visit(ctx.tree, [], names, sites, set())
        cov = self.coverage()
        for node, qual in sites:
            key = f"{path}::{qual}"
            if key not in cov:
                yield ctx.finding(
                    self.id, node,
                    f"compiled-program factory `{key}` is not registered "
                    "in the program-contract registry: add a "
                    "ProgramContract covering it (or an EXEMPT_SITES "
                    "entry with the why) in dgraph_tpu/analysis/"
                    "programs.py — kernels land with a contract, not a "
                    "hope (docs/analysis.md#program-contracts)",
                )

    def _visit(
        self, node: ast.AST, stack: List[str], names: Set[str],
        out: List[Tuple[ast.AST, str]], seen: Set[int],
    ) -> None:
        qual = ".".join(stack) if stack else "<module>"
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in node.decorator_list:
                if _is_jit_expr(dec, names):
                    # anchor on the decorator line so the pragma sits
                    # where the construction is
                    out.append((dec, ".".join(stack + [node.name])))
                    seen.update(
                        id(s) for s in ast.walk(dec)
                        if isinstance(s, ast.Call)
                    )
            stack = stack + [node.name]
        elif isinstance(node, ast.ClassDef):
            stack = stack + [node.name]
        elif isinstance(node, ast.Assign) and _is_factory_construction(
            node.value, names
        ):
            # `intersect_batch = jax.jit(...)` at module level is named
            # by its target; inside a factory function the function IS
            # the site name
            t = node.targets[0]
            site = (
                t.id if qual == "<module>" and isinstance(t, ast.Name)
                else qual
            )
            out.append((node, site))
            seen.add(id(node.value))
        elif (
            _is_factory_construction(node, names) and id(node) not in seen
        ):
            out.append((node, qual))
            seen.add(id(node))
        for child in ast.iter_child_nodes(node):
            self._visit(child, stack, names, out, seen)


# -- rule: naked-resident-transfer --------------------------------------------

def _residentish(node: ast.AST) -> bool:
    """Does this expression reach into a resident arena's device
    buffers?  Matches any name/attribute mentioning ``resident`` (e.g.
    ``arena.resident()``, ``self._resident``) and the ``off``/``dst``
    lanes of a receiver conventionally named for one (``ra``/``nra``)."""
    for sub in ast.walk(node):
        if isinstance(sub, ast.Attribute):
            if "resident" in sub.attr:
                return True
            if sub.attr in ("off", "dst"):
                base = sub.value
                if isinstance(base, ast.Name) and base.id in (
                    "ra", "nra", "resident"
                ):
                    return True
                if (
                    isinstance(base, ast.Call)
                    and isinstance(base.func, ast.Attribute)
                    and base.func.attr == "resident"
                ):
                    return True
        elif isinstance(sub, ast.Name) and "resident" in sub.id:
            return True
    return False


class NakedResidentTransfer(Rule):
    id = "naked-resident-transfer"
    doc = (
        "jax.device_put / np.asarray / jnp.asarray on a resident "
        "arena's device buffers outside models/arena.py — the resident "
        "tier's whole contract is that the CSR never re-crosses the "
        "host/device boundary after seeding (ledger h2d/d2h = 0 for a "
        "warm hop); staging or fetching those buffers elsewhere "
        "reintroduces the transfer tax the tier deletes, uncharged"
    )

    # models/arena.py is the sanctioned home of every resident-buffer
    # staging (ResidentArena.seed / apply_delta, both ledger-charged)
    _HOME = "models/arena.py"
    _XFER = (
        "jax.device_put", "device_put",
        "np.asarray", "numpy.asarray", "np.array", "numpy.array",
        "jnp.asarray", "jnp.array",
    )

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        if ctx.path.replace("\\", "/").endswith(self._HOME):
            return
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call) or not node.args:
                continue
            if _dotted(node.func) not in self._XFER:
                continue
            if any(_residentish(a) for a in node.args):
                yield ctx.finding(
                    self.id, node,
                    "transfer primitive on a resident arena buffer: the "
                    "pinned CSR must never re-cross the boundary outside "
                    "models/arena.py (seed/apply_delta, ledger-charged) "
                    "— expand via ResidentArena.expand_packed and fetch "
                    "only the packed result, or pragma the site with the "
                    "WHY",
                )


# -- rule: naked-collective ---------------------------------------------------

class NakedCollective(Rule):
    id = "naked-collective"
    doc = (
        "shard_map / psum / all_gather / ppermute outside dgraph_tpu/"
        "mesh/ and dgraph_tpu/parallel/ — cross-chip collectives are "
        "the mesh plane's contract surface (placement-invariant "
        "reassembly, exchange-bytes ledger attribution, program "
        "contracts); a collective grown elsewhere ships none of that"
    )

    # the two sanctioned homes: parallel/ (per-hop mesh steps) and
    # mesh/ (the fused serving plane, PR 17)
    _HOMES = ("dgraph_tpu/mesh/", "dgraph_tpu/parallel/")
    _COLLECTIVES = frozenset(
        {"shard_map", "psum", "all_gather", "ppermute"}
    )

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        path = ctx.path.replace("\\", "/")
        if any(h in path for h in self._HOMES):
            return
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            dotted = _dotted(node.func)
            name = dotted.rsplit(".", 1)[-1]
            if name not in self._COLLECTIVES:
                continue
            yield ctx.finding(
                self.id, node,
                f"cross-chip collective `{dotted}` outside the mesh "
                "plane: collectives live in dgraph_tpu/mesh/ (fused "
                "serving programs) or dgraph_tpu/parallel/ (per-hop "
                "steps), where reassembly stays placement-invariant, "
                "exchange bytes are ledger-charged, and the program "
                "carries a checked contract — move the program there "
                "or pragma the site with the WHY",
            )


ALL_RULES: Tuple[Rule, ...] = (
    HostSyncInJit(),
    RecompileHazard(),
    WallClockDuration(),
    SwallowedException(),
    NakedPeerRpc(),
    NakedAtomicWrite(),
    NakedStageTiming(),
    NakedRouteThreshold(),
    NakedVersionKey(),
    NakedDeviceSync(),
    UncheckedHopLoop(),
    UnregisteredMetric(),
    UnregisteredProgramFactory(),
    NakedResidentTransfer(),
    NakedCollective(),
)
