"""graftcheck tier 2 — device-program contract checker.

Tier 1 (rules.py / lockorder.py / witness.py / pytest_budget.py) guards
the *Python* that builds programs: no host syncs in traced bodies, no
jits in loops, bounded compile counts.  What it cannot see is the
compiled program itself — and after PR 1 (fused hops), PR 9 (MXU tiles)
and PR 10 (calibrated routes) the engine's correctness-and-speed story
*is* program structure: ``intersect_many`` is fast because its jaxpr
contains no serial ``scan``; ``multi_hop`` is cheap because its carry
buffers are donated and aliased; the program cache is bounded because
two frontiers in one capacity bucket trace byte-identical programs.
Those invariants lived as scattered one-off asserts (``"scan[" not in
…`` greps in a bench script and test_spgemm.py) and one *suppressed* donation
warning (ops/batch.py) — folklore, not contract.

This module makes them enforced:

- **`ProgramContract`**: one registered entry per compiled-kernel
  family.  Each contract builds representative *instances* (the kernel
  traced at small bucketed shapes) and declares its invariants:
  scan/while-freedom, no host callbacks, a dtype discipline (the
  uid-int32 / tile-f32 rule), donated-carry aliasing, implicit-transfer
  freedom under ``jax.transfer_guard``, a cost budget, and bucket-key
  soundness (two raw sizes in one cache bucket must trace the SAME
  program — the recompile-storm bug class, caught statically).
- **Golden fingerprints**: every (contract, instance) pair's normalized
  jaxpr hashes into ``analysis/programs.json``.  Structural drift — a
  rewrite reintroducing a scan, losing donation, widening a dtype —
  fails ``python -m dgraph_tpu.analysis --programs`` (and CI) until the
  change is explicitly re-blessed with ``--update-programs``.
- **Site coverage**: every ``jax.jit`` / ``pl.pallas_call`` construction
  in the package maps to a contract (``covers``) or an explicit
  exemption (``EXEMPT_SITES``, with the WHY); the graftlint rule
  ``unregistered-program-factory`` (rules.py) fails on any factory that
  is neither — a future Pallas kernel lands with a contract, not a hope.

Module import stays lightweight by design (rules.py reads the coverage
table during linting): jax, numpy and the ops modules import lazily
inside the contract builders.

Docs: docs/analysis.md ("Program contracts").  CLI: ``python -m
dgraph_tpu.analysis --programs [--update-programs]``.
"""

from __future__ import annotations

import hashlib
import json
import re
import warnings
from dataclasses import dataclass, field
from functools import partial
from pathlib import Path
from typing import Callable, Dict, List, Optional, Sequence, Set, Tuple

GOLDENS_PATH = Path(__file__).with_name("programs.json")

# checks run by run_check / check_contract; assert_contract defaults to
# the trace-only subset so benches can call it without paying a compile
STRUCTURE_CHECKS = ("scan", "callback", "dtype")
ALL_CHECKS = STRUCTURE_CHECKS + (
    "golden", "stability", "donation", "transfer", "cost", "bucket",
)

@dataclass
class ProgramInstance:
    """One traced shape of a kernel: the call a real caller would make
    (args already through the caller-side bucketing helpers, so the
    fingerprint covers the shape the program cache actually keys on)."""

    key: str                      # bucket key, e.g. "K4xL64"
    fn: Callable                  # the (usually jit-wrapped) kernel
    args: tuple                   # device-ready positional args
    kwargs: dict = field(default_factory=dict)   # static kwargs
    # per-instance invariant overrides (None = inherit the contract's
    # declaration) — e.g. expand_filter_compact is scan-free until a
    # keep-set brings in member_mask's searchsorted binary search:
    donate: Optional[Tuple[int, ...]] = None
    donate_unused_ok: Tuple[int, ...] = ()
    scan_free: Optional[bool] = None
    dtypes: Optional[frozenset] = None


@dataclass
class BucketProbe:
    """Bucket-key soundness probe: ``make(n)`` builds the instance a
    caller at raw size ``n`` would trace; every pair in ``pairs`` maps
    to one cache bucket and must produce identical arg shapes AND
    identical program fingerprints."""

    pairs: Tuple[Tuple[int, int], ...]
    make: Callable[[int], ProgramInstance]


@dataclass
class ProgramContract:
    name: str
    covers: Tuple[str, ...]        # "<relpath>::<qualname>" factory sites
    build: Callable[[], List[ProgramInstance]]
    scan_free: bool = True         # no lax.scan / lax.while in the jaxpr
    dtypes: frozenset = frozenset({"int32", "bool"})
    donate: Tuple[int, ...] = ()   # flat argnums that must be donated
    donate_unused_ok: Tuple[int, ...] = ()  # donated-but-unaliased OK
    transfer_free: bool = True     # runs under transfer_guard("disallow")
    max_bytes: Optional[int] = None  # cost budget; None = tile budget
    max_flops: Optional[int] = None
    bucket_probe: Optional[BucketProbe] = None
    experimental: bool = False     # registered, not yet load-bearing
    notes: str = ""


@dataclass
class Violation:
    contract: str
    instance: str
    check: str
    message: str

    def render(self) -> str:
        return (
            f"[{self.check}] {self.contract} / {self.instance}: "
            f"{self.message}"
        )


# -- jaxpr introspection ------------------------------------------------------


def _sub_jaxprs(param):
    from jax.extend import core

    out = []

    def rec(x):
        if isinstance(x, core.ClosedJaxpr):
            out.append(x.jaxpr)
        elif isinstance(x, core.Jaxpr):
            out.append(x)
        elif isinstance(x, (tuple, list)):
            for e in x:
                rec(e)

    rec(param)
    return out


def _walk_jaxpr(closed):
    """Yield every (sub-)jaxpr of a ClosedJaxpr, outermost first."""
    stack = [closed.jaxpr]
    while stack:
        j = stack.pop()
        yield j
        for eqn in j.eqns:
            for p in eqn.params.values():
                stack.extend(_sub_jaxprs(p))


def primitive_names(closed) -> Set[str]:
    out: Set[str] = set()
    for j in _walk_jaxpr(closed):
        for eqn in j.eqns:
            out.add(eqn.primitive.name)
    return out


def aval_dtypes(closed) -> Set[str]:
    out: Set[str] = set()
    for j in _walk_jaxpr(closed):
        vs = list(j.constvars) + list(j.invars) + list(j.outvars)
        for eqn in j.eqns:
            vs += list(eqn.invars) + list(eqn.outvars)
        for v in vs:
            aval = getattr(v, "aval", None)
            if aval is not None and hasattr(aval, "dtype"):
                out.add(str(aval.dtype))
    return out


def _trace(inst: ProgramInstance):
    import jax

    fn = partial(inst.fn, **inst.kwargs) if inst.kwargs else inst.fn
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        return jax.make_jaxpr(fn)(*inst.args)


_SRC_LOC = re.compile(r"\S+\.py:\d+(:\d+)?")
_FROZENSET = re.compile(r"frozenset\(\{([^{}]*)\}\)")


def fingerprint_of(closed) -> str:
    """Normalized-jaxpr hash.  str(jaxpr) names variables afresh on
    every pretty-print (a, b, c, …), so the text — and hence the hash —
    is deterministic across processes for an unchanged program.  Source
    locations (pallas_call params carry `file.py:line` provenance) are
    scrubbed: the fingerprint pins program STRUCTURE, and must survive
    a comment edit above the kernel or a different checkout path."""
    norm = _SRC_LOC.sub("<src>", str(closed))
    norm = re.sub(r"\s+", " ", norm.strip())
    # a frozenset param (shard_map's manual_axes) prints in hash order,
    # which string-hash randomization changes from process to process
    norm = _FROZENSET.sub(
        lambda m: "frozenset({%s})" % ", ".join(sorted(m.group(1).split(", "))),
        norm,
    )
    return hashlib.sha256(norm.encode()).hexdigest()[:16]


def _arg_shapes(inst: ProgramInstance) -> Tuple[Tuple[str, str], ...]:
    import jax

    leaves = jax.tree_util.tree_leaves(inst.args)
    return tuple(
        (str(getattr(x, "shape", ())), str(getattr(x, "dtype", "?")))
        for x in leaves
    )


# -- lowering-level checks (donation, cost) -----------------------------------


def _lower(inst: ProgramInstance):
    """Lower the instance, silencing JAX's lower-time diagnostics (the
    unusable-donation warning is expected for donate_unused_ok carries;
    donation checks read Lowered.args_info + StableHLO attrs instead —
    the warning only fires on the first lowering of a shape per
    process, so it is NOT a usable signal)."""
    import jax

    fn = inst.fn
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        if hasattr(fn, "lower"):
            return fn.lower(*inst.args, **inst.kwargs)
        return jax.jit(
            partial(fn, **inst.kwargs) if inst.kwargs else fn
        ).lower(*inst.args)


_MAIN_SIG = re.compile(r"func\.func public @main\((.*?)\)\s*->", re.S)


def donation_attrs(lowered_text: str) -> Dict[int, Tuple[bool, bool]]:
    """Per flat-arg index: (aliased via tf.aliasing_output, declared via
    jax.buffer_donor) parsed from the StableHLO main signature."""
    m = _MAIN_SIG.search(lowered_text)
    if not m:
        return {}
    out: Dict[int, Tuple[bool, bool]] = {}
    for p in re.split(r",\s*(?=%arg\d+)", m.group(1)):
        am = re.match(r"%arg(\d+)", p)
        if am:
            out[int(am.group(1))] = (
                "tf.aliasing_output" in p, "jax.buffer_donor" in p,
            )
    return out


def _donated_flags(lowered) -> List[bool]:
    """Per flat-arg donation DECLARATION from Lowered.args_info — the
    authoritative, cache-independent signal (the lower-time warning
    only fires on the first lowering of a shape per process)."""
    import jax

    leaves = jax.tree_util.tree_leaves(
        lowered.args_info, is_leaf=lambda a: hasattr(a, "donated")
    )
    return [bool(getattr(a, "donated", False)) for a in leaves]


def _cost_analysis(lowered) -> Optional[dict]:
    try:
        compiled = lowered.compile()
        ca = compiled.cost_analysis()
    except Exception:  # noqa: BLE001 — cost_analysis is best-effort per backend
        return None
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else None
    return ca if isinstance(ca, dict) else None


def _default_max_bytes() -> int:
    # the per-arena densified-tile budget doubles as the "no single
    # checked program may touch more than this at representative
    # shapes" ceiling (DGRAPH_TPU_TILE_BUDGET, docs/deploy.md)
    from dgraph_tpu.utils import planconfig

    return planconfig.tile_budget()


# -- per-contract check driver ------------------------------------------------


def check_contract(
    contract: ProgramContract,
    goldens: Optional[dict] = None,
    checks: Sequence[str] = ALL_CHECKS,
) -> Tuple[List[Violation], Dict[str, str], dict]:
    """Run the selected checks; returns (violations, fingerprints,
    stats).  ``goldens`` is the per-contract {instance_key: hash} dict
    (None = skip the golden compare even if 'golden' is selected)."""
    import jax

    violations: List[Violation] = []
    fingerprints: Dict[str, str] = {}
    stats = {"programs": 0, "bytes": 0.0, "flops": 0.0}

    def bad(inst_key: str, check: str, msg: str) -> None:
        violations.append(Violation(contract.name, inst_key, check, msg))

    for inst in contract.build():
        stats["programs"] += 1
        closed = _trace(inst)
        fp = fingerprint_of(closed)
        fingerprints[inst.key] = fp

        if "stability" in checks and fingerprint_of(_trace(inst)) != fp:
            bad(inst.key, "stability",
                "re-tracing the same instance produced a different "
                "fingerprint — the factory is nondeterministic (clock/"
                "RNG/dict-order leaking into the trace)")

        prims = primitive_names(closed)
        scan_free = (
            inst.scan_free if inst.scan_free is not None
            else contract.scan_free
        )
        if "scan" in checks and scan_free:
            for p in ("scan", "while"):
                if p in prims:
                    bad(inst.key, "scan",
                        f"declared scan/while-free but the jaxpr contains "
                        f"`{p}` — a serial loop re-entered the kernel "
                        "(see ops/sets.py intersect_many for the "
                        "tree-reduction discipline; searchsorted keeps a "
                        "scan even 'unrolled', so a kernel that adds a "
                        "binary search must re-declare)")
        if "callback" in checks:
            for p in (
                "pure_callback", "io_callback",
                "debug_callback",  # jax.debug.callback
                "debug_print",     # jax.debug.print
            ):
                if p in prims:
                    bad(inst.key, "callback",
                        f"host callback `{p}` inside a compiled kernel: "
                        "every dispatch would round-trip to Python — "
                        "remove the callback (jax.debug.print included) "
                        "from the production program")
        if "dtype" in checks:
            allowed = inst.dtypes if inst.dtypes is not None else contract.dtypes
            stray = aval_dtypes(closed) - allowed
            if stray:
                bad(inst.key, "dtype",
                    f"dtype(s) {sorted(stray)} off the declared "
                    f"discipline {sorted(allowed)} — an implicit "
                    "promotion (f64 upcast, int→float mean, int64 "
                    "emulation) doubles bytes and falls off the fast "
                    "unit; cast explicitly at the host boundary instead")

        if "golden" in checks and goldens is not None:
            want = goldens.get(inst.key)
            if want is None:
                bad(inst.key, "golden",
                    f"no golden fingerprint recorded for this program "
                    f"(got {fp}); bless it with "
                    "`python -m dgraph_tpu.analysis --update-programs`")
            elif want != fp:
                bad(inst.key, "golden",
                    f"program fingerprint drifted: golden {want}, "
                    f"traced {fp} — the compiled structure changed; "
                    "re-run the contract checks and re-bless with "
                    "--update-programs if intentional")

        donate = inst.donate if inst.donate is not None else contract.donate
        unused_ok = tuple(inst.donate_unused_ok) + tuple(
            contract.donate_unused_ok
        )
        need_lower = (
            ("donation" in checks and donate)
            or "cost" in checks
        )
        if need_lower:
            lowered = _lower(inst)
            if "donation" in checks and donate:
                attrs = donation_attrs(lowered.as_text())
                flags = _donated_flags(lowered)
                for argnum in donate:
                    aliased, declared = attrs.get(argnum, (False, False))
                    donated = bool(
                        flags[argnum]
                    ) if argnum < len(flags) else False
                    if not donated:
                        # args_info.donated is the declaration itself
                        # (cache-independent, unlike the lower-time
                        # warning) — losing it means every call now
                        # allocates a fresh carry
                        bad(inst.key, "donation",
                            f"flat arg {argnum} is no longer donated "
                            "(lowered args_info.donated is False) — "
                            "the donate_argnums declaration was lost")
                    elif argnum in unused_ok:
                        pass  # declared, legitimately unaliased carry
                    elif not aliased and not declared:
                        # single-device programs pin the alias pair
                        # statically (tf.aliasing_output) and DROP the
                        # attribute entirely when the donation is
                        # unusable; sharded (shard_map/pjit) programs
                        # instead mark jax.buffer_donor and leave the
                        # pairing to XLA buffer assignment — either
                        # attr means the buffer is reusable, a bare
                        # %arg means the donation was lost
                        bad(inst.key, "donation",
                            f"flat arg {argnum} is donated but NOT "
                            "aliased to any output (no "
                            "tf.aliasing_output / jax.buffer_donor "
                            "attr) — XLA cannot reuse the buffer "
                            "(shape/dtype mismatch with every "
                            "output); fix the carry layout or declare "
                            "it donate_unused_ok with the why")
            if "cost" in checks:
                ca = _cost_analysis(lowered)
                if ca is not None:
                    b = float(ca.get("bytes accessed", 0.0))
                    fl = float(ca.get("flops", 0.0))
                    stats["bytes"] += b
                    stats["flops"] += fl
                    cap_b = (
                        contract.max_bytes
                        if contract.max_bytes is not None
                        else _default_max_bytes()
                    )
                    if b > cap_b:
                        bad(inst.key, "cost",
                            f"program touches {b:.0f} bytes, over the "
                            f"contract budget of {cap_b} — a "
                            "representative-shape program outgrew its "
                            "tile/HBM envelope (densified operand? "
                            "accidental broadcast?)")
                    if (
                        contract.max_flops is not None
                        and fl > contract.max_flops
                    ):
                        bad(inst.key, "cost",
                            f"program costs {fl:.0f} flops, over the "
                            f"contract budget of {contract.max_flops}")

        if "transfer" in checks and contract.transfer_free:
            try:
                import jax.numpy as jnp

                # fresh device copies OUTSIDE the guard: donation-bearing
                # programs consume their carry buffers, and instances of
                # one contract may share fixture arrays
                dargs = jax.tree_util.tree_map(
                    lambda a: jnp.array(a) if hasattr(a, "dtype") else a,
                    inst.args,
                )
                fn = inst.fn
                if not hasattr(fn, "lower"):
                    # bare Python fns would run eagerly, where even a
                    # `x + 1` constant is an implicit transfer — the
                    # contract is about the COMPILED program
                    fn = jax.jit(partial(fn, **inst.kwargs))
                    kwargs = {}
                else:
                    kwargs = inst.kwargs
                with jax.transfer_guard("disallow"):
                    out = fn(*dargs, **kwargs)
                jax.block_until_ready(out)
            except Exception as e:  # noqa: BLE001 — guard raises backend-specific types
                bad(inst.key, "transfer",
                    "implicit host<->device transfer (or failure) while "
                    "running the program on device_put-staged args under "
                    f"jax.transfer_guard('disallow'): {e}")

    if "bucket" in checks and contract.bucket_probe is not None:
        probe = contract.bucket_probe
        for n1, n2 in probe.pairs:
            i1, i2 = probe.make(n1), probe.make(n2)
            if _arg_shapes(i1) != _arg_shapes(i2):
                bad(f"bucket({n1},{n2})", "bucket",
                    f"raw sizes {n1} and {n2} share a cache bucket but "
                    "trace DIFFERENT arg shapes — the factory keys on "
                    "the raw size, so every frontier wiggle compiles a "
                    "fresh program (recompile storm); bucket before "
                    "padding (ops/sets.py bucket/bucket_fine)")
            elif fingerprint_of(_trace(i1)) != fingerprint_of(_trace(i2)):
                bad(f"bucket({n1},{n2})", "bucket",
                    f"raw sizes {n1} and {n2} share a cache bucket and "
                    "arg shapes but trace different programs — a "
                    "non-shape value (the raw size itself?) leaked into "
                    "the trace as a static argument")

    return violations, fingerprints, stats


def assert_contract(
    name: str, checks: Sequence[str] = STRUCTURE_CHECKS
) -> None:
    """Single-source-of-truth entry for benches/tests that used to
    hand-grep jaxprs: run the registered contract's (default:
    trace-only) checks and raise AssertionError on any violation."""
    violations, _, _ = check_contract(REGISTRY[name], checks=checks)
    if violations:
        raise AssertionError(
            f"program contract {name!r} violated:\n"
            + "\n".join("  " + v.render() for v in violations)
        )


# -- goldens ------------------------------------------------------------------


def load_goldens(path: Optional[Path] = None) -> dict:
    p = Path(path) if path else GOLDENS_PATH
    if not p.exists():
        return {}
    return json.loads(p.read_text()).get("programs", {})


def goldens_release_mismatch(path: Optional[Path] = None) -> Optional[str]:
    """Why the goldens cannot be compared in this process, or None.  A
    jaxpr's text — and hence every fingerprint — changes with the JAX
    release, so goldens blessed under another release say nothing about
    this tree: the remedy is to re-bless, not to read N drifted hashes."""
    import jax

    p = Path(path) if path else GOLDENS_PATH
    if not p.exists():
        return None
    blessed = json.loads(p.read_text()).get("jax")
    if blessed == jax.__version__:
        return None
    return (
        f"goldens were blessed under jax {blessed}, this is jax "
        f"{jax.__version__}: re-bless with `python -m dgraph_tpu.analysis "
        "--update-programs`"
    )


def write_goldens(fingerprints: dict, path: Optional[Path] = None) -> None:
    import jax

    p = Path(path) if path else GOLDENS_PATH
    payload = {
        "comment": [
            "Golden program fingerprints per (kernel contract, bucketed",
            "shape): sha256[:16] of the normalized jaxpr.  Structural",
            "drift (a reintroduced scan, lost donation, widened dtype,",
            "changed fusion) fails `python -m dgraph_tpu.analysis",
            "--programs`; re-bless an INTENTIONAL change with",
            "`--update-programs` after the contract checks pass.",
        ],
        "jax": jax.__version__,
        "programs": {
            k: dict(sorted(v.items()))
            for k, v in sorted(fingerprints.items())
        },
    }
    p.write_text(json.dumps(payload, indent=2) + "\n")


def collect_fingerprints(
    registry: Optional[Dict[str, ProgramContract]] = None,
) -> Dict[str, Dict[str, str]]:
    """Trace every registered instance (no lowering/compiling) and
    return {contract: {instance_key: fingerprint}}."""
    reg = REGISTRY if registry is None else registry
    out: Dict[str, Dict[str, str]] = {}
    for name in sorted(reg):
        _, fps, _ = check_contract(reg[name], checks=())
        out[name] = fps
    return out


# -- CLI driver ---------------------------------------------------------------


def run_check(
    registry: Optional[Dict[str, ProgramContract]] = None,
    goldens_path: Optional[Path] = None,
    update: bool = False,
    checks: Sequence[str] = ALL_CHECKS,
    echo: Callable[[str], None] = print,
) -> int:
    """The ``--programs`` entry point: check every registered contract
    against its declared invariants and the golden fingerprints.
    ``update`` re-blesses the goldens (after the non-golden checks still
    pass — a broken program cannot be blessed into the contract)."""
    reg = REGISTRY if registry is None else registry
    goldens = load_goldens(goldens_path)
    active = tuple(c for c in checks if not (update and c == "golden"))
    all_violations: List[Violation] = []
    stale = goldens_release_mismatch(goldens_path)
    if stale and "golden" in active:
        all_violations.append(Violation("*", "*", "golden", stale))
        active = tuple(c for c in active if c != "golden")
    all_fps: Dict[str, Dict[str, str]] = {}
    n_programs = 0
    for name in sorted(reg):
        contract = reg[name]
        # an absent goldens file / contract entry means every
        # fingerprint is "missing" — a failure to bless, never a skip
        violations, fps, stats = check_contract(
            contract, goldens=goldens.get(name, {}), checks=active
        )
        all_violations.extend(violations)
        all_fps[name] = fps
        n_programs += stats["programs"]
        tag = " [experimental]" if contract.experimental else ""
        status = "ok" if not violations else f"{len(violations)} violation(s)"
        echo(
            f"  {name:32s} {stats['programs']:2d} program(s)  "
            f"{status}{tag}"
        )
    if "golden" in active:
        # the compare must be bidirectional: a golden with no traced
        # program behind it (instance renamed/removed, contract
        # deleted) is dead weight masquerading as a blessed review
        for name in sorted(goldens):
            traced = all_fps.get(name)
            if traced is None:
                all_violations.append(Violation(
                    name, "*", "golden",
                    "goldens carry a contract that is no longer "
                    "registered — remove it via --update-programs",
                ))
                continue
            for key in sorted(set(goldens[name]) - set(traced)):
                all_violations.append(Violation(
                    name, key, "golden",
                    "orphaned golden fingerprint: no registered "
                    "instance traces this key anymore — re-bless with "
                    "--update-programs to drop it",
                ))
    n_contracts = sum(1 for c in reg.values() if not c.experimental)
    n_exp = len(reg) - n_contracts
    for v in all_violations:
        echo(v.render())
    if all_violations:
        echo(
            f"programs: {len(all_violations)} contract violation(s) "
            f"across {n_programs} traced programs"
        )
        return 1
    if update:
        write_goldens(all_fps, goldens_path)
        echo(
            f"programs: blessed {n_programs} fingerprints from "
            f"{n_contracts} contracts (+{n_exp} experimental) into "
            f"{goldens_path or GOLDENS_PATH}"
        )
        return 0
    echo(
        f"programs: clean — {n_contracts} contracts "
        f"(+{n_exp} experimental), {n_programs} programs traced, "
        "fingerprints match goldens"
    )
    return 0


# ============================================================================
# The registry: one contract per compiled-kernel family.
# Builders import jax/numpy/ops lazily so importing this module (the
# lint rule does, per file) costs nothing.
# ============================================================================


def _jnp():
    import jax.numpy as jnp
    import numpy as np

    return jnp, np


def _small_csr():
    """Shared fixture: an 8-row CSR over a 16-uid universe, mixed
    degrees (0..4), host + device forms."""
    jnp, np = _jnp()
    deg = np.array([2, 3, 0, 4, 1, 0, 3, 3], np.int64)
    h_offsets = np.zeros(9, np.int64)
    np.cumsum(deg, out=h_offsets[1:])
    h_dst = (np.arange(h_offsets[-1], dtype=np.int32) * 5) % 16
    # ascending within each row (the arena invariant)
    for i in range(8):
        lo, hi = int(h_offsets[i]), int(h_offsets[i + 1])
        h_dst[lo:hi] = np.sort(h_dst[lo:hi])
    h_src = np.arange(8, dtype=np.int64)
    return (
        h_src, h_offsets, h_dst,
        jnp.asarray(h_offsets.astype(np.int32)), jnp.asarray(h_dst),
    )


def _sets_mat(k: int, length: int):
    jnp, np = _jnp()
    from dgraph_tpu.ops import sets

    return jnp.asarray(
        np.stack([
            sets.pad_to(np.arange(i, i + 5), length) for i in range(k)
        ])
    )


def _b_intersect_many() -> List[ProgramInstance]:
    from dgraph_tpu.ops import sets

    return [
        ProgramInstance(
            f"K{k}xL{l}", sets.intersect_many, (_sets_mat(k, l),)
        )
        for k, l in ((2, 64), (5, 64), (8, 128))
    ]


def _b_union_many() -> List[ProgramInstance]:
    from dgraph_tpu.ops import sets

    return [
        ProgramInstance(f"K{k}xL{l}", sets.union_many, (_sets_mat(k, l),))
        for k, l in ((2, 64), (6, 64))
    ]


def _b_set_algebra() -> List[ProgramInstance]:
    jnp, np = _jnp()
    from dgraph_tpu.ops import sets

    a = jnp.asarray(sets.pad_to(np.arange(0, 20, 2), 64))
    b = jnp.asarray(sets.pad_to(np.arange(0, 30, 3), 64))
    src = jnp.asarray(np.arange(0, 32, 2, dtype=np.int32))
    return [
        ProgramInstance("intersect_L64", sets.intersect, (a, b)),
        ProgramInstance("union_L64", sets.union, (a, b)),
        ProgramInstance("difference_L64", sets.difference, (a, b)),
        ProgramInstance("member_mask_L64", sets.member_mask, (a, b)),
        ProgramInstance("sort_unique_L64", sets.sort_unique, (a,)),
        ProgramInstance("rows_of_L64", sets.rows_of, (src, a)),
        ProgramInstance(
            "range_rows_C64", sets.range_rows,
            (jnp.int32(3), jnp.int32(9)), {"cap": 64},
        ),
    ]


def _csr_expand_inst(n_rows: int, raw_cap: int) -> ProgramInstance:
    jnp, np = _jnp()
    from dgraph_tpu.ops import sets

    _, _, _, offsets, dst = _small_csr()
    rows = jnp.asarray(
        sets.pad_rows(
            np.arange(min(n_rows, 8), dtype=np.int64), sets.bucket(n_rows)
        )
    )
    cap = sets.bucket(raw_cap)
    return ProgramInstance(
        f"R{sets.bucket(n_rows)}xC{cap}", sets.expand_csr,
        (offsets, dst, rows), {"cap": cap},
    )


def _b_expand_csr() -> List[ProgramInstance]:
    return [_csr_expand_inst(4, 16), _csr_expand_inst(8, 32)]


def _inline_layout():
    """Small but real inline-head layout (ops/sets.py expand_inline_seg
    docstring): 8 rows, three of them with overflow chunks."""
    jnp, np = _jnp()
    from dgraph_tpu.ops.sets import INLINE, SENT

    degs = [3, 10, 0, 20, 2, 0, 9, 1]
    metap = np.zeros((8, 8), np.int32)
    chunks: list = []
    for i, d in enumerate(degs):
        targets = np.arange(i, i + d, dtype=np.int32)
        head = np.full(INLINE, SENT, np.int32)
        head[: min(d, INLINE)] = targets[: min(d, INLINE)]
        ov = targets[INLINE:]
        metap[i, 0] = len(chunks)
        metap[i, 1] = d
        metap[i, 2:] = head
        for c in range(-(-max(0, d - INLINE) // 8)):
            ch = np.full(8, SENT, np.int32)
            seg = ov[c * 8: (c + 1) * 8]
            ch[: len(seg)] = seg
            chunks.append(ch)
    ovc = np.stack(chunks) if chunks else np.full((1, 8), SENT, np.int32)
    return jnp.asarray(metap), jnp.asarray(ovc)


def _b_expand_inline() -> List[ProgramInstance]:
    jnp, np = _jnp()
    from dgraph_tpu.ops import sets

    metap, ovc = _inline_layout()
    anyorder = jnp.asarray(np.array([0, 1, 3, 4, 6, 7, -1, -1], np.int32))
    return [
        ProgramInstance(
            "seg_B8xC8", sets.expand_inline_seg,
            (metap, ovc, anyorder), {"capc": 8},
        ),
    ]


def _b_batched_set_ops() -> List[ProgramInstance]:
    jnp, np = _jnp()
    from dgraph_tpu.ops import batch, sets

    a = jnp.asarray(
        np.stack([sets.pad_to(np.arange(i, i + 6), 64) for i in range(4)])
    )
    b = jnp.asarray(
        np.stack([sets.pad_to(np.arange(0, 12, 2), 64)] * 4)
    )
    m3 = jnp.asarray(
        np.stack([np.stack([sets.pad_to(np.arange(3), 32)] * 3)] * 4)
    )
    return [
        ProgramInstance("intersect_B4xL64", batch.intersect_batch, (a, b)),
        ProgramInstance("difference_B4xL64", batch.difference_batch, (a, b)),
        ProgramInstance("union_many_B4xK3xL32", batch.union_many_batch, (m3,)),
        ProgramInstance("member_mask_B4xL64", batch.member_mask_batch, (a, b)),
        ProgramInstance("sort_unique_B4xL64", batch.sort_unique_batch, (a,)),
    ]


def _ascending_inst(n_rows: int, raw_cap: int) -> ProgramInstance:
    jnp, np = _jnp()
    from dgraph_tpu.ops import batch, sets

    _, _, _, offsets, dst = _small_csr()
    rows = jnp.asarray(
        sets.pad_rows(
            np.arange(min(n_rows, 8), dtype=np.int64), sets.bucket(n_rows)
        )
    )
    cap = sets.bucket(raw_cap)
    return ProgramInstance(
        f"R{sets.bucket(n_rows)}xC{cap}", batch.expand_ascending,
        (offsets, dst, rows), {"cap": cap},
    )


def _b_expand_ascending() -> List[ProgramInstance]:
    return [_ascending_inst(4, 16), _ascending_inst(8, 32)]


def _b_expand_filter_compact() -> List[ProgramInstance]:
    jnp, np = _jnp()
    from dgraph_tpu.ops import batch, sets

    _, _, _, offsets, dst = _small_csr()
    keep = jnp.asarray(sets.pad_to(np.arange(0, 16, 2), 32))
    rows1 = jnp.asarray(sets.pad_rows(np.arange(4, dtype=np.int64), 8))
    rowsb = jnp.asarray(
        np.stack([sets.pad_rows(np.arange(4, dtype=np.int64), 8)] * 4)
    )
    return [
        # keep-bearing instances re-declare: the fused member_mask is a
        # searchsorted (log-depth scan + uint32 carry, see _SS_NOTE)
        ProgramInstance(
            "fused_R8xC32xF1", batch.expand_filter_compact,
            (offsets, dst, rows1), {"cap": 32, "keeps": (keep,)},
            scan_free=False, dtypes=_INT_SS,
        ),
        ProgramInstance(
            "fused_R8xC32xF0xO16", batch.expand_filter_compact,
            (offsets, dst, rows1), {"cap": 32, "keeps": (), "cap_out": 16},
        ),
        ProgramInstance(
            "batch_B4xR8xC32", batch._effc_batch,
            (offsets, dst, rowsb), {"cap": 32, "keeps": (keep,),
                                    "cap_out": None},
            scan_free=False, dtypes=_INT_SS,
        ),
    ]


def _b_multi_hop() -> List[ProgramInstance]:
    jnp, np = _jnp()
    from dgraph_tpu.ops import batch, sets

    _, _, _, offsets, dst = _small_csr()
    f = jnp.asarray(sets.pad_to(np.array([0, 1, 3]), 32))
    vis = jnp.asarray(np.full(32, sets.SENT, np.int32))
    lut = jnp.asarray(
        sets.pad_rows(np.arange(8, dtype=np.int64), 16)
    )
    return [
        # track_visited=False leaves the donated visited carry (flat arg
        # 3) untouched — donated but legitimately unaliased.  This is
        # the contract behind ops/batch.py's scoped warning handling.
        ProgramInstance(
            "H2xC32_novisited", batch._multi_hop_jit,
            (offsets, dst, f, vis),
            {"n_hops": 2, "cap": 32, "track_visited": False, "lut": None},
            donate_unused_ok=(3,),
        ),
        ProgramInstance(
            "H3xC32_visited", batch._multi_hop_jit,
            (offsets, dst, f, vis),
            {"n_hops": 3, "cap": 32, "track_visited": True, "lut": lut},
        ),
        # PR 18 segmented variants: the per-segment program the
        # segment loop dispatches at k=1 — the same _multi_hop_jit
        # bucketed on n_hops, so the bucket key stays sound over k and
        # the donated carry contract holds segment-to-segment.
        ProgramInstance(
            "H1xC32_seg", batch._multi_hop_jit,
            (offsets, dst, f, vis),
            {"n_hops": 1, "cap": 32, "track_visited": False, "lut": None},
            donate_unused_ok=(3,),
        ),
        ProgramInstance(
            "H1xC32_seg_visited", batch._multi_hop_jit,
            (offsets, dst, f, vis),
            {"n_hops": 1, "cap": 32, "track_visited": True, "lut": lut},
        ),
    ]


def _b_path_search() -> List[ProgramInstance]:
    """The path search's four programs over a 16-uid layout built the way
    models/arena.py PathLayout builds it (row = uid, 0-padded esrc)."""
    jnp, np = _jnp()
    from dgraph_tpu.ops import bfs, sets

    h_src, h_offsets, h_dst, _, _ = _small_csr()
    deg = np.diff(h_offsets)
    off = np.zeros(17, np.int32)
    np.cumsum(np.bincount(h_src, weights=deg, minlength=16), out=off[1:])
    off = jnp.asarray(np.stack([off[:-1], off[1:]], axis=1))
    dst = jnp.asarray(sets.pad_to(h_dst, 32))
    esrc = jnp.asarray(sets.pad_to(np.repeat(h_src, deg), 32, fill=0))
    cap, chunk = bfs.capacities(32, int(deg.max()))
    src, to = jnp.int32(0), jnp.int32(7)
    st = bfs.start(off, src, cap, chunk)
    state = tuple(range(3, 3 + len(st)))   # flat args of the donated state
    return [
        ProgramInstance("U16xC%dxK%d" % (cap, chunk), bfs.start, (off, src),
                        {"cap": cap, "chunk": chunk}, scan_free=True),
        # all levels in one program, and the one-level segment the segment
        # loop dispatches: the same jit, bucketed on the shapes alone
        ProgramInstance("U16xE32_all", bfs.run_levels,
                        (off, dst, esrc, st, to, jnp.int32(1 << 30)), {"chunk": chunk},
                        donate=state),
        ProgramInstance("U16xE32_seg", bfs.run_levels,
                        (off, dst, esrc, bfs.start(off, src, cap, chunk), to, jnp.int32(1)),
                        {"chunk": chunk}, donate=state),
        ProgramInstance("U16_walk", bfs.walk_back, (st["par"], to), {}),
        ProgramInstance("U16_finish", bfs.finish, (bfs.start(off, src, cap, chunk), to), {}),
    ]


def _b_mesh_multi_hop() -> List[ProgramInstance]:
    jnp, np = _jnp()
    import jax

    from dgraph_tpu.mesh.programs import mesh_multi_hop_step
    from dgraph_tpu.ops import sets
    from dgraph_tpu.parallel.mesh import make_mesh, shard_arena_rows

    if len(jax.devices()) < 8:
        raise RuntimeError(
            "the mesh.multi_hop contract builds an 8-wide Mesh; run "
            "under XLA_FLAGS=--xla_force_host_platform_device_count=8 "
            "(the analysis CLI injects this itself when the backend is "
            "uninitialized, and tests/conftest.py forces it for the "
            "whole suite)"
        )
    mesh = make_mesh(8, data=1)
    h_src, h_offsets, h_dst, _, _ = _small_csr()
    sa = shard_arena_rows(h_src, h_offsets, h_dst, mesh)
    f32 = jnp.asarray(sets.pad_to(np.array([0, 1, 3], np.int64), 32))
    f64 = jnp.asarray(sets.pad_to(np.array([0, 1, 3], np.int64), 64))
    return [
        ProgramInstance(
            "H2xC32", mesh_multi_hop_step(mesh, 32, 2),
            (sa.src, sa.offsets, sa.dst, f32), {},
        ),
        ProgramInstance(
            "H3xC64", mesh_multi_hop_step(mesh, 64, 3),
            (sa.src, sa.offsets, sa.dst, f64), {},
        ),
        # PR 18 segmented variant: the one-hop step the mesh segment
        # loop dispatches at k=1 (mesh_multi_hop_step's lru_cache
        # bounds the per-k executables).
        ProgramInstance(
            "H1xC32_seg", mesh_multi_hop_step(mesh, 32, 1),
            (sa.src, sa.offsets, sa.dst, f32), {},
        ),
    ]


def _tiles():
    jnp, np = _jnp()
    from dgraph_tpu.ops import spgemm

    h_src, h_offsets, h_dst, _, _ = _small_csr()
    pt = spgemm.build_tiles(h_src, h_offsets, h_dst, t=spgemm.tile_size())
    m = spgemm.mask_lanes(pt.universe, pt.t)
    return pt, m


def _mask_inst(universe: int) -> ProgramInstance:
    jnp, np = _jnp()
    from dgraph_tpu.ops import spgemm

    pt, _ = _tiles()
    m = spgemm.mask_lanes(universe, pt.t)
    x = jnp.zeros((m,), jnp.float32).at[0].set(1.0)
    return ProgramInstance(
        f"M{m}", spgemm.expand_mask, (pt.bi, pt.bj, pt.tiles, x)
    )


def _b_mask_algebra() -> List[ProgramInstance]:
    jnp, np = _jnp()
    from dgraph_tpu.ops import sets, spgemm

    pt, m = _tiles()
    x = jnp.zeros((m,), jnp.float32).at[3].set(1.0)
    xb = jnp.zeros((4, m), jnp.float32).at[:, 2].set(1.0)
    stack = jnp.ones((3, m), jnp.float32)
    uids = jnp.asarray(sets.pad_to(np.arange(0, 14, 2), 64))
    return [
        ProgramInstance(
            f"expand_M{m}", spgemm.expand_mask, (pt.bi, pt.bj, pt.tiles, x)
        ),
        ProgramInstance(
            f"counts_M{m}", spgemm.expand_counts,
            (pt.bi, pt.bj, pt.tiles, x),
        ),
        ProgramInstance(
            f"expand_B4xM{m}", spgemm.expand_mask_batch,
            (pt.bi, pt.bj, pt.tiles, xb),
        ),
        ProgramInstance(
            f"intersect_masks_K3xM{m}", spgemm.intersect_masks, (stack,)
        ),
        ProgramInstance(
            f"uids_to_mask_M{m}", spgemm.uids_to_mask, (uids,), {"m": m}
        ),
    ]


def _b_intersect_stack() -> List[ProgramInstance]:
    jnp, np = _jnp()
    from dgraph_tpu.ops import spgemm

    mat = _sets_mat(4, 64)
    matb = _sets_mat(3, 64)[None].repeat(2, axis=0)
    return [
        ProgramInstance("K4xL64", spgemm.intersect_stack, (mat,)),
        ProgramInstance(
            "B2xK3xL64", spgemm.intersect_stack_batch, (matb,)
        ),
    ]


def _b_mask_chain() -> List[ProgramInstance]:
    jnp, np = _jnp()
    from dgraph_tpu.ops import spgemm

    pt, m = _tiles()
    x0 = jnp.zeros((m,), jnp.float32).at[0].set(1.0)
    keep = jnp.ones((m,), jnp.float32)
    ops2 = ((pt.bi, pt.bj, pt.tiles), (pt.bi, pt.bj, pt.tiles))
    return [
        ProgramInstance(
            f"L2xM{m}", spgemm.run_mask_chain,
            (ops2, (None, keep), (pt.degs, pt.degs), x0),
        ),
        # PR 18 segmented variant: the single-level chain segment the
        # joinplan segment loop dispatches at k=1, masks threaded
        # device-resident between segments.
        ProgramInstance(
            f"L1xM{m}_seg", spgemm.run_mask_chain,
            (ops2[:1], (keep,), (pt.degs,), x0),
        ),
    ]


def _b_triangle() -> List[ProgramInstance]:
    jnp, np = _jnp()
    from dgraph_tpu.ops import spgemm

    pt, m = _tiles()
    x = jnp.zeros((m,), jnp.float32).at[0].set(1.0)
    xb = jnp.zeros((2, m), jnp.float32).at[:, 0].set(1.0)
    tri = (pt.bi, pt.bj, pt.tiles) * 3
    return [
        ProgramInstance(f"M{m}", spgemm.triangle_mask, (*tri, x)),
        ProgramInstance(
            f"B2xM{m}", spgemm.triangle_mask_batch, (*tri, xb)
        ),
    ]


def _b_order() -> List[ProgramInstance]:
    jnp, np = _jnp()
    from dgraph_tpu.ops import order, sets

    src = jnp.asarray(np.arange(0, 32, 2, dtype=np.int32))
    ranks = jnp.asarray(np.arange(16, dtype=np.int32))
    uids = jnp.asarray(sets.pad_to(np.arange(0, 20, 2), 32))
    seg = jnp.asarray(
        sets.pad_to(np.repeat(np.arange(4), 4), 32, fill=-1)
    )
    r = jnp.asarray(sets.pad_to(np.arange(16), 32, fill=-1))
    return [
        # the rank gather is one vectorized binary search (_SS_NOTE)
        ProgramInstance("gather_ranks_B32", order.gather_ranks,
                        (src, ranks, uids),
                        scan_free=False, dtypes=_INT_SS),
        ProgramInstance("sort_perm_C32_asc", order.segmented_sort_perm,
                        (seg, r), {"desc": False}),
        ProgramInstance("sort_perm_C32_desc", order.segmented_sort_perm,
                        (seg, r), {"desc": True}),
    ]


def _b_packed_expand() -> List[ProgramInstance]:
    jnp, np = _jnp()
    from dgraph_tpu.ops import sets
    from dgraph_tpu.query import engine as qe

    _, _, _, offsets, dst = _small_csr()
    rows = jnp.asarray(sets.pad_rows(np.arange(4, dtype=np.int64), 8))
    return [
        ProgramInstance(
            "csr_R8xC32", qe._packed_expand_csr,
            (offsets, dst, rows), {"cap": 32},
        ),
    ]


def _resident_fixture():
    """Tiny CSR in the ResidentArena storage layout (models/arena.py):
    bucketed offsets, dst SENT-padded to _resident_cap's 1024-granule +
    slack-tile-group contract — what ops/pallas_gather.py walks in HBM."""
    jnp, np = _jnp()
    from dgraph_tpu.models.arena import _resident_cap
    from dgraph_tpu.ops import sets

    degs = np.array([3, 0, 5, 2, 1, 0, 4, 2], np.int64)
    off = np.zeros(9, np.int32)
    off[1:] = np.cumsum(degs).astype(np.int32)
    E = int(off[-1])
    dst = np.full(_resident_cap(E), sets.SENT, np.int32)
    dst[:E] = np.arange(100, 100 + E, dtype=np.int32)
    rows = sets.pad_rows(
        np.array([0, 2, 3, 6], np.int64), 8
    ).astype(np.int32)
    return jnp.asarray(off), jnp.asarray(dst), jnp.asarray(rows)


def _gather_inst(raw_cap: int) -> ProgramInstance:
    jnp, np = _jnp()
    from dgraph_tpu.ops import sets
    from dgraph_tpu.ops.pallas_gather import gather_pallas

    off, dst, rows = _resident_fixture()
    cap = sets.bucket(raw_cap)
    return ProgramInstance(
        f"R8xC{cap}", gather_pallas, (off, dst, rows),
        {"cap": cap, "interpret": True},
    )


def _b_pallas_gather() -> List[ProgramInstance]:
    from dgraph_tpu.ops.pallas_gather import gather_pallas_packed

    off, dst, rows = _resident_fixture()
    return [
        _gather_inst(32),
        ProgramInstance(
            "packed_R8xC32", gather_pallas_packed, (off, dst, rows),
            {"cap": 32, "interpret": True},
        ),
    ]


def _b_resident_merge() -> List[ProgramInstance]:
    jnp, np = _jnp()
    from dgraph_tpu.models import arena as marena
    from dgraph_tpu.ops import sets

    off, dst, _rows = _resident_fixture()
    # padded delta pairs exactly as CSRArena._apply_delta_locked packs
    # them: SENT-filled pads, adds absent from / dels present in the
    # live buffers (the store-journal contract the merge leans on)
    ar = jnp.asarray(sets.pad_to(np.array([0, 2], np.int32), 8))
    ad = jnp.asarray(sets.pad_to(np.array([990, 991], np.int32), 8))
    dr = jnp.asarray(sets.pad_to(np.array([2], np.int32), 8))
    dd = jnp.asarray(sets.pad_to(np.array([103], np.int32), 8))
    return [
        ProgramInstance(
            "E17xD8", marena._resident_merge, (off, dst, ar, ad, dr, dd)
        ),
    ]


def _b_arena_scatter() -> List[ProgramInstance]:
    jnp, np = _jnp()
    from dgraph_tpu.models import arena as marena

    # as CSRArena._layouts_take_delta calls it: a layout table with its
    # (index, row) pairs padded past the end, and the 1-D LUT with entries
    table = jnp.asarray(np.zeros((16, 8), np.int32))
    idx = jnp.asarray(np.array([3, 15, 16, 16], np.int32))
    rows = jnp.asarray(np.ones((4, 8), np.int32))
    lut = jnp.asarray(np.full(32, -1, np.int32))
    return [
        ProgramInstance("T16x8xK4", marena._scatter_rows, (table, idx, rows)),
        ProgramInstance(
            "L32xK4", marena._scatter_rows, (lut, idx, rows[:, 0])
        ),
    ]


_INT = frozenset({"int32", "bool"})
# searchsorted-bearing kernels: jnp.searchsorted lowers to a log-depth
# lax.scan whose index carry is uint32 (documented at ops/sets.py
# _intersect_pair_sorted — the reason intersect_many needed the sort-
# based tree).  Kernels that embed the binary search declare this set
# and scan_free=False; everything else stays on the strict discipline.
_INT_SS = _INT | {"uint32"}
_MASK = frozenset({"float32", "int32", "bool"})
_OPS = "dgraph_tpu/ops"

_SS_NOTE = (
    "  (searchsorted binary searches lower to a bounded log-depth "
    "lax.scan with a uint32 index carry — the declared scan_free=False "
    "/ uint32 allowance covers exactly that, nothing else.)"
)


def _csr_probe() -> BucketProbe:
    # bucket(10) == bucket(12) == 16; bucket(5) == bucket(7) == 8
    return BucketProbe(
        pairs=((10, 12), (5, 7)),
        make=lambda n: _csr_expand_inst(4, n),
    )


def _ascending_probe() -> BucketProbe:
    return BucketProbe(
        pairs=((10, 12),),
        make=lambda n: _ascending_inst(4, n),
    )


def _mask_probe() -> BucketProbe:
    # mask_lanes buckets the block count: two universes under one
    # bucketed block count must share one program
    return BucketProbe(pairs=((10, 16),), make=_mask_inst)


def _gather_probe() -> BucketProbe:
    # bucket(10) == bucket(12) == 16: two frontier totals in one pow2
    # capacity bucket must trace ONE resident-gather program
    return BucketProbe(pairs=((10, 12), (5, 7)), make=_gather_inst)


REGISTRY: Dict[str, ProgramContract] = {
    c.name: c
    for c in (
        ProgramContract(
            name="sets.intersect_many",
            covers=(f"{_OPS}/sets.py::intersect_many",),
            build=_b_intersect_many,
            dtypes=_INT,
            notes="k-way intersection as a log-depth tree reduction; "
                  "the scan-free declaration IS the perf contract.",
        ),
        ProgramContract(
            name="sets.union_many",
            covers=(f"{_OPS}/sets.py::union_many",),
            build=_b_union_many,
            dtypes=_INT,
            notes="k-way union as one flat bitonic sort.",
        ),
        ProgramContract(
            name="sets.set_algebra",
            covers=(
                f"{_OPS}/sets.py::count_valid",
                f"{_OPS}/sets.py::compact",
                f"{_OPS}/sets.py::sort_unique",
                f"{_OPS}/sets.py::member_mask",
                f"{_OPS}/sets.py::intersect",
                f"{_OPS}/sets.py::difference",
                f"{_OPS}/sets.py::union",
                f"{_OPS}/sets.py::mask_to_set",
                f"{_OPS}/sets.py::frontier_rows",
                f"{_OPS}/sets.py::rows_of",
                f"{_OPS}/sets.py::range_rows",
            ),
            build=_b_set_algebra,
            scan_free=False,
            dtypes=_INT_SS,
            notes="the scalar sorted-unique-padded algebra "
                  "(docs/sets-contract.md)." + _SS_NOTE,
        ),
        ProgramContract(
            name="sets.expand_csr",
            covers=(f"{_OPS}/sets.py::expand_csr",),
            build=_b_expand_csr,
            dtypes=_INT,
            bucket_probe=_csr_probe(),
            notes="the engine's hot posting-list gather; bucket pairs "
                  "pin the pow2 capacity discipline.",
        ),
        ProgramContract(
            name="sets.expand_inline",
            covers=(f"{_OPS}/sets.py::expand_inline_seg",),
            build=_b_expand_inline,
            dtypes=_INT,
            notes="inline-head posting gather (the fused chain's).",
        ),
        ProgramContract(
            name="batch.set_ops",
            covers=(
                f"{_OPS}/batch.py::intersect_batch",
                f"{_OPS}/batch.py::difference_batch",
                f"{_OPS}/batch.py::union_many_batch",
                f"{_OPS}/batch.py::member_mask_batch",
                f"{_OPS}/batch.py::sort_unique_batch",
            ),
            build=_b_batched_set_ops,
            scan_free=False,
            dtypes=_INT_SS,
            notes="[B, L] vmapped set algebra — one dispatch per "
                  "batch." + _SS_NOTE,
        ),
        ProgramContract(
            name="batch.expand_ascending",
            covers=(f"{_OPS}/batch.py::expand_ascending",),
            build=_b_expand_ascending,
            dtypes=_INT,
            bucket_probe=_ascending_probe(),
            notes="telescoped ascending-row CSR expansion.",
        ),
        ProgramContract(
            name="batch.expand_filter_compact",
            covers=(
                f"{_OPS}/batch.py::expand_filter_compact",
                f"{_OPS}/batch.py::_effc_batch",
            ),
            build=_b_expand_filter_compact,
            dtypes=_INT,
            notes="whole hop (gather -> filter -> compact) in one "
                  "program; the per-op path is >= (2+k) dispatches.  "
                  "Filterless instances are strictly scan-free; "
                  "keep-set instances re-declare per instance (the "
                  "fused member_mask is a searchsorted).",
        ),
        ProgramContract(
            name="batch.multi_hop",
            covers=(f"{_OPS}/batch.py::_multi_hop_jit",),
            build=_b_multi_hop,
            scan_free=False,   # the scan IS the design: one program, N hops
            dtypes=_INT_SS,
            donate=(2, 3),
            donate_unused_ok=(3,),
            notes="lax.scan multi-hop driver with donated (frontier, "
                  "visited) carries.  The program exposes exactly one "
                  "[cap]-shaped output, so at most one carry can alias "
                  "— the visited buffer (flat arg 3) is declared "
                  "donate_unused_ok, which is the checked contract "
                  "behind ops/batch.py's scoped handling of JAX's "
                  "unusable-donation warning (the frontier carry, arg "
                  "2, MUST alias)." + _SS_NOTE,
        ),
        ProgramContract(
            name="bfs.path_search",
            covers=(
                f"{_OPS}/bfs.py::run_levels",
                f"{_OPS}/bfs.py::start",
                f"{_OPS}/bfs.py::walk_back",
                f"{_OPS}/bfs.py::finish",
            ),
            build=_b_path_search,
            scan_free=False,   # the level loop IS the design: lax.while_loop
            dtypes=_INT,
            notes="shortest(from:, to:) at unit cost: level-synchronous BFS "
                  "over the listed predicates' merged layout, levels driven "
                  "by lax.while_loop, each done as a gather (chunks of the "
                  "frontier list) or a sweep (every edge) by the frontier's "
                  "out-degree sum against the layout's size; a gathered "
                  "level's parents (least uid) are decided by its one sort "
                  "of (target, source) pairs and written once a uid found, "
                  "a swept one's scatter-min'ed; the path is walked back on "
                  "the device.  The state is donated from segment to "
                  "segment (run_levels' instances declare it).",
        ),
        ProgramContract(
            name="spgemm.mask_algebra",
            covers=(
                f"{_OPS}/spgemm.py::expand_counts",
                f"{_OPS}/spgemm.py::expand_mask",
                f"{_OPS}/spgemm.py::expand_mask_batch",
                f"{_OPS}/spgemm.py::uids_to_mask",
                f"{_OPS}/spgemm.py::intersect_masks",
            ),
            build=_b_mask_algebra,
            dtypes=_MASK,
            bucket_probe=_mask_probe(),
            notes="MXU tile tier: frontier-bitmap x adjacency products; "
                  "f32 is the tile discipline (MXU-native), int32/bool "
                  "only at the boundaries.",
        ),
        ProgramContract(
            name="spgemm.intersect_stack",
            covers=(
                f"{_OPS}/spgemm.py::intersect_stack",
                f"{_OPS}/spgemm.py::intersect_stack_batch",
            ),
            build=_b_intersect_stack,
            scan_free=False,
            dtypes=_INT_SS,
            notes="k-way uid-set intersection in ONE program (k-1 "
                  "parallel probes + one compacting sort)." + _SS_NOTE,
        ),
        ProgramContract(
            name="spgemm.run_mask_chain",
            covers=(f"{_OPS}/spgemm.py::run_mask_chain",),
            build=_b_mask_chain,
            dtypes=_MASK,
            notes="the generic-join driver: a whole multi-level chain "
                  "as one program, masks device-resident between "
                  "levels.",
        ),
        ProgramContract(
            name="spgemm.triangle_mask",
            covers=(
                f"{_OPS}/spgemm.py::triangle_mask",
                f"{_OPS}/spgemm.py::triangle_mask_batch",
            ),
            build=_b_triangle,
            dtypes=_MASK,
            notes="fused two-legs + cycle-closing kernel.",
        ),
        ProgramContract(
            name="order.segmented_sort",
            covers=(
                f"{_OPS}/order.py::gather_ranks",
                f"{_OPS}/order.py::segmented_sort_perm",
            ),
            build=_b_order,
            dtypes=_INT,
            notes="device-side segmented order-by: rank gather + stable "
                  "(segment, +-rank) lexsort; the gather_ranks instance "
                  "re-declares for its searchsorted probe, the sort "
                  "permutation itself is strictly scan-free.",
        ),
        ProgramContract(
            name="engine.packed_expand",
            covers=("dgraph_tpu/query/engine.py::_make_packed_expand.run",),
            build=_b_packed_expand,
            dtypes=_INT,
            notes="engine-boundary wrapper concatenating (out, seg) "
                  "into one fetch; structurally it must stay a thin "
                  "shell over the registered expansion kernel.",
        ),
        ProgramContract(
            name="pallas.gather",
            covers=(
                f"{_OPS}/pallas_gather.py::gather_pallas",
                f"{_OPS}/pallas_gather.py::gather_pallas_packed",
            ),
            build=_b_pallas_gather,
            scan_free=False,   # per-tile row walk: fori + while loops
            # dma_sem: the kernel's DMA semaphore scratch
            # (pltpu.SemaphoreType.DMA) — kernel data stays strictly int32
            dtypes=_INT | {"dma_sem"},
            bucket_probe=_gather_probe(),
            notes="device-resident posting gather (PR 16, the "
                  "route:resident walk primitive): 8-row-aligned "
                  "HBM->VMEM window copies over ResidentArena's pinned "
                  "CSR, realigned onto output tiles by lane rotation, "
                  "byte-identical to expand_csr; contract-checked in "
                  "interpret mode, compiled for TPU v5e in "
                  "tests/test_chip_compile.py.",
        ),
        ProgramContract(
            name="resident.merge",
            covers=("dgraph_tpu/models/arena.py::_resident_merge",),
            build=_b_resident_merge,
            scan_free=False,
            dtypes=_INT_SS,
            notes="on-device delta application for resident arenas "
                  "(PR 16): lexsort merge of live edges + netted journal "
                  "pairs into the NEXT epoch's (offsets, dst) — the "
                  "device twin of CSRArena._apply_delta_locked.  Only "
                  "the padded delta pairs ever cross h2d." + _SS_NOTE,
        ),
        ProgramContract(
            name="arena.scatter_rows",
            covers=("dgraph_tpu/models/arena.py::_scatter_rows",),
            build=_b_arena_scatter,
            notes="PR 34: a write's delta scattered into a device inline "
                  "layout or LUT that is there (touched metap rows, moved "
                  "or new overflow chunks, new uid->row entries); indices "
                  "past the end are the padding and are dropped.  NOT "
                  "donated by design: a holder of the old table keeps a "
                  "whole snapshot.  Held to a build from the host mirrors "
                  "by tests/test_write_under_read.py.",
        ),
        ProgramContract(
            name="mesh.multi_hop",
            covers=("dgraph_tpu/mesh/programs.py::mesh_multi_hop_step",),
            build=_b_mesh_multi_hop,
            scan_free=False,   # the hop scan IS the design (+ rows_of's
                               # searchsorted probe)
            dtypes=_INT_SS,
            donate=(3,),
            # the frontier seed aliases the [cap] final-frontier output
            # across the shard_map boundary; transfer_free stays False
            # because the checker's host-built operands reshard onto
            # the 8-wide mesh at call time — on the serving path the
            # ShardedArena operands are placed once and stay resident
            # (models/arena.py sharded_csr cache)
            transfer_free=False,
            notes="PR 17 mesh serving plane: the whole multi-hop chain "
                  "as ONE shard_map program — per-hop cross-chip "
                  "frontier exchange (all_gather of each shard's "
                  "bucketed expand_csr, psum of edge counts) runs "
                  "between lax.scan iterations on the ICI, never "
                  "through the host; byte-parity with the unsharded "
                  "scan driver pinned by tests/test_mesh_serving.py."
                  + _SS_NOTE,
        ),
    )
}


# jit/pallas construction sites that deliberately carry NO traced
# contract — each with the why.  The graftlint rule
# `unregistered-program-factory` accepts a site iff it appears here or
# in some contract's `covers`.
EXEMPT_SITES: Dict[str, str] = {
    "dgraph_tpu/query/chain.py::_run_fused": (
        "composite of registered kernels (expand_inline_seg, "
        "gather_ranks, segmented_sort_perm) whose static spec tuple "
        "comes from engine planning state; covered end-to-end by "
        "tests/test_chain.py parity + the compile-budget hook.  The "
        "PR 18 segmented grouping (static carry flag + level-slice "
        "tuples) is the same composite over a level subrange — "
        "byte-parity with the monolithic call pinned by "
        "tests/test_segments.py"
    ),
    "dgraph_tpu/parallel/mesh.py::sharded_expand_step": (
        "needs a live device Mesh; byte-parity with the registered "
        "expand_csr/sort_unique kernels pinned by tests/test_mesh_*"
    ),
    "dgraph_tpu/parallel/mesh.py::seg_expand_packed_step": (
        "needs a live device Mesh; parity pinned by tests/test_mesh_*"
    ),
    "dgraph_tpu/parallel/mesh.py::batched_hop_step": (
        "needs a live device Mesh; wraps registered "
        "expand_filter_compact"
    ),
    "dgraph_tpu/parallel/mesh.py::tile_expand_step": (
        "needs a live device Mesh; same math as registered "
        "spgemm.expand_mask (psum-combined), parity pinned by "
        "tests/test_spgemm.py mesh case"
    ),
    "dgraph_tpu/utils/calibrate.py::measure": (
        "micro-calibration probe (pre-compiled no-op for dispatch "
        "overhead) — intentionally trivial, never on the serving path"
    ),
    "dgraph_tpu/utils/calibrate.py::measure.gather": (
        "micro-calibration probe (synthetic gather rate)"
    ),
    "dgraph_tpu/utils/calibrate.py::measure.macs": (
        "micro-calibration probe (tile MAC rate)"
    ),
}


def covered_sites() -> Set[str]:
    """Every factory site the registry accounts for (contract covers +
    explicit exemptions) — the lint rule's acceptance set."""
    out: Set[str] = set(EXEMPT_SITES)
    for c in REGISTRY.values():
        out.update(c.covers)
    return out
