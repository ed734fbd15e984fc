"""Test env: force an 8-device virtual CPU mesh.

Mirrors the reference's test strategy (SURVEY.md §4): in-process fixtures,
no network, multi-"group" logic exercised in one process — here, a virtual
multi-device mesh on CPU.

Tests run on the CPU, whatever else the machine has: the platform is
forced here (so a plain ``pytest`` without JAX_PLATFORMS=cpu in the
environment behaves the same) and XLA_FLAGS is set before the first
device query.  The chip is described, never attached, in exactly one file:
tests/test_chip_compile.py.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# hermetic planner calibration: a bench round persists measured rates to
# scratch/planner_calib.json, and server boots load it — tier-1 results
# must not depend on whether a bench ran on this checkout first.  Tests
# that exercise the file lifecycle point this at a tmp_path explicitly.
os.environ.setdefault("DGRAPH_TPU_CALIBRATION_FILE", "")

import jax

jax.config.update("jax_platforms", "cpu")

# ---------------------------------------------------------------------------
# graftcheck runtime invariants (dgraph_tpu/analysis/, docs/analysis.md):
#
# 1. compile-count budgets: every XLA compilation is counted via
#    jax.monitoring; each test's delta is checked against
#    analysis/budgets.json (pytest_runtest_call is imported below — in
#    conftest namespace it registers as a hook).  @pytest.mark.
#    compile_budget(n) overrides; @pytest.mark.transfer_guard wraps the
#    test in jax.transfer_guard.
# 2. lock-order witness: lock constructors in dgraph_tpu modules are
#    wrapped so every acquisition feeds a lockdep-style order table;
#    observing both (A before B) and (B before A) anywhere in the run
#    fails the session.  DGRAPH_TPU_WITNESS=0 disables (e.g. when
#    bisecting a perf delta).
# 3. Eraser lockset witness (graftcheck tier 3): classes declaring
#    __race_fields__ get __setattr__-wrapped at arm time; a multi-thread
#    field written with an empty candidate lockset is a data race and
#    fails the session like an inversion.  Co-gated: DGRAPH_TPU_WITNESS=0
#    disarms both, DGRAPH_TPU_RACES=0 disarms just the lockset half.
# ---------------------------------------------------------------------------

from dgraph_tpu.analysis import witness as _witness  # noqa: E402
from dgraph_tpu.analysis.pytest_budget import (  # noqa: E402,F401
    budget_plugin_configure,
    budget_plugin_report,
    pytest_runtest_call,  # hook: budget + transfer-guard enforcement
)

_WITNESS_ON = os.environ.get("DGRAPH_TPU_WITNESS", "1") != "0"

# 3. program-contract goldens guard (graftcheck tier 2): the golden
#    fingerprints in analysis/programs.json are re-blessed ONLY by an
#    explicit `--update-programs` run — a test that writes them through
#    the default path would silently rewrite the contract for every
#    future run.  Hash at configure, verify at session end.
import hashlib  # noqa: E402
from pathlib import Path  # noqa: E402

_GOLDENS = Path(__file__).resolve().parents[1] / (
    "dgraph_tpu/analysis/programs.json"
)
_GOLDENS_HASH0 = (
    hashlib.sha1(_GOLDENS.read_bytes()).hexdigest()
    if _GOLDENS.exists() else None
)


def pytest_configure(config):
    budget_plugin_configure(config)
    if _WITNESS_ON:
        _witness.arm()


def pytest_runtest_setup(item):
    # re-arm per test: modules imported lazily since the last arm (test
    # bodies do `from dgraph_tpu.cache import ...` at call time) get
    # their lock constructors wrapped too.  Idempotent and cheap — a
    # prefix scan of sys.modules.
    if _WITNESS_ON:
        _witness.arm()


def pytest_terminal_summary(terminalreporter):
    budget_plugin_report(terminalreporter)
    w = _witness.current()
    if w is not None:
        inv = w.inversions()
        if inv:
            terminalreporter.write_line("")
            terminalreporter.write_line(
                "LOCK-ORDER INVERSIONS OBSERVED (witness recorder):",
                red=True,
            )
            for line in inv:
                terminalreporter.write_line("  " + line, red=True)
        races = w.races()
        if races:
            terminalreporter.write_line("")
            terminalreporter.write_line(
                "DATA RACES OBSERVED (Eraser lockset witness):",
                red=True,
            )
            for line in races:
                terminalreporter.write_line("  " + line, red=True)


def pytest_sessionfinish(session, exitstatus):
    w = _witness.current()
    if w is not None and session.exitstatus == 0 and (
        w.inversions() or w.races()
    ):
        # an inversion is a deadlock waiting for the right interleaving,
        # and an empty-lockset multi-thread write is a torn read waiting
        # for the wrong one: fail the run even when every individual
        # test passed
        session.exitstatus = 1
    now = (
        hashlib.sha1(_GOLDENS.read_bytes()).hexdigest()
        if _GOLDENS.exists() else None
    )
    if now != _GOLDENS_HASH0:
        # diagnose UNCONDITIONALLY: on an otherwise-failing run the
        # mutation would persist on disk, seed the next session's
        # baseline hash, and escape detection forever
        import sys

        print(
            "\nPROGRAM GOLDENS MUTATED DURING THE RUN: a test rewrote "
            "dgraph_tpu/analysis/programs.json — goldens change only "
            "via an explicit `python -m dgraph_tpu.analysis "
            "--update-programs`; point test blessings at tmp_path and "
            "`git checkout` the file before the next run.",
            file=sys.stderr,
        )
        if session.exitstatus == 0:
            session.exitstatus = 1
