"""DeviceExpander's one device dispatch, and the guard on what it may call.

- Parity: each of the two per-level programs (``csr`` — every backend but
  the TPU's; ``resident`` — the TPU's, here under the Pallas interpreter
  through the constructor argument) against ``arena.expand_host`` byte for
  byte, over the frontier shapes a served query produces, with the route
  label and the h2d/d2h bytes the ledger must book.
- Guard: every program registered in analysis/programs.py is called from
  a served module, or stands on a list of named debt.
"""

import ast
from pathlib import Path

import numpy as np
import pytest

import dgraph_tpu
from dgraph_tpu import ops
from dgraph_tpu.analysis import programs
from dgraph_tpu.models import PostingStore
from dgraph_tpu.obs import ledger as ledgermod
from dgraph_tpu.query.engine import DeviceExpander, QueryEngine

# ------------------------------------------------------------------ parity


def _random_edges(rng, n_src, fanout, n_dst=5000):
    src = np.repeat(np.arange(1, n_src + 1), fanout)
    return src, rng.integers(1, n_dst, size=len(src))


def _ascending(rng):
    src, dst = _random_edges(rng, 300, 20)
    return src, dst, (), np.unique(rng.integers(1, 301, size=100))


def _permuted(rng):
    """An ordered root hands the next level its uids in value order."""
    src, dst, _dels, f = _ascending(rng)
    return src, dst, (), rng.permutation(f)


def _missing_uids(rng):
    """Uids the arena holds no row for, before, between and after its
    rows: the odd uids have edges, the frontier asks for every uid."""
    src, dst = _random_edges(rng, 150, 12)
    return 2 * src + 1, dst, (), np.arange(1, 400)


def _celebrity(rng):
    src, dst = _random_edges(rng, 60, 5)
    star = np.full(1500, 17)  # one row wider than 2^10
    src = np.concatenate([src, star])
    dst = np.concatenate([dst, 10_000 + np.arange(1500)])
    return src, dst, (), np.arange(10, 30)


def _zero_degree_rows(rng):
    """A sparse arena whose deleted rows stay, at degree 0."""
    src, dst = _random_edges(rng, 80, 6)
    src = 7 * src
    gone = [7 * u for u in (3, 4, 40, 80)]
    return src, dst, gone, 7 * np.arange(1, 81)


def _past_one_row_bucket(rng):
    src, dst = _random_edges(rng, 1200, 3)
    return src, dst, (), np.arange(1, 1031)  # 1030 rows: bucket 2048


FRONTIERS = {
    "ascending": _ascending,
    "permuted": _permuted,
    "missing_uids": _missing_uids,
    "celebrity": _celebrity,
    "zero_degree_rows": _zero_degree_rows,
    "past_one_row_bucket": _past_one_row_bucket,
}


@pytest.mark.pallas_interpret
@pytest.mark.parametrize("frontier", list(FRONTIERS))
@pytest.mark.parametrize("program", ["csr", "resident"])
def test_device_program_equals_host_expansion(program, frontier):
    src_e, dst_e, gone, uids = FRONTIERS[frontier](np.random.default_rng(5))
    st = PostingStore()
    st.apply_schema("p: uid .")
    st.bulk_set_uid_edges("p", src_e, dst_e)
    eng = QueryEngine(st)
    eng.expander = DeviceExpander(eng, program=program)
    eng.expand_device_min = 1  # pinned: every level takes the device route
    a = eng.arenas.data("p")
    for u in gone:
        for d in dst_e[src_e == u]:
            st.del_edge("p", int(u), int(d))
    if gone:
        a = eng.arenas.data("p")  # the delta lands in place
        assert (np.diff(a.h_offsets) == 0).sum() == len(gone)
    # what the program reads is put on the device outside the window, as
    # it is for every hop of a warm server but the first
    a.resident() if program == "resident" else a.ensure_device()

    uids = np.asarray(uids, dtype=np.int64)
    rows = a.rows_for_uids_host(uids)
    want_out, want_seg = a.expand_host(rows)
    assert len(want_out) > 0

    led = ledgermod.Ledger()
    tok = ledgermod.activate(led)
    try:
        out, seg = eng.expander.expand(a, uids, attr="p")
    finally:
        ledgermod.deactivate(tok)

    assert out.dtype == want_out.dtype and seg.dtype == want_seg.dtype
    assert out.tobytes() == want_out.tobytes()
    assert seg.tobytes() == want_seg.tobytes()
    assert eng.expander._route == program
    assert led.hops == {program: 1}
    assert led.hop_edges == {program: len(want_out)}
    # the frontier's rows go up, the packed out|seg buffer comes back
    assert led.bytes_h2d == rows.nbytes
    assert led.bytes_d2h == 2 * ops.bucket(len(want_out)) * 4


# ------------------------------------------------------------------- guard
#
# A registered program is SERVED when something outside its own file, the
# ops package's export list and the analysis package refers to it by name —
# or something in its own file does that is itself served (a jitted private
# behind its public wrapper).  The check reads names, not call graphs: a
# common name (``start``, ``union``) can pass on someone else's attribute,
# so it catches the kernel nobody calls, not every one.

_PKG = Path(dgraph_tpu.__file__).parent
_NOT_SERVING = ("dgraph_tpu/ops/__init__.py", "dgraph_tpu/analysis/")

# Registered, contract-checked, and called by no served module: debt, by
# name (ROADMAP.md D1).  A case here asserts the kernel is STILL unserved,
# so wiring one up or deleting it takes it off the list in the same PR.
UNSERVED = {
    # the scalar sorted-set algebra: the engine folds its filters on the
    # host (numpy) or through spgemm.intersect_stack; what serves of
    # sets.py is sort_unique, member_mask, rows_of and the expansions
    "dgraph_tpu/ops/sets.py::count_valid",
    "dgraph_tpu/ops/sets.py::mask_to_set",
    "dgraph_tpu/ops/sets.py::intersect",
    "dgraph_tpu/ops/sets.py::difference",
    "dgraph_tpu/ops/sets.py::intersect_many",
    "dgraph_tpu/ops/sets.py::union_many",
    "dgraph_tpu/ops/sets.py::range_rows",
    # the [B, L] batched set algebra of PR 1: tests/test_batch_ops.py only
    "dgraph_tpu/ops/batch.py::intersect_batch",
    "dgraph_tpu/ops/batch.py::difference_batch",
    "dgraph_tpu/ops/batch.py::union_many_batch",
    "dgraph_tpu/ops/batch.py::member_mask_batch",
    "dgraph_tpu/ops/batch.py::sort_unique_batch",
    "dgraph_tpu/ops/batch.py::_effc_batch",
    # MXU tier kernels the join planner never reaches (it runs
    # run_mask_chain, uids_to_mask and intersect_stack)
    "dgraph_tpu/ops/spgemm.py::expand_counts",
    "dgraph_tpu/ops/spgemm.py::expand_mask",
    "dgraph_tpu/ops/spgemm.py::expand_mask_batch",
    "dgraph_tpu/ops/spgemm.py::intersect_masks",
    "dgraph_tpu/ops/spgemm.py::intersect_stack_batch",
    "dgraph_tpu/ops/spgemm.py::triangle_mask",
    "dgraph_tpu/ops/spgemm.py::triangle_mask_batch",
}

_COVERS = sorted(s for c in programs.REGISTRY.values() for s in c.covers)


def _names(node) -> set:
    out = set()
    for n in ast.walk(node):
        if isinstance(n, ast.Name):
            out.add(n.id)
        elif isinstance(n, ast.Attribute):
            out.add(n.attr)
        elif isinstance(n, ast.ImportFrom):
            out.update(a.name for a in n.names)
    return out


def _bound(stmt) -> list:
    if isinstance(stmt, (ast.FunctionDef, ast.ClassDef)):
        return [stmt.name]
    if isinstance(stmt, ast.Assign):
        return [t.id for t in stmt.targets if isinstance(t, ast.Name)]
    return []


@pytest.fixture(scope="module")
def package():
    """path → (every name the module refers to, and for each top-level
    statement the names it binds and the names it refers to)."""
    out = {}
    for p in _PKG.rglob("*.py"):
        tree = ast.parse(p.read_text())
        rel = "dgraph_tpu/" + p.relative_to(_PKG).as_posix()
        out[rel] = (
            _names(tree), [(_bound(st), _names(st)) for st in tree.body]
        )
    return out


def _served(package, path: str, name: str, seen=()) -> bool:
    for other, (names, _stmts) in package.items():
        if other != path and not other.startswith(_NOT_SERVING):
            if name in names:
                return True
    for holders, names in package[path][1]:
        if name in names:
            for holder in holders:
                if holder != name and holder not in seen and _served(
                    package, path, holder, seen + (name,)
                ):
                    return True
    return False


@pytest.mark.parametrize("site", _COVERS)
def test_registered_program_has_a_served_caller(package, site):
    path, qual = site.split("::")
    served = _served(package, path, qual.split(".")[0])
    if site in UNSERVED:
        assert not served, f"{site} is served now: take it off UNSERVED"
    else:
        assert served, (
            f"{site} is registered in analysis/programs.py but no module "
            "outside its own file, ops/__init__.py and analysis/ refers to "
            "it: wire it into a served path or delete it with its contract"
        )


def test_unserved_debt_names_registered_programs():
    assert UNSERVED <= set(_COVERS), sorted(UNSERVED - set(_COVERS))
