"""Pallas kernel tier parity suite (`pallas-interpret` CI job).

The segment-gather kernel (ops/pallas_gather.py) is pinned byte-identical
against TWO references — the pure numpy oracle gather_reference AND
expand_csr, the XLA program every other backend runs — over the real
ResidentArena slack-padded layout.

Runs in Pallas interpret mode (CPU backend, like the rest of the suite).
Interpret mode skips Mosaic lowering: tests/test_chip_compile.py compiles
the kernel for a described v5e.
"""

import numpy as np
import pytest

import jax.numpy as jnp

# the pallas-interpret CI job re-runs this module on its own (these
# tests also run inside tier-1 — the marker adds a name, not an excuse)
pytestmark = pytest.mark.pallas_interpret


# gather_pallas walks a ResidentArena-layout CSR (SENT slack-padded dst,
# bucketed offsets) — every case below runs the kernel over the REAL
# seeded layout and byte-compares against BOTH the pure-numpy oracle
# (gather_reference) and the staged XLA program (expand_csr), the two
# references the resident engine route must be indistinguishable from.


def _seeded_csr(rng, n, n_edges):
    from dgraph_tpu.models.arena import ResidentArena, csr_dense_from_edges

    src = rng.integers(1, n, size=n_edges)
    dst = rng.integers(1, n, size=n_edges)
    a = csr_dense_from_edges(src, dst, n)
    ra = ResidentArena.seed(a.h_offsets, a.host_dst(), a.n_rows, a.n_edges)
    return a, ra


def _gather_check(a, ra, rows, cap):
    from dgraph_tpu import ops

    rj = jnp.asarray(rows)
    out, seg, total = ops.gather_pallas(ra.off, ra.dst, rj, cap,
                                        interpret=True)
    w_out, w_seg, w_total = ops.gather_reference(
        a.h_offsets, a.host_dst(), rows, cap
    )
    assert int(total) == min(w_total, 2**31 - 1)
    assert np.array_equal(np.asarray(out), w_out)
    assert np.array_equal(np.asarray(seg), w_seg)
    # XLA reference: the staged program the resident route replaces
    x_out, x_seg, x_total = ops.expand_csr(
        jnp.asarray(a.h_offsets.astype(np.int32)),
        jnp.asarray(a.host_dst().astype(np.int32)),
        rj, cap,
    )
    assert np.array_equal(np.asarray(out), np.asarray(x_out))
    assert np.array_equal(np.asarray(seg), np.asarray(x_seg))
    assert int(total) == int(x_total)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_gather_pallas_matches_oracle_and_xla(seed):
    from dgraph_tpu import ops

    rng = np.random.default_rng(seed)
    a, ra = _seeded_csr(rng, 500, 6000)
    f = np.unique(rng.integers(0, a.n_rows, size=64)).astype(np.int64)
    rows = ops.pad_rows(f, ops.bucket(len(f))).astype(np.int32)
    cap = ops.bucket(int(np.sum(
        a.h_offsets[f + 1] - a.h_offsets[f]
    )) or 1)
    _gather_check(a, ra, rows, cap)


def test_gather_pallas_empty_frontier():
    from dgraph_tpu import ops

    rng = np.random.default_rng(3)
    a, ra = _seeded_csr(rng, 100, 800)
    rows = np.full(8, -1, dtype=np.int32)  # all pad lanes
    out, seg, total = ops.gather_pallas(ra.off, ra.dst, jnp.asarray(rows),
                                        128, interpret=True)
    assert int(total) == 0
    assert (np.asarray(out) == ops.SENT).all()
    assert (np.asarray(seg) == -1).all()


def test_gather_pallas_padded_rows_interleaved():
    """-1 pad lanes ANYWHERE in the frontier (not just the tail): each
    is skipped without consuming an output slot, matching pad_rows-style
    engine frontiers and the oracle's row<0 skip."""
    from dgraph_tpu import ops

    rng = np.random.default_rng(4)
    a, ra = _seeded_csr(rng, 300, 4000)
    rows = np.array([-1, 5, -1, 17, 42, -1, 99, -1], dtype=np.int32)
    cap = ops.bucket(int(np.sum(np.diff(a.h_offsets))) or 1)
    _gather_check(a, ra, rows, cap)


def test_gather_pallas_heavy_row_straddles_tiles():
    """One row's posting span crosses several 128-lane VMEM tiles (deg
    300 > 2 tiles) plus a trailing light row whose leading tile must
    overwrite the heavy row's tail-tile garbage."""
    from dgraph_tpu import ops
    from dgraph_tpu.models.arena import ResidentArena, csr_dense_from_edges

    heavy = np.full(300, 7, dtype=np.int64)
    light = np.array([9, 9, 9], dtype=np.int64)
    src = np.concatenate([heavy, light])
    dst = np.arange(1, len(src) + 1, dtype=np.int64)
    a = csr_dense_from_edges(src, dst, 16)
    ra = ResidentArena.seed(a.h_offsets, a.host_dst(), a.n_rows, a.n_edges)
    rows = ops.pad_rows(
        np.array([np.searchsorted(a.h_src, 7),
                  np.searchsorted(a.h_src, 9)], dtype=np.int64),
        8,
    ).astype(np.int32)
    _gather_check(a, ra, rows, ops.bucket(303))


def test_gather_pallas_truncates_at_cap():
    """cap below the frontier's total degree: silent truncation, total
    reports the untruncated count — both exactly as the oracle."""
    from dgraph_tpu import ops

    rng = np.random.default_rng(5)
    a, ra = _seeded_csr(rng, 200, 3000)
    f = np.arange(0, min(a.n_rows, 64), dtype=np.int64)
    rows = ops.pad_rows(f, 64).astype(np.int32)
    _gather_check(a, ra, rows, 128)


def test_gather_pallas_packed_layout():
    """The packed variant is exactly concat([out, seg]) of the unpacked
    one — the single-fetch layout the engine's resident hop reads."""
    from dgraph_tpu import ops

    rng = np.random.default_rng(6)
    a, ra = _seeded_csr(rng, 200, 2500)
    f = np.unique(rng.integers(0, a.n_rows, size=32)).astype(np.int64)
    rows = jnp.asarray(ops.pad_rows(f, 32).astype(np.int32))
    cap = 4096
    out, seg, _ = ops.gather_pallas(ra.off, ra.dst, rows, cap,
                                    interpret=True)
    packed = np.asarray(ops.gather_pallas_packed(ra.off, ra.dst, rows, cap,
                                                 interpret=True))
    assert packed.shape == (2 * cap,)
    assert np.array_equal(packed[:cap], np.asarray(out))
    assert np.array_equal(packed[cap:], np.asarray(seg))


# -------------------------------------------- program-count discipline


@pytest.mark.compile_budget(None)
def test_repeat_shapes_compile_zero_new_programs():
    """The resident tier's serving-loop discipline: after the first call
    at a given (shape, cap) key, repeated hops at the same shapes launch
    the CACHED program — zero new XLA compilations (the same pin the
    bucketed staged routes carry, analysis/budgets.json)."""
    from dgraph_tpu import ops
    from dgraph_tpu.analysis.pytest_budget import compile_count

    rng = np.random.default_rng(12)
    a, ra = _seeded_csr(rng, 300, 4000)
    f = np.unique(rng.integers(0, a.n_rows, size=40)).astype(np.int64)
    rows = jnp.asarray(ops.pad_rows(f, 64).astype(np.int32))
    # warm the program once (compiles allowed here)
    ops.gather_pallas_packed(ra.off, ra.dst, rows, 4096, interpret=True)
    c0 = compile_count()
    for _ in range(3):
        ops.gather_pallas_packed(ra.off, ra.dst, rows, 4096, interpret=True)
    assert compile_count() == c0, "repeat shapes recompiled"
