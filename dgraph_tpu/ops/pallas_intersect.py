"""Pallas k-way sorted-set intersect kernel (k <= 8 lanes).

The XLA k-way intersection (ops/sets.py intersect_many) is a log-depth
tree of pairwise merge-dedups: ceil(log2 k) rounds of bitonic sorts over
2L-wide concatenations — scan-free, but every round re-sorts the full
width.  This kernel takes the EmptyHeaded route (PAPERS.md): run the set
intersection directly over the stored layout.  Lane 0 is the probe set;
per 128-slot VMEM block each candidate is membership-tested against the
other k-1 rows by a tiled VPU compare (the rows sit whole in VMEM — a
[128 x L] equality tile per lane, no sorts, no scans), and one epilog
bitonic sort compacts survivors.  Survivors of row 0 are already sorted-
unique, so the result is byte-identical to ``intersect_many``.

Status (PR 21): REFUSED by the TPU v5e compiler; no served path calls it
(bench_ops.py and tests only).  Compiled for a described v5e in the
sandbox: ``MosaicError: INTERNAL: Mosaic failed to compile TPU kernel:
cannot statically prove that index in dimension 0 is a multiple of 1024``
on the ``tpu.vector_store`` of the 1-D ``(128,)`` survivor block — a 1-D
int32 buffer is tiled ``(1024)`` in VMEM, so the output wants a
``[L/128, 128]`` shape (tests/test_chip_compile.py pins the message as a
strict xfail; ROADMAP S5 owns the repair).  Correctness is verified in
Pallas interpret mode on CPU (tests/test_pallas.py, the
`pallas-interpret` CI tier), and the kernel is registered in the
device-program contract registry (analysis/programs.py
"pallas.intersect").
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from dgraph_tpu.ops.sets import SENT, sort_desc_free

KMAX = 8  # static lane budget: the engine's chain planner never funnels
          # more than 8 predicates into one k-way node (query/chain.py)


def _kernel(mat_ref, out_ref):
    from jax.experimental import pallas as pl

    k = mat_ref.shape[0]
    b = pl.program_id(0)
    a = mat_ref[0, pl.ds(b * 128, 128)]
    ok = a != SENT
    for j in range(1, k):  # k is static: the loop unrolls at trace time
        row = mat_ref[j]
        ok &= jnp.any(a[:, None] == row[None, :], axis=1)
    out_ref[pl.ds(b * 128, 128)] = jnp.where(ok, a, SENT)


@partial(jax.jit, static_argnames=("interpret",))
def intersect_pallas(mat: jnp.ndarray, interpret: bool = False) -> jnp.ndarray:
    """Intersect the K rows of a [K, L] sorted-unique-SENT-padded matrix,
    byte-identical to ``ops.sets.intersect_many(mat)`` (int32[L], sorted
    ascending, SENT-padded).  K <= KMAX (static)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    k, L = mat.shape
    assert 1 <= k <= KMAX, f"k={k} exceeds the {KMAX}-lane kernel budget"
    Lp = ((max(L, 128) + 127) // 128) * 128
    matp = jnp.full((k, Lp), SENT, jnp.int32).at[:, :L].set(mat)
    raw = pl.pallas_call(
        _kernel,
        grid=(Lp // 128,),
        in_specs=[
            pl.BlockSpec((k, Lp), lambda b: (0, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((Lp,), lambda b: (0,), memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((Lp,), jnp.int32),
        interpret=interpret,
    )(matp)
    # epilog compaction: survivors are a subset of sorted-unique lane 0,
    # so one value sort reproduces intersect_many's output exactly
    return sort_desc_free(raw)[:L]


def intersect_reference(mat) -> "list":
    """Pure-python oracle (for tests): sorted intersection of the valid
    entries of every row."""
    import numpy as np

    mat = np.asarray(mat)
    acc = set(int(v) for v in mat[0] if v != SENT)
    for row in mat[1:]:
        acc &= set(int(v) for v in row if v != SENT)
    return sorted(acc)
