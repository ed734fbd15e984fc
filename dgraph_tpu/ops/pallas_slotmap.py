"""Pallas fused slot-map kernel for grouped overflow expansion.

The XLA slot-map (ops/sets.py _ov_slot_map) spends one scatter plus three
O(n log n) scan passes per expansion — profiler-attributed at ~25% of the
headline bench's device time (docs/ROOFLINE.md).  This kernel computes
the same chunkid vector in ONE VMEM-resident pass per query, using the
structure the grouped layout guarantees (VERDICT r3 next-step #1: "a
Pallas fused segmented-scan kernel"):

- rows in the productive prefix are ascending-distinct and ALL have
  cd >= 1, so output starts (cstart) are strictly increasing — at most
  128 rows can start inside any 128-slot block;
- V[j] = cs[j] - cstart[j] (the telescoped chunk-id offset) is
  non-decreasing, so the prefix contribution to any block is just the
  LAST qualifying row's V.

Per 128-slot block the kernel takes the prefix offset plus a <=128-row
window max — a [128 x 128] VPU tile — instead of global scans/scatters.

Status (PR 21): REFUSED by the TPU v5e compiler, off every default path.
Compiled for a described v5e in the sandbox, the Pallas TPU lowering
stops before Mosaic runs: ``NotImplementedError: Unimplemented primitive
in Pallas TPU lowering for KernelType.TC: cumsum``
(tests/test_chip_compile.py pins the message as a strict xfail; ROADMAP
S5 owns the repair — the in-kernel prefix sums need a lane-rotate
scan or an MXU triangular matmul in their place).  Until then
DGRAPH_TPU_SLOTMAP's auto mode selects the XLA slot-map on every backend
(ops/sets.py use_slotmap_pallas); 'force' runs the kernel anywhere — under
the interpreter on CPU, the mode the parity property tests pin
(tests/test_pallas.py, vs both the XLA slot-map and slotmap_reference).
The contract registry entry (analysis/programs.py "pallas.slotmap") stays
FULL in interpret mode: golden fingerprint, callback/dtype/transfer
audits, a cost entry and a bucket probe.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from dgraph_tpu.ops.sets import SENT


def _kernel(cs_ref, cd_ref, out_ref, vbuf, cbuf):
    from jax.experimental import pallas as pl

    pcap = cs_ref.shape[1]
    capc = out_ref.shape[1]
    R = pcap // 128
    NB = capc // 128

    cd2 = cd_ref[0].reshape(R, 128)
    cs2 = cs_ref[0].reshape(R, 128)
    # two-level inclusive cumsum of cd: lanes within a row, then row
    # offsets — all in registers/VMEM, no HBM passes
    lane = jnp.cumsum(cd2, axis=1)
    row_tot = lane[:, -1:]
    row_off = jnp.cumsum(row_tot, axis=0) - row_tot
    ccum = lane + row_off
    cstart = ccum - cd2
    total = ccum[-1, -1]
    v = cs2 - cstart
    # stage cstart/V into scratch so per-block windows can dynamic-slice;
    # the +128 pad (cstart=+inf, v=-1) lets windows read past the end
    cbuf[0:R] = cstart
    cbuf[R : R + 1] = jnp.full((1, 128), SENT, jnp.int32)
    vbuf[0:R] = v
    vbuf[R : R + 1] = jnp.full((1, 128), -1, jnp.int32)
    cflat = cbuf[:].reshape(-1)
    vflat = vbuf[:].reshape(-1)

    slots128 = jax.lax.broadcasted_iota(jnp.int32, (128, 1), 0)

    def block(b, _):
        base = b * 128
        # rows wholly before this block: count(cstart <= base - 1);
        # strictly-increasing cstart makes the last of them the prefix max
        hi0 = jnp.sum((cflat[: R * 128] <= base - 1).astype(jnp.int32))
        pref = jnp.where(hi0 > 0, vflat[jnp.maximum(hi0 - 1, 0)], -1)
        # <=128 rows can START inside a 128-slot block (cstart strictly
        # increasing): one [slots x rows] tile covers the window
        wc = jax.lax.dynamic_slice(cflat, (hi0,), (128,))
        wv = jax.lax.dynamic_slice(vflat, (hi0,), (128,))
        si = base + slots128  # [128, 1]
        cand = jnp.where(wc[None, :] <= si, wv[None, :], -1)  # [128, 128]
        g = jnp.maximum(jnp.max(cand, axis=1, keepdims=True), pref)
        cid = g + si
        ok = si < total
        out_ref[0, pl.ds(base, 128)] = jnp.where(ok, cid, -1).reshape(128)
        return 0

    jax.lax.fori_loop(0, NB, block, 0)


@partial(jax.jit, static_argnames=("capc", "interpret"))
def slotmap_pallas(cs: jnp.ndarray, cd: jnp.ndarray, capc: int, interpret: bool = False):
    """Batched grouped slot-map: cs/cd int32[Q, pcap] (pcap % 128 == 0,
    valid rows a strictly-ascending productive prefix per query) →
    chunkid int32[Q, capc] with -1 beyond each query's total."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    q, pcap = cs.shape
    assert pcap % 128 == 0 and capc % 128 == 0
    grid = (q,)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, pcap), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, pcap), lambda i: (i, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, capc), lambda i: (i, 0), memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((q, capc), jnp.int32),
        scratch_shapes=[
            pltpu.VMEM((pcap // 128 + 1, 128), jnp.int32),
            pltpu.VMEM((pcap // 128 + 1, 128), jnp.int32),
        ],
        interpret=interpret,
    )(cs, cd)


def slotmap_reference(cs: np.ndarray, cd: np.ndarray, capc: int) -> np.ndarray:
    """Host reference of the same mapping (for tests): expand each row's
    chunk range in order."""
    out = np.full(capc, -1, dtype=np.int32)
    pos = 0
    for s, d in zip(cs.tolist(), cd.tolist()):
        for k in range(d):
            if pos < capc:
                out[pos] = s + k
            pos += 1
    return out
