"""Pallas segment-gather kernel: frontier expansion over a RESIDENT CSR.

The XLA posting gather (ops/sets.py expand_csr) re-derives slot ownership
per hop — a scatter plus two O(cap) scan passes — and, worse, runs over
arena tensors the engine re-stages host→device after every mutation
(models/arena.py ensure_device: the staging tax the planner exists to
price).  This kernel is the device-resident tier's walk primitive
(docs/ROOFLINE.md "Device-resident data plane"): it streams the
frontier's posting spans out of the pinned ``dst`` buffer into the output
— no owner scatter, no prefix-sum over the output, no staged copy of the
arena.

Shape of the kernel (what Mosaic on a TPU v5e accepts — the first draft's
per-row 128-lane DMAs at element-granular offsets were refused: a 1-D
int32 buffer in HBM is tiled ``(1024)``, and neither a slice shape nor a
dynamic slice offset may break the tiling):

- ``dst`` is viewed as ``[NT, 128]`` (a bitcast of the 1-D buffer: the
  resident capacity is a multiple of 1024) and only ever DMA'd in
  ``GROUP``-row windows at 8-row-aligned offsets — whole ``(8, 128)``
  tiles.  The last window fetched stays in VMEM, and ascending frontiers
  walk ``dst`` ascending, so neighbouring rows reuse it without a DMA.
- The grid runs over OUTPUT blocks of ``OB`` tiles (auto-pipelined VMEM
  blocks — no output DMAs to order).  Per output tile the kernel walks
  the frontier rows whose spans overlap it (a row pointer carried in SMEM
  scratch across the sequential grid), realigns each source window onto
  the tile's lanes with two dynamic lane rotations, and merges it under
  the row's lane mask.
- The per-row ``start`` (exclusive cumsum of degrees, ``B+1`` entries)
  and ``sstart`` (span start in ``dst``) tables ride scalar prefetch into
  SMEM.  SMEM on a v5e is 1 MiB, which bounds the frontier at
  ``MAX_ROWS`` per kernel launch; wider frontiers take the XLA program
  over the same resident buffers (same bytes out, no second copy of the
  arena).

Layout contract ("the store format IS the kernel format"): ``dst`` has a
multiple of 1024 lanes with at least 1024 SENT lanes past the live edges
(``models/arena.py`` ``_resident_cap``), so every window a live span can
need lies inside the buffer.  Lanes past ``total`` are masked by the
epilog (SENT / -1), making the output byte-identical to ``expand_csr`` on
the same inputs.

Status (PR 21): compiles for TPU v5e at film-21M widths
(tests/test_chip_compile.py) and served ``route:resident`` on a v5e chip
with answers equal to the numpy reference (chip_smoke.py; CHANGES.md
PR 21).  Interpret-mode parity: tests/test_pallas.py.  Registered in the
device-program contract registry (analysis/programs.py "pallas.gather").
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from dgraph_tpu.ops.sets import SENT, expand_csr

LANES = 128   # one VPU lane row of int32
GROUP = 16    # source rows per DMA window: any 2 consecutive rows at an
              # 8-aligned base (two (8, 128) tiles)
OB = 8        # output tiles per grid step (one (8, 128) block)
# frontier rows one launch can carry: start[B+1] + sstart[B] int32 in the
# 1 MiB SMEM of a v5e ("Allocation (size=1052672) would exceed memory
# (size=1048576) ... space=smem" at 2x this)
MAX_ROWS = 1 << 16


def _kernel(start_ref, sstart_ref, dst_hbm, out_ref, seg_ref, win, state, sem):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    nrows = sstart_ref.shape[0]
    ntiles = dst_hbm.shape[0]
    j = pl.program_id(0)

    @pl.when(j == 0)
    def _init():
        state[0] = 0    # first frontier row not yet fully written
        state[1] = -1   # base row of the window held in ``win``

    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)

    def tile(k, _):
        lo = (j * OB + k) * LANES
        hi = lo + LANES
        r0 = state[0]

        def overlaps(c):
            r = c[0]
            return jnp.logical_and(
                r < nrows, start_ref[jnp.minimum(r, nrows - 1)] < hi
            )

        def merge_row(c):
            r, ot, st = c
            s = start_ref[r]
            e = start_ref[r + 1]
            # dst index that lands on lane 0 of this tile; may sit below
            # the span (masked lanes) or below 0 (first rows of the arena)
            src_lo = sstart_ref[r] + lo - s
            a = src_lo >> 7             # floor: arithmetic shift
            rot = src_lo & (LANES - 1)
            base = jnp.clip((a >> 3) << 3, 0, ntiles - GROUP)

            @pl.when(jnp.logical_and(e > s, base != state[1]))
            def _fetch():
                cp = pltpu.make_async_copy(
                    dst_hbm.at[pl.ds(pl.multiple_of(base, 8), GROUP), :],
                    win, sem,
                )
                cp.start()
                cp.wait()
                state[1] = base

            # the tile's 128 source lanes straddle window rows a, a+1; a
            # row "-1" only ever feeds masked lanes, so clamp it
            ia = a - base
            va = win[pl.ds(jnp.maximum(ia, 0), 1), :]
            vb = win[pl.ds(ia + 1, 1), :]
            sh = (LANES - rot) & (LANES - 1)
            got = jnp.where(
                lane < LANES - rot, pltpu.roll(va, sh, 1), pltpu.roll(vb, sh, 1)
            )
            pos = lo + lane
            mine = jnp.logical_and(pos >= s, pos < e)
            return r + 1, jnp.where(mine, got, ot), jnp.where(mine, r, st)

        r_end, ot, st = jax.lax.while_loop(
            overlaps, merge_row,
            (r0, jnp.full((1, LANES), SENT, jnp.int32),
             jnp.full((1, LANES), -1, jnp.int32)),
        )
        out_ref[pl.ds(k, 1), :] = ot
        seg_ref[pl.ds(k, 1), :] = st
        # the last row merged continues into the next tile iff its span
        # runs past this one
        straddles = jnp.logical_and(r_end > r0, start_ref[r_end] > hi)
        state[0] = jnp.where(straddles, r_end - 1, r_end)
        return 0

    jax.lax.fori_loop(0, OB, tile, 0)


@partial(jax.jit, static_argnames=("cap", "interpret"))
def gather_pallas(
    offsets: jnp.ndarray,
    dst: jnp.ndarray,
    rows: jnp.ndarray,
    cap: int,
    interpret: bool = False,
):
    """Resident-CSR frontier expansion, byte-identical to
    ``ops.sets.expand_csr(offsets, dst, rows, cap)``.

    Args:
      offsets: int32[Sb+1] CSR row offsets (padding rows degree 0).
      dst:     int32[Ek] packed target uids with Ek % 1024 == 0 and at
               least 1024 SENT lanes of slack past the live edges (the
               ResidentArena storage contract; see module docstring).
      rows:    int32[B] arena row indices, negative = skip.
      cap:     static output capacity (bucketed total degree).

    Returns (out int32[cap], seg int32[cap], total int32) exactly as
    expand_csr: out grouped by source (ascending within a group),
    SENT-padded; seg = producing index into ``rows``, -1-padded.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    nrows = rows.shape[0]
    assert nrows >= 1
    assert dst.shape[0] % 1024 == 0 and dst.shape[0] != 1024, (
        "resident dst: whole (8, 128) tiles plus a slack tile group"
    )
    if dst.shape[0] == 0 or nrows > MAX_ROWS:
        # edgeless arena (static shortcut), or a frontier whose row
        # tables outgrow SMEM: the XLA program over the same buffers
        return expand_csr(offsets, dst, rows, cap)
    # XLA prolog: the same O(B) frontier math as expand_csr's head — the
    # O(cap) owner scatter/scan chain is what the kernel deletes
    valid = rows >= 0
    r = jnp.where(valid, rows, 0)
    deg = jnp.where(valid, offsets[r + 1] - offsets[r], 0)
    cum = jnp.cumsum(deg).astype(jnp.int32)
    total = cum[-1]
    start = jnp.concatenate([jnp.zeros((1,), jnp.int32), cum])
    sstart = jnp.where(valid, offsets[r], 0).astype(jnp.int32)

    blk = OB * LANES
    capk = ((cap + blk - 1) // blk) * blk
    out_k, seg_k = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(capk // blk,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],  # dst stays in HBM
            out_specs=[
                pl.BlockSpec((OB, LANES), lambda j, *_: (j, 0)),
                pl.BlockSpec((OB, LANES), lambda j, *_: (j, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((GROUP, LANES), jnp.int32),
                pltpu.SMEM((2,), jnp.int32),
                pltpu.SemaphoreType.DMA,
            ],
        ),
        out_shape=[jax.ShapeDtypeStruct((capk // LANES, LANES), jnp.int32)] * 2,
        # the row pointer and the cached window carry across grid steps
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)
        ),
        interpret=interpret,
    )(start, sstart, dst.reshape(-1, LANES))
    i = jnp.arange(cap, dtype=jnp.int32)
    ok = i < total
    out = jnp.where(ok, out_k.reshape(-1)[:cap], SENT)
    seg = jnp.where(ok, seg_k.reshape(-1)[:cap], -1)
    return out, seg, total


@partial(jax.jit, static_argnames=("cap", "interpret"))
def gather_pallas_packed(
    offsets: jnp.ndarray,
    dst: jnp.ndarray,
    rows: jnp.ndarray,
    cap: int,
    interpret: bool = False,
) -> jnp.ndarray:
    """``gather_pallas`` with the engine's packed transfer layout:
    ``concat([out, seg])`` (int32[2*cap]) so the resident hop fetches one
    buffer, exactly like the staged ``_packed_expand_csr`` program
    (query/engine.py).  The caller already knows ``total`` host-side."""
    out, seg, _ = gather_pallas(offsets, dst, rows, cap, interpret=interpret)
    return jnp.concatenate([out, seg])


def gather_reference(h_offsets, h_dst, rows, cap):
    """Pure-numpy oracle of the same contract (for tests): expand each
    non-negative row's span in order, SENT/-1 pad, silent truncation."""
    import numpy as np

    out = np.full(cap, SENT, dtype=np.int32)
    seg = np.full(cap, -1, dtype=np.int32)
    pos = 0
    for j, row in enumerate(np.asarray(rows).tolist()):
        if row < 0:
            continue
        for e in range(int(h_offsets[row]), int(h_offsets[row + 1])):
            if pos < cap:
                out[pos] = h_dst[e]
                seg[pos] = j
            pos += 1
    return out, seg, pos
