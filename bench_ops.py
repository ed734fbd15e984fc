"""Per-kernel microbenchmarks: the measured numbers behind
docs/ROOFLINE.md, reproducible in one command.

Measures, at headline-bench-like shapes (200-query batches):
  - expand_inline_grouped      (XLA slot-map)
  - expand_inline_grouped_pallas (Pallas slot-map; CPU backend only, in
    interpret mode — the TPU compiler refuses the kernel)
  - sort_unique dedup at the hop-2 width
  - member_mask set membership
plus the BATCHED-vs-PER-OP comparison for the fused hop executor
(ops/batch.py): for B ∈ {1, 64, 1024} and L ∈ {256, 4096}, one fused
``expand_filter_compact`` program per hop versus the per-op dispatch
sequence (expand, merge, one intersect per predicate, compact), with
DISPATCH AND COMPILE COUNTS recorded per path — the dispatch ratio is
the fusion win the headline bench banks.

PR 16 adds the resident-tier A/B: the Pallas segment-gather over an
HBM-pinned ResidentArena vs expand_csr staged and vs expand_csr paying
the post-mutation re-staging tax, plus intersect_pallas vs
intersect_many at k ∈ {2,4,8} (env: BO_RES_NODES/BO_RES_EDGES/
BO_RES_FRONTIER/BO_RES_SETLEN).  On the CPU backend the Pallas arms run
in interpret mode and emit mode=interpret / perf_claim=false — those rows
prove the harness and the dispatch discipline, not a speedup.

One JSON line per measurement: {"kernel", "value", "unit", "platform",
"device_kind", "device_count", ...extras}.

Usage: python bench_ops.py    (env: BO_NODES/BO_EDGES/BO_Q scale it; runs
on the backend JAX gives it — JAX_PLATFORMS=cpu for a rehearsal)
"""

import json
import os
import time

import numpy as np


class DispatchCounter:
    """Counts device dispatches (one per jitted-callable invocation from
    the host, via ``call``) and XLA compiles (via the jax.monitoring
    backend_compile event) while active.

    jax.monitoring offers register but no unregister, so ONE module
    listener dispatches to whichever counter is currently active —
    entering N counters over a run must not accumulate N live closures.
    """

    _active = None
    _listener_installed = False

    def __init__(self):
        self.dispatches = 0
        self.compiles = 0

    @classmethod
    def _install_listener(cls):
        if cls._listener_installed:
            return
        import jax

        def on_event(event, duration, **kw):
            c = cls._active
            if c is not None and event.endswith("backend_compile_duration"):
                c.compiles += 1

        jax.monitoring.register_event_duration_secs_listener(on_event)
        cls._listener_installed = True

    def __enter__(self):
        type(self)._install_listener()
        type(self)._active = self
        return self

    def __exit__(self, *exc):
        type(self)._active = None
        return False

    def call(self, fn, *args, **kw):
        """Invoke a jitted callable, counting it as ONE device dispatch."""
        self.dispatches += 1
        return fn(*args, **kw)


def bench_batched_vs_per_op(platform, emit):
    """The fused-hop dispatch-count comparison: a hop with K filter
    predicates as ONE fused program vs the per-op dispatch sequence the
    pre-fusion engine issued."""
    import jax
    import jax.numpy as jnp

    from dgraph_tpu import ops
    from bench import build_graph

    n_nodes = int(os.environ.get("BO_NODES2", 100_000))
    n_edges = int(os.environ.get("BO_EDGES2", 800_000))
    a = build_graph(n_nodes, n_edges)
    rng = np.random.default_rng(11)
    K = 4  # filter predicates per hop

    merge_op = ops.sort_unique_batch
    intersect_op = ops.intersect_batch
    compact_op = jax.jit(jax.vmap(ops.compact))

    keep_np = [
        np.unique(rng.integers(1, n_nodes + 1, size=n_nodes // 8))
        for _ in range(K)
    ]
    keeps = tuple(
        jnp.asarray(ops.pad_to(k, ops.bucket(len(k)))) for k in keep_np
    )

    for B in (1, 64, 1024):
        for L in (256, 4096):
            seeds = [
                np.unique(rng.integers(1, n_nodes + 1, size=max(4, L // 8)))
                for _ in range(B)
            ]
            cap = ops.bucket(
                max(int(a.degree_of_rows(s).sum()) for s in seeds)
            )
            rows = jnp.asarray(np.stack([ops.pad_rows(s, L) for s in seeds]))
            # per-op building block: its own jitted dispatch per call
            expand_op = jax.jit(jax.vmap(
                lambda r: ops.expand_ascending(a.offsets, a.dst, r, cap)[0]
            ))
            keeps_b = tuple(
                jnp.broadcast_to(k, (B,) + k.shape) for k in keeps
            )

            # fused: ONE program for the whole hop
            with DispatchCounter() as cf:
                r = cf.call(
                    ops.expand_filter_compact_batch,
                    a.offsets, a.dst, rows, cap, keeps,
                )
                jax.block_until_ready(r)
                compiles = cf.compiles
                t0 = time.time()
                r = cf.call(
                    ops.expand_filter_compact_batch,
                    a.offsets, a.dst, rows, cap, keeps,
                )
                jax.block_until_ready(r)
                fused_s = time.time() - t0

            # per-op: expand, merge, K intersects, compact — one
            # dispatch each (the engine's pre-fusion shape)
            def per_op(counter):
                out = counter.call(expand_op, rows)
                u = counter.call(merge_op, out)
                for k in keeps_b:
                    u = counter.call(intersect_op, u, k)
                return counter.call(compact_op, u)

            with DispatchCounter() as cp:
                jax.block_until_ready(per_op(cp))
                n0 = cp.dispatches
                t0 = time.time()
                jax.block_until_ready(per_op(cp))
                per_op_s = time.time() - t0
                per_dispatches = cp.dispatches - n0

            emit("fused_hop_vs_per_op", per_op_s / fused_s, "x speedup", {
                "B": B, "L": L, "predicates": K,
                "fused_dispatches_per_hop": 1,
                "per_op_dispatches_per_hop": per_dispatches,
                "dispatch_ratio": float(per_dispatches),
                "fused_compiles": compiles,
                "fused_s": round(fused_s, 4),
                "per_op_s": round(per_op_s, 4),
            })


def bench_kway_intersection(platform, emit):
    """MXU join tier, k-way grid: for B ∈ {1, 64, 1024} and
    k ∈ {2, 4, 8}, ONE intersect_stack_batch program versus the per-op
    pairwise fold (k-1 intersect_batch dispatches).  Checksum parity
    against the set-op reference is ASSERTED in the bench; the dispatch
    count per k-way intersection drops to O(1)."""
    import jax
    import jax.numpy as jnp

    from dgraph_tpu import ops

    # dense-ish sets (filter predicates over a shared hot neighborhood):
    # the k≥4 rows are where the single-program tier wins; k=2 is the
    # honesty row — "pairwise" IS one op there, so the fused kernel has
    # nothing to fuse and the ratio hovers around 1.
    rng = np.random.default_rng(13)
    n = int(os.environ.get("BO_KWAY_UNIVERSE", 1200))
    L = int(os.environ.get("BO_KWAY_L", 1024))

    # satellite guard: the k-way folds no longer serialize.  The
    # scan-free property is a registered program contract now —
    # the bench just invokes the single source of truth instead of
    # hand-grepping jaxprs (analysis/programs.py, trace-only checks).
    from dgraph_tpu.analysis import programs

    programs.assert_contract("sets.intersect_many")
    programs.assert_contract("sets.union_many")

    for B in (1, 64, 1024):
        for k in (2, 4, 8):
            sets = [
                [
                    np.unique(rng.integers(1, n, size=L - L // 4))
                    for _ in range(k)
                ]
                for _ in range(B)
            ]
            mat = np.stack(
                [
                    np.stack([ops.pad_to(s, L) for s in row])
                    for row in sets
                ]
            )
            dmat = jnp.asarray(mat)
            rows2d = [jnp.asarray(mat[:, i]) for i in range(k)]

            with DispatchCounter() as cf:
                r = cf.call(ops.intersect_stack_batch, dmat)
                jax.block_until_ready(r)
                compiles = cf.compiles
                fused_s = float("inf")
                for _ in range(3):
                    t0 = time.time()
                    r = cf.call(ops.intersect_stack_batch, dmat)
                    jax.block_until_ready(r)
                    fused_s = min(fused_s, time.time() - t0)
            got = np.asarray(r)

            def per_op(counter):
                u = rows2d[0]
                for i in range(1, k):
                    u = counter.call(ops.intersect_batch, u, rows2d[i])
                return u

            with DispatchCounter() as cp:
                ref_out = per_op(cp)
                jax.block_until_ready(ref_out)
                n0 = cp.dispatches
                per_op_s = float("inf")
                for _ in range(3):
                    t0 = time.time()
                    ref_out = per_op(cp)
                    jax.block_until_ready(ref_out)
                    per_op_s = min(per_op_s, time.time() - t0)
                per_dispatches = (cp.dispatches - n0) // 3
            ref_np = np.asarray(ref_out)

            # checksum parity vs the set-op reference, asserted here
            SENT = ops.SENT
            chk_f = np.where(got == SENT, 0, got).sum(dtype=np.int64)
            chk_p = np.where(ref_np == SENT, 0, ref_np).sum(dtype=np.int64)
            assert chk_f == chk_p, (chk_f, chk_p)
            for b in range(B):
                np.testing.assert_array_equal(
                    got[b][got[b] != SENT], ref_np[b][ref_np[b] != SENT]
                )
            assert per_dispatches == k - 1

            emit("kway_intersect_spgemm_vs_per_op", per_op_s / fused_s,
                 "x speedup", {
                     "B": B, "k": k,
                     "spgemm_dispatches": 1,
                     "per_op_dispatches": per_dispatches,
                     "spgemm_compiles": compiles,
                     "checksum": int(chk_f),
                     "parity": "ok",
                     "spgemm_s": round(fused_s, 4),
                     "per_op_s": round(per_op_s, 4),
                 })


def bench_triangle(platform, emit):
    """MXU join tier, fused triangle kernel: two legs + closing-predicate
    tiles in ONE program vs the per-op gather pipeline (expand, dedup,
    expand, dedup, reverse expand, dedup, intersect = 7 dispatches),
    over B ∈ {1, 64, 1024} root sets.  Set parity asserted per row."""
    import jax
    import jax.numpy as jnp

    from dgraph_tpu import ops
    from dgraph_tpu.ops import spgemm
    from dgraph_tpu.query.chain import _topm_deg_sum
    from bench import build_graph

    # DENSE community-shaped subgraph — the worst-case-optimal join's
    # design point (EmptyHeaded's triangle wins are on dense cyclic
    # neighborhoods): every materialized tile lane is useful, while the
    # gather pipeline pays sort width proportional to the fan-out
    # explosion.  Sparse shapes route pairwise via the joinplan cost
    # model — that asymmetry is WHY the route choice exists.
    n_nodes = int(os.environ.get("BO_TRI_NODES", 512))
    n_edges = int(os.environ.get("BO_TRI_EDGES", 32768))
    R = int(os.environ.get("BO_TRI_ROOTS", 48))
    a = build_graph(n_nodes, n_edges, seed=5)
    rev = build_graph(n_nodes, n_edges, seed=6)  # closing pred (reverse)
    t = spgemm.tile_size()
    pt = spgemm.build_tiles(a.h_src, a.h_offsets, a.host_dst(), t=t)
    pr = spgemm.build_tiles(rev.h_src, rev.h_offsets, rev.host_dst(), t=t)
    assert pt is not None and pr is not None
    uni = max(pt.universe, pr.universe)
    m = spgemm.mask_lanes(uni, t)
    rng = np.random.default_rng(17)
    SENT = ops.SENT

    for B in (1, 64, 1024):
        roots = [
            np.unique(rng.integers(1, n_nodes, size=R)) for _ in range(B)
        ]
        Lr = ops.bucket(max(len(r) for r in roots))
        rmat = np.stack([ops.pad_to(r, Lr) for r in roots])
        drmat = jnp.asarray(rmat)
        # masks for the fused path (built once per query in the engine)
        xm = np.zeros((B, m), dtype=np.float32)
        for i, r in enumerate(roots):
            xm[i, r] = 1.0
        dxm = jnp.asarray(xm)

        cap1 = ops.bucket(
            max(int(a.degree_of_rows(r).sum()) for r in roots)
        )
        capw = ops.bucket(
            max(int(rev.degree_of_rows(r).sum()) for r in roots)
        )
        cap2 = ops.bucket(_topm_deg_sum(a, min(cap1, a.n_distinct_dst())))

        # dense arenas: uid == row, but SENT pads must become the -1
        # skip marker (frontier_rows) before entering the slot map
        ex1 = jax.jit(jax.vmap(
            lambda r: ops.expand_ascending(
                a.offsets, a.dst, ops.frontier_rows(r), cap1
            )[0]
        ))
        ex2 = jax.jit(jax.vmap(
            lambda r: ops.expand_ascending(
                a.offsets, a.dst, ops.frontier_rows(r), cap2
            )[0]
        ))
        exw = jax.jit(jax.vmap(
            lambda r: ops.expand_ascending(
                rev.offsets, rev.dst, ops.frontier_rows(r), capw
            )[0]
        ))
        dedup = ops.sort_unique_batch

        def per_op(counter):
            l1 = counter.call(dedup, counter.call(ex1, drmat))
            l2 = counter.call(dedup, counter.call(ex2, l1))
            w = counter.call(dedup, counter.call(exw, drmat))
            return counter.call(ops.intersect_batch, l2, w)

        with DispatchCounter() as cp:
            ref_out = per_op(cp)
            jax.block_until_ready(ref_out)
            n0 = cp.dispatches
            per_op_s = float("inf")
            for _ in range(3):
                t0 = time.time()
                ref_out = per_op(cp)
                jax.block_until_ready(ref_out)
                per_op_s = min(per_op_s, time.time() - t0)
            per_dispatches = (cp.dispatches - n0) // 3
        ref_np = np.asarray(ref_out)

        with DispatchCounter() as cf:
            z = cf.call(
                spgemm.triangle_mask_batch,
                pt.bi, pt.bj, pt.tiles, pt.bi, pt.bj, pt.tiles,
                pr.bi, pr.bj, pr.tiles, dxm,
            )
            jax.block_until_ready(z)
            compiles = cf.compiles
            fused_s = float("inf")
            for _ in range(3):
                t0 = time.time()
                z = cf.call(
                    spgemm.triangle_mask_batch,
                    pt.bi, pt.bj, pt.tiles, pt.bi, pt.bj, pt.tiles,
                    pr.bi, pr.bj, pr.tiles, dxm,
                )
                jax.block_until_ready(z)
                fused_s = min(fused_s, time.time() - t0)
        zm = np.asarray(z)

        # parity: fused closing masks == the set-op reference pipeline
        chk = 0
        for b in range(B):
            want = ref_np[b][ref_np[b] != SENT].astype(np.int64)
            got = np.flatnonzero(zm[b] > 0).astype(np.int64)
            np.testing.assert_array_equal(got, np.unique(want))
            chk += int(got.sum())

        emit("triangle_spgemm_vs_per_op", per_op_s / fused_s, "x speedup", {
            "B": B, "roots": R,
            "spgemm_dispatches": 1,
            "per_op_dispatches": per_dispatches,
            "spgemm_compiles": compiles,
            "tiles": int(pt.n_tiles + pr.n_tiles),
            "checksum": chk,
            "parity": "ok",
            "spgemm_s": round(fused_s, 4),
            "per_op_s": round(per_op_s, 4),
        })


def bench_resident_tier(platform, emit):
    """Resident Pallas tier vs the staged XLA route (PR 16): the
    segment-gather over a ResidentArena pinned in HBM against (a)
    expand_csr on already-staged tensors and (b) expand_csr paying the
    re-staging tax the resident tier deletes (device_put of the CSR
    before the hop — what the staged engine does after every mutation);
    plus the k-way intersect kernel vs intersect_many.  Dispatch and
    compile counts per arm, warm-path timed.

    Honest per-backend note: on the CPU backend the Pallas kernels run
    in INTERPRET mode — correctness speed, not a perf claim (the emitted
    rows carry mode=interpret so nobody graphs them as one).  On a TPU
    the gather compiles through Mosaic; the intersect kernel is refused
    by the v5e compiler (ops/pallas_intersect.py Status) and its row
    says so instead of running.  The dispatch/compile discipline pins
    hold on any backend."""
    import jax
    import jax.numpy as jnp

    from dgraph_tpu import ops
    from dgraph_tpu.models.arena import ResidentArena
    from bench import build_graph

    n_nodes = int(os.environ.get("BO_RES_NODES", 200_000))
    n_edges = int(os.environ.get("BO_RES_EDGES", 1_500_000))
    nf = int(os.environ.get("BO_RES_FRONTIER", 2048))
    interp = platform == "cpu"
    note = {"mode": "interpret" if interp else "mosaic",
            "perf_claim": not interp}

    a = build_graph(n_nodes, n_edges)
    ra = ResidentArena.seed(a.h_offsets, a.host_dst(), a.n_rows, a.n_edges)
    rng = np.random.default_rng(13)
    f = np.unique(rng.integers(0, a.n_rows, size=nf)).astype(np.int64)
    rows = jax.device_put(
        np.asarray(ops.pad_rows(f, ops.bucket(len(f))), np.int32)
    )
    deg = (a.h_offsets[1:] - a.h_offsets[:-1]).astype(np.int64)
    total = int(deg[f].sum())
    cap = ops.bucket(total)
    off32 = np.ascontiguousarray(a.h_offsets, dtype=np.int32)
    dst32 = np.ascontiguousarray(a.host_dst(), dtype=np.int32)
    off_dev = jax.device_put(off32)
    dst_dev = jax.device_put(dst32)

    def timed(counter, fn):
        r = fn(counter)  # warm: compile + stage constants
        jax.block_until_ready(r)
        compiles, n0 = counter.compiles, counter.dispatches
        t0 = time.time()
        jax.block_until_ready(fn(counter))
        return time.time() - t0, compiles, counter.dispatches - n0

    with DispatchCounter() as c:
        s, compiles, disp = timed(c, lambda c: c.call(
            ops.gather_pallas_packed, ra.off, ra.dst, rows, cap,
            interpret=interp,
        ))
    emit("gather_resident_pallas", total / s, "edges/s", {
        **note, "frontier": len(f), "cap": cap,
        "dispatches_per_hop": disp, "compiles": compiles,
        "h2d_bytes_per_hop": int(rows.nbytes),
    })

    with DispatchCounter() as c:
        s, compiles, disp = timed(c, lambda c: c.call(
            ops.expand_csr, off_dev, dst_dev, rows, cap
        ))
    emit("gather_staged_xla", total / s, "edges/s", {
        "frontier": len(f), "cap": cap,
        "dispatches_per_hop": disp, "compiles": compiles,
        "h2d_bytes_per_hop": int(rows.nbytes),
    })

    def restaged(counter):
        # the post-mutation hop of the staged route: the CSR crosses
        # host->device again before the gather can run
        o = jax.device_put(off32)
        d = jax.device_put(dst32)
        return counter.call(ops.expand_csr, o, d, rows, cap)

    with DispatchCounter() as c:
        s, compiles, disp = timed(c, restaged)
    emit("gather_staged_xla_restaged", total / s, "edges/s", {
        "frontier": len(f), "cap": cap,
        "dispatches_per_hop": disp, "compiles": compiles,
        "h2d_bytes_per_hop": int(rows.nbytes + off32.nbytes + dst32.nbytes),
    })

    # k-way intersect: the kernel vs the XLA merge tree
    L = int(os.environ.get("BO_RES_SETLEN", 8192))
    for k in (2, 4, 8):
        setsk = [
            np.unique(rng.integers(0, L * 4, size=L * 3 // 4)).astype(
                np.int32
            )
            for _ in range(k)
        ]
        mat = jnp.asarray(np.stack([
            np.asarray(ops.pad_to(s_, L)) for s_ in setsk
        ]))
        if interp:
            with DispatchCounter() as c:
                s, compiles, disp = timed(c, lambda c, m=mat: c.call(
                    ops.intersect_pallas, m, interpret=True
                ))
            emit("intersect_pallas", k * L / s, "elems/s", {
                **note, "k": k, "L": L,
                "dispatches": disp, "compiles": compiles,
            })
        else:
            emit("intersect_pallas", float("nan"), "elems/s", {
                "k": k, "L": L, "mode": "refused",
                "refusal": "Mosaic: cannot statically prove that index "
                           "in dimension 0 is a multiple of 1024 "
                           "(ops/pallas_intersect.py Status)",
            })
        with DispatchCounter() as c:
            s, compiles, disp = timed(c, lambda c, m=mat: c.call(
                ops.intersect_many, m
            ))
        emit("intersect_many_xla", k * L / s, "elems/s", {
            "k": k, "L": L, "dispatches": disp, "compiles": compiles,
        })


def main():
    from bench import build_graph, device_identity

    dev = device_identity()
    platform = dev["platform"]
    import jax
    import jax.numpy as jnp

    from dgraph_tpu import ops
    from dgraph_tpu.ops.sets import SENT

    def emit(kernel, value, unit, extra=None):
        rec = {
            "kernel": kernel, "value": round(value, 1), "unit": unit,
            **dev,
        }
        if extra:
            rec.update(extra)
        print(json.dumps(rec), flush=True)

    bench_batched_vs_per_op(platform, emit)
    bench_kway_intersection(platform, emit)
    bench_triangle(platform, emit)
    bench_resident_tier(platform, emit)

    n_nodes = int(os.environ.get("BO_NODES", 500_000))
    n_edges = int(os.environ.get("BO_EDGES", 4_000_000))
    Q = int(os.environ.get("BO_Q", 200))
    n_seeds = 2048

    a = build_graph(n_nodes, n_edges)
    metap, ov = a.inline_layout_grouped()
    deg = (a.h_offsets[1:] - a.h_offsets[:-1]).astype(np.int64)
    rng = np.random.default_rng(7)
    fronts = []
    for _ in range(Q):
        f = np.unique(rng.integers(1, n_nodes + 1, size=n_seeds))
        key = np.asarray(ops.skey_encode(f, deg[f] > ops.INLINE))
        fronts.append(f[np.argsort(key, kind="stable")])
    fcap = ops.bucket(max(len(f) for f in fronts))
    capc = ops.bucket_fine(
        max(int(a.ov_chunk_degree_of_rows(f).sum()) for f in fronts)
    )
    pcap = ops.bucket_fine(
        max(int((deg[f] > ops.INLINE).sum()) for f in fronts)
    )
    fmat = jnp.asarray(np.stack([ops.pad_to(f, fcap) for f in fronts]))
    rows = jnp.where(fmat == SENT, -1, fmat)
    edges_total = sum(int(deg[f].sum()) for f in fronts)

    def best(fn, n=4):
        fn()  # compile
        b = float("inf")
        for _ in range(n):
            t0 = time.time()
            jax.block_until_ready(fn())
            b = min(b, time.time() - t0)
        return b

    expanders = [("expand_inline_grouped", ops.expand_inline_grouped)]
    if platform == "cpu":
        # interpret mode; the TPU v5e compiler refuses the slot-map kernel
        # (ops/pallas_slotmap.py Status)
        expanders.append(
            ("expand_inline_grouped_pallas", ops.expand_inline_grouped_pallas)
        )
    for name, expander in expanders:
        run = jax.jit(jax.vmap(lambda r: expander(metap, ov, r, capc, pcap)))
        s = best(lambda: run(rows))
        emit(name, edges_total / s, "edges/s")

    wide = ops.bucket(fcap * ops.INLINE + capc * ops.CHUNK // 4)
    mat = jnp.asarray(
        rng.integers(1, n_nodes, size=(Q, wide)).astype(np.int32)
    )
    s = best(lambda: jax.jit(jax.vmap(ops.sort_unique))(mat))
    emit("sort_unique", Q * wide / s, "elems/s")

    b = jnp.asarray(
        np.sort(rng.integers(1, n_nodes, size=(Q, 4096)).astype(np.int32), axis=1)
    )
    mm = jax.jit(jax.vmap(ops.member_mask))
    s = best(lambda: mm(mat[:, :4096], b))
    emit("member_mask", Q * 4096 / s, "probes/s")


if __name__ == "__main__":
    main()
