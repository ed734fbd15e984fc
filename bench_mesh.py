"""Sharded-vs-local expansion throughput over every device JAX gives this
process (VERDICT r3 item 4: a recorded ratio at a 21M-scale predicate).

The mesh is built from ``len(jax.devices())``: the four chips of a TPU
host, or — for a rehearsal of the SPMD program structure (shard_map +
all_gather + device reassembly) — however many virtual CPU devices the
caller asked for:

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        python bench_mesh.py

On virtual devices that share one host's cores the ratio says only that
the collectives are not pathological; on chips it is the ICI crossover.
The result names the platform, device kind and device count it was
taken on.

Usage: python bench_mesh.py   (env: BM_EDGES, default 21_000_000)
"""

import json
import os
import time

import jax
import numpy as np

from bench import device_identity
from dgraph_tpu import ops
from dgraph_tpu.models.arena import csr_dense_from_edges
from dgraph_tpu.parallel.mesh import (
    make_mesh,
    shard_arena_rows,
    sharded_expand_segments,
)


def main():
    dev = device_identity()
    n_edges = int(os.environ.get("BM_EDGES", 21_000_000))
    n_nodes = max(1024, n_edges // 10)
    rng = np.random.default_rng(5)
    src = rng.integers(1, n_nodes + 1, size=n_edges)
    dst = rng.integers(1, n_nodes + 1, size=n_edges)
    t0 = time.time()
    a = csr_dense_from_edges(src, dst, n_nodes)
    build_s = time.time() - t0

    mesh = make_mesh(len(jax.devices()), data=1)
    t0 = time.time()
    sa = shard_arena_rows(a.h_src, a.h_offsets, a.host_dst(), mesh)
    shard_s = time.time() - t0

    frontiers = [
        np.unique(rng.integers(1, n_nodes + 1, size=4096)) for _ in range(10)
    ]
    cap = ops.bucket(
        max(
            int(a.degree_of_rows(a.rows_for_uids_host(f)).sum())
            for f in frontiers
        )
    )

    # warm both paths (compile)
    sharded_expand_segments(mesh, sa, frontiers[0], cap)
    rows0 = ops.pad_rows(a.rows_for_uids_host(frontiers[0]), ops.bucket(len(frontiers[0])))
    out, seg, _ = ops.expand_csr(a.offsets, a.dst, rows0, cap)
    np.asarray(out)

    t0 = time.time()
    edges = 0
    for f in frontiers:
        o, ptr = sharded_expand_segments(mesh, sa, f, cap)
        edges += len(o)
    sharded_s = time.time() - t0

    t0 = time.time()
    edges_l = 0
    for f in frontiers:
        rows = ops.pad_rows(a.rows_for_uids_host(f), ops.bucket(len(f)))
        out, seg, _t = ops.expand_csr(a.offsets, a.dst, rows, cap)
        seg_h = np.asarray(seg)
        edges_l += int((seg_h >= 0).sum())
    local_s = time.time() - t0

    assert edges == edges_l, (edges, edges_l)

    # crossover sweep: where does sharded beat local as expansion size
    # grows?  One point per frontier size (VERDICT r3 weak #5 asked for a
    # curve, not an anecdote).  On the virtual CPU mesh this exercises
    # structure; the ICI curve comes from running the same sweep on a pod.
    curve = []
    for n_seed in (256, 1024, 4096, 16384, 65536):
        fs = [np.unique(rng.integers(1, n_nodes + 1, size=n_seed)) for _ in range(3)]
        capn = ops.bucket(max(
            int(a.degree_of_rows(a.rows_for_uids_host(f)).sum()) for f in fs
        ))
        sharded_expand_segments(mesh, sa, fs[0], capn)  # warm
        t0 = time.time()
        for f in fs:
            sharded_expand_segments(mesh, sa, f, capn)
        sh_ms = (time.time() - t0) / len(fs) * 1e3
        rows = ops.pad_rows(a.rows_for_uids_host(fs[0]), ops.bucket(len(fs[0])))
        np.asarray(ops.expand_csr(a.offsets, a.dst, rows, capn)[0])  # warm
        t0 = time.time()
        for f in fs:
            rows = ops.pad_rows(a.rows_for_uids_host(f), ops.bucket(len(f)))
            out, seg, _t = ops.expand_csr(a.offsets, a.dst, rows, capn)
            np.asarray(seg)
        lo_ms = (time.time() - t0) / len(fs) * 1e3
        curve.append({
            "seeds": n_seed, "cap": capn,
            "sharded_ms": round(sh_ms, 1), "local_ms": round(lo_ms, 1),
            "ratio_local_over_sharded": round(lo_ms / sh_ms, 2),
        })

    # serving-plane arm (PR 17): the same expansions dispatched THROUGH
    # the MeshExecutor entry points the server actually calls
    # (dgraph_tpu/mesh/executor.py) — devguard bracket + placement +
    # attribution included — plus the fused multi-hop program whose
    # cross-chip frontier exchange runs between scan levels on the ICI,
    # A/B'd against the same hops as separate per-level dispatches.
    from dgraph_tpu.mesh.executor import MeshExecutor
    from dgraph_tpu.mesh.programs import exchange_bytes_per_hop

    class _Arenas:
        """The executor's ArenaManager surface, minimally: one already
        sharded predicate (the bench controls placement explicitly)."""

        mesh_fault = None  # no elastic fault domain: a fixed mesh

        def __init__(self, mesh, sa):
            self.mesh = mesh
            self._sa = sa

        def sharded_csr(self, attr, reverse=False):
            return self._sa

    ex = MeshExecutor(_Arenas(mesh, sa))
    stats = {}
    ex.expand("link", False, frontiers[0], cap, stats)  # warm
    t0 = time.time()
    for f in frontiers:
        ex.expand("link", False, f, cap, stats)
    exec_s = time.time() - t0

    n_hops = int(os.environ.get("BM_HOPS", 3))
    hop_cap = ops.bucket(int(os.environ.get("BM_HOP_CAP", 65536)))
    seed_f = frontiers[0][: min(len(frontiers[0]), hop_cap)]
    ex.multi_hop("link", False, seed_f, n_hops, hop_cap, stats)  # warm
    t0 = time.time()
    fs, _totals = ex.multi_hop("link", False, seed_f, n_hops, hop_cap, stats)
    fused_s = time.time() - t0
    # the ladder: the same traversal as n_hops separate sharded
    # dispatches, each frontier crossing the host between levels —
    # exactly the per-hop round trip the fused program deletes
    from dgraph_tpu.ops.sets import SENT

    t0 = time.time()
    f = seed_f
    ladder = []
    for _ in range(n_hops):
        o, _ptr = ex.expand("link", False, f, hop_cap, stats)
        f = np.unique(o)[: hop_cap]
        ladder.append(f)
    ladder_s = time.time() - t0
    # parity: the fused program's per-level frontiers match the ladder's
    for lvl in range(n_hops):
        got = np.asarray(fs[lvl])
        got = got[got != SENT]
        assert np.array_equal(got, ladder[lvl][: len(got)]), f"hop {lvl}"

    executor = {
        "expand_ms": round(exec_s / len(frontiers) * 1e3, 1),
        "n_hops": n_hops,
        "hop_cap": hop_cap,
        "fused_multi_hop_ms": round(fused_s * 1e3, 1),
        "ladder_multi_hop_ms": round(ladder_s * 1e3, 1),
        "ratio_ladder_over_fused": round(ladder_s / fused_s, 2),
        "exchange_bytes_per_hop": exchange_bytes_per_hop(mesh, hop_cap),
    }

    print(json.dumps({
        "metric": "mesh_sharded_vs_local_expand",
        "edges_per_query": edges // len(frontiers),
        "sharded_ms": round(sharded_s / len(frontiers) * 1e3, 1),
        "local_ms": round(local_s / len(frontiers) * 1e3, 1),
        "ratio_local_over_sharded": round(local_s / sharded_s, 2),
        **dev,
        "build_s": round(build_s, 1),
        "shard_s": round(shard_s, 1),
        "crossover_curve": curve,
        "executor": executor,
    }))


if __name__ == "__main__":
    main()
