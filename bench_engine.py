"""Engine-latency benchmark: the wiki performance-page queries.

Mirrors the reference's published query latencies (BASELINE.md:
3-hop Tom-Hanks-style co-actor query 2-3ms warm / 8-9ms cold;
4-level Spielberg detail query 30-35ms warm / 87ms cold, on an i7
laptop over the Freebase 21M film graph).  Builds a synthetic film
graph at configurable scale, bulk-loads it through the real mutation
path (native scanner when available), and measures the same two query
shapes through parse → execute → JSON.

Usage: python bench_engine.py            (env: BE_DIRECTORS, BE_RUNS)
Prints one JSON line per query shape.
"""

import json
import os
import random
import time

from bench import device_identity
from dgraph_tpu.models import PostingStore
from dgraph_tpu.query import QueryEngine
from dgraph_tpu.utils.filmgen import SCHEMA


def build(n_directors: int, films_per: int = 8, actors_per_film: int = 6,
          n_actors: int | None = None, seed: int = 7) -> str:
    rng = random.Random(seed)
    n_actors = n_actors or n_directors * 3
    lines = []
    uid = 1

    def u(x):
        return f"<0x{x:x}>"

    genres = []
    for gi in range(24):
        genres.append(uid)
        lines.append(f'{u(uid)} <name> "Genre {gi}" .')
        uid += 1
    actors = []
    for ai in range(n_actors):
        actors.append(uid)
        lines.append(f'{u(uid)} <name> "Actor {ai}" .')
        uid += 1
    for di in range(n_directors):
        d = uid
        uid += 1
        lines.append(f'{u(d)} <name> "Director {di}" .')
        for fi in range(films_per):
            f = uid
            uid += 1
            lines.append(f'{u(f)} <name> "Film {di}-{fi}" .')
            y = 1960 + rng.randrange(60)
            lines.append(f'{u(f)} <initial_release_date> "{y}-0{1 + rng.randrange(9)}-1{rng.randrange(9)}" .')
            lines.append(f'{u(d)} <director.film> {u(f)} .')
            lines.append(f'{u(f)} <genre> {u(rng.choice(genres))} .')
            for _ in range(actors_per_film):
                p = uid
                uid += 1
                a = rng.choice(actors)
                lines.append(f'{u(p)} <performance.actor> {u(a)} .')
                lines.append(f'{u(f)} <starring> {u(p)} .')
    return "\n".join(lines)


def main():
    dev = device_identity()
    print(f"# backend: {dev}", flush=True)
    n_directors = int(os.environ.get("BE_DIRECTORS", 2000))
    runs = int(os.environ.get("BE_RUNS", 20))

    st = PostingStore()
    eng = QueryEngine(st)
    t0 = time.time()
    rdf = build(n_directors)
    gen_s = time.time() - t0
    t0 = time.time()
    eng.run("mutation { schema { %s } set { %s } }" % (SCHEMA, rdf))
    load_s = time.time() - t0
    n_quads = rdf.count("\n") + 1

    # the two wiki shapes, seeded on a mid-graph entity
    co_actor = """
    { me(func: eq(name, "Actor 7")) {
        ~performance.actor { ~starring {
          name
          starring { performance.actor { name } }
        } }
    } }"""
    detail = """
    { dir(func: eq(name, "Director 11")) {
        name
        director.film (orderasc: initial_release_date) {
          name
          initial_release_date
          genre { name }
          starring { performance.actor { name } }
        }
    } }"""

    results = {}
    for label, q in (("3hop_coactor", co_actor), ("4level_detail", detail)):
        cold0 = time.time()
        out = eng.run(q)
        cold_ms = (time.time() - cold0) * 1e3
        assert out, f"{label} returned empty"
        times = []
        for _ in range(runs):
            t0 = time.time()
            eng.run(q)
            times.append((time.time() - t0) * 1e3)
        times.sort()
        results[label] = {
            "cold_ms": round(cold_ms, 2),
            "warm_p50_ms": round(times[len(times) // 2], 2),
            "warm_min_ms": round(times[0], 2),
        }

    # -- order-by at scale: device segmented rank-sort vs host sorted -------
    # (worker/sort.go analog; VERDICT r1 #5).  One fan-out node with 1M+
    # children ordered by an int value.
    n_big = int(os.environ.get("BE_ORDER_N", 1_000_000))
    import numpy as np

    from dgraph_tpu.models.store import Edge
    from dgraph_tpu.models.types import TypeID, TypedValue
    from dgraph_tpu.query.engine import QueryEngine as _QE

    st2 = PostingStore()
    st2.apply_schema("rank: int .\nbig: uid .")
    rng = np.random.default_rng(5)
    kids = np.arange(2, n_big + 2)
    st2.bulk_set_uid_edges("big", np.full(n_big, 1), kids)
    pd = st2.pred("rank")
    vals = rng.integers(0, 1 << 30, size=n_big)
    for u, v in zip(kids.tolist(), vals.tolist()):
        pd.values[(u, "")] = TypedValue(TypeID.INT, int(v))
    st2.dirty.add("rank")
    eng2 = QueryEngine(st2)
    qo = "{ q(func: uid(0x1)) { big (orderasc: rank, first: 10) { _uid_ } } }"
    eng2.run(qo)  # warm (arena + compile)
    t0 = time.time()
    dev_out = eng2.run(qo)
    dev_ms = (time.time() - t0) * 1e3
    orig = _QE._device_order_perm
    _QE._device_order_perm = lambda *a, **k: None
    try:
        t0 = time.time()
        host_out = eng2.run(qo)
        host_ms = (time.time() - t0) * 1e3
    finally:
        _QE._device_order_perm = orig
    assert dev_out == host_out, "device order != host order at 1M"
    results["orderby_1m"] = {
        "n": n_big,
        "device_ms": round(dev_ms, 1),
        "host_ms": round(host_ms, 1),
        "speedup": round(host_ms / dev_ms, 2),
    }

    # -- incremental arena refresh: mutate+query p50 on a 10M-edge pred ----
    # (VERDICT r3 item 6: delta overlay vs full rebuild, target >= 10x)
    n_inc = int(os.environ.get("BE_INC_N", 10_000_000))
    import numpy as np

    st3 = PostingStore()
    st3.apply_schema("name: string @index(exact) .\nbig: uid .")
    rng3 = np.random.default_rng(11)
    st3.bulk_set_uid_edges(
        "big", rng3.integers(1, 1_000_001, size=n_inc), rng3.integers(1, 1_000_001, size=n_inc)
    )
    from dgraph_tpu.models.store import Edge as _Edge

    eng3 = QueryEngine(st3)
    eng3.run("{ q(func: uid(0x1)) { big { _uid_ } } }")  # build the arena

    def mutate_and_query(dst_base, n_rounds=9):
        # dst_base must differ per phase: re-adding an existing edge is a
        # no-op touch that skips arena work entirely (a round-4 audit
        # caught the phases sharing dsts, so "full rebuild" measured
        # no-ops at 0.4ms)
        times = []
        for i in range(n_rounds):
            t0 = time.time()
            st3.apply(_Edge(pred="big", src=1, dst=dst_base + i))
            eng3.run("{ q(func: uid(0x1)) { big (first: 3) { _uid_ } } }")
            times.append((time.time() - t0) * 1e3)
        times.sort()
        return times[len(times) // 2]

    inc_p50 = mutate_and_query(2_000_000)
    # force the full-rebuild path for the same workload
    orig_delta_max = PostingStore.DELTA_MAX
    PostingStore.DELTA_MAX = 0
    try:
        full_p50 = mutate_and_query(2_100_000)
    finally:
        PostingStore.DELTA_MAX = orig_delta_max
    results["incremental_refresh_10m"] = {
        "edges": n_inc,
        "incremental_p50_ms": round(inc_p50, 1),
        "full_rebuild_p50_ms": round(full_p50, 1),
        "speedup": round(full_p50 / inc_p50, 2),
    }

    # -- fused-chain A/B: engine edges/s on a big fan-out chain ------------
    # (VERDICT r2 #2: an ENGINE-level device number, not just raw kernels.)
    # Same query, same engine; the knob is whether eligible uid chains
    # fuse into one device program (query/chain.py) or run per-level.
    qc = "{ q(func: has(director.film)) { director.film { starring { performance.actor { name } } } } }"
    eng.chain_threshold = 0
    eng.run(qc)  # warm: arenas, LUTs, compile
    t0 = time.time()
    fused_out = eng.run(qc)
    fused_ms = (time.time() - t0) * 1e3
    edges = eng.stats["edges"]
    fused_levels = eng.stats["chain_fused_levels"]
    eng.chain_threshold = 10**18
    eng.run(qc)  # warm the per-level path too
    t0 = time.time()
    plain_out = eng.run(qc)
    plain_ms = (time.time() - t0) * 1e3
    assert eng.stats["edges"] == edges, "paths traversed different edge counts"
    assert json.dumps(fused_out, sort_keys=True, default=str) == json.dumps(
        plain_out, sort_keys=True, default=str
    ), "fused chain != per-level results"
    results["chain_fanout"] = {
        "edges": edges,
        "fused_levels": fused_levels,
        "fused_ms": round(fused_ms, 1),
        "per_level_ms": round(plain_ms, 1),
        "fused_edges_per_sec": round(edges / (fused_ms / 1e3), 1),
        "speedup": round(plain_ms / fused_ms, 2),
    }

    for label, r in results.items():
        print(json.dumps({"metric": f"engine_{label}", **r, **dev}))
    print(
        f"# graph: {n_directors} directors, {n_quads} quads "
        f"(gen {gen_s:.1f}s, load {load_s:.1f}s = {n_quads/load_s:,.0f} quads/s); "
        f"{runs} warm runs. Reference (i7, 21M graph): 3hop 2-3ms warm / "
        f"8-9ms cold; 4level 30-35ms warm / 87ms cold (BASELINE.md)."
    )


if __name__ == "__main__":
    main()
