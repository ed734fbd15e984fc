"""A write's refresh on the host, by function — CPU, counts.

In process, no HTTP, no chip: generate the film graph at ``--quads`` with the
benchmark's own generator, load it into a ``DgraphServer``, warm the readwrite
mix's read classes (so the four walked arenas, their inline layouts and LUTs
and the ``name`` exact index are cached, as in the cell), then send N
``add_film`` writes under ``cProfile`` and print what a write's
``ArenaManager.refresh`` costs by function, the mirror counter's growth and
the arenas' sizes.  In the sandbox every time is the SANDBOX's host numpy and
the CPU backend's "device" (which copies a scattered table whole, waits, and
frees the old one inside ``_layouts_take_delta``): read the counts and the
proportions of the host functions, never a device number.  Through the chip
tool the same script times the chip host's functions with the real scatters.

    JAX_PLATFORMS=cpu PYTHONPATH=. python scratch/refresh_prof.py --quads 500000 --writes 10

The cell's own scale is ``--quads 5250000`` (about 6 GB and ten minutes here:
mind the sandbox's shared memory).
"""

import argparse
import cProfile
import os
import pstats
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "benchmark")]

READS = ("hot_actor4", "two_hop", "coactor3")
FUNCS = ("refresh", "_refresh_dirty", "_take_journal", "_try_apply_delta", "apply_delta",
         "_apply_delta_locked", "_take_delta_host", "_extended", "_merge", "_spliced",
         "_with_new_rows", "_roomy", "_topm_replace", "_layouts_take_delta", "_inline_take_delta", "_inline_rows",
         "_put_scatter", "take_values", "insert_empty_rows", "_path_layouts_take",
         "_repair_hop_entries", "insert", "concatenate", "_note_untagged")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quads", type=int, default=500_000)
    ap.add_argument("--seed", type=int, default=5)
    ap.add_argument("--writes", type=int, default=10)
    args = ap.parse_args()

    import filmgen
    import run
    import trafficgen
    from dgraph_tpu.models import PostingStore
    from dgraph_tpu.serve.server import DgraphServer
    from dgraph_tpu.utils.metrics import metrics

    g = filmgen.generate(args.quads, args.seed)
    srv = DgraphServer(PostingStore())
    srv.engine.chain_threshold = 0            # the fused route whatever the scale
    srv.engine.arenas.shard_threshold = 1 << 62
    srv.run_query("mutation { schema { %s } }" % filmgen.SCHEMA)
    lines = filmgen.nquad_lines(g, 0, len(g.director))
    for lo in range(0, len(lines), 100_000):
        srv.run_query("mutation { set {\n%s\n} }" % "\n".join(lines[lo:lo + 100_000]))
    world = run.World(g)
    classes = trafficgen.load_classes(trafficgen.load_json("traffic", "readwrite.json"), world)

    def ask(cls, root, tag):
        kind = classes[cls]
        out = {k: v for k, v in dict(srv.run_query(kind.text(root, tag))).items()
               if k not in ("server_latency", "extensions")}
        problem = kind.check(out, kind.expect(root), tag)
        if problem is not None:
            raise SystemExit(f"{cls} {root}: {problem}")

    fresh = classes["add_film"].pool()
    films = [int(k) for k in fresh[:: max(1, len(fresh) // (args.writes + 2))]]
    for c in READS:                           # build the arenas, layouts and LUTs
        pool = classes[c].pool()
        for r in (pool[0], pool[len(pool) // 2]):
            ask(c, int(r), "w")
    for k in films[:2]:                       # the first writes make the mirrors' room
        ask("add_film", k, "w")
        ask("read_back", k, "w")

    def mirrors():
        fam = metrics.labeled("dgraph_arena_mirror_updates_total", label="how")
        return dict(fam.snapshot())

    was = mirrors()
    prof = cProfile.Profile()
    walls = []
    refresh = srv.engine.arenas.refresh

    def timed_refresh():
        if not srv.store.dirty:               # a read's: nothing to take
            return refresh()
        t0 = time.perf_counter()
        prof.enable()
        try:
            return refresh()
        finally:
            prof.disable()
            walls.append(1e3 * (time.perf_counter() - t0))

    srv.engine.arenas.refresh = timed_refresh
    for k in films[2:2 + args.writes]:
        ask("add_film", k, "p")
        ask("read_back", k, "p")
    srv.engine.arenas.refresh = refresh
    now = mirrors()

    import jax

    backend = jax.default_backend()
    tag = "CPU, counts: never a device number" if backend == "cpu" else f"host functions on a {backend} machine"
    print(f"# {tag} — {args.quads} quads, {len(walls)} writes")
    print(f"refresh wall ms a write (cProfile on): median {np.median(walls):.2f}  "
          f"min {min(walls):.2f}  max {max(walls):.2f}")
    print("mirror updates:", {h: now.get(h, 0) - was.get(h, 0) for h in sorted(now)} or "no such counter")
    stats = pstats.Stats(prof)
    rows = []
    for (fname, _line, func), (cc, nc, tt, ct, _callers) in stats.stats.items():
        name = func.strip("<>").split(" ")[-1].split(".")[-1].strip("'")
        if name in FUNCS and ("dgraph_tpu" in fname or fname == "~" or "numpy" in fname):
            rows.append((ct, nc, tt, name, os.path.basename(fname)))
    print(f"{'function':28s} {'calls/write':>11s} {'own ms/write':>12s} {'cum ms/write':>12s}")
    for ct, nc, tt, name, fname in sorted(rows, reverse=True):
        print(f"{name + ' (' + fname + ')':40s} {nc / len(walls):8.1f} "
              f"{1e3 * tt / len(walls):12.2f} {1e3 * ct / len(walls):12.2f}")
    am = srv.engine.arenas
    print("arenas (rows / edges / room of h_src, h_offsets, h_dst, _ov_coff):")
    for label, a in [("starring", am.data("starring")), ("performance.actor", am.data("performance.actor")),
                     ("~performance.actor", am.reverse("performance.actor")),
                     ("~starring", am.reverse("starring"))]:
        bufs = getattr(a, "_bufs", None) or {}
        room = {n: len(bufs[n]) - len(v) for n, v in (("h_src", a.h_src), ("h_offsets", a.h_offsets),
                ("_h_dst", a.host_dst()), ("_ov_coff", a._ov_coff if a._ov_coff is not None else ()))
                if n in bufs}
        print(f"  {label:20s} {a.n_rows:>9,d} / {a.n_edges:>9,d}  room {room}")
    idx = am.index("name", "exact")
    print(f"  name exact index     {len(idx.tokens):>9,d} tokens / {idx.csr.n_edges:>9,d} uids")
    srv.stop()


if __name__ == "__main__":
    main()
