"""Layer kernels (ops/bfs.py): the least time the chip's memory could move the
bytes of the path searches executed in the traced window
(``work_paths.path_bytes`` of the REFERENCE's edges and rows for those
answers: a function of the queries' work, not of sweep or gather) over the
time the device's operations ran in the trace.  The BFS is the only device
work of a path cell.  An answer counts where the program executed it (its
ledger shows the search's edges; a result-cache hit did no device work).
Nothing to read -> nothing returned."""

import work_paths


def read(obs):
    t = obs.trace
    if not t or not obs.peaks:
        return None
    edges = rows = 0
    for e, tail in zip(obs.expect, obs.tails):
        led = (tail.get("extensions") or {}).get("ledger") or {}
        if e is not None and (led.get("hop_edges") or {}).get("path"):
            edges += e["edges"]
            rows += e["rows"]
    return work_paths.roofline_share(edges, rows, t["busy_s"], obs.peaks["hbm_bytes_per_s"])
