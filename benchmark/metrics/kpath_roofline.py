"""Layer kernels (ops/kbfs.py): the least time the chip's memory could move the
bytes of the k-path searches executed in the traced window
(``work_kpaths.kpath_bytes`` of the REFERENCE's edges, arrivals and prefix
reads for those answers: a function of the queries' work, not of the
program's sorts) over the time the device's operations ran in the trace.
The k-path search is the only device work of the cell.  An answer counts
where the program executed it (its ledger shows the search's edges; a
result-cache hit did no device work).  Nothing where there is no trace, the
program lacks the k-path counter family (a parent commit) or no search ran
on the device."""

import work_kpaths


def read(obs):
    t = obs.trace
    if not t or not obs.peaks:
        return None
    if not obs.delta("dgraph_kpath_searches_total").get("device"):
        return None
    edges = rows = steps = 0
    for e, tail in zip(obs.expect, obs.tails):
        led = (tail.get("extensions") or {}).get("ledger") or {}
        if e is not None and (led.get("hop_edges") or {}).get("path"):
            edges += e["edges"]
            rows += e["rows"]
            steps += e["loop_steps"]
    return work_kpaths.roofline_share(edges, rows, steps, t["busy_s"],
                                      obs.peaks["hbm_bytes_per_s"])
