"""Layer kernels (ops/sets.py, query/chain.py; models/arena.py's scatters):
the least time the chip's memory could move the bytes of the traced window's
device work — the traversal the device routes carried
(``work.traversal_bytes``, as ``traversal_roofline`` counts it) PLUS what the
window's acknowledged writes have to add to the layouts
(``work_writes.write_bytes`` of the REFERENCE's count of touched rows, new
chunks and new uid -> row entries) — over ``devices`` x the peak x the time
the device's operations ran.  Nothing where the program lacks the write
counter (a parent commit) or nothing ran on the device."""

import harness
import work
import work_writes


def read(obs):
    t = obs.trace
    if not t or not obs.peaks or t["busy_s"] <= 0 or t["devices"] <= 0:
        return None
    if "ok" not in obs.delta("dgraph_writes_total"):
        return None
    on_device, total = harness.route_split(obs.delta("dgraph_ledger_hop_edges_total"))
    if total <= 0 or on_device <= 0:
        return None
    rows = sum(
        e["rows"] for e, tail in zip(obs.expect, obs.tails)
        if e is not None and ((tail.get("extensions") or {}).get("ledger") or {}).get("edges")
    )
    touch = [e["touch"] for e, ok in zip(obs.expect, obs.ok)
             if ok and e is not None and "touch" in e]
    moved = work.traversal_bytes(on_device, rows * on_device / total) + work_writes.write_bytes(
        sum(x["rows"] for x in touch), sum(x["chunks"] for x in touch),
        sum(x["lut"] for x in touch))
    return 100.0 * (moved / (t["devices"] * obs.peaks["hbm_bytes_per_s"])) / t["busy_s"]
