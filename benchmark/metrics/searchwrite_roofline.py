"""Layer kernels (ops/bfs.py; models/arena.py's scatters): the least time the
chip's memory could move the bytes of the traced window's device work — the
path searches executed (``work_paths.path_bytes`` of the REFERENCE's edges and
rows, as ``path_roofline`` counts them; a path-back is a search) PLUS what the
window's acknowledged writes have to add to the merged layout
(``work_path_writes.write_bytes`` of the REFERENCE's count of new offset rows
and edge slots) — over the peak x the time the device's operations ran.
Nothing where there is no trace, the program lacks the layout's counter (a
parent commit) or no search ran on the device."""

import work_path_writes
import work_paths


def read(obs):
    t = obs.trace
    if not t or not obs.peaks or t["busy_s"] <= 0:
        return None
    if "delta" not in obs.delta("dgraph_path_layout_updates_total"):
        return None
    edges = rows = new_rows = new_slots = 0
    for e, tail in zip(obs.expect, obs.tails):
        led = (tail.get("extensions") or {}).get("ledger") or {}
        if e is not None and (led.get("hop_edges") or {}).get("path"):
            edges += e["edges"]
            rows += e["rows"]
        # a path-back is only ever sent for a film whose write was acknowledged
        # (generators/closed_follow.py): its expectation carries what that film
        # added to the layout, by the reference's count
        touch = (e or {}).get("path_touch")
        if touch:
            new_rows += touch["rows"]
            new_slots += touch["slots"]
    if edges <= 0:
        return None
    moved = work_paths.path_bytes(edges, rows) + work_path_writes.write_bytes(new_rows, new_slots)
    return 100.0 * (moved / obs.peaks["hbm_bytes_per_s"]) / t["busy_s"]
