"""Layer kernels (ops/pallas_gather.py, ops/sets.py, ops/batch.py): the
least time the chip's memory could move the bytes of the traversal work the
device routes carried in the traced window (``work.traversal_bytes`` — a
function of edges and frontier rows, whatever kernel does it) over the time
its operations ran in the trace.  Memory-bound by construction: a traversal
has no arithmetic to speak of.  On a mesh the bytes are held against ALL the
traced chips' bandwidth (``trace["devices"]`` x one chip's peak) and the
busy time is the mean a chip.

Edges on device routes: ``dgraph_ledger_hop_edges_total{route}`` over the
window (only the program knows the route).  Frontier rows: the reference's
rows of the answers that were executed (their ledger shows edges), scaled by
the device routes' share of the edges.  Nothing to read -> nothing returned."""

import harness
import work


def read(obs):
    t = obs.trace
    if not t or not obs.peaks:
        return None
    on_device, total = harness.route_split(obs.delta("dgraph_ledger_hop_edges_total"))
    if total <= 0 or on_device <= 0:
        return None
    rows = sum(
        e["rows"] for e, tail in zip(obs.expect, obs.tails)
        if e is not None and ((tail.get("extensions") or {}).get("ledger") or {}).get("edges")
    )
    return work.roofline_share(on_device, rows * on_device / total, t["busy_s"],
                               obs.peaks["hbm_bytes_per_s"], devices=t["devices"])
