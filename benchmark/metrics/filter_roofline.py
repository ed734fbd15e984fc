"""Layer kernels (ops/sets.py ``intersect_stack``, ``expand_csr``, the chain's
kernels): the least time the chip's memory could move the bytes of the
filtered traversals executed in the traced window (``work_filter.filter_bytes``
of the REFERENCE's edges, frontier rows and k-way elements for those
answers: a function of the queries, not of the route) over the time the
device's operations ran in the trace.  An answer counts where the program
executed its traversal (its ledger shows edges).  Nothing where there is no
trace, the program lacks the k-way element family (a parent commit) or no
request was executed."""

import work_filter


def read(obs):
    t = obs.trace
    if not t or not obs.peaks:
        return None
    if not obs.delta("dgraph_kway_intersect_elements_total"):
        return None
    edges = rows = elements = 0
    for e, tail in zip(obs.expect, obs.tails):
        led = (tail.get("extensions") or {}).get("ledger") or {}
        if e is not None and led.get("edges"):
            edges += e["edges"]
            rows += e["rows"]
            elements += e["kway"]
    return work_filter.roofline_share(edges, rows, elements, t["busy_s"],
                                      obs.peaks["hbm_bytes_per_s"])
