"""Layer kernels (ops/kbfs.py): of the arrivals the device's k-path searches
took, the share that were re-arrivals — a uid taken again at a later level
than its first (``dgraph_kpath_arrivals_total{again}`` over both kinds,
window deltas): the work past the shortest paths.  Nothing where the program
has no such family or took no arrival."""


def read(obs):
    by = obs.delta("dgraph_kpath_arrivals_total")
    total = sum(by.values())
    return 100.0 * by.get("again", 0.0) / total if total > 0 else None
