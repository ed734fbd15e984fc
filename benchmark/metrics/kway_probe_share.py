"""Layer engine (query/functions.py ``FuncResolver.probe``, booked by
query/joinplan.py ``note_probe``): of the window's k-way intersections, by
count, the share a filter leaf answered by looking its candidates up instead
of building its set — ``dgraph_kway_intersect_total{probe}`` over every
route, window deltas.  Nothing where the family has no ``probe`` label (a
parent commit) or the window intersected nothing."""


def read(obs):
    by = obs.delta("dgraph_kway_intersect_total")
    total = sum(by.values())
    return 100.0 * by["probe"] / total if "probe" in by and total > 0 else None
