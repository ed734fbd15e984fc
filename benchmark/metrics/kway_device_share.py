"""Layer planner (query/joinplan.py ``kway_intersect``, priced by
query/planner.py ``kway_route``): of the elements the window's k-way
intersections took in, the share the batched device program intersected —
``dgraph_kway_intersect_elements_total{device}`` over both routes, window
deltas.  Nothing where the program has no such family (a parent commit) or
intersected nothing."""


def read(obs):
    by = obs.delta("dgraph_kway_intersect_elements_total")
    total = sum(by.values())
    return 100.0 * by.get("device", 0.0) / total if total > 0 else None
