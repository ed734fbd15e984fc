"""Layer planner (query/planner.py ``path_route``): of the k-path blocks
(``numpaths`` > 1) the window executed, the share the device route answered —
``dgraph_kpath_searches_total{device}`` over all its routes (``host``: the
planner chose the Dijkstra; ``fallback``: the device gave up), window deltas.
Nothing where the program has no such family (a parent commit) or executed
no k-path block."""


def read(obs):
    by = obs.delta("dgraph_kpath_searches_total")
    total = sum(by.values())
    return 100.0 * by.get("device", 0.0) / total if total > 0 else None
