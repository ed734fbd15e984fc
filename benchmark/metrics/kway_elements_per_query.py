"""Layer engine (query/joinplan.py ``kway_intersect``): elements the window's
k-way intersections took in (the sets' sizes summed, both routes:
``dgraph_kway_intersect_elements_total``) over the requests the program
counted (``dgraph_num_queries_total``), window deltas — the size the planner
weighs, per request.  Nothing where the program has no such family (a parent
commit) or counted no request."""

import stagecount


def read(obs):
    by = obs.delta("dgraph_kway_intersect_elements_total")
    n = stagecount.queries(obs)
    return sum(by.values()) / n if by and n else None
