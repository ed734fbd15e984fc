"""Layer engine (query/functions.py, query/joinplan.py): mean milliseconds a
request of the window spent on its filters — the resolver's index
expansions, the recheck of index candidates against values and the k-way
intersections: ``dgraph_filter_stage_us_total{resolver_expand, recheck,
kway}`` (the engine stats ``resolver_expand_ms``, ``recheck_ms``,
``kway_ms``, folded in once a request) over ``dgraph_num_queries_total``,
window deltas.  Nothing where the program has no such family (a parent
commit)."""

import stagecount

PARTS = ("resolver_expand", "recheck", "kway")


def read(obs):
    by = obs.delta("dgraph_filter_stage_us_total")
    n = stagecount.queries(obs)
    if any(p not in by for p in PARTS) or not n:
        return None
    return sum(by[p] for p in PARTS) / 1e3 / n
