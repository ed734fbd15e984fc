"""Layer engine (query/shortest.py): levels the device route expanded per
search it answered — ``dgraph_path_levels_total`` over
``dgraph_path_searches_total{device}``, window deltas.  Nothing where the
program has no such family (the parent of PR 28) or answered no search."""


def read(obs):
    levels = obs.delta("dgraph_path_levels_total")
    n = obs.delta("dgraph_path_searches_total").get("device", 0.0)
    return sum(levels.values()) / n if levels and n > 0 else None
