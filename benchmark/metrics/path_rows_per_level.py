"""Layer kernels (ops/bfs.py): a level's mean width —
``dgraph_path_frontier_rows_total`` over ``dgraph_path_levels_total``, window
deltas: what the per-level choice between gather and sweep sees.  Nothing
where the program has no such families or expanded no level."""


def read(obs):
    rows = sum(obs.delta("dgraph_path_frontier_rows_total").values())
    levels = sum(obs.delta("dgraph_path_levels_total").values())
    return rows / levels if levels > 0 else None
