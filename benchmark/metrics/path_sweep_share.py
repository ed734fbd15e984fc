"""Layer kernels (ops/bfs.py): of the levels the window's searches expanded,
the share done as a sweep over every edge of the layout and not gathered from
the frontier list — ``dgraph_path_level_ways_total{way}``, window deltas.  It
says whether the gather / sweep break-even sends the levels it is meant to.
Nothing where the program lacks the family or a label, or no level ran."""


def read(obs):
    grown = obs.delta("dgraph_path_level_ways_total")
    if "gather" not in grown or "sweep" not in grown:
        return None
    total = grown["gather"] + grown["sweep"]
    return 100.0 * grown["sweep"] / total if total > 0 else None
