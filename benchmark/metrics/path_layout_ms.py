"""Layer arenas (models/arena.py ``ArenaManager._path_layouts_take``, inside
the writer's ``refresh`` bracket and taken out of it): mean milliseconds an
answered request of the window spent bringing cached ``PathLayout``s up to a
write — the delta's three scatters, or a rebuild — stage ``path_layout`` of
``dgraph_ledger_stage_us_total`` over ``dgraph_num_queries_total``, window
deltas, as every stage metric.  Nothing where the program lacks the stage."""

import stagecount


def read(obs):
    return stagecount.mean_ms(obs, "path_layout")
