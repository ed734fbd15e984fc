"""Layer engine (query/engine.py ``_exec_child_inner``): mean milliseconds a
request of the window spent in a level's host work that is no expansion —
value leaves, counts, the level's uniques, filter, facets and ordering.  Stage
``assemble`` of ``dgraph_ledger_stage_us_total`` over
``dgraph_num_queries_total``, window deltas."""

import stagecount


def read(obs):
    return stagecount.mean_ms(obs, "assemble")
