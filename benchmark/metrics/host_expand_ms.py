"""Layer engine (query/engine.py ``_host_fallback``): mean milliseconds a
request of the window spent expanding levels on the host's CSR mirror — the
planner's route for small frontiers.  Stage ``host_expand`` of
``dgraph_ledger_stage_us_total`` over ``dgraph_num_queries_total``, window
deltas."""

import stagecount


def read(obs):
    return stagecount.mean_ms(obs, "host_expand")
