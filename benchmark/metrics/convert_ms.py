"""Layer engine (query/chain.py, query/engine.py): mean milliseconds a request
of the window spent in host numpy turning the packed buffer into each level's
uid matrix.  Stage ``convert`` of ``dgraph_ledger_stage_us_total`` over
``dgraph_num_queries_total``, window deltas."""

import stagecount


def read(obs):
    return stagecount.mean_ms(obs, "convert")
