"""Layer arenas (models/arena.py; the puts of query/chain.py and
query/engine.py): mean milliseconds a request of the window spent staging
host -> device — root vectors, keep sets, row vectors, and an inline layout or
LUT built and put on first use.  Stage ``h2d`` of
``dgraph_ledger_stage_us_total`` over ``dgraph_num_queries_total``, window
deltas."""

import stagecount


def read(obs):
    return stagecount.mean_ms(obs, "h2d")
