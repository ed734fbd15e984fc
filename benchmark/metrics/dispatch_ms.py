"""Layer engine (query/chain.py ``_run_fused``; query/engine.py per-level
programs): mean milliseconds a request of the window spent calling device
programs until the asynchronous result was in hand — trace-cache lookup,
argument handling, enqueue.  Stage ``dispatch`` of
``dgraph_ledger_stage_us_total`` over ``dgraph_num_queries_total``, window
deltas."""

import stagecount


def read(obs):
    return stagecount.mean_ms(obs, "dispatch")
