"""Layer planner (gql parser; query/engine.py block roots; query/chain.py up to
the dispatch): mean milliseconds a request of the window spent parsing,
building the subgraph, resolving each block's root function / filter / order,
and planning the fused chain (estimates, keep sets, caps).  Stages ``parse`` +
``plan`` of ``dgraph_ledger_stage_us_total`` over ``dgraph_num_queries_total``,
window deltas."""

import stagecount


def read(obs):
    return stagecount.mean_ms(obs, "parse", "plan")
