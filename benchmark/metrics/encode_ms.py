"""Layer encoder (query/outputnode.py): mean milliseconds a request of the
window spent in ``encode_block`` / ``encode_path`` building result objects.
Stage ``encode`` of ``dgraph_ledger_stage_us_total`` over
``dgraph_num_queries_total``, window deltas."""

import stagecount


def read(obs):
    return stagecount.mean_ms(obs, "encode")
