"""Layer entry (serve/server.py handler): mean milliseconds a request of the
window spent between ``run_query``'s return and the end of the socket write —
``json.dumps`` of the answer and the write: the inside of
``entry_overhead_ms``.  Stage ``http_write`` of
``dgraph_ledger_stage_us_total`` over ``dgraph_num_queries_total``, window
deltas."""

import stagecount


def read(obs):
    return stagecount.mean_ms(obs, "http_write")
