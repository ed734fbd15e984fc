"""Layer entry (cache/result.py, probed and filled by sched/scheduler.py): mean
milliseconds a request of the window spent on the result cache — the probe
before admission and the put after execution, whose footprint walk grows with
the answer.  Stage ``result_cache`` of ``dgraph_ledger_stage_us_total`` over
``dgraph_num_queries_total``, window deltas."""

import stagecount


def read(obs):
    return stagecount.mean_ms(obs, "result_cache")
