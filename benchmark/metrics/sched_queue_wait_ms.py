"""Layer scheduler (sched/scheduler.py, sched/cohort.py): mean milliseconds a
request of the window waited — admission to the start of execution (the
cohort's flush deadline, the engine lock, a worker) plus the hop merger's
waits.  Stages ``queue`` + ``merge_wait`` of ``dgraph_ledger_stage_us_total``
over ``dgraph_num_queries_total``, window deltas."""

import stagecount


def read(obs):
    return stagecount.mean_ms(obs, "queue", "merge_wait")
