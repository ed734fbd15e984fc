"""Layer scheduler (sched/cohort.py ``SchedRequest.wait``): mean milliseconds a
request of the window spent between its verdict on the flush worker and its
own handler thread running again — with concurrent callers, the wait for the
interpreter lock.  Stage ``handoff`` of ``dgraph_ledger_stage_us_total`` over
``dgraph_num_queries_total``, window deltas."""

import stagecount


def read(obs):
    return stagecount.mean_ms(obs, "handoff")
