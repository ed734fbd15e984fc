"""Layer scheduler (sched/scheduler.py singleflight): mean milliseconds a
request of the window that was dealt an identical request's result waited
for that twin — after its cohort's pop, or from its admission where it
attached to a twin already executing.  ``dgraph_sched_wait_us_total{twin}``
over ``dgraph_num_queries_total``, window deltas: one part of the ``queue``
stage in ``sched_queue_wait_ms``.  Nothing where the program lacks the
family."""

import stagecount


def read(obs):
    grown, n = obs.delta("dgraph_sched_wait_us_total"), stagecount.queries(obs)
    return grown["twin"] / 1e3 / n if "twin" in grown and n else None
