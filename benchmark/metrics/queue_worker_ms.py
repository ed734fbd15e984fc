"""Layer scheduler (sched/scheduler.py ``_next_cohort``): mean milliseconds a
request of the window waited, after its cohort came due, for a free flush
worker to pop it — less the part a writer barred the engine lock meanwhile,
which is ``queue_writer_ms``'s.  ``dgraph_sched_wait_us_total{worker}`` over
``dgraph_num_queries_total``, window deltas: one part of the ``queue`` stage
in ``sched_queue_wait_ms``.  Nothing where the program lacks the family."""

import stagecount


def read(obs):
    grown, n = obs.delta("dgraph_sched_wait_us_total"), stagecount.queries(obs)
    return grown["worker"] / 1e3 / n if "worker" in grown and n else None
