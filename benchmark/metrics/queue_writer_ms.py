"""Layer scheduler (sched/cohort.py ``SchedRequest._book_waits``; the engine
lock's barred clock, utils/rwlock.py): mean milliseconds a request of the
window waited, after its cohort came due and until the flush held the
lock's shared side, while a writer held or awaited the exclusive side —
the readers a write holds up.  ``dgraph_sched_wait_us_total{writer}`` over
``dgraph_num_queries_total``, window deltas: one part of the ``queue`` stage
in ``sched_queue_wait_ms``.  Nothing where the program lacks the family."""

import stagecount


def read(obs):
    grown, n = obs.delta("dgraph_sched_wait_us_total"), stagecount.queries(obs)
    return grown["writer"] / 1e3 / n if "writer" in grown and n else None
