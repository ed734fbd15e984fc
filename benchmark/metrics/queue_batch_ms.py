"""Layer scheduler (sched/scheduler.py ``_admit``, ``_came_due``): mean
milliseconds a request of the window spent from its admission to the moment
its cohort came due — filled, its flush deadline, or an idle beat.
``dgraph_sched_wait_us_total{batch}`` over ``dgraph_num_queries_total``,
window deltas: one part of the ``queue`` stage in ``sched_queue_wait_ms``.
Nothing where the program lacks the family."""

import stagecount


def read(obs):
    grown, n = obs.delta("dgraph_sched_wait_us_total"), stagecount.queries(obs)
    return grown["batch"] / 1e3 / n if "batch" in grown and n else None
