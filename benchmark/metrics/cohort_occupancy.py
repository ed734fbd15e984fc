"""Layer scheduler (sched/scheduler.py): mean requests a flushed cohort held
(``dgraph_sched_cohort_occupancy`` histogram, sum over count, window deltas)."""


def read(obs):
    total = sum(obs.delta("dgraph_sched_cohort_occupancy_sum").values())
    flushes = sum(obs.delta("dgraph_sched_cohort_occupancy_count").values())
    return total / flushes if flushes > 0 else None
