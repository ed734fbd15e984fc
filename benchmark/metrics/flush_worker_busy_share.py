"""Layer scheduler (sched/scheduler.py ``_worker_loop``): the share of the
window the flush workers spent in flushes, each from its pop to its end —
``dgraph_sched_worker_busy_us_total``'s growth (a flush running at a scrape
counts up to it) over the pool's size (the gauge ``dgraph_sched_workers``)
times the time between the two scrapes (``dgraph_uptime_seconds``' growth:
the client's window ends before the last answers are in, so a share over it
reads above 100 at a pool that never rests).  Nothing where the program
lacks a family."""


def read(obs):
    busy = obs.delta("dgraph_sched_worker_busy_us_total")
    workers = obs.counters_after.get("dgraph_sched_workers", {}).get("")
    span = sum(obs.delta("dgraph_uptime_seconds").values())
    if not busy or not workers or span <= 0:
        return None
    return 100.0 * sum(busy.values()) / 1e6 / (workers * span)
