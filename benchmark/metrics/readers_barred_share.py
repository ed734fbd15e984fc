"""Layer entry (serve/server.py's engine lock, utils/rwlock.py ``barred_s``):
the share of the window in which a writer held or awaited the exclusive
side, so a reader arriving had to wait — the lock's busy time beside the
readers' ``queue_writer_ms``.  ``dgraph_engine_lock_barred_us_total``'s growth
(an interval open at a scrape counts up to it) over the time between the two
scrapes (``dgraph_uptime_seconds``' growth).  Nothing where the program lacks
a family."""


def read(obs):
    barred = obs.delta("dgraph_engine_lock_barred_us_total")
    span = sum(obs.delta("dgraph_uptime_seconds").values())
    if not barred or span <= 0:
        return None
    return 100.0 * sum(barred.values()) / 1e6 / span
