"""Layer entry (cache/result.py): result-cache hits over queries, both as
the program counts them over the window
(``dgraph_qcache_result_events_total{event="hit"}``,
``dgraph_num_queries_total``)."""


def read(obs):
    hits = obs.delta("dgraph_qcache_result_events_total").get("hit", 0.0)
    n = sum(obs.delta("dgraph_num_queries_total").values())
    return 100.0 * hits / n if n > 0 else None
