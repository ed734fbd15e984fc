"""End to end: the 95th percentile of the same latencies as
``query_p50_ms`` — all requests of the window, no chunking, no trimming."""

import stats


def read(obs):
    return 1e3 * stats.percentile(obs.latency_s, 95) if obs.latency_s else None
