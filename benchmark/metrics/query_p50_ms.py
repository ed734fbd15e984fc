"""End to end: the median latency of every request of the window that was
answered — the caller's clock round its POST; a request in flight at the
close is waited for and its wait counts."""

import stats


def read(obs):
    return 1e3 * stats.percentile(obs.latency_s, 50) if obs.latency_s else None
