"""Layer entry, the client's side of ``/query``: the 95th percentile of the
same latencies as ``query_p50_ms`` — all requests of the window, no chunking,
no trimming.  It was the end-to-end ``query_p95_ms`` until PR 33: on
``film-q4.traverse``, the one cell that listed it, the check's runs spread by
a fifth of its median, which no bound the contract allows can hold (PERF.md
section 2), so it is reported here, unbounded, in every cell."""

import stats


def read(obs):
    return 1e3 * stats.percentile(obs.latency_s, 95) if obs.latency_s else None
