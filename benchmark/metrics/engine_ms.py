"""Layer engine (query/engine.py, query/chain.py, behind the scheduler): the
median ``server_latency.processing`` of the window's answers — parse to
result, queueing included.  The server's host clock."""

import statistics

import compare


def read(obs):
    v = [s for s in compare.server_seconds(obs.tails, "processing") if s is not None]
    return 1e3 * statistics.median(v) if v else None
