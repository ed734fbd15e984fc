"""What the stage metrics share: window deltas of the program's own account
of a request's time (``dgraph_ledger_stage_us_total{stage}``, microseconds:
obs/ledger.py STAGES, docs/deploy.md "Stage catalogue") over the requests
the program counted in the window (``dgraph_num_queries_total``).

Means, not medians, so that the stages ADD UP: their sum is the mean request's
accounted time, to be held against the mean ``server_latency.total``.  A
program without a stage's label (a parent commit) gives ``None``, never 0.
"""

from __future__ import annotations

STAGE_US = "dgraph_ledger_stage_us_total"
# every stage run_query's own clock covers (http_write runs after it stopped)
IN_REQUEST = ("parse", "result_cache", "queue", "merge_wait", "plan", "host_expand",
              "h2d", "dispatch", "fetch", "convert", "assemble", "encode", "handoff")


def queries(obs):
    """Requests the program counted in the window; None where none."""
    n = sum(obs.delta("dgraph_num_queries_total").values())
    return n if n > 0 else None


def stage_us(obs, *stages):
    """The window's microseconds in ``stages`` together; None where the
    program does not expose one of them."""
    grown = obs.delta(STAGE_US)
    if any(s not in grown for s in stages):
        return None
    return sum(grown[s] for s in stages)


def mean_ms(obs, *stages):
    """Mean milliseconds a request of the window spent in ``stages``."""
    us, n = stage_us(obs, *stages), queries(obs)
    return None if us is None or n is None else us / 1e3 / n


def bytes_per_query(obs, direction):
    """``dgraph_ledger_bytes_total{dir}`` of the window over its answered
    requests."""
    grown = obs.delta("dgraph_ledger_bytes_total")
    if direction not in grown or not obs.answered:
        return None
    return grown[direction] / len(obs.answered)
