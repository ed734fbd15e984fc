"""End to end: the edges that every correct answer completed inside the
window stands for — counted by the benchmark's reference walk for that
request (``expect["edges"]``), never by the program's ledger, so no later PR
can move the count; an answer from the result cache counts, the user got it
— over ALL the window's seconds."""

import stats


def read(obs):
    if not obs.latency_s:
        return None
    edges = sum(e["edges"] for r, ok, e in zip(obs.records, obs.ok, obs.expect)
                if ok and r[4] <= obs.t_close)
    return stats.rate(float(edges), obs.window_s)
