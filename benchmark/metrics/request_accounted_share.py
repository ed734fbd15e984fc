"""Layer engine (obs/ledger.py): the share of the window's request time that
the stage catalogue accounts for — every stage inside ``run_query``'s clock
(all but ``http_write``) over ``dgraph_query_latency_seconds_sum``, window
deltas.  What is missing is a wait no stage brackets yet."""

import stagecount


def read(obs):
    us = stagecount.stage_us(obs, *stagecount.IN_REQUEST)
    wall_s = sum(obs.delta("dgraph_query_latency_seconds_sum").values())
    return None if us is None or wall_s <= 0 else 100.0 * us / 1e6 / wall_s
