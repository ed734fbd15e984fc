"""Layer arenas (models/arena.py ``ArenaManager.refresh``, bracketed in
serve/server.py ``_run_locked``): mean milliseconds an answered request of the
window spent taking a write's journal into the cached arenas and their device
layouts — stage ``refresh`` of ``dgraph_ledger_stage_us_total`` over
``dgraph_num_queries_total``, window deltas, as every stage metric.  The writer
pays it, under the exclusive lock, so every reader waits for it."""

import stagecount


def read(obs):
    return stagecount.mean_ms(obs, "refresh")
