"""Layer entry (serve/server.py, query/engine.py, models/wal.py): mean
milliseconds an acknowledged write of the window spent in the write path's
own stages — ``write_lock`` (the wait for the exclusive side), ``write_apply``
(quads to edges, blank nodes to uids, the store and its journals) and
``write_wal`` (the appends, the flush, the group-commit barrier) of
``dgraph_ledger_stage_us_total`` over ``dgraph_writes_total{result="ok"}``,
window deltas.  The refresh of the arenas is ``refresh_ms``.  Nothing where
the program lacks a stage or the counter, or acknowledged no write."""

import stagecount


def read(obs):
    us = stagecount.stage_us(obs, "write_lock", "write_apply", "write_wal")
    ok = obs.delta("dgraph_writes_total").get("ok")
    return None if us is None or not ok else us / 1e3 / ok
