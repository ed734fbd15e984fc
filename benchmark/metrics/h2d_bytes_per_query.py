"""Layer arenas (models/arena.py; the puts of query/chain.py and
query/engine.py): bytes an answered request of the window put on the device —
root vectors, keep sets, row vectors, layouts built on first use
(``dgraph_ledger_bytes_total{dir="h2d"}``, window delta, over the answered)."""

import stagecount


def read(obs):
    return stagecount.bytes_per_query(obs, "h2d")
