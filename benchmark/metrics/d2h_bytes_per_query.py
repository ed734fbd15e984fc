"""Layer engine (the fetches of query/chain.py and query/engine.py): bytes an
answered request of the window copied back from the device — the packed result
buffers, capacity-sized whatever the answer holds
(``dgraph_ledger_bytes_total{dir="d2h"}``, window delta, over the answered)."""

import stagecount


def read(obs):
    return stagecount.bytes_per_query(obs, "d2h")
