"""Layer engine (``np.asarray`` of a program's result in query/chain.py and
query/engine.py): mean milliseconds a request of the window spent waiting for
the device and copying the capacity-sized result buffer back.  Stage ``fetch``
of ``dgraph_ledger_stage_us_total`` over ``dgraph_num_queries_total``, window
deltas."""

import stagecount


def read(obs):
    return stagecount.mean_ms(obs, "fetch")
