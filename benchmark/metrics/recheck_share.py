"""Layer engine (query/functions.py ``_ineq_one``): of the candidate uids an
inequality over a lossy (or lang-spanning) index gave in the window, the
share compared against their values — ``dgraph_index_recheck_uids_total``:
``value`` over ``token`` + ``value``, window deltas.  A candidate whose token
row lies strictly inside the range is taken whole (``token``); only the
bound's own token row is compared.  Nothing where the program has no such
family (a parent commit) or rechecked nothing."""


def read(obs):
    by = obs.delta("dgraph_index_recheck_uids_total")
    total = sum(by.values())
    return 100.0 * by.get("value", 0.0) / total if total > 0 else None
