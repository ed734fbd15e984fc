"""Layer engine (ops/kbfs.py via query/shortest.py): arrivals a device k-path
search took (``dgraph_kpath_arrivals_total``, both kinds) per device search
(``dgraph_kpath_searches_total{device}``), window deltas: the search's size
in the unit its tables and lists are sized by.  Nothing where the program has
no such family or answered no search on the device."""


def read(obs):
    arrivals = obs.delta("dgraph_kpath_arrivals_total")
    n = obs.delta("dgraph_kpath_searches_total").get("device", 0.0)
    return sum(arrivals.values()) / n if arrivals and n > 0 else None
