"""Layer engine / arenas: XLA compilations inside the window
(``dgraph_xla_compiles_total``, window delta) — a program read back from the
persistent cache counts too.  A count: 0 is the aim."""


def read(obs):
    return sum(obs.delta("dgraph_xla_compiles_total").values())
