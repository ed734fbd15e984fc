"""Layer entry, the client's side of ``/query``: ``edges_per_s``'s own
arithmetic — the reference's edges of every correct answer completed inside
the window, over ALL the window's seconds — in a cell where it is no
end-to-end metric: on ``film-q4.traverse`` the check's runs spread by a sixth
of its median (PERF.md section 2), so there it is reported here, unbounded."""

import trafficgen


def read(obs):
    return trafficgen.load_module("metrics", "edges_per_s").read(obs)
