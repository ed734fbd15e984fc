"""Layer planner (query/planner.py ``path_route``): of the ``shortest`` blocks
the window executed — the searches between generated actors AND the
path-backs into written films — the share the device route answered
(``dgraph_path_searches_total{route}``, window delta).  A write grows the uid
space by about 10 uids and the listed arenas by about 32 rows and edges, so
the route's test (the uid space no wider than what the arenas hold) has to
keep every search on the device.  Nothing where no block was executed."""


import trafficgen


def read(obs):
    # ``path_device_share``'s own arithmetic, in a cell where it moves ``query_p50_ms``
    return trafficgen.load_module("metrics", "path_device_share").read(obs)
