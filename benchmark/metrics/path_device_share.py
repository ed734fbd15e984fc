"""Layer planner (query/planner.py ``path_route``): of the ``shortest`` blocks
the window executed, the share the device route answered
(``dgraph_path_searches_total{route}``, window delta).  ``device_edge_share``
cannot say it: ``harness.DEVICE_ROUTES`` predates the ``path`` route and reads
its edges as host.  Nothing where no block was executed."""


def read(obs):
    by = obs.delta("dgraph_path_searches_total")
    total = sum(by.values())
    return 100.0 * by.get("device", 0.0) / total if total > 0 else None
