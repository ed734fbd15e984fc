"""Layer planner (query/planner.py): of the edges the window's hops carried,
the share that rode a device route (``dgraph_ledger_hop_edges_total{route}``,
window delta).  Nothing where no hop carried an edge."""

import harness


def read(obs):
    on_device, total = harness.route_split(obs.delta("dgraph_ledger_hop_edges_total"))
    return 100.0 * on_device / total if total > 0 else None
