"""Layer engine (obs/device.py): programs the backend compiled COLD inside the
window — ``dgraph_xla_compiles_total`` (every backend-compile bracket, reads
from the persistent cache included) less ``dgraph_xla_cache_reads_total``.
A count: 0 is the aim."""


def read(obs):
    reads = obs.delta("dgraph_xla_cache_reads_total")
    if not reads:
        return None
    return sum(obs.delta("dgraph_xla_compiles_total").values()) - sum(reads.values())
