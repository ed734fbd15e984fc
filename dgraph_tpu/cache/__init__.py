"""Snapshot-versioned two-tier query cache (ISSUE 3).

Tier 1 (`HopCache`, cache/hop.py): hop-expansion memoization at the
DeviceExpander seam — repeat per-level expansions over an unchanged
store snapshot skip the device dispatch entirely.

Tier 2 (`ResultCache`, cache/result.py): whole-response memoization in
front of the cohort scheduler — repeat queries skip admission, cohort
wait and execution.

Both tiers share the `VersionedLFUCache` core (cache/core.py):
mutation-epoch invalidation via the store's monotonic ``version``,
incremental generation sweeping, and byte-budgeted LFU-with-aging
admission.  Gate: ``DGRAPH_TPU_CACHE`` (default on; ``0`` restores
the cache-less path byte-identically).
"""

from dgraph_tpu.cache.core import VersionedLFUCache, cache_enabled
from dgraph_tpu.cache.hop import HopCache, frontier_digest
from dgraph_tpu.cache.result import (
    Answer,
    ResultCache,
    cacheable,
    request_digest,
)

__all__ = [
    "Answer",
    "VersionedLFUCache",
    "HopCache",
    "ResultCache",
    "cache_enabled",
    "cacheable",
    "frontier_digest",
    "request_digest",
]
