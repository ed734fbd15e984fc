"""Compiled mesh programs: multi-hop traversal with IN-PROGRAM exchange.

The PR 13/16 serving path dispatches the mesh ONCE PER HOP
(parallel/mesh.py::sharded_expand_segments): each level pays a host
round trip to slice the packed buffer, rebuild the frontier, and
dispatch again — exactly the per-level staging the single-device chain
scan (ops/batch.py::multi_hop) already deleted.  The program here is
the mesh twin of that scan: ``lax.scan`` over hops INSIDE one
``shard_map``, so the cross-chip frontier exchange (``all_gather`` of
each shard's bucketed expansion, ``psum`` of the edge counts) happens
between scan iterations on the ICI, never through the host.  The
frontier carry is donated — XLA threads one [cap] buffer across every
level instead of allocating per hop.

Byte-parity contract: each hop's merged frontier is
``sort_unique(all_gather(per-shard expand_csr))[:cap]`` — the same
sorted-unique-padded set the unsharded ``multi_hop`` driver produces
(its per-hop ``sort_unique(expand_ascending(...))``), because the
shards partition the rows and the re-sort erases gather order.
tests/test_mesh_serving.py pins chain results sharded == unsharded.

Memoized per (mesh, cap, n_hops) like every step in parallel/mesh.py:
jax.jit caches on function identity, and caps ride ops.bucket so the
program family stays bounded (analysis/budgets.json entries cap the
compile count in CI).

Elastic fault domain (PR 20): a ``Mesh`` hashes by its device set +
axis names, so programs built here key cleanly per mesh EPOCH — an
eviction re-shards onto a sub-mesh and compiles its own bounded
family, and the staged rejoin's flip back to the memoized boot mesh
hash-hits the original cache (zero recompiles; mesh/fault.py warms
the candidate mesh's shapes BEFORE the cutover either way).
"""

from __future__ import annotations

from functools import lru_cache

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

from dgraph_tpu import ops


@lru_cache(maxsize=64)
def mesh_multi_hop_step(mesh: Mesh, cap: int, n_hops: int):
    """Build the jitted fused multi-hop mesh program.

    Signature: ``fn(src, offsets, dst, frontier)`` where src/offsets/
    dst are a ShardedArena's [n_model, ...] arrays and frontier is the
    replicated [cap] sorted-unique-padded seed (int32 on device).
    Returns ``(frontiers int32[n_hops, cap], totals int32[n_hops],
    final int32[cap])`` — per-level post-dedup frontiers and global
    edge counts, plus the final frontier (the output the donated seed
    buffer aliases).

    Every hop shares one capacity (lax.scan needs a uniform carry
    shape), so callers plan ``cap`` from the worst level, exactly like
    the unsharded scan driver (query/chain.py::_try_chain_scan)."""

    def local(src, offsets, dst, frontier):
        src, offsets, dst = src[0], offsets[0], dst[0]

        def body(f, _):
            # local expansion of the rows this shard owns (rows_of
            # resolves a uid only on its owner — off-shard uids expand
            # to nothing here and to their targets on the owner chip)
            rows = ops.rows_of(src, f)
            out, _seg, t = ops.expand_csr(offsets, dst, rows, cap)
            # the cross-chip frontier exchange, INSIDE the program:
            # every shard contributes its bucketed [cap] expansion over
            # the ICI, the count reduction rides psum, and the re-sort
            # erases gather order so placement can't leak into results
            gathered = jax.lax.all_gather(out, "model")  # [n_model, cap]
            nxt = ops.sort_unique(gathered.reshape(-1))[:cap]
            total = jax.lax.psum(t, "model")
            return nxt, (nxt, total)

        final, (fs, totals) = jax.lax.scan(
            body, frontier, None, length=n_hops
        )
        return fs, totals, final

    fn = shard_map(
        local,
        mesh=mesh,
        in_specs=(P("model", None), P("model", None), P("model", None), P()),
        out_specs=(P(), P(), P()),
        check_vma=False,
    )
    # the [cap] final-frontier output exists exactly so the donated
    # seed buffer has something to alias — the scan's internal carry
    # then reuses it across every level (the batch.multi_hop donation
    # discipline, contract-checked in analysis/programs.py)
    return jax.jit(fn, donate_argnums=(3,))


def exchange_bytes_per_hop(mesh: Mesh, cap: int) -> int:
    """The cross-chip payload one hop of the fused program moves: each
    of the n_model chips all_gathers the other shards' [cap] int32
    expansions ((n-1)/n of the gathered buffer crosses the ICI) plus
    the psum'd count lane.  An ESTIMATE for ledger attribution — the
    collective's wire format is XLA's business — but a monotone,
    shape-accurate one, which is what capacity dashboards need."""
    n = int(mesh.shape["model"])
    per_chip = (n - 1) * cap * 4 + (n - 1) * 4
    return n * per_chip
