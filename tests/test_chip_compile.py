"""The served path's programs, compiled for the chip they are deployed on.

The TPU's compiler is installed in the sandbox and compiles for a chip that
is DESCRIBED, not attached (guide: on-chip-measurement, section 2).  These
cases hold the kernels and jitted steps of the default served path to it at
the widths the film-21M deployment gives them, so a construct the chip's
compiler refuses (interpret mode accepts nearly anything) fails here at no
chip time.  A compile that passes is not a chip run and says nothing about
results or speed: chip_smoke.py is the run.

This is the ONLY file that describes the chip.  The topology is described
inside a module-scoped fixture — never at import, in a ``skipif``, in
``parametrize`` arguments or in conftest.py: only one process at a time may
load the TPU's library, every xdist worker imports every test file, and only
the worker this file is dealt to may load it.  Compiles run in the test's own
process, with JAX's persistent compilation cache off around them (an
executable compiled for a described chip cannot be read back without one).
"""

import os

import numpy as np
import pytest

# the compiler's log directory, not the chip's: off, or it writes under /tmp
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

# film-21M widths (dgraph_tpu/utils/filmgen.py at 21M quads): the largest
# predicates (performance.actor, starring) hold ~7.0M edges; frontier
# buckets reach 2^16 rows, output capacities 2^22
EDGES = 7_000_000
ROWS = 7_000_000
FRONTIER = 1 << 16
CAP = 1 << 22


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — whatever keeps the chip from being described
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    return Mesh(np.array(topo.devices).reshape(1, 4), ("data", "model"))


@pytest.fixture(scope="module")
def no_compile_cache():
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _shape(sharding):
    def s(*shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    return s


def _resident_shapes(s, edges=EDGES, rows=ROWS):
    from dgraph_tpu import ops
    from dgraph_tpu.models.arena import _resident_cap

    return s(ops.bucket(rows) + 1), s(_resident_cap(edges))


def test_gather_pallas_compiles_at_film_widths(one_chip, no_compile_cache):
    """The resident tier's walk primitive (route:resident on a TPU) goes
    through Mosaic at deployed widths: the kernel is in the executable, and
    the pinned CSR is its only large argument."""
    from dgraph_tpu.ops.pallas_gather import MAX_ROWS, gather_pallas_packed

    s = _shape(one_chip)
    off, dst = _resident_shapes(s)
    assert FRONTIER <= MAX_ROWS
    c = gather_pallas_packed.lower(off, dst, s(FRONTIER), cap=CAP).compile()
    assert "tpu_custom_call" in c.as_text()
    m = c.memory_analysis()
    assert m.argument_size_in_bytes < 4 * (off.shape[0] + dst.shape[0] + FRONTIER) + 4096
    assert m.output_size_in_bytes == 2 * CAP * 4


def test_gather_wider_than_smem_takes_xla_over_the_same_buffers(one_chip, no_compile_cache):
    """A frontier whose row tables outgrow the 1 MiB SMEM compiles too —
    as the XLA program over the resident buffers, not the kernel."""
    from dgraph_tpu.ops.pallas_gather import MAX_ROWS, gather_pallas_packed

    s = _shape(one_chip)
    off, dst = _resident_shapes(s)
    c = gather_pallas_packed.lower(off, dst, s(2 * MAX_ROWS), cap=CAP).compile()
    assert "tpu_custom_call" not in c.as_text()


def test_packed_expand_csr_compiles(one_chip, no_compile_cache):
    from dgraph_tpu import ops
    from dgraph_tpu.query import engine as qe

    s = _shape(one_chip)
    c = qe._packed_expand_csr.lower(
        s(ops.bucket(ROWS) + 1), s(ops.bucket(EDGES)), s(FRONTIER), cap=CAP
    ).compile()
    assert c.memory_analysis().output_size_in_bytes == 2 * CAP * 4


def test_expand_inline_seg_compiles(one_chip, no_compile_cache):
    from dgraph_tpu import ops
    from dgraph_tpu.ops import sets

    s = _shape(one_chip)
    # the fused chain's posting gather, the arena at film width; frontier
    # and overflow capacity cut 16x — at 2^16 rows / 2^19 chunks the same
    # program takes the compiler 15 s
    sets.expand_inline_seg.lower(
        s(ops.bucket(ROWS), 8), s(1 << 20, 8), s(FRONTIER // 16), capc=1 << 15
    ).compile()


def test_sort_unique_compiles(one_chip, no_compile_cache):
    from dgraph_tpu.ops import sets

    s = _shape(one_chip)
    sets.sort_unique.lower(s(CAP)).compile()


def test_resident_merge_compiles(one_chip, no_compile_cache):
    """The on-device delta merge of a resident arena.  Its two stable
    multi-key sorts take the chip's compiler 50 s at 2^14 edges and 93 s
    at film-21M width (8.3M lanes) in the sandbox (PERF.md, compile
    survey) — too long for tier-1 — so this case is cut to a 2^10-edge
    arena: the same program and operations, a far narrower sort."""
    from dgraph_tpu.models import arena as marena

    s = _shape(one_chip)
    off, dst = _resident_shapes(s, edges=1 << 10, rows=1 << 10)
    marena._resident_merge.lower(off, dst, s(8), s(8), s(8), s(8)).compile()


def test_mesh_multi_hop_compiles_on_four_chips(mesh4, no_compile_cache):
    """The mesh plane's fused multi-hop on the described 1x4 mesh: the
    frontier exchange is in the program as collectives, and each chip is
    handed a quarter of the row-sharded arena — not all of it."""
    from dgraph_tpu import ops
    from dgraph_tpu.mesh.programs import mesh_multi_hop_step

    shard = _shape(NamedSharding(mesh4, P("model", None)))
    repl = _shape(NamedSharding(mesh4, P()))
    sp = ops.bucket(-(-ROWS // 4))
    ep = ops.bucket(-(-EDGES // 4))
    cap = 1 << 16  # at 2^20 the exchange's re-sort takes the compiler 18 s
    c = mesh_multi_hop_step(mesh4, cap, 2).lower(
        shard(4, sp), shard(4, sp + 1), shard(4, ep), repl(cap)
    ).compile()
    text = c.as_text()
    assert "all-gather" in text or "all-reduce" in text
    arena_bytes = 4 * 4 * (sp + sp + 1 + ep)
    per_chip = c.memory_analysis().argument_size_in_bytes
    # a quarter of the arena plus the replicated frontier, give or take padding
    assert abs(per_chip - (arena_bytes // 4 + 4 * cap)) < 1 << 16, (per_chip, arena_bytes)
