"""Level-synchronous BFS over SEVERAL predicates at once: the device half
of ``shortest(from:, to:)`` at unit cost (query/shortest.py).

The listed predicates' arenas are merged into ONE CSR over the uid space
(``models/arena.py`` ``PathLayout``: row = uid, a uid's edges under the
first listed predicate first).  The frontier, the level of every reached
uid and its parent stay on the device from the first level to the one
that holds the target; the host gets back the distance, the path
(parents walked back on the device) and the sums the ledger books, never
a frontier.

    L0 = {from};  L(i+1) = every uid reached from Li under ANY listed
    predicate and not reached before;  parent(v) = the LEAST uid of Li
    with an edge to v.  The search ends after the first level that holds
    the target, or with an empty level.

State over the uid space: ``lvl`` (-1 = not reached) and ``par``.  A
level is done one of two ways, chosen per level from the frontier's size
and the layout's size:

- **gather**: the frontier as a LIST, ``chunk`` slots of its edges at a
  time: the slot -> edge map telescoped from one scatter a row (as
  ``batch.expand_ascending``), one ``dst`` gather a slot, each target's
  level read, its parent scatter-min'ed, its level set, the new uids
  sorted into the next list with their degrees.  About nine random
  accesses a slot and none over the uid space: the cost follows the
  frontier's edges.
- **sweep**: every edge of the layout reads its source's level and
  scatter-mins its source into a candidate table over the uid space:
  two random accesses an edge of the LAYOUT, whatever the frontier
  holds; no list, no sort.

On the chip a random access costs about the same gathered or scattered
(9-10 ns an element on a v5e; PERF.md, PR 28) and a streaming pass is
nearly free beside it.  So a level goes to the sweep when the frontier's
out-degree sum times ``_ACCESS_PER_SLOT`` passes the layout's edges times
``_ACCESS_PER_EDGE`` — or when the frontier outgrew its list.  Both
numbers are on the device when the level starts: a level's size and
out-degree sum are counted as its uids are found.
"""

from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp

from dgraph_tpu.ops.sets import SENT, bucket, sort_unique

# Random accesses the two ways of doing a level make, per unit of work,
# COUNTED from the code below, not fitted to a run: a gather's slot reads
# dst and the target's level, mins the parent, sets the level, reads the
# new uid's two offsets, and shares its row's three (two offsets, the
# previous row's source) and two scatters with one other slot (a chunk
# holds half as many rows as slots); a sweep's edge reads its source's
# level and mins the candidate.  They are this module's priors in the sense
# of utils/calibrate.py's: the calibration measures no rate for either way
# (PERF.md Open questions 15e), and a change to either level's code has to
# recount them (tests/test_path_search.py holds the list to the count)
_ACCESS_PER_SLOT = 9
_ACCESS_PER_EDGE = 2
PATH_CAP = 64        # the path comes back this many uids a walk
HEAD = 5             # result header: found, levels, rows, edges, sweeps


def _pow2_floor(n: int) -> int:
    return 1 << (max(1, int(n)).bit_length() - 1)


def capacities(n_edge_slots: int, max_degree: int) -> Tuple[int, int]:
    """(list capacity, chunk) for a layout of ``n_edge_slots`` edge slots
    whose widest uid has ``max_degree`` edges.

    The list holds the largest frontier a gather can still win with: past
    ``_ACCESS_PER_EDGE * slots / _ACCESS_PER_SLOT`` edges the sweep is
    cheaper.  A chunk is a sixteenth of that — a level then overshoots its
    edges by a few chunks at most — and never under the widest uid, which
    has to fit one chunk whole."""
    top = max(8, _pow2_floor(_ACCESS_PER_EDGE * max(1, n_edge_slots) // _ACCESS_PER_SLOT))
    chunk = max(8, top >> 4, bucket(max(1, max_degree)))
    return max(top, chunk), chunk


def small_chunk(chunk: int) -> int:
    """The chunk of a level that fits it whole: a sixteenth of ``chunk``."""
    return max(8, chunk >> 4)


def _gather_chunk(off, dst, lvl, par, uids, n_valid, cur, chunk):
    """Expand the first ``n_valid`` of ``uids`` (int32[chunk // 2]), whose
    edges fit ``chunk`` slots.  Returns (lvl, par, the new uids
    sorted-unique SENT-padded [chunk], how many, their degrees)."""
    C, R = chunk, uids.shape[0]
    ub = lvl.shape[0]
    i = jnp.arange(C, dtype=jnp.int32)
    # ascending within the chunk: the telescoping below needs it
    u = jnp.sort(jnp.where(jnp.arange(R, dtype=jnp.int32) < n_valid, uids, SENT))
    valid = u != SENT
    uc = jnp.where(valid, u, 0)
    o0 = off[uc]
    deg = jnp.where(valid, off[uc + 1] - o0, 0)
    cum = jnp.cumsum(deg)
    productive = deg > 0
    slot = jnp.where(productive, cum - deg, C)     # C = dropped
    # slot -> edge: each productive row's first slot holds the jump from
    # the previous productive row's end; a prefix sum plus the slot's own
    # index is the edge (rows ascend, so do their starts)
    end = jnp.where(productive, o0 + deg, 0)
    prev_end = jnp.concatenate([jnp.zeros((1,), end.dtype), jax.lax.cummax(end)[:-1]])
    jump = jnp.zeros((C,), jnp.int32).at[slot].set(
        jnp.where(productive, o0 - prev_end, 0), mode="drop")
    edge = jnp.cumsum(jump) + i
    # slot -> source uid, telescoped the same way
    idx = jnp.where(productive, jnp.arange(R, dtype=jnp.int32), -1)
    prev = jnp.concatenate([jnp.full((1,), -1, jnp.int32), jax.lax.cummax(idx)[:-1]])
    prev_src = jnp.where(prev >= 0, uc[jnp.maximum(prev, 0)], 0)
    step = jnp.zeros((C,), jnp.int32).at[slot].set(
        jnp.where(productive, uc - prev_src, 0), mode="drop")
    src = jnp.cumsum(step)
    live = i < cum[-1]
    out = jnp.where(live, dst[jnp.clip(edge, 0, dst.shape[0] - 1)], SENT)
    at = lvl[jnp.clip(out, 0, ub - 1)]
    fresh = live & (at < 0)
    # a uid another chunk of this level reached first still takes a lesser parent
    again = live & (at == cur + 1)
    par = par.at[jnp.where(fresh | again, out, ub)].min(src, mode="drop")
    lvl = lvl.at[jnp.where(fresh, out, ub)].set(cur + 1, mode="drop")
    new = sort_unique(jnp.where(fresh, out, SENT))
    is_new = new != SENT
    nc = jnp.where(is_new, new, 0)
    new_deg = jnp.where(is_new, off[nc + 1] - off[nc], 0)
    return lvl, par, new, jnp.sum(is_new).astype(jnp.int32), new_deg


def _gather_level(off, dst, chunk, cap, st):
    """One level from the frontier list (capacity ``cap``), ``chunk`` slots
    of its edges at a time."""
    fl, cd, f, cur = st["fl"], st["cd"], st["f"], st["cur"]
    rows = chunk // 2
    j = jnp.arange(rows, dtype=jnp.int32)

    def cond(c):
        return c[0] < f

    def body(c):
        a, lvl, par, nfl, ncd, n_next, m_next = c
        # the chunk [a, a + nb): as many uids as fit ``chunk`` slots
        below = jnp.where(a > 0, cd[jnp.maximum(a - 1, 0)], 0)
        upto = jax.lax.dynamic_slice(cd, (a,), (rows,))
        nb = jnp.sum(((upto - below) <= chunk) & (a + j < f)).astype(jnp.int32)
        nb = jnp.maximum(nb, 1)
        uids = jax.lax.dynamic_slice(fl, (a,), (rows,))
        lvl, par, new, n, new_deg = _gather_chunk(off, dst, lvl, par, uids, nb, cur, chunk)
        # append at n_next; past ``cap`` the list is lost (n_next says so)
        at = jnp.minimum(n_next, cap)
        nfl = jax.lax.dynamic_update_slice(nfl, new, (at,))
        ncd = jax.lax.dynamic_update_slice(ncd, m_next + jnp.cumsum(new_deg), (at,))
        return (a + nb, lvl, par, nfl, ncd, n_next + n,
                m_next + jnp.sum(new_deg).astype(jnp.int32))

    zero = jnp.int32(0)
    _, lvl, par, nfl, ncd, n_next, m_next = jax.lax.while_loop(
        cond, body, (zero, st["lvl"], st["par"], st["nfl"], st["ncd"], zero, zero))
    # the lists swap: the next level reads what this one wrote
    return dict(st, lvl=lvl, par=par, fl=nfl, cd=ncd, nfl=fl, ncd=cd,
                f=n_next, m=m_next, listed=n_next <= cap)


def _sweep_level(off, dst, esrc, chunk, st):
    """One level from the level table: every edge of the layout."""
    lvl, par, cur = st["lvl"], st["par"], st["cur"]
    ub = lvl.shape[0]
    active = lvl[esrc] == cur
    cand = jnp.full((ub,), SENT, jnp.int32).at[jnp.where(active, dst, ub)].min(
        esrc, mode="drop")
    new = (cand != SENT) & (lvl < 0)
    par = jnp.where(new, cand, par)
    lvl = jnp.where(new, cur + 1, lvl)
    deg = off[1:] - off[:-1]
    f = jnp.sum(new).astype(jnp.int32)
    m = jnp.sum(jnp.where(new, deg, 0)).astype(jnp.int32)
    fl, cd = st["fl"], st["cd"]
    cap = fl.shape[0] - chunk

    def relist(_):
        # the frontier is back under the list's capacity: one sort of the
        # uid space puts it there again
        uids = jnp.sort(jnp.where(new, jnp.arange(ub, dtype=jnp.int32), SENT))
        uids = jnp.concatenate([uids, jnp.full((max(0, cap - ub),), SENT, jnp.int32)])[:cap]
        ok = uids != SENT
        uc = jnp.where(ok, uids, 0)
        d = jnp.where(ok, off[uc + 1] - off[uc], 0)
        return (jnp.concatenate([uids, jnp.full((chunk,), SENT, jnp.int32)]),
                jnp.concatenate([jnp.cumsum(d), jnp.zeros((chunk,), jnp.int32)]))

    listed = (f <= cap) & (m <= cap)
    fl, cd = jax.lax.cond(listed, relist, lambda _: (fl, cd), None)
    return dict(st, lvl=lvl, par=par, fl=fl, cd=cd, f=f, m=m, listed=listed)


@partial(jax.jit, static_argnames=("chunk",), donate_argnums=(3,))
def run_levels(off, dst, esrc, st, to, steps, chunk):
    """Up to ``steps`` levels of the search (all of them: a large number).
    ``st``: the state ``start`` made or an earlier call returned; donated."""
    cap = st["fl"].shape[0] - chunk
    small = small_chunk(chunk)

    def cond(c):
        st, left = c
        return (~st["found"]) & (st["f"] > 0) & (left > 0)

    def body(c):
        st, left = c
        st = dict(st, rows=st["rows"] + st["f"], edges=st["edges"] + st["m"])
        # the per-level choice (module docstring): ``cap`` is where the
        # frontier's edges at the gather's rate meet the layout's at the
        # sweep's; a level that fits one small chunk whole (the first levels
        # of nearly every search) does not pay for a large one
        gather = st["listed"] & (st["m"] <= cap)
        tiny = gather & (st["m"] <= small) & (st["f"] <= small // 2)
        st = jax.lax.switch(
            jnp.where(tiny, 0, jnp.where(gather, 1, 2)),
            [lambda s: _gather_level(off, dst, small, cap, s),
             lambda s: _gather_level(off, dst, chunk, cap, s),
             lambda s: _sweep_level(off, dst, esrc, chunk, s)],
            st,
        )
        st = dict(st, cur=st["cur"] + 1, found=st["lvl"][to] >= 0,
                  sweeps=st["sweeps"] + jnp.where(gather, 0, 1).astype(jnp.int32))
        return st, left - 1

    st, _ = jax.lax.while_loop(cond, body, (st, steps))
    return st


@partial(jax.jit, static_argnames=("cap", "chunk"))
def start(off, src, cap, chunk):
    """The state before level 0: the source alone, at level 0."""
    ub = off.shape[0] - 1
    n = cap + chunk
    d0 = (off[src + 1] - off[src]).astype(jnp.int32)
    zero = jnp.int32(0)
    return {
        "lvl": jnp.full((ub,), -1, jnp.int32).at[src].set(0),
        "par": jnp.full((ub,), SENT, jnp.int32),
        "fl": jnp.full((n,), SENT, jnp.int32).at[0].set(src),
        "cd": jnp.zeros((n,), jnp.int32).at[0].set(d0),
        "nfl": jnp.full((n,), SENT, jnp.int32),
        "ncd": jnp.zeros((n,), jnp.int32),
        "f": jnp.int32(1), "m": d0, "cur": zero,
        "rows": zero, "edges": zero, "sweeps": zero,
        "found": jnp.bool_(False), "listed": jnp.bool_(True),
    }


@jax.jit
def walk_back(par, at):
    """``at`` and its PATH_CAP - 1 ancestors (SENT past the source)."""
    ub = par.shape[0]

    def body(i, c):
        buf, u = c
        return buf.at[i].set(u), jnp.where(u == SENT, SENT, par[jnp.clip(u, 0, ub - 1)])

    buf, _ = jax.lax.fori_loop(
        0, PATH_CAP, body, (jnp.full((PATH_CAP,), SENT, jnp.int32), jnp.int32(at)))
    return buf


@jax.jit
def finish(st, to):
    """int32[HEAD + PATH_CAP]: found, levels done, rows, edges, sweeps, then
    the path's last PATH_CAP uids, target first (``walk_back`` goes on
    from the last of them for a longer path)."""
    head = jnp.stack([st["found"].astype(jnp.int32), st["cur"], st["rows"],
                      st["edges"], st["sweeps"]])
    return jnp.concatenate([head, walk_back(st["par"], to)])
