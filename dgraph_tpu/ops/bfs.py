"""Level-synchronous BFS over SEVERAL predicates at once: the device half
of ``shortest(from:, to:)`` at unit cost (query/shortest.py).

The listed predicates' arenas are merged into ONE CSR over the uid space
(``models/arena.py`` ``PathLayout``: row = uid, a uid's edges under the
first listed predicate first; ``off[u]`` = the (first, past-the-last) edge
slots of uid ``u``, side by side: one row gather reads both).  The
frontier, the parent of every reached uid and its level stay on the device
from the first level to the one that holds the target; the host gets back
the distance, the path (parents walked back on the device) and the sums
the ledger books, never a frontier.

    L0 = {from};  L(i+1) = every uid reached from Li under ANY listed
    predicate and not reached before;  parent(v) = the LEAST uid of Li
    with an edge to v.  The search ends after the first level that holds
    the target, or with an empty level.

State over the uid space: ``par`` (SENT = not reached; the source is its
own parent) is the visited set AND the parent; ``mark``, a flag a uid, is
the frontier of a level that was swept, written and read by the sweep
alone.  A level is done one of two ways, chosen per level from the
frontier's size and the layout's size:

- **gather**: the frontier as a LIST — ascending, duplicate-free, each
  uid beside its first edge slot and the running sum of the degrees —
  ``chunk`` slots of its edges at a time: the slot -> edge and slot ->
  source maps telescoped from one scatter a row (as
  ``batch.expand_ascending``), one ``dst`` gather a slot, the target's
  parent read.  A chunk WRITES nothing over the uid space: a target that
  had no parent when the level started goes to the level's finds, once a
  slot that reached it, its source beside it.  The finds are sorted ONCE
  after the loop, as (target, source) pairs, in a size class of the
  level's edges: the first of a target's run is its least source — the
  sort that makes the next list also decides every parent.  One pass
  over that list reads each new uid's offsets (its slot and degree, exact:
  the search books them) and writes its parent.  No access over the uid
  space that is not a frontier's: the cost follows the frontier's edges.
- **sweep**: every edge of the layout reads its source's mark and
  scatter-mins its source into a candidate table over the uid space:
  two random accesses an edge of the LAYOUT, whatever the frontier
  holds; no list, no sort.  The mark is the sweep's own: after a listed
  level it is written from the list (one access a list slot), after a
  sweep it is what that sweep found.

On the chip a random access costs 9-10 ns an element on a v5e, gathered
or scattered, a dropped index as much as a live one (PERF.md, PRs 28 and
31: a scatter-min 10.0, a scatter-set 6.45; ``_take`` reads a word for
2.5) and a streaming pass or a sort is nearly free beside it: the two
sorts that make 131,072 (target, source) pairs a list with its parents
take 0.39 ms, what forty thousand scattered words cost (PERF.md, PR 35).
So a level goes to the sweep when the
frontier's out-degree sum times ``_ACCESS_PER_SLOT`` passes the layout's
edges times ``_ACCESS_PER_EDGE`` — or when the frontier outgrew its list.
Both numbers are on the device when the level starts: a level's size and
out-degree sum are counted as its uids are listed.
"""

from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp

from dgraph_tpu.ops.sets import SENT, bucket

# Random accesses the two ways of doing a level make, per unit of work,
# COUNTED from the code below, not fitted to a run.  A gather's slot reads
# dst and the target's parent (``_gather_chunk``: two), shares its row's
# telescoping scatter with one other slot (a chunk holds half as many rows
# as slots: a half), and finds one new uid at most, whose offsets are read
# and whose parent is written once, from the sorted list (``_enlist``: two).
# A sweep's edge reads its source's mark and mins the candidate.  The mark a
# sweep writes from the list, when the level before was listed, is one
# access a list SLOT and not an edge's: it is left out of the constant, so
# the break-even leans to the sweep by at most the list's own length, a
# fifth of what that sweep does.  They are this module's priors in the sense
# of utils/calibrate.py's: the calibration measures no rate for either way
# (PERF.md Open questions 15e), and a change to either level's code has to
# recount them (tests/test_path_search.py counts the jaxprs' gathers and
# scatters and holds the constants to the sum)
_ACCESS_PER_SLOT = 4.5
_ACCESS_PER_EDGE = 2
_LANES = 128         # a vector register's lanes: the row ``_take`` gathers
PATH_CAP = 64        # the path comes back this many uids a walk
HEAD = 5             # result header: found, levels, rows, edges, sweeps


def _fine_floor(n: int) -> int:
    """``n`` rounded DOWN to a 1/8-step of a power of two (the steps of
    ``ops.bucket_fine``, which rounds up)."""
    base = 1 << (max(1, int(n)).bit_length() - 1)
    step = max(1, base >> 3)
    return base + (int(n) - base) // step * step


def capacities(n_edge_slots: int, max_degree: int) -> Tuple[int, int]:
    """(list capacity, chunk) for a layout of ``n_edge_slots`` edge slots
    whose widest uid has ``max_degree`` edges.

    The list holds the largest frontier a gather can still win with: past
    ``_ACCESS_PER_EDGE * slots / _ACCESS_PER_SLOT`` edges the sweep is
    cheaper (a bucketed size under it: the shapes depend on the layout's
    bucket alone).  A chunk is a power of two about a twentieth of that — a
    level then overshoots its edges by a few chunks at most — and never
    under the widest uid, which has to fit one chunk whole."""
    top = max(8, _fine_floor(int(_ACCESS_PER_EDGE * max(1, n_edge_slots) / _ACCESS_PER_SLOT)))
    chunk = bucket(max(8, top >> 5, max_degree))
    return max(top, chunk), chunk


def small_chunk(chunk: int) -> int:
    """The chunk of a level that fits it whole: a sixteenth of ``chunk``."""
    return max(8, chunk >> 4)


def _sort_sizes(chunk: int, top: int) -> Tuple[int, ...]:
    """The static sizes a level's finds are sorted at: ``chunk`` and its
    doublings under ``top``, then ``top`` (pairs cost over twice what keys
    do to sort: a level pays for at most twice its edges, not four times)."""
    sizes = []
    while chunk < top:
        sizes.append(chunk)
        chunk *= 2
    return (*sizes, top)


def _take(table, idx):
    """``table[idx]`` for in-bounds ``idx`` into a 1-D table: its rows of
    ``_LANES`` gathered whole and the lane picked.  On the chip a gather of
    scalars costs 8.8 ns an element and this 2.5 (PERF.md, PR 31); a table
    whose size ``_LANES`` does not divide (a toy layout) is read plainly."""
    if table.shape[0] % _LANES:
        return table[idx]
    rows = table.reshape(-1, _LANES).at[idx // _LANES].get(mode="promise_in_bounds")
    lane = jnp.arange(_LANES, dtype=idx.dtype)
    return jnp.sum(jnp.where(lane == (idx % _LANES)[:, None], rows, 0), axis=1)


def _gather_chunk(dst, par, uids, starts, cum, n_valid, chunk):
    """Expand the first ``n_valid`` of ``uids`` (int32[chunk // 2],
    ascending), whose edges fit ``chunk`` slots: ``starts`` their first edge
    slots, ``cum`` the running sum of their degrees from the chunk's start.
    Returns (the targets that had no parent when the level started — SENT
    elsewhere, a uid once for every slot that reached it — int32[chunk],
    each slot's source, the live slots).  ``par`` is read, never written."""
    C, R = chunk, uids.shape[0]
    ub = par.shape[0]
    i = jnp.arange(C, dtype=jnp.int32)
    valid = jnp.arange(R, dtype=jnp.int32) < n_valid
    cum = jnp.where(valid, cum, 0)
    total = jnp.max(cum)
    deg = jnp.where(valid, cum - jnp.concatenate([jnp.zeros((1,), cum.dtype), cum[:-1]]), 0)
    productive = deg > 0
    slot = jnp.where(productive, cum - deg, C)     # C = dropped
    # slot -> edge and slot -> source uid, telescoped: each productive row's
    # first slot holds the jump from the previous productive row's end (its
    # uid), a prefix sum — plus the slot's own index — is the edge (the
    # source).  Rows ascend, so do their starts: the previous row's are a
    # running maximum.  Both maps ride ONE scatter, two values a row
    here = jnp.stack([jnp.where(productive, starts + deg, 0), jnp.where(productive, uids, 0)])
    prev = jnp.concatenate(
        [jnp.zeros((2, 1), here.dtype), jax.lax.cummax(here, axis=1)[:, :-1]], axis=1)
    first = jnp.stack([starts, uids]) - prev
    run = jnp.cumsum(jnp.zeros((2, C), jnp.int32).at[:, slot].set(
        jnp.where(productive, first, 0), mode="drop"), axis=1)
    edge, src = run[0] + i, run[1]
    live = i < total
    out = jnp.where(live, _take(dst, jnp.clip(edge, 0, dst.shape[0] - 1)), SENT)
    fresh = live & (_take(par, jnp.clip(out, 0, ub - 1)) == SENT)
    return jnp.where(fresh, out, SENT), src, total


def _sort_unique_pairs(keys, vals):
    """``sets.sort_unique`` with a payload: the distinct ``keys`` ascending
    (SENT after them) and, beside each, the LEAST of the ``vals`` that stood
    beside it.  Two keys, no stability asked for: on the chip that is the
    cheaper sort of a pair (PERF.md, PR 35)."""
    keys, vals = jax.lax.sort((keys, vals), num_keys=2, is_stable=False)
    dup = jnp.concatenate([jnp.zeros((1,), dtype=bool), keys[1:] == keys[:-1]])
    return jax.lax.sort((jnp.where(dup, SENT, keys), vals), num_keys=1, is_stable=False)


def _enlist(off, piece, st, n, parents=None):
    """The first ``n`` of ``st["fl"]`` (ascending uids) made a frontier
    list, ``piece`` uids at a time: each uid's first edge slot and the
    running sum of the degrees beside it, and its parent written where
    ``parents`` (aligned with the list) is given.  Returns (st, the degrees'
    sum)."""
    k = jnp.arange(piece, dtype=jnp.int32)
    write = parents is not None

    def cond(c):
        return c[0] < n

    def body(c):
        b, fo, cd, m, par = c
        u = jax.lax.dynamic_slice(st["fl"], (b,), (piece,))
        ok = b + k < n
        span = off[jnp.where(ok, u, 0)]
        d = jnp.where(ok, span[:, 1] - span[:, 0], 0)
        if write:
            par = par.at[jnp.where(ok, u, par.shape[0])].set(
                jax.lax.dynamic_slice(parents, (b,), (piece,)), mode="drop")
        fo = jax.lax.dynamic_update_slice(fo, span[:, 0], (b,))
        cd = jax.lax.dynamic_update_slice(cd, m + jnp.cumsum(d), (b,))
        return b + piece, fo, cd, m + jnp.sum(d).astype(jnp.int32), par

    zero = jnp.int32(0)
    _, fo, cd, m, par = jax.lax.while_loop(
        cond, body, (zero, st["fo"], st["cd"], zero, st["par"] if write else ()))
    return dict(st, fo=fo, cd=cd, **({"par": par} if write else {})), m


def _gather_level(off, dst, chunk, top, st):
    """One level of at most ``top`` edges from the frontier list, ``chunk``
    slots of them at a time.  The next list holds at most the level's edges:
    it never outgrows a list this level fitted."""
    fl, fo, cd, f, m = st["fl"], st["fo"], st["cd"], st["f"], st["m"]
    rows = chunk // 2
    j = jnp.arange(rows, dtype=jnp.int32)

    def cond(c):
        return c[0] < f

    def body(c):
        a, w, raw, via = c
        # the chunk [a, a + nb): as many uids as fit ``chunk`` slots
        below = jnp.where(a > 0, cd[jnp.maximum(a - 1, 0)], 0)
        cum = jax.lax.dynamic_slice(cd, (a,), (rows,)) - below
        nb = jnp.sum((cum <= chunk) & (a + j < f)).astype(jnp.int32)
        nb = jnp.maximum(nb, 1)
        new, src, total = _gather_chunk(
            dst, st["par"], jax.lax.dynamic_slice(fl, (a,), (rows,)),
            jax.lax.dynamic_slice(fo, (a,), (rows,)), cum, nb, chunk)
        # one find a live slot, end to end: the level's finds take ``m`` slots
        return (a + nb, w + total, jax.lax.dynamic_update_slice(raw, new, (w,)),
                jax.lax.dynamic_update_slice(via, src, (w,)))

    zero = jnp.int32(0)
    _, _, raw, via = jax.lax.while_loop(cond, body, (zero, zero, st["raw"], st["via"]))

    # the finds sorted once beside their sources, duplicates out and the
    # least source kept, at the least size that holds them: the next list
    # over the one this level has done with, its parents over the sources
    def sort_at(size, fl, raw, via):
        new, parents = _sort_unique_pairs(
            jnp.where(jnp.arange(size, dtype=jnp.int32) < m, raw[:size], SENT), via[:size])
        return (jax.lax.dynamic_update_slice(fl, new, (0,)),
                jax.lax.dynamic_update_slice(via, parents, (0,)),
                jnp.sum(new != SENT).astype(jnp.int32))

    sizes = _sort_sizes(chunk, top)
    at = sum((m > s).astype(jnp.int32) for s in sizes[:-1])
    fl, via, f_next = jax.lax.switch(at, [partial(sort_at, s) for s in sizes], fl, raw, via)
    st, m_next = _enlist(off, chunk, dict(st, fl=fl, raw=raw, via=via), f_next, parents=via)
    return dict(st, f=f_next, m=m_next, listed=jnp.bool_(True))


def _sweep_level(off, dst, esrc, chunk, st):
    """One level from a mark of the frontier over the uid space: every edge
    of the layout."""
    par, fl = st["par"], st["fl"]
    ub, n = par.shape[0], fl.shape[0]

    def from_list(_):
        # the level before left a list (only a sweep leaves a mark)
        at = jnp.where(jnp.arange(n, dtype=jnp.int32) < st["f"], fl, ub)
        return jnp.zeros((ub,), dtype=bool).at[at].set(True, mode="drop")

    mark = jax.lax.cond(st["listed"], from_list, lambda _: st["mark"], None)
    cand = jnp.full((ub,), SENT, jnp.int32).at[jnp.where(mark[esrc], dst, ub)].min(
        esrc, mode="drop")
    new = (cand != SENT) & (par == SENT)
    par = jnp.where(new, cand, par)
    deg = off[:, 1] - off[:, 0]
    f = jnp.sum(new).astype(jnp.int32)
    m = jnp.sum(jnp.where(new, deg, 0)).astype(jnp.int32)
    cap = n - chunk
    lists = {"fl": fl, "fo": st["fo"], "cd": st["cd"]}

    def relist(lists):
        # the frontier is back under the list's capacity: one sort of the
        # uid space puts it there again (its parents are written already)
        uids = jnp.sort(jnp.where(new, jnp.arange(ub, dtype=jnp.int32), SENT))
        uids = jnp.concatenate([uids, jnp.full((max(0, n - ub),), SENT, jnp.int32)])[:n]
        return _enlist(off, chunk, dict(lists, fl=uids), f)[0]

    listed = (f <= cap) & (m <= cap)
    lists = jax.lax.cond(listed, relist, lambda lists: lists, lists)
    return dict(st, **lists, par=par, mark=new, f=f, m=m, listed=listed)


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
            [lambda s: _gather_level(off, dst, small, small, s),
             lambda s: _gather_level(off, dst, chunk, cap + chunk, s),
             lambda s: _sweep_level(off, dst, esrc, chunk, s)],
            st,
        )
        st = dict(st, cur=st["cur"] + 1, found=st["par"][to] != SENT,
                  sweeps=st["sweeps"] + jnp.where(gather, 0, 1).astype(jnp.int32))
        return st, left - 1

    st, _ = jax.lax.while_loop(cond, body, (st, steps))
    return st


@partial(jax.jit, static_argnames=("cap", "chunk"))
def start(off, src, cap, chunk):
    """The state before level 0: the source alone, its own parent."""
    ub = off.shape[0]
    n = cap + chunk
    d0 = (off[src, 1] - off[src, 0]).astype(jnp.int32)
    zero = jnp.int32(0)
    return {
        "par": jnp.full((ub,), SENT, jnp.int32).at[src].set(src),
        # the frontier of a level that was swept (``_sweep_level`` alone
        # writes and reads it)
        "mark": jnp.zeros((ub,), dtype=bool),
        # the frontier list: uids, their first edge slots, their degrees'
        # running sum; ``raw``: what a level's chunks found, before the
        # sort, ``via``: the sources they were found from (after the sort:
        # the new list's parents)
        "fl": jnp.full((n,), SENT, jnp.int32).at[0].set(src),
        "fo": jnp.zeros((n,), jnp.int32).at[0].set(off[src, 0]),
        "cd": jnp.zeros((n,), jnp.int32).at[0].set(d0),
        "raw": jnp.full((n,), SENT, jnp.int32),
        "via": jnp.zeros((n,), jnp.int32),
        "f": jnp.int32(1), "m": d0, "cur": zero,
        "rows": zero, "edges": zero, "sweeps": zero,
        "found": jnp.bool_(False), "listed": jnp.bool_(True),
    }


@jax.jit
def walk_back(par, at):
    """``at`` and its PATH_CAP - 1 ancestors (SENT past the source, which is
    its own parent)."""
    ub = par.shape[0]

    def body(i, c):
        buf, u = c
        p = par[jnp.clip(u, 0, ub - 1)]
        return buf.at[i].set(u), jnp.where((u == SENT) | (p == u), SENT, p)

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
