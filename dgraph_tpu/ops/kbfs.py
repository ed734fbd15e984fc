"""K shortest SIMPLE paths at unit cost on the device: the device half of
``shortest(from:, to:, numpaths: k)`` with k > 1 (query/shortest.py), over
the listed predicates' merged layout (``models/arena.py`` ``PathLayout``,
whose pairs ``PathLayout.kpath_tables`` lists once more: each uid's DISTINCT
targets, ascending) and with the one-path search's chunk gather
(``ops/bfs.py`` ``_windows``, ``_window_map``, ``_take``).

The rule (one for both routes; the Dijkstra's heap key (cost, uid, path)
gives exactly this order at unit cost):

    a path never visits a uid twice; a uid is TAKEN at most k times, in
    order of length and, between equal lengths, least source-first uid
    sequence first; the first k arrivals at ``to`` are the answer, and an
    arrival at ``to`` is not expanded.  Two listed predicates joining one
    pair are one hop.

Level-synchronous over ARRIVALS.  An arrival is a (slot, uid) pair — the
uid's slot-th taken path — addressed ``uid << sh | slot`` (``sh`` the bits
of k rounded up to a power of two: a uid's arrivals share a row of
``_LANES``), with the arrival id of its parent (``apar``; the source is its
own parent).  ``info`` holds a word a uid: its arrivals so far (the low
byte) and the level of its first (above it).  The frontier is a LIST of
arrival ids in rule order — the order of their paths, source first — each
beside its uid's first pair slot and the running sum of the pair counts, as
the one-path search's list; the next level's list is written beside it.

A level walks its list ``chunk`` pair slots at a time, IN RULE ORDER (a
list row's targets ascend: the candidates (arrival, target) of a level come
in the order of the paths they extend), and a chunk:

1. finds each slot's row by a count in its block's window of the rows (no
   row of the list is empty, so every window covers its block);
2. drops a candidate whose target is full (k arrivals taken: ``to``'s
   included) as the chunk starts;
3. walks, for a target taken at an EARLIER level, the parent's prefix back
   to that target's first level: the candidate is a loop where the target is
   on it (the simple-path rule; only a re-arrival can fail it).  The
   candidates that need it are sorted to the front and walked a piece at a
   time;
4. sorts its candidates by (target, slot): a target's candidates in rule
   order, and one that comes when the target already holds k (counting the
   ones kept before it) is full, a loop is dropped, any other kept.  The
   sort carries each candidate's parent arrival and its target's ``info``
   word, and its loop bit below the slot in the second key;
5. sorts the kept back to slot order, carrying each one's arrival id,
   parent, ``info`` word (its count with it, the level of its uid's first)
   and its uid's pair count; writes the words into ``info``, a piece at a
   time — the next chunk sees them — the parents to the level's own list
   (``apar`` takes them once the level is done: a prefix walk reads only
   earlier levels, and a table that a chunk both read and wrote would be
   copied whole for it), and appends those that have an edge and are not
   ``to``, in slot order, to the next list.  So a chunk gathers back
   nothing it already holds.

So the candidates are taken one after the other in rule order, and each
target's are the first (k - taken) that pass.  The search ends when ``to``
holds k arrivals or the frontier has no edge.  The lists hold k arrivals of
every uid that is a pair's target: no level outgrows them.  ``finish`` returns a
header and the k paths walked back through the parent arrivals.

Work of a search, by definition (what the ledger books): ``edges`` the sum,
over the expanded arrivals (levels 0..L-1; arrivals at ``to`` are not
expanded; a level is expanded where its arrivals hold an edge), of their
out-degree under the listed predicates (``odeg``: a pair under two
predicates twice); ``rows`` the number of those arrivals.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from dgraph_tpu.ops.bfs import PATH_CAP, _take, _window_map, _windows, small_chunk
from dgraph_tpu.ops.bfs import capacities as _one_path_capacities
from dgraph_tpu.ops.sets import SENT, bucket_fine

MAX_K = 255          # arrivals a uid: ``info``'s low byte
KHEAD = 9            # paths, levels, rows, edges, first, again, kept, full, loop
_IDS = 1 << 31       # arrival ids are int32


def shift(k: int) -> int:
    """Bits of an arrival id's slot: k rounded up to a power of two."""
    return max(0, (int(k) - 1).bit_length())


def fits(k: int, universe: int) -> bool:
    """Whether ``k`` arrivals a uid over uids up to ``universe`` are
    addressable (int32 arrival ids, the layout's uid space rounded up; a
    count byte a uid)."""
    return 1 < k <= MAX_K and ((int(universe) + 2) * 2) << shift(k) < _IDS


def capacities(targets: int, n_pairs: int, max_degree: int, k: int):
    """(list capacity, chunk) for ``targets`` uids that are a pair's target,
    ``n_pairs`` pair slots and the widest uid's ``max_degree``: the one-path
    search's chunk, and k arrivals of every uid a search can take — what a
    level can keep at most — rounded up to an eighth-step of a power of two.
    So a write that adds a few targets compiles nothing, and a list's rows
    of ``_LANES`` are whole, which ``_take`` reads as rows."""
    _, chunk = _one_path_capacities(n_pairs, max_degree)
    return bucket_fine(max(8, int(k) * int(targets))), chunk


def _klevel(kp, to, k, chunk, st):
    """One level: the list's candidates, ``chunk`` pair slots at a time, in
    rule order (module docstring).  ``kp``: (targets, first slot, pair count,
    out-degree) of the layout's pairs (``PathLayout.kpath_tables``)."""
    kdst, kfirst, kdeg, odeg = kp
    sh = shift(k)
    C, rows = chunk, chunk // 2
    P = max(8, C // 8)          # the walks and the writes go a piece of this many at a time
    fl, fo, cd, f, lev = st["fl"], st["fo"], st["cd"], st["f"], st["cur"]
    apar = st["apar"]
    ub = st["info"].shape[0]
    i = jnp.arange(C, dtype=jnp.int32)
    j = jnp.arange(rows, dtype=jnp.int32)
    n32 = lambda x: jnp.sum(x).astype(jnp.int32)  # noqa: E731

    def cond(c):
        return c[0] < f

    def body(c):
        a, s = c
        below = jnp.where(a > 0, cd[jnp.maximum(a - 1, 0)], 0)
        cum = jax.lax.dynamic_slice(cd, (a,), (rows,)) - below
        nb = jnp.maximum(n32((cum <= C) & (a + j < f)), 1)
        valid = j < nb
        ends = jnp.where(valid, cum, C + 1)
        total = jnp.max(jnp.where(valid, cum, 0))
        deg = jnp.where(valid, ends - jnp.concatenate([jnp.zeros((1,), cum.dtype), ends[:-1]]), 0)
        base = jnp.where(valid, jax.lax.dynamic_slice(fo, (a,), (rows,)) - (ends - deg), 0)
        win, _ = _windows(ends, base, jnp.where(valid, a + j, 0), total, C)
        edge, pos = _window_map(win, C)
        live = i < total
        v = jnp.clip(jnp.where(live, _take(kdst, jnp.clip(edge, 0, kdst.shape[0] - 1)), 0),
                     0, ub - 1)
        iv = _take(s["info"], v)
        taken, low = iv & 0xFF, iv >> 8
        at = _take(fl, jnp.clip(pos, 0, fl.shape[0] - 1))
        full0 = live & (taken >= k)
        need = live & ~full0 & (taken > 0) & (low <= lev)

        # the simple-path rule: the parent's prefix, from this level back to
        # the target's first (no arrival of the target lies below it) — for
        # the candidates that need it alone, a piece of them at a time
        n_need = n32(need)
        pad = jnp.full((P,), SENT, jnp.int32)
        asked = jnp.concatenate([jax.lax.sort(jnp.where(need, i, SENT)), pad])

        def wcond(w):
            return jnp.any(w[2])

        # (``apar`` is not written inside a level: its ancestors are all there)
        def wbody(w):
            d, cur, act, hit, want, low_p = w
            hit = hit | (act & ((cur >> sh) == want))
            act = act & ~hit & (lev - d > low_p)
            cur = jnp.where(act, _take(apar, jnp.where(act, cur, 0)), cur)
            return d + 1, cur, act, hit, want, low_p

        def ncond(q):
            return q[0] < n_need

        def nbody(q):
            b, hits = q
            sl = jnp.clip(jax.lax.dynamic_slice(asked, (b,), (P,)), 0, C - 1)
            act = b + jnp.arange(P, dtype=jnp.int32) < n_need
            _, _, _, hit, _, _ = jax.lax.while_loop(
                wcond, wbody, (jnp.int32(0), _take(at, sl), act, jnp.zeros((P,), dtype=bool),
                               _take(v, sl), _take(low, sl)))
            return b + P, jax.lax.dynamic_update_slice(hits, hit.astype(jnp.int32), (b,))

        _, hits = jax.lax.while_loop(
            ncond, nbody, (jnp.int32(0), jnp.zeros((C + P,), jnp.int32)))
        loop = need & (_take(hits[:C], jnp.clip(jnp.cumsum(need) - 1, 0, C - 1)) > 0)

        # a target's candidates in rule order: the first that find room are
        # kept.  A slot's parent arrival and ``info`` word ride the sort, its
        # loop bit below the slot in the second key (a slot is under C <=
        # 2**17 and unique: the order is the slots')
        tv, tl, par, ivs = jax.lax.sort(
            (jnp.where(live & ~full0, v, SENT), (i << 1) | loop.astype(jnp.int32), at, iv),
            num_keys=2, is_stable=False)
        ok = tv != SENT
        ti = tl >> 1
        ts, lows = ivs & 0xFF, ivs >> 8
        loops = ok & ((tl & 1) > 0)
        passes = (ok & ~loops).astype(jnp.int32)
        before = jnp.cumsum(passes) - passes
        head = ok & jnp.concatenate([jnp.ones((1,), bool), tv[1:] != tv[:-1]])
        before = before - jax.lax.cummax(jnp.where(head, before, 0))
        full = ok & (ts + before >= k)
        keep = ok & ~full & ~loops
        aid = (tv << sh) + ts + before
        arrived = keep & (tv != to)
        kd = _take(kdeg, jnp.where(ok, tv, 0))
        listed = arrived & (kd > 0)
        # what a kept arrival leaves in ``info``: its uid's arrivals with it,
        # beside the level of the uid's first
        word = (jnp.where(ts == 0, lev + 1, lows) << 8) | (ts + before + 1)

        # the kept, in slot order: first those the next list takes, then the
        # others (``to``'s, and arrivals without an edge); each with its
        # parent, its ``info`` word and its uid's pair count
        _, got, gpar, gword, gdeg = jax.lax.sort(
            (jnp.where(listed, ti, jnp.where(keep, C + ti, SENT)), aid, par, word, kd),
            num_keys=1, is_stable=False)
        n_list, n_keep = n32(listed), n32(keep)
        kept_aid = jnp.concatenate([got, pad])
        kept_word = jnp.concatenate([gword, pad])

        # their words into ``info``, a piece at a time: the next chunk sees
        # them; their parents go to the level's own list, for ``apar`` after it
        def pcond(q):
            return q[0] < n_keep

        def pbody(q):
            b, info = q
            g = jax.lax.dynamic_slice(kept_aid, (b,), (P,))
            live_p = b + jnp.arange(P, dtype=jnp.int32) < n_keep
            return b + P, info.at[jnp.where(live_p, g >> sh, ub)].max(
                jax.lax.dynamic_slice(kept_word, (b,), (P,)), mode="drop")

        _, info = jax.lax.while_loop(pcond, pbody, (jnp.int32(0), s["info"]))
        kw = s["k2"]
        na = jax.lax.dynamic_update_slice(s["na"], got, (kw,))
        npar = jax.lax.dynamic_update_slice(s["np"], gpar, (kw,))
        here = i < n_list
        u = jnp.where(here, got >> sh, 0)
        dd = jnp.where(here, gdeg, 0)
        w = s["f2"]
        first = keep & ((ts == 0) | (lows == lev + 1))
        return a + nb, dict(
            s, info=info, na=na, np=npar, k2=kw + n_keep,
            nl=jax.lax.dynamic_update_slice(s["nl"], jnp.where(here, got, SENT), (w,)),
            no=jax.lax.dynamic_update_slice(s["no"], _take(kfirst, u), (w,)),
            nc=jax.lax.dynamic_update_slice(s["nc"], s["m2"] + jnp.cumsum(dd), (w,)),
            f2=w + n_list, m2=s["m2"] + n32(dd),
            mo2=s["mo2"] + n32(jnp.where(here, _take(odeg, u), 0)),
            nr2=s["nr2"] + n32(arrived),
            first=s["first"] + n32(first), again=s["again"] + n32(keep & ~first),
            kept=s["kept"] + n32(keep), full=s["full"] + n32(full0) + n32(full),
            loop=s["loop"] + n32(loops & ~full))

    zero = jnp.int32(0)
    _, st = jax.lax.while_loop(
        cond, body, (zero, dict(st, f2=zero, m2=zero, mo2=zero, nr2=zero, k2=zero)))

    # the level's kept arrivals' parents, a chunk of them at a time
    def acond(q):
        return q[0] < st["k2"]

    def abody(q):
        b, apar = q
        g = jax.lax.dynamic_slice(st["na"], (b,), (C,))
        g = jnp.where(b + i < st["k2"], g, apar.shape[0])
        return b + C, apar.at[g].set(jax.lax.dynamic_slice(st["np"], (b,), (C,)), mode="drop")

    _, apar = jax.lax.while_loop(acond, abody, (zero, apar))
    # the next list becomes the list; the old one's buffers take the next
    return dict(st, apar=apar, fl=st["nl"], fo=st["no"], cd=st["nc"], nl=fl, no=fo, nc=cd,
                f=st["f2"], m=st["m2"], mo=st["mo2"], nrows=st["nr2"])


@partial(jax.jit, static_argnames=("chunk", "k"), donate_argnums=(1,))
def run_levels(kp, st, to, steps, chunk, k):
    """Up to ``steps`` levels of the search (all of them: a large number).
    ``st``: the state ``start`` made or an earlier call returned; donated."""
    small = small_chunk(chunk)

    def cond(c):
        st, left = c
        return (st["found"] < k) & (st["m"] > 0) & (left > 0)

    def body(c):
        st, left = c
        st = dict(st, rows=st["rows"] + st["nrows"], edges=st["edges"] + st["mo"])
        # a level that fits one small chunk whole does not pay for a large one
        tiny = (st["m"] <= small) & (st["f"] <= small // 2)
        st = jax.lax.cond(tiny, lambda s: _klevel(kp, to, k, small, s),
                          lambda s: _klevel(kp, to, k, chunk, s), st)
        st = dict(st, cur=st["cur"] + 1, found=st["info"][to] & 0xFF)
        return st, left - 1

    st, _ = jax.lax.while_loop(cond, body, (st, steps))
    return st


@partial(jax.jit, static_argnames=("cap", "chunk", "k"))
def start(kp, src, cap, chunk, k):
    """The state before level 0: the source's one arrival, its own parent."""
    _, kfirst, kdeg, odeg = kp
    ub = kfirst.shape[0]
    n = cap + chunk
    a0 = src << shift(k)
    zero = jnp.int32(0)
    lists = {
        # the frontier list: arrival ids in rule order, their uids' first
        # pair slots, the running sum of their pair counts; and the next
        # level's, written while this one is read
        "fl": jnp.full((n,), SENT, jnp.int32).at[0].set(a0),
        "fo": jnp.zeros((n,), jnp.int32).at[0].set(kfirst[src]),
        "cd": jnp.zeros((n,), jnp.int32).at[0].set(kdeg[src]),
        "nl": jnp.full((n,), SENT, jnp.int32),
        "no": jnp.zeros((n,), jnp.int32),
        "nc": jnp.zeros((n,), jnp.int32),
        # the level's kept arrivals and their parents, for ``apar`` once the
        # level is done (a prefix walk reads ``apar`` all through it)
        "na": jnp.full((n,), SENT, jnp.int32),
        "np": jnp.zeros((n,), jnp.int32),
    }
    return {
        # the parent arrival of every arrival (SENT: none), and a uid's
        # arrivals (low byte) beside the level of its first
        "apar": jnp.full((ub << shift(k),), SENT, jnp.int32).at[a0].set(a0),
        "info": jnp.zeros((ub,), jnp.int32).at[src].set(1),
        **lists,
        # the list's rows, pair slots and out-degree sum, its arrivals (those
        # without an edge too: the rows a level books)
        "f": (kdeg[src] > 0).astype(jnp.int32), "m": kdeg[src], "mo": odeg[src],
        "nrows": jnp.int32(1),
        "f2": zero, "m2": zero, "mo2": zero, "nr2": zero, "k2": zero,
        "cur": zero, "found": zero, "rows": zero, "edges": zero,
        "first": zero, "again": zero, "kept": zero, "full": zero, "loop": zero,
    }


@jax.jit
def walk_back(apar, at):
    """Arrival ``at`` and its PATH_CAP - 1 ancestors, as arrival ids (SENT
    past the source, which is its own parent); ``>> shift(k)`` is the uid."""
    n = apar.shape[0]

    def body(i, c):
        buf, a = c
        p = apar[jnp.clip(a, 0, n - 1)]
        return buf.at[i].set(a), jnp.where((a == SENT) | (p == a), SENT, p)

    buf, _ = jax.lax.fori_loop(
        0, PATH_CAP, body, (jnp.full((PATH_CAP,), SENT, jnp.int32), jnp.int32(at)))
    return buf


@partial(jax.jit, static_argnames=("k",))
def finish(st, to, k):
    """int32[KHEAD + k * PATH_CAP]: paths found, levels, rows, edges, first
    arrivals, re-arrivals, candidates kept, full and looped; then each of the
    k paths' last PATH_CAP arrival ids, target first, in the rule's order
    (SENT for a path not found)."""
    head = jnp.stack([st["found"], st["cur"], st["rows"], st["edges"], st["first"],
                      st["again"], st["kept"], st["full"], st["loop"]])
    at = to << shift(k)
    walks = [jnp.where(s < st["found"], walk_back(st["apar"], at + s), SENT)
             for s in range(k)]
    return jnp.concatenate([head, *walks])
