"""The plain reference of the k-path cell: the k shortest SIMPLE paths of
``shortest(from:, to:, numpaths: k)`` at unit cost, in numpy, level by level
over ARRIVALS, under a LIST of predicates walked forwards (``pred``) or
backwards (``~pred``).  It imports nothing of the program and nothing of JAX.

The rule (the configuration's; ``benchmark/configs/film21m-q4-kpaths.json``):

    a path never visits a uid twice; a uid is TAKEN at most k times, in
    order of length and, between equal lengths, least source-first uid
    sequence first; the first k arrivals at ``to`` are the answer, and an
    arrival at ``to`` is not expanded.  Two listed predicates that join one
    pair are one hop.

Level L holds the arrivals taken at length L, in the order of their paths.
A candidate is (an arrival of level L, a target of its uid), and a level's
candidates are taken one after the other in (arrival, target) order: one
whose target holds k arrivals when it comes is full; else, for a target
taken at an earlier level, one whose target is on the arrival's prefix is a
loop (the prefix is read back to the target's first level: ``loop_steps``
counts those reads); any other is kept.  Level L + 1 is the kept, in
(arrival, target) order.

What a search stands for, by definition (what the program's ledger books):

    edges       the sum, over the expanded arrivals (levels 0 .. L-1; an
                arrival at ``to`` is not expanded; a level is expanded where
                its arrivals hold an edge), of their out-degree under the
                listed predicates (an edge under two predicates twice)
    rows        the number of those arrivals
    first       arrivals taken at a uid's first level (the source's not)
    again       arrivals taken at a later level (re-arrivals)
    full, loop  distinct candidates dropped for a full target / the rule
                (first + again = kept)
    loop_steps  prefix uids read by the simple-path rule
"""

from __future__ import annotations

import numpy as np


class _Arrivals:
    """Growing arrays of the arrivals: uid, parent arrival, level."""

    def __init__(self, src: int):
        self.uid = np.zeros(1024, np.int64)
        self.par = np.zeros(1024, np.int64)
        self.lev = np.zeros(1024, np.int64)
        self.uid[0], self.n = src, 1

    def add(self, uid, par, lev) -> np.ndarray:
        n, m = self.n, len(uid)
        if n + m > len(self.uid):
            size = max(2 * len(self.uid), n + m)
            for name in ("uid", "par", "lev"):
                a = getattr(self, name)
                b = np.zeros(size, np.int64)
                b[:n] = a[:n]
                setattr(self, name, b)
        self.uid[n:n + m], self.par[n:n + m], self.lev[n:n + m] = uid, par, lev
        self.n = n + m
        return np.arange(n, n + m)

    def path(self, a: int) -> list:
        out = [int(self.uid[a])]
        while a != 0:
            a = int(self.par[a])
            out.append(int(self.uid[a]))
        return out[::-1]


def ksearch(expand, n: int, src: int, dst: int, k: int, with_paths: bool = False,
            near=None, bound: int = 0) -> dict:
    """The rule over ``expand(uids) -> (out-degrees, candidate rows, targets)``
    — each arrival's distinct targets, rows ascending, targets ascending
    within a row — for uids < ``n``.

    With ``near`` (each uid's distance to ``dst``, -1 where it has none) the
    search is BOUNDED: a candidate whose length plus its target's distance
    passes ``bound`` is left out, and ``cut`` says whether one was.  That
    leaves the arrivals at ``dst`` of length up to ``bound`` as they are: a
    left-out arrival's extensions are left out too, and it only ever takes a
    slot after every arrival of its uid that can still reach ``dst`` in
    time (those lie at earlier levels).  The work counts are then the
    bounded search's, not the rule's."""
    res = {"lengths": [], "edges": 0, "rows": 0, "levels": 0, "first": 0, "again": 0,
           "kept": 0, "full": 0, "loop": 0, "loop_steps": 0, "cut": False}
    if src == dst:
        res["lengths"] = [0]
        if with_paths:
            res["paths"] = [[src]]
        return res
    taken = np.zeros(n, np.int64)
    low = np.zeros(n, np.int64)         # the level of a uid's first arrival
    arr = _Arrivals(src)
    taken[src] = 1
    frontier = np.zeros(1, np.int64)    # arrival ids, in rule order
    at_dst = []
    level = 0
    while taken[dst] < k and len(frontier):
        uids = arr.uid[frontier]
        deg, p, v = expand(uids)        # in rule order: by arrival, then target
        if deg.sum() == 0:
            break
        res["levels"] += 1
        res["rows"] += len(frontier)
        res["edges"] += int(deg.sum())
        if near is not None:
            far = near[v]
            late = (far < 0) | (level + 1 + far > bound)
            res["cut"] |= bool((late & (far >= 0)).any())
            p, v = p[~late], v[~late]
        c = taken[v]
        live = np.flatnonzero(c < k)
        res["full"] += len(v) - len(live)
        # the simple-path rule, for a target taken at an earlier level: the
        # prefix from this level back to the target's first
        loop = np.zeros(len(v), bool)
        check = live[c[live] > 0]
        cur, want = frontier[p[check]], v[check]
        steps = level - low[want] + 1
        while len(check):
            hit = arr.uid[cur] == want
            loop[check[hit]] = True
            go = ~hit & (arr.lev[cur] > low[want])
            check, cur, want = check[go], arr.par[cur[go]], want[go]
        # a target's candidates in rule order (one sort of target << 32 |
        # their order): one that comes when the target holds k, those kept
        # before it counted, is full; a loop is dropped; any other kept
        key = np.sort((v[live] << 32) | live)
        tv, at = key >> 32, key & 0xFFFFFFFF
        passes = ~loop[at]
        before = np.cumsum(passes) - passes
        head = np.r_[True, tv[1:] != tv[:-1]] if len(tv) else np.zeros(0, bool)
        before -= np.maximum.accumulate(np.where(head, before, 0))
        full = c[at] + before >= k
        res["full"] += int(full.sum())
        res["loop"] += int((loop[at] & ~full).sum())
        checked = np.zeros(len(v), bool)
        checked[at[~full]] = True
        res["loop_steps"] += int(steps[checked[live[c[live] > 0]]].sum())
        kept = np.sort(at[~full & passes])
        kv, kc = v[kept], c[kept]
        res["kept"] += len(kept)
        res["first"] += int((kc == 0).sum())
        res["again"] += int((kc > 0).sum())
        level += 1
        ids = arr.add(kv, frontier[p[kept]], np.full(len(kept), level))
        low[kv[kc == 0]] = level
        np.add.at(taken, kv, 1)
        at_dst += ids[kv == dst].tolist()
        frontier = ids[kv != dst]
    res["lengths"] = [int(arr.lev[a]) for a in at_dst]
    if with_paths:
        res["paths"] = [arr.path(a) for a in at_dst]
    return res


BALLS = 16                              # distance tables kept, the most recently used


class _Ball:
    """Distances to one uid, by a BFS over the reversed pairs, grown a level
    at a time as far as asked."""

    def __init__(self, ref, dst: int):
        self.src_of, self.first = ref.backward()
        self.d = np.full(ref.n, -1, np.int32)
        self.d[dst] = 0
        self.level, self.depth = np.array([dst], np.int64), 0

    def _grow(self):
        lo, hi = self.first[self.level], self.first[self.level + 1]
        n = hi - lo
        at = np.arange(int(n.sum())) + np.repeat(lo - (np.cumsum(n) - n), n)
        out = self.src_of[at]
        out = out[self.d[out] < 0]
        self.depth += 1
        self.d[out] = self.depth
        self.level = np.flatnonzero(self.d == self.depth) if len(out) else out

    def reach(self, src: int) -> int:
        """``src``'s distance (-1: none)."""
        while self.d[src] < 0 and len(self.level):
            self._grow()
        return int(self.d[src])

    def near(self, bound: int) -> np.ndarray:
        """The distances, exact up to ``bound``; bound + 1 for a uid farther
        or not yet reached, -1 for one that cannot reach at all."""
        while self.depth < bound and len(self.level):
            self._grow()
        if not len(self.level):
            return self.d
        return np.where(self.d < 0, bound + 1, self.d)


class KPathReference:
    def __init__(self, edges: dict, listed, k: int):
        """``edges``: predicate -> (src uids, dst uids); ``listed``: the
        predicates as the query lists them (``~`` = walked backwards)."""
        self.listed, self.k = list(listed), int(k)
        srcs, dsts, self._pairs = [], [], {}
        for tok in self.listed:
            s, d = edges[tok.lstrip("~")]
            if tok.startswith("~"):
                s, d = d, s
            s, d = np.asarray(s, np.int64), np.asarray(d, np.int64)
            srcs.append(s)
            dsts.append(d)
            self._pairs[tok] = np.sort((s << 32) | d)
        src, dst = np.concatenate(srcs), np.concatenate(dsts)
        self.n = int(max(src.max(), dst.max())) + 2 if len(src) else 2
        # every edge counts for the work, a pair once for the candidates
        self.deg = np.bincount(src, minlength=self.n)
        pairs = np.unique((src << 32) | dst)
        s1, self.dst = pairs >> 32, pairs & 0xFFFFFFFF
        self.off = np.zeros(self.n + 1, np.int64)
        np.cumsum(np.bincount(s1, minlength=self.n), out=self.off[1:])
        self._memo = {}
        self._back = None
        self._balls = {}                # dst -> _Ball: a star is asked for again and again

    def expand(self, uids: np.ndarray):
        lo, hi = self.off[uids], self.off[uids + 1]
        n = hi - lo
        rows = np.repeat(np.arange(len(uids)), n)
        at = np.arange(int(n.sum())) + np.repeat(lo - (np.cumsum(n) - n), n)
        return self.deg[uids], rows, self.dst[at]

    def paths(self, src: int, dst: int) -> list:
        """The rule's paths from ``src`` to ``dst``, as ``search`` finds them,
        by bounded searches (``ksearch``): first to the distance, then one
        further each time while fewer than k arrive and one was cut — the
        region that can still reach ``dst`` in time, not the graph."""
        key = (src, dst, "paths")
        if key not in self._memo:
            if src == dst or max(src, dst) >= self.n - 1:
                got = self.search(src, dst, with_paths=True)["paths"]
            else:
                ball = self._balls.pop(dst, None) or _Ball(self, dst)
                self._balls[dst] = ball         # the most recent last
                while len(self._balls) > BALLS:
                    self._balls.pop(next(iter(self._balls)))
                bound, got = ball.reach(src), []
                while bound >= 0:
                    r = ksearch(self.expand, self.n, src, dst, self.k, True,
                                ball.near(bound), bound)
                    got = r["paths"]
                    if len(got) >= self.k or not r["cut"]:
                        break
                    bound += 1
            self._memo[key] = got
        return self._memo[key]

    def backward(self):
        """(source of each pair, ordered by target; where each target's run
        starts): the pairs reversed, for the BFS to a target."""
        if self._back is None:
            order = np.argsort(self.dst, kind="stable")
            self._back = (np.repeat(np.arange(self.n), np.diff(self.off))[order],
                          np.searchsorted(self.dst[order], np.arange(self.n + 1)))
        return self._back

    def search(self, src: int, dst: int, with_paths: bool = False) -> dict:
        """``ksearch`` over the listed predicates, memoised by the pair: the
        rule's whole work (and, asked for, its paths)."""
        key = (src, dst, with_paths)
        if key not in self._memo:
            if src != dst and max(src, dst) >= self.n - 1:
                # an endpoint no edge knows of: nothing is expanded
                r = ksearch(None, self.n, 0, 0, self.k, with_paths)
                r = dict(r, lengths=[], **({"paths": []} if with_paths else {}))
            else:
                r = ksearch(self.expand, self.n, src, dst, self.k, with_paths)
            self._memo[key] = r
        return self._memo[key]

    def holds(self, u: int, v: int) -> list:
        """The listed predicates (``~`` stripped: what a hop is rendered
        under) that hold the edge u -> v in the direction listed."""
        key = (np.int64(u) << 32) | np.int64(v)
        out = []
        for tok, pairs in self._pairs.items():
            i = np.searchsorted(pairs, key)
            if i < len(pairs) and pairs[i] == key:
                out.append(tok.lstrip("~"))
        return out
