"""The plain reference of the path cells: a level-synchronous BFS in numpy over
the generated edge arrays (``FilmGraph.edges()``), under a LIST of predicates
walked forwards (``pred``) or backwards (``~pred``).  It imports nothing of
the program and nothing of JAX.

    L0 = {from};  L(i+1) = every uid reached from Li under any listed
    predicate and not reached before.  The search ends after the first level
    that holds ``to`` (the distance d), or with an empty level (no path).

What a search stands for, by definition (ISSUE 28; what the program's ledger
has to book, whatever the program does to find the path):

    edges   the sum, over the levels 0 .. d-1 (every non-empty level where
            there is no path) and over the listed predicates, of the
            out-degree of every uid of the level
    rows    the sum of those levels' sizes
"""

from __future__ import annotations

import numpy as np


class PathReference:
    def __init__(self, edges: dict, listed):
        """``edges``: predicate -> (src uids, dst uids); ``listed``: the
        predicates as the query lists them (``~`` = walked backwards)."""
        self.listed = list(listed)
        srcs, dsts, self._pairs = [], [], {}
        for tok in self.listed:
            s, d = edges[tok.lstrip("~")]
            if tok.startswith("~"):
                s, d = d, s
            s, d = np.asarray(s, np.int64), np.asarray(d, np.int64)
            srcs.append(s)
            dsts.append(d)
            # (src << 32 | dst), sorted: the membership test of the check
            self._pairs[tok] = np.sort((s << 32) | d)
        src, dst = np.concatenate(srcs), np.concatenate(dsts)
        self.n = int(max(src.max(), dst.max())) + 2 if len(src) else 2
        order = np.argsort(src, kind="stable")
        self.dst = dst[order]
        self.off = np.zeros(self.n + 1, np.int64)
        np.cumsum(np.bincount(src, minlength=self.n), out=self.off[1:])

    def neighbours(self, frontier: np.ndarray):
        """(out-degree sum, targets with repeats) of a uid array."""
        lo, hi = self.off[frontier], self.off[frontier + 1]
        deg = hi - lo
        total = int(deg.sum())
        if total == 0:
            return 0, np.empty(0, np.int64)
        within = np.arange(total) - np.repeat(np.cumsum(deg) - deg, deg)
        return total, self.dst[np.repeat(lo, deg) + within]

    def search(self, src: int, dst: int, with_path: bool = False) -> dict:
        """{"d": distance or None, "levels": [sizes of the levels expanded],
        "edges", "rows"} and, asked for, "path": a shortest path's uids (the
        least-uid predecessor at every step back from ``dst``)."""
        if src == dst:
            return {"d": 0, "levels": [], "edges": 0, "rows": 0, "path": [src]}
        level = np.full(self.n, -1, np.int32)
        frontier = np.array([src], np.int64)
        if src >= self.n - 1 or dst >= self.n - 1:
            return {"d": None, "levels": [1], "edges": 0, "rows": 1, "path": None}
        level[src] = 0
        sizes, edges, d = [], 0, 0
        while len(frontier) and level[dst] < 0:
            sizes.append(len(frontier))
            n, out = self.neighbours(frontier)
            edges += n
            d += 1
            new = np.unique(out[level[out] < 0])
            level[new] = d
            frontier = new
        found = level[dst] >= 0
        res = {"d": d if found else None, "levels": sizes, "edges": edges,
               "rows": int(sum(sizes)), "path": None}
        if found and with_path:
            path = [dst]
            for lv in range(d - 1, -1, -1):
                at = np.flatnonzero(level == lv)
                _, out = self.neighbours(at)
                deg = self.off[at + 1] - self.off[at]
                path.append(int(np.repeat(at, deg)[out == path[-1]].min()))
            res["path"] = path[::-1]
        return res

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
