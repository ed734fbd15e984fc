"""The plain reference: level-by-level traversal over the generated edge
arrays in numpy.  It imports nothing of the program (no JAX, no dgraph_tpu)
and takes nothing the program made — only the arrays ``filmgen.generate``
returns for the run's seed.  Copied from ``chip_smoke.py``'s ``Walker``
(PR 21) and widened by what the answers of ``/query`` need: traversal with
multiplicity (a JSON answer repeats a child under every parent object that
reaches it) and the name <-> uid maps of the generated entities.
"""

from __future__ import annotations

import numpy as np

import filmgen


class Walker:
    """For a frontier of uids, every (src, dst) edge of the predicate whose
    src (or, for ``~pred``, dst) is in the frontier."""

    def __init__(self, g: filmgen.FilmGraph, edges: dict | None = None):
        self.g = g
        self._idx = {}
        for pred, (src, dst) in (edges if edges is not None else g.edges()).items():
            for key, a, b in ((pred, src, dst), ("~" + pred, dst, src)):
                order = np.argsort(a, kind="stable")
                self._idx[key] = (a[order], b[order])

    def _spans(self, pred: str, f: np.ndarray):
        keys, vals = self._idx[pred]
        lo = np.searchsorted(keys, f, side="left")
        hi = np.searchsorted(keys, f, side="right")
        deg = hi - lo
        n = int(deg.sum())
        if n == 0:
            return vals, deg, np.empty(0, np.int64)
        starts = np.repeat(lo, deg)
        within = np.arange(n) - np.repeat(np.cumsum(deg) - deg, deg)
        return vals, deg, starts + within

    def expand(self, pred: str, frontier: np.ndarray):
        """(edges traversed, sorted unique targets) from a uid SET: the
        work a traversal has to do, and what the engine's ledger counts."""
        f = np.unique(np.asarray(frontier, dtype=np.int64))
        vals, _, at = self._spans(pred, f)
        return len(at), np.unique(vals[at])

    def children(self, pred: str, parents: np.ndarray):
        """(degree of each parent, targets in parent order) from a uid LIST
        with repeats: what a nested JSON answer holds, one object a
        traversed (parent object, child) pair."""
        p = np.asarray(parents, dtype=np.int64)
        vals, deg, at = self._spans(pred, p)
        return deg, vals[at]

    def chain(self, root: np.ndarray, preds) -> list:
        """[(edges, uid set)] per level of a straight chain of predicates."""
        out, f = [], root
        for p in preds:
            n, f = self.expand(p, f)
            out.append((n, f))
        return out

    def nested(self, root: np.ndarray, preds) -> list:
        """[targets with repeats] per level of the same chain as a nested
        answer renders it."""
        out, f = [], np.asarray(root, dtype=np.int64)
        for p in preds:
            _, f = self.children(p, f)
            out.append(f)
        return out


class Names:
    """The generated entities' names, both ways, by arithmetic on the
    generated arrays (``filmgen.nquad_lines`` renders the same)."""

    def __init__(self, g: filmgen.FilmGraph):
        self.g = g
        self._first_film = np.searchsorted(g.film_dir, np.arange(len(g.director)))

    def uid(self, entity: str, name: str) -> int:
        g = self.g
        if entity == "actor":      # "Actor <index>"
            return g.actor_base + int(name[6:])
        if entity == "film":       # "Film <director>-<ordinal>"
            d, n = name[5:].split("-")
            return int(g.film[self._first_film[int(d)] + int(n)])
        raise KeyError(entity)

    def uids(self, entity: str, names) -> np.ndarray:
        return np.fromiter((self.uid(entity, n) for n in names), np.int64, len(names))

    def name(self, entity: str, uid: int) -> str:
        g = self.g
        if entity == "actor":
            return f"Actor {uid - g.actor_base}"
        if entity == "film":
            f = int(np.searchsorted(g.film, uid))
            return f"Film {int(g.film_dir[f])}-{int(g.film_no[f])}"
        raise KeyError(entity)


def actors_by_cast(g: filmgen.FilmGraph) -> np.ndarray:
    """Actor indices with a role, the most-cast first (ties by index)."""
    roles = np.bincount(g.perf_actor - g.actor_base, minlength=g.n_actors)
    order = np.argsort(-roles, kind="stable")
    return order[: int((roles > 0).sum())]
