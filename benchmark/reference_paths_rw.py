"""The plain reference of the ``searchwrite`` mix: what the path search that
follows a write (``path_back``) has to answer, and why every OTHER search of
the mix keeps one right answer while the writes go in.  Plain numpy and
Python; it imports nothing of the program and takes nothing the program
made — only ``reference_rw.Written`` (a written film's edges as a function
of (k, tag)) and ``reference_paths.PathReference`` (the numpy BFS), run over
THOSE edges alone.

A written film is a component of its own under the listed predicates
(``closed`` is the proof obligation): the film, its c performances and its c
newcomers, each newcomer with ONE role.  So the search from the film's LAST
newcomer to its FIRST has one path — newcomer, its performance, the film, the
first performance, the first newcomer: 4 hops — and where c = 1 the search
goes to the film itself, 2 hops.  The walk stands for 3c + 1 edges over
c + 2 frontier rows (3 and 2 where c = 1), by ``reference_paths``'s own
definition; the program's ledger is held to that in the warm-up.

The uids the program assigns are not known here: the film's nodes are
numbered 1 (the film), 1 + j (performance j) and 1 + c + j (newcomer j), and
what is compared is what does not depend on them — the path's length, the
predicate every hop is rendered under, the names of its named uids.
"""

from __future__ import annotations

import numpy as np

import reference_paths


def _ends(line: str) -> tuple:
    """(subject, predicate without its brackets, object) of one N-Quad of
    ``reference_rw.Written.quads``."""
    s, p, o = line.split(" ", 2)
    return s, p[1:-1], o.rsplit(" .", 1)[0]


class WrittenPaths:
    """``path_back`` over the films ``written`` (a ``reference_rw.Written``)
    describes, under the predicates ``listed``."""

    def __init__(self, written, listed):
        self.written = written
        self.listed = list(listed)
        self._memo = {}

    def edges(self, k: int) -> dict:
        """predicate -> (src, dst) of film k's uid edges among its own
        nodes, by the numbering above — from ``Written.quads``, the text that
        is sent, and not from a formula of its own."""
        c = self.written.cast_size(k)
        node = {"_:f": 1, **{f"_:p{j}": 1 + j for j in range(1, c + 1)},
                **{f"_:a{j}": 1 + c + j for j in range(1, c + 1)}}
        out = {}
        for s, p, o in map(_ends, self.written.quads(k, "x")):
            if s in node and o in node:
                out.setdefault(p, []).append((node[s], node[o]))
        return {p: tuple(np.array(x, np.int64) for x in zip(*e)) for p, e in out.items()}

    def ends(self, k: int) -> tuple:
        """(from, to) of film k's ``path_back``: its last newcomer, and its
        first — or the film itself where the cast is one."""
        c = self.written.cast_size(k)
        return 1 + 2 * c, (2 + c if c > 1 else 1)

    def names(self, k: int, tag: str, nodes) -> list:
        """The names of those of ``nodes`` that have one (a performance has
        none), sorted."""
        c, w = self.written.cast_size(k), self.written
        return sorted(w.film_name(k, tag) if n == 1 else w.actor_name(k, tag, n - 1 - c)
                      for n in nodes if n == 1 or n > 1 + c)

    def search(self, k: int) -> dict:
        """``reference_paths``'s search of film k's component: {"d",
        "levels", "edges", "rows", "path", "keys"}, ``keys`` the predicate
        each hop is rendered under (``~`` stripped)."""
        if k not in self._memo:
            edges = {p: (np.empty(0, np.int64),) * 2 for p in
                     {t.lstrip("~") for t in self.listed}}
            edges.update({p: e for p, e in self.edges(k).items() if p in edges})
            ref = reference_paths.PathReference(edges, self.listed)
            r = ref.search(*self.ends(k), with_path=True)
            r["keys"] = [ref.holds(u, v)[0] for u, v in zip(r["path"], r["path"][1:])]
            self._memo[k] = r
        return self._memo[k]

    def layout_touch(self, k: int) -> dict:
        """What film k adds to a merged layout of the four listed
        directions: a row for every node that holds an edge (all 1 + 2c do),
        a slot for every listed edge (c ``starring``, c ``performance.actor``,
        each both ways)."""
        e = self.edges(k)
        both = [t.lstrip("~") for t in self.listed]
        return {"rows": len({int(n) for p in set(both) for x in e.get(p, ()) for n in x}),
                "slots": sum(len(e[p][0]) for p in both if p in e)}


def closed(g, written, ks, listed) -> bool:
    """The proof obligation: of every N-Quad the mix writes under a LISTED
    predicate (either direction), both ends are blank nodes of that one
    mutation — so no listed predicate leads out of a written film and none
    leads into one: a search between generated actors meets no written node
    (its answer is ``reference_paths``'s over the generated arrays, whatever
    was written), and a search from a newcomer never leaves its film.  An
    existing uid may appear only under a predicate that is not listed (the
    director, the genre).  Raises where that fails."""
    walked = {t.lstrip("~") for t in listed}
    for k in ks:
        for line in written.quads(k, "x"):
            s, p, o = _ends(line)
            if p in walked and not (s.startswith("_:") and o.startswith("_:")):
                raise AssertionError(f"film {k}: {line!r} ties the film to the walked graph")
    return True
