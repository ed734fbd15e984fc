"""The plain reference of the ``readwrite`` mix: what a written film holds,
what its read-back has to answer, and why every OTHER read of the mix keeps
one right answer while the writes go in.  Plain numpy and Python; it imports
nothing of the program and takes nothing the program made — only the arrays
``filmgen.generate`` returns for the run's seed, and (k, tag).

A write, root k under the block alias ``tag``: ONE film no generated node can
reach — a film ``_:f`` named ``Film <tag>-<k>`` with a release date, hung on
an EXISTING director (``<d> <director.film> _:f``) and an existing genre
(``_:f <genre> <g>``), and c performances ``_:f <starring> _:p<j>``,
``_:p<j> <performance.actor> _:a<j>``, ``_:a<j> <name> "Newcomer
<tag>-<k>-<j>"``: 4 + 3c N-Quads, c from ``filmgen``'s own cast law (bounded
Pareto, mean 6 by name and 4.5 by measure, cap 8) as a function of k: 17.5 N-Quads on average.  The tag is part of every name, so
the warm-up's films and a re-run window's are films of their own.

The comparison (``compare.py``) memoises the expected answer by (class, root)
and sees no clock, so every (class, root, tag) must have ONE right answer:
``isolated`` is the proof obligation — no written edge has an endpoint among
the generated actors, performances or films, and no class of the mix walks
``director.film`` or ``genre``, the two predicates that tie a new film to the
old graph.
"""

from __future__ import annotations

import numpy as np

import filmgen

POOL = 65536            # fresh film ids a run may write
LAW_SEED = 0x66696C6D   # the cast law's draws: the same for every run, as
#                         filmgen's degree sequences are (STRUCTURE_SEED)


class Written:
    """Films the mix writes, as a function of (k, tag) and the run's graph."""

    def __init__(self, g: filmgen.FilmGraph):
        self.g = g
        # filmgen._zipfish(rng, 6, 8, n): performances per film
        self.cast = filmgen._zipfish(np.random.default_rng(LAW_SEED), 6, 8, POOL)
        self.by_cast = np.argsort(self.cast, kind="stable")   # ids, smallest cast first

    def cast_size(self, k: int) -> int:
        return int(self.cast[int(k)])

    def director(self, k: int) -> int:
        return int(self.g.director[(int(k) * 2654435761) % len(self.g.director)])

    def genre(self, k: int) -> int:
        return 1 + int(k) % filmgen.GENRES

    def date(self, k: int) -> str:
        k = int(k)
        return f"{1960 + k % 60}-0{1 + k % 9}-1{k % 7}"

    @staticmethod
    def film_name(k: int, tag: str) -> str:
        return f"Film {tag}-{int(k)}"

    @staticmethod
    def actor_name(k: int, tag: str, j: int) -> str:
        return f"Newcomer {tag}-{int(k)}-{j}"

    def blanks(self, k: int) -> list:
        """The blank nodes of film k's mutation, without the ``_:``."""
        c = self.cast_size(k)
        return ["f"] + [f"p{j}" for j in range(1, c + 1)] + [f"a{j}" for j in range(1, c + 1)]

    def quads(self, k: int, tag: str) -> list:
        """The N-Quads of film k: 4 + 3c lines."""
        out = [
            f'_:f <name> "{self.film_name(k, tag)}" .',
            f'_:f <initial_release_date> "{self.date(k)}" .',
            f"<0x{self.director(k):x}> <director.film> _:f .",
            f"_:f <genre> <0x{self.genre(k):x}> .",
        ]
        for j in range(1, self.cast_size(k) + 1):
            out += [
                f"_:f <starring> _:p{j} .",
                f"_:p{j} <performance.actor> _:a{j} .",
                f'_:a{j} <name> "{self.actor_name(k, tag, j)}" .',
            ]
        return out

    def read_back(self, k: int, tag: str, lost: int = 0) -> list:
        """What the co-actor text rooted at film k's LAST newcomer answers
        once the film is written: one role, the film by name, all c
        performances, all c names.  ``lost`` leaves the last performances
        out — an arena or an index that missed the delta (the control)."""
        c = self.cast_size(k)
        cast = [{"performance.actor": [{"name": self.actor_name(k, tag, j)}]}
                for j in range(1, c + 1 - lost)]
        film = {"name": self.film_name(k, tag), "starring": cast}
        return [{"name": self.actor_name(k, tag, c),
                 "~performance.actor": [{"~starring": [film]}]}]

    def read_back_work(self, k: int) -> dict:
        """Edges traversed and frontier rows read by the read-back: one role,
        one film, c performances, c actors."""
        c = self.cast_size(k)
        return {"edges": 2 + 2 * c, "rows": 3 + c}

    def layout_touch(self, k: int) -> dict:
        """What film k adds to the four arenas the mix's classes walk
        (``starring``, ``performance.actor`` and their reverses), counted as
        any inline-head layout over them has to: new rows, new overflow
        chunks (a row's targets past its first six, eight a chunk), new
        uid -> row entries."""
        c = self.cast_size(k)
        return {"rows": 1 + 3 * c, "chunks": (max(0, c - 6) + 7) // 8, "lut": 1 + 3 * c}


def isolated(g: filmgen.FilmGraph, written: Written, ks) -> bool:
    """The proof obligation: of every N-Quad the mix writes, the only
    endpoints that are not blank nodes are an existing DIRECTOR (subject of
    ``director.film``) and an existing GENRE (object of ``genre``) — none is
    a generated actor, performance or film, so no walk over ``starring`` or
    ``performance.actor`` (either way) that starts at a generated actor can
    reach a written node, and none that starts at a written node can leave
    its film.  Raises where that fails."""
    walked = np.concatenate([g.film, g.perf, g.actor_base + np.arange(g.n_actors)])
    for k in ks:
        for line in written.quads(k, "x"):
            s, p, o = line.split(" ", 2)
            o = o.rsplit(" .", 1)[0]
            for end in (s, o):
                if end.startswith("<0x"):
                    uid = int(end[1:-1], 16)
                    if p not in ("<director.film>", "<genre>") or np.isin(uid, walked):
                        raise AssertionError(f"film {k}: {line!r} touches the walked graph")
    return True
