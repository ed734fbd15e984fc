"""The plain reference of ``film21m-q4-filter``: a filtered traversal over the
generated arrays in numpy — an actor's films (or a director's), kept where
the film's genre is G and its release date is on or after D, then the kept
films' casts.  No index, no tokenizer, no program: the date is compared as
the (year, month, day) the generator drew, whole.

It imports nothing of the program and takes nothing the program made, only
the arrays ``filmgen.generate`` returns for the run's seed.
"""

from __future__ import annotations

import numpy as np

import filmgen


def date_key(year, month, day):
    """(year, month, day) as one sortable integer; ``day`` is the day of the
    month (the generator's day digit d renders as day 10 + d)."""
    return (np.asarray(year, np.int64) * 100 + month) * 100 + day


def parse_date(text: str) -> int:
    """``date_key`` of a "YYYY-MM-DD" bound."""
    y, m, d = text.split("-")
    return int(date_key(int(y), int(m), int(d)))


class FilterReference:
    """The answers of the two filtered classes for one generated graph."""

    def __init__(self, g: filmgen.FilmGraph):
        self.g = g
        self.film_key = date_key(g.film_date[:, 0], g.film_date[:, 1], 10 + g.film_date[:, 2])
        actor = g.perf_actor - g.actor_base
        self._by_actor = np.argsort(actor, kind="stable")
        self._first_role = np.concatenate(
            [[0], np.cumsum(np.bincount(actor, minlength=g.n_actors))])
        self._first_perf = np.searchsorted(g.perf_film, np.arange(len(g.film) + 1))
        self._first_film = np.searchsorted(g.film_dir, np.arange(len(g.director) + 1))
        self.genre_films = np.bincount(g.film_genre, minlength=filmgen.GENRES + 1)
        self._sorted_keys = np.sort(self.film_key)

    # -- what the filter keeps ---------------------------------------------------

    def keeps(self, films: np.ndarray, genre: int, date: str) -> np.ndarray:
        """The mask of ``films`` (indices into ``g.film``) the filter keeps:
        genre uid ``genre`` and release date on or after ``date``."""
        return (self.g.film_genre[films] == genre) & (self.film_key[films] >= parse_date(date))

    def on_or_after(self, date):
        """Films released on or after ``date`` (a bound's text, or an array
        of ``date_key``s): the date set's size."""
        key = parse_date(date) if isinstance(date, str) else np.asarray(date)
        return len(self._sorted_keys) - np.searchsorted(self._sorted_keys, key)

    # -- the walks ---------------------------------------------------------------

    def roles(self, actor: int) -> np.ndarray:
        """Performance indices of actor index ``actor``, ascending."""
        return np.sort(self._by_actor[self._first_role[actor]:self._first_role[actor + 1]])

    def cast(self, films: np.ndarray) -> np.ndarray:
        """Performance indices of ``films`` (sorted film indices), in uid order."""
        lo, hi = self._first_perf[films], self._first_perf[films + 1]
        n = hi - lo
        within = np.arange(int(n.sum())) - np.repeat(np.cumsum(n) - n, n)
        return np.repeat(lo, n) + within

    def costars(self, actor: int, genre: int, date: str) -> dict:
        """The ``filter_costars`` text: {"films": the actor's distinct films,
        "kept": those the filter keeps, "actors": the kept films' cast as
        sorted unique uids, "perfs": the kept films' performances}."""
        perfs = self.roles(actor)
        films = np.unique(self.g.perf_film[perfs])
        kept = films[self.keeps(films, genre, date)]
        cast = self.cast(kept)
        return {"roles": len(perfs), "films": films, "kept": kept, "perfs": cast,
                "actors": np.unique(self.g.perf_actor[cast])}

    def director(self, d: int, genre: int, date: str) -> dict:
        """The ``filter_director`` text: {"films": the director's films,
        "kept": those the filter keeps, "perfs": their performances}."""
        films = np.arange(self._first_film[d], self._first_film[d + 1])
        kept = films[self.keeps(films, genre, date)]
        return {"films": films, "kept": kept, "perfs": self.cast(kept)}
