"""Query kind ``filtered``: a traversal whose film level is narrowed by
``@filter(uid(F) and ge(initial_release_date, "$D"))``, F being the films of
genre G (a var block over ``~genre``) — the Dgraph v0.7 wiki's "Filters" and
"Query variables" on the film set.  A query class of this kind is DATA
(``benchmark/queries/<class>.json``):

    text     the GraphQL+- text; ``$ROOT`` stands for the root's index (an
             actor's or a director's), ``$G`` for the genre's and ``$D`` for
             the date bound (YYYY-MM-DD)
    shape    "costars" (the actor's films, kept by the filter, then their
             casts' actors through a ``uid(A)`` block, compared as a set) or
             "director" (the director's films, kept by the filter, each with
             its name, date and cast, compared as nested objects)
    blocks   the names of the text's aliasable query blocks; the first is
             the one compared
    root     the requests: {"law", "draws": how many, "who": {"entity":
             "actor"|"director", ...}, "genre": {"first", "last"}, "date":
             {"years", "months", "day_digits"}}.  An actor is drawn by its
             cast rank ({"pool": "most_cast", "from_rank", "top", "law",
             "s"}), a director uniformly over all; the genre and the date's
             year, month and day digit uniformly over their closed ranges.

The (root, G, D) of every request id are drawn ONCE, from the class name's
own stream, as ``query_kinds/paths.py`` draws its pairs: the same seed gives
the same draws, and every seed the same ranks; the seed deals the graph (who
holds which rank, which film has which date).  ``pool()`` is the request ids
RANKED BY THE k-WAY INTERSECTION'S SIZE (the level's films, the genre's films
and the films on or after D: ``reference_filter``), so ``root.law`` walks
sizes evenly and every stretch of every seed's deck asks for the same shares.

The reference is ``reference_filter.FilterReference`` (numpy over the
generated arrays: no index, no tokenizer).
"""

from __future__ import annotations

import json
import re
import zlib

import numpy as np

import reference_filter


class QueryKind:
    def __init__(self, name: str, spec: dict, world):
        self.name = name
        self.spec = spec
        self.world = world
        self.shape = spec["shape"]
        self.block = spec["blocks"][0]
        self._alias = re.compile(r"\b(%s)\(func:" % "|".join(map(re.escape, spec["blocks"])))
        self._draws = None

    # -- the reference, one for both classes ---------------------------------------

    @property
    def ref(self) -> reference_filter.FilterReference:
        memo = self.world.__dict__
        if "_filter_ref" not in memo:
            memo["_filter_ref"] = reference_filter.FilterReference(self.world.g)
        return memo["_filter_ref"]

    # -- the requests --------------------------------------------------------------

    def draws(self) -> np.ndarray:
        """int64[draws, 3]: (root index, genre index, date key) of every
        request id, ranked by the k-way intersection's size."""
        if self._draws is None:
            r = self.spec["root"]
            n = int(r["draws"])
            rng = np.random.default_rng([0x66696C74, zlib.crc32(self.name.encode())])
            u = rng.random((n, 5))
            root = self._who(r["who"], u[:, 0])
            g = r["genre"]
            genre = int(g["first"]) + _pick(u[:, 1], int(g["last"]) - int(g["first"]) + 1)
            d = r["date"]
            (y0, y1), (m0, m1), (d0, d1) = d["years"], d["months"], d["day_digits"]
            key = reference_filter.date_key(
                y0 + _pick(u[:, 2], y1 - y0 + 1), m0 + _pick(u[:, 3], m1 - m0 + 1),
                10 + d0 + _pick(u[:, 4], d1 - d0 + 1))
            out = np.stack([root, genre, key], axis=1).astype(np.int64)
            self._draws = out[np.argsort(self._sizes(out), kind="stable")]
        return self._draws

    def _who(self, who: dict, u: np.ndarray) -> np.ndarray:
        if who["entity"] == "director":
            return _pick(u, len(self.world.g.director))
        if who["entity"] != "actor" or who["pool"] != "most_cast":
            raise ValueError(f"{self.name}: roots are most-cast actors or directors")
        lo = int(who.get("from_rank", 1)) - 1
        ranked = self.world.actors_by_cast[lo:int(who["top"])]
        w = np.arange(1, len(ranked) + 1, dtype=np.float64) ** -float(who.get("s", 1.0))
        at = np.searchsorted(np.cumsum(w) / w.sum(), u, side="right")
        return ranked[np.minimum(at, len(ranked) - 1)]

    def _sizes(self, draws: np.ndarray) -> np.ndarray:
        """The k-way intersection's elements of each request: the level's
        distinct films, the genre's films and the films on or after D."""
        ref = self.ref
        g = self.world.g
        if self.shape == "director":
            level = np.diff(np.searchsorted(g.film_dir, [draws[:, 0], draws[:, 0] + 1]), axis=0)[0]
        else:
            level = np.array([len(np.unique(g.perf_film[ref.roles(int(a))]))
                              for a in draws[:, 0].tolist()])
        return level + ref.genre_films[draws[:, 1] + 1] + ref.on_or_after(draws[:, 2])

    def pool(self) -> np.ndarray:
        return np.arange(int(self.spec["root"]["draws"]), dtype=np.int64)

    def request(self, i: int) -> tuple:
        """(root index, genre uid, date bound text) of request id ``i``."""
        root, genre, key = self.draws()[int(i)].tolist()
        return root, genre + 1, f"{key // 10000}-{key // 100 % 100:02d}-{key % 100:02d}"

    def text(self, i: int, tag: str = "") -> str:
        return self.text_of(*self.request(i), tag=tag)

    def text_of(self, root: int, genre: int, date: str, tag: str = "") -> str:
        t = (self.spec["text"].replace("$ROOT", str(root))
             .replace("$G", str(genre - 1)).replace("$D", date))
        return self._alias.sub(rf"\1{tag}(func:", t) if tag else t

    # -- the reference's answer ------------------------------------------------------

    def expect(self, i: int, walker=None) -> dict:
        return self.expect_of(*self.request(i), walker=walker)

    def expect_of(self, root: int, genre: int, date: str, walker=None) -> dict:
        """What a correct answer holds, and the work it stands for: ``edges``
        (every level expanded from its uid set, the genre's var block
        included: what the program's ledger counts), ``rows`` (frontier rows
        read) and ``kway`` (the elements of the filter's k-way
        intersection)."""
        ref = walker or self.ref
        n_genre = int(ref.genre_films[genre])
        kway_rest = n_genre + int(ref.on_or_after(date))
        if self.shape == "director":
            r = ref.director(root, genre, date)
            cast = len(r["perfs"])
            return {"edges": n_genre + len(r["films"]) + 2 * cast,
                    "rows": 2 + len(r["kept"]) + cast,
                    "kway": len(r["films"]) + kway_rest,
                    "want": sorted(map(_canon, self._films(r)))}
        r = ref.costars(root, genre, date)
        cast = len(r["perfs"])
        return {"edges": n_genre + 2 * r["roles"] + 2 * cast,
                "rows": 2 + r["roles"] + len(r["kept"]) + cast,
                "kway": len(r["films"]) + kway_rest,
                "want": r["actors"]}

    def _films(self, r: dict) -> list:
        """The director's kept films as the answer renders them."""
        g = self.world.g
        names = self.world.names
        out = []
        cast = r["perfs"]
        at = np.searchsorted(g.perf_film[cast], r["kept"], side="left").tolist() + [len(cast)]
        for j, f in enumerate(r["kept"].tolist()):
            perfs = cast[at[j]:at[j + 1]]
            out.append({
                "name": names.name("film", int(g.film[f])),
                "initial_release_date": g.date_str(f) + "T00:00:00Z",
                "starring": [{"performance.actor": [{"name": names.name("actor", int(a))}]}
                             for a in g.perf_actor[perfs].tolist()],
            })
        return out

    # -- the comparison --------------------------------------------------------------

    def check(self, out: dict, expect: dict, tag: str = "") -> str | None:
        objs = out.get(self.block + tag) or []
        want = expect["want"]
        if self.shape == "director":
            try:
                got = sorted(_canon(f) for me in objs for f in me.get("director.film", ()))
            except (AttributeError, TypeError, KeyError) as e:
                return f"{self.name}: the films are unreadable: {e!r}"
            if len(got) != len(want):
                return f"{self.name}: {len(got)} films, the reference {len(want)}"
            if got != want:
                return f"{self.name}: the films differ from the reference"
            return None
        try:
            got = np.sort(self.world.names.uids("actor", [o["name"] for o in objs]))
        except (KeyError, ValueError, IndexError, TypeError) as e:
            return f"{self.name}: names unreadable: {e!r}"
        if len(got) > 1 and (np.diff(got) == 0).any():
            return f"{self.name}: a uid twice in the uid() block"
        if not np.array_equal(got, want):
            return (f"{self.name}: the co-stars differ from the reference "
                    f"({len(got)} against {len(want)})")
        return None

    # -- the answer, from any reference (controls, tests) ------------------------------

    def render(self, i: int, walker=None) -> dict:
        return self.render_of(*self.request(i), walker=walker)

    def render_of(self, root: int, genre: int, date: str, walker=None) -> dict:
        """The answer as ``walker`` (a ``FilterReference``) finds it; with
        none, the reference's."""
        ref = walker if isinstance(walker, reference_filter.FilterReference) else self.ref
        if self.shape == "director":
            films = self._films(ref.director(root, genre, date))
            return {self.block: [{"director.film": films}] if films else []}
        names = self.world.names
        actors = ref.costars(root, genre, date)["actors"]
        return {self.block: [{"name": names.name("actor", int(a))} for a in actors.tolist()]}


def _pick(u: np.ndarray, n: int) -> np.ndarray:
    """u in [0, 1) -> 0..n-1, uniformly."""
    return np.minimum((u * n).astype(np.int64), n - 1)


def _canon(film: dict) -> str:
    """A film object as one comparable string: its keys and values, the
    cast as a sorted list (the multiset of its performances)."""
    cast = sorted(json.dumps(p, sort_keys=True) for p in film.get("starring", ()))
    return json.dumps({**film, "starring": cast}, sort_keys=True)
