"""Query kind ``paths``: "how is X connected to Y" — two actors found by name,
``shortest(from: uid(A), to: uid(B))`` under a list of predicates, and a
second block over the path's uids.  A query class of this kind is DATA
(``benchmark/queries/<class>.json``):

    text     the GraphQL+- text; ``$FROM`` / ``$TO`` stand for the two
             actors' indices
    listed   the predicates inside ``shortest``, in the text's order
    root     the pairs: {"law", "pairs": how many, "from": {...}, "to": {...}}
             An endpoint is {"pool": "cast", "from_rank"} (every actor with a
             role from that cast rank on), {"pool": "most_cast", "from_rank",
             "top"} (cast ranks from_rank..top) with its own "law", or
             {"pool": "costars"} (both: two actors of one film, the film
             drawn uniformly).  The pairs are drawn ONCE, from the frozen
             structure (ranks, not uids); the seed deals who holds which
             rank and who plays in which film, so it decides how far apart
             a pair is.  ``pool()`` is the pair ids 0..pairs-1 RANKED BY THE
             SEARCH'S SIZE (``_by_size``), as the other kinds' pools are
             ranked by cast or films: ``root.law`` walks them evenly, so
             every stretch of every seed's deck asks for the same shares of
             near and far pairs, of small and large searches.
    blocks   the names of the text's aliasable query blocks

This file is the kind's interpreter.  The reference is
``reference_paths.PathReference`` (a numpy BFS over the generated arrays).
An answer is correct where: it holds no ``_path_`` exactly where the
reference finds no path; else ONE path of the reference's length d, from
``from`` to ``to``, no uid twice, every hop an edge of the generated arrays
under the listed predicate it is rendered under; and the second block holds
exactly the names of the path's named uids (actors and films; a performance
has no name).  Which of several equal paths is not compared: the deployment
leaves it unspecified.
"""

from __future__ import annotations

import re
import zlib

import numpy as np

import reference_paths


class QueryKind:
    def __init__(self, name: str, spec: dict, world):
        self.name = name
        self.spec = spec
        self.world = world
        self.listed = list(spec["listed"])
        self._alias = re.compile(r"\b(%s)\(func:" % "|".join(map(re.escape, spec["blocks"])))
        self._pairs = None

    # -- the reference, one for all classes that list the same predicates ----------

    @property
    def ref(self) -> reference_paths.PathReference:
        memo = self.world.__dict__.setdefault("_path_refs", {})
        key = tuple(self.listed)
        if key not in memo:
            memo[key] = reference_paths.PathReference(self.world.g.edges(), self.listed)
        return memo[key]

    # -- the pairs -------------------------------------------------------------------

    def pairs(self) -> np.ndarray:
        """int64[pairs, 2]: (from, to) actor INDICES of every pair id."""
        if self._pairs is None:
            r = self.spec["root"]
            n = int(r["pairs"])
            rng = np.random.default_rng([0x70617468, zlib.crc32(self.name.encode())])
            u = rng.random((n, 2))
            if r["from"]["pool"] == "costars":
                self._pairs = self._costars(u[:, 0])
            else:
                a, b = self._draw(r["from"], u[:, 0]), self._draw(r["to"], u[:, 1])
                clash = a == b                       # never a path to oneself
                b[clash] = self._draw(r["to"], (u[clash, 1] + 0.5) % 1.0)
                self._pairs = np.stack([a, b], axis=1)
            self._pairs = self._pairs[self._by_size(self._pairs)]
        return self._pairs

    def _by_size(self, pairs: np.ndarray) -> np.ndarray:
        """The order of ``pairs`` by what the search from the first to the
        second has to expand in THIS seed's graph, from the two actors'
        films alone (no search is run): how many films apart they are — one
        (they share a film), two (a co-star of one is a co-star of the
        other) or more, where a search ends up expanding the whole graph —
        and then the roles the source's co-stars hold (the level a two-film
        search spends its edges on; the source's own roles for one film).
        A window answers a few hundred searches of 10^3 to 7 x 10^6 edges:
        dealt by pair id alone, the share of three-film searches in a window
        swung by a sixth with the seed and ``query_p50_ms`` with it."""
        g = self.world.g
        actor = g.perf_actor - g.actor_base
        roles = np.bincount(actor, minlength=g.n_actors)
        by_actor = np.argsort(actor, kind="stable")
        first_role = np.concatenate([[0], np.cumsum(roles)])
        first = np.searchsorted(g.perf_film, np.arange(len(g.film) + 1))
        memo = {}

        def costars(a: int) -> np.ndarray:
            if a not in memo:
                films = g.perf_film[by_actor[first_role[a]:first_role[a + 1]]]
                lo, n = first[films], first[films + 1] - first[films]
                within = np.arange(int(n.sum())) - np.repeat(np.cumsum(n) - n, n)
                cast = np.unique(actor[np.repeat(lo, n) + within])
                memo[a] = cast[cast != a]
            return memo[a]

        apart = np.empty(len(pairs), np.int64)
        size = np.empty(len(pairs), np.int64)
        for i, (a, b) in enumerate(pairs.tolist()):
            near = costars(a)
            if b in near:
                apart[i], size[i] = 1, roles[a]
            else:
                apart[i] = 2 if len(np.intersect1d(near, costars(b), assume_unique=True)) else 3
                size[i] = roles[near].sum()
        return np.lexsort((size, apart))

    def _draw(self, end: dict, u: np.ndarray) -> np.ndarray:
        ranked = self.world.actors_by_cast[int(end.get("from_rank", 1)) - 1:]
        if end["pool"] == "most_cast":
            ranked = ranked[: int(end["top"]) - int(end.get("from_rank", 1)) + 1]
        elif end["pool"] != "cast":
            raise ValueError(f"{self.name}: unknown pool {end['pool']!r}")
        if end.get("law", "uniform") == "zipf":
            w = np.arange(1, len(ranked) + 1, dtype=np.float64) ** -float(end.get("s", 1.0))
            at = np.searchsorted(np.cumsum(w) / w.sum(), u, side="right")
        else:
            at = (u * len(ranked)).astype(np.int64)
        return ranked[np.minimum(at, len(ranked) - 1)].astype(np.int64)

    def _costars(self, u: np.ndarray) -> np.ndarray:
        """Two actors of one film, the film uniform over those that have two
        (neither the generator's actor 0: from_rank 2, as everywhere)."""
        g = self.world.g
        hub = int(self.world.actors_by_cast[0])
        actor = g.perf_actor - g.actor_base
        first = np.searchsorted(g.perf_film, np.arange(len(g.film)))
        last = np.append(first[1:], len(g.perf_film))
        out = np.empty((len(u), 2), np.int64)
        f = (u * len(g.film)).astype(np.int64)
        for i, film in enumerate(f.tolist()):
            while True:
                cast = [a for a in dict.fromkeys(actor[first[film]:last[film]].tolist())
                        if a != hub]
                if len(cast) >= 2:
                    break
                film = (film + 1) % len(g.film)
            out[i] = cast[0], cast[1]
        return out

    def pool(self) -> np.ndarray:
        return np.arange(int(self.spec["root"]["pairs"]), dtype=np.int64)

    def _uids(self, root: int):
        a, b = self.pairs()[int(root)].tolist()
        base = self.world.g.actor_base
        return base + a, base + b

    # -- the request -------------------------------------------------------------------

    def text(self, root: int, tag: str = "") -> str:
        a, b = self.pairs()[int(root)].tolist()
        t = self.spec["text"].replace("$FROM", str(a)).replace("$TO", str(b))
        return self._alias.sub(rf"\1{tag}(func:", t) if tag else t

    # -- the reference's answer ----------------------------------------------------------

    def expect(self, root: int, walker=None) -> dict:
        src, dst = self._uids(root)
        r = self.ref.search(src, dst)
        return {"edges": r["edges"], "rows": r["rows"], "levels": r["levels"],
                "want": {"d": r["d"], "from": src, "to": dst}}

    # -- the comparison --------------------------------------------------------------------

    def check(self, out: dict, expect: dict, tag: str = "") -> str | None:
        want = expect["want"]
        paths = out.get("_path_") or []
        hops = out.get(self.spec["blocks"][0] + tag) or []
        if want["d"] is None:
            if paths or hops:
                return f"{self.name}: a path where the reference finds none"
            return None
        if len(paths) != 1:
            return f"{self.name}: {len(paths)} paths, the reference one of {want['d']} hops"
        try:
            uids, keys = _walk(paths[0])
        except (KeyError, TypeError, ValueError, IndexError) as e:
            return f"{self.name}: the path is unreadable: {e!r}"
        if len(uids) - 1 != want["d"]:
            return f"{self.name}: {len(uids) - 1} hops, the reference's distance is {want['d']}"
        if uids[0] != want["from"] or uids[-1] != want["to"]:
            return f"{self.name}: the path does not run from `from` to `to`"
        if len(set(uids)) != len(uids):
            return f"{self.name}: a uid twice on the path"
        for u, v, key in zip(uids, uids[1:], keys):
            if key not in self.ref.holds(u, v):
                return f"{self.name}: hop {u:#x} -> {v:#x} is no edge under {key!r}"
        named = sorted(n for n in map(self._name, uids) if n is not None)
        try:
            got = sorted(h["name"] for h in hops)
        except (KeyError, TypeError) as e:
            return f"{self.name}: the second block is unreadable: {e!r}"
        if got != named:
            return (f"{self.name}: the second block holds {len(got)} names, "
                    f"the path's uids have {len(named)}")
        return None

    def _name(self, uid: int):
        g = self.world.g
        if g.actor_base <= uid < g.actor_base + g.n_actors:
            return self.world.names.name("actor", uid)
        f = int(np.searchsorted(g.film, uid))
        if f < len(g.film) and g.film[f] == uid:
            return self.world.names.name("film", uid)
        return None

    # -- the answer, from any walker (controls, tests) ------------------------------------------

    def render(self, root: int, walker=None) -> dict:
        """The answer as ``walker`` finds it (``reference.Walker``'s
        ``expand`` is all that is asked of it: a control's broken graph or
        capped frontier shows in the path); with none, the reference's."""
        src, dst = self._uids(root)
        if walker is None:
            path = self.ref.search(src, dst, with_path=True)["path"]
        else:
            path = self._search_with(walker, src, dst)
        if path is None:
            return {}
        node = None
        for u, v in zip(reversed(path), [None] + list(reversed(path))):
            cur = {"_uid_": hex(u)}
            if node is not None:
                cur[(self.ref.holds(u, v) or [self.listed[0].lstrip("~")])[0]] = [node]
            node = cur
        names = [self._name(u) for u in sorted(path)]
        return {"_path_": [node],
                self.spec["blocks"][0]: [{"name": n} for n in names if n is not None]}

    def _search_with(self, walker, src: int, dst: int):
        """A shortest path's uids over what ``walker.expand`` returns, level
        by level, or None where it reaches no further."""
        levels = [np.array([src], np.int64)]
        seen = levels[0]
        while dst not in levels[-1]:
            out = np.unique(np.concatenate(
                [walker.expand(tok, levels[-1])[1] for tok in self.listed]))
            new = np.setdiff1d(out, seen, assume_unique=True)
            if not len(new):
                return None
            seen = np.union1d(seen, new)
            levels.append(new)
        path = [dst]
        for level in levels[-2::-1]:
            back = np.concatenate([walker.expand(_flip(tok), np.array([path[-1]]))[1]
                                   for tok in self.listed])
            prev = np.intersect1d(back, level)
            if not len(prev):
                return None
            path.append(int(prev[0]))
        return path[::-1]


def _flip(tok: str) -> str:
    return tok[1:] if tok.startswith("~") else "~" + tok


def _walk(node: dict):
    """([uid, ...], [the key each hop is rendered under, ...]) of a ``_path_``."""
    uids, keys = [], []
    while True:
        uids.append(int(node["_uid_"], 16))
        nxt = [k for k in node if k != "_uid_"]
        if not nxt:
            return uids, keys
        if len(nxt) != 1 or len(node[nxt[0]]) != 1:
            raise ValueError(f"a path object with {len(nxt)} keys")
        keys.append(nxt[0])
        node = node[nxt[0]][0]
