"""Query kind ``kpaths``: "how else are X and Y connected" — the ``paths``
kind's two actors and text with ``numpaths: k``: the k shortest SIMPLE
paths, and a second block over the union of their uids.  A class of this
kind is the ``paths`` kind's data (``benchmark/queries/<class>.json``: text,
listed, root, blocks) plus ``numpaths``; its pairs and the texts are that
kind's (``query_kinds/paths.py``, imported, not copied), and so is the order
of its pools within one estimated length of the k-th path, which ranks them
first (``_by_size``).

The reference is ``reference_kpaths.KPathReference`` (the configuration's
rule, level by level over arrivals in numpy).  An answer is correct where:
it holds as many paths as the reference finds, of the reference's lengths
(sorted); each runs from ``from`` to ``to`` with no uid twice, every hop an
edge of the generated arrays under the listed predicate it is rendered
under; no two paths are the same; and the second block holds exactly the
names of the union of the paths' named uids.  Which of several paths of one
length is not compared: the deployment leaves it unspecified.
"""

from __future__ import annotations

import numpy as np

import reference_kpaths
import trafficgen

_paths = trafficgen.load_module("query_kinds", "paths")


class _Expect(dict):
    """The reference's answer: ``want`` at once, the work on first read."""

    WORK = ("edges", "rows", "levels", "again", "loop_steps")

    def __init__(self, ref, src, dst, **kw):
        super().__init__(**kw)
        self._ask = (ref, src, dst)

    def __missing__(self, key):
        if key not in self.WORK:
            raise KeyError(key)
        ref, src, dst = self._ask
        r = ref.search(src, dst)
        self.update({w: r[w] for w in self.WORK})
        return self[key]


class QueryKind(_paths.QueryKind):
    def __init__(self, name: str, spec: dict, world):
        super().__init__(name, spec, world)
        self.k = int(spec["numpaths"])

    # -- the reference, one for all classes that list the same predicates at one k ------

    @property
    def kref(self) -> reference_kpaths.KPathReference:
        memo = self.world.__dict__.setdefault("_kpath_refs", {})
        key = (tuple(self.listed), self.k)
        if key not in memo:
            memo[key] = reference_kpaths.KPathReference(self.world.g.edges(), self.listed, self.k)
        return memo[key]

    def expect(self, root: int, walker=None) -> dict:
        """``want`` from the reference's bounded searches (``paths``); the
        search's work — ``edges``, ``rows``, ``levels``, ``again``,
        ``loop_steps`` — from the whole rule, run where one is read (the
        warm-up's ledger, the traced window's readers)."""
        src, dst = self._uids(root)
        lengths = sorted(len(p) - 1 for p in self.kref.paths(src, dst))
        return _Expect(self.kref, src, dst,
                       want={"lengths": lengths, "from": src, "to": dst})

    # -- the pairs: the paths kind's pools, ranked by the k-path search's size -------------

    def _by_size(self, pairs: np.ndarray) -> np.ndarray:
        """The order of ``pairs`` by what the k-path search has to expand in
        THIS seed's graph: first by how long its k-th path likely is
        (``_kth_hops``: 4, 8 or 12 and more hops; the search expands the
        levels below it, and one that has to go past 8 expands nearly the
        whole graph), then in the paths kind's order (films apart, the
        co-stars' roles).  Ranked by the paths kind's order alone, the
        share of searches that go past 8 hops in a window was the seed's:
        6 and 12 of the first 148 requests for two seeds, the reference's
        edges a request 5.47M and 6.28M."""
        first = np.empty(len(pairs), np.int64)
        first[super()._by_size(pairs)] = np.arange(len(pairs))
        return np.lexsort((first, self._kth_hops(pairs)))

    def _kth_hops(self, pairs: np.ndarray) -> np.ndarray:
        """For each (from, to) actor pair, the length the rule's k-th path
        likely has, from the two actors' films alone (no search is run): 4
        where they have k routes through a shared film (a -> performance ->
        film -> performance -> b), 8 where those and the simple routes
        through one co-star c (a ... c ... b over two distinct films) number
        k, else 12 — which stands for 12 and more.  The rule's cap of k
        arrivals a uid can leave a counted route untaken: an estimate, for
        the order of the deck alone."""
        g = self.world.g
        actor = g.perf_actor - g.actor_base
        roles = np.bincount(actor, minlength=g.n_actors)
        by_actor = np.argsort(actor, kind="stable")
        first_role = np.concatenate([[0], np.cumsum(roles)])
        first = np.searchsorted(g.perf_film, np.arange(len(g.film) + 1))
        memo = {}

        def films_of(x: int) -> np.ndarray:
            return g.perf_film[by_actor[first_role[x]:first_role[x + 1]]]

        def routes(x: int):
            """(other actors, routes through one shared film to each),
            ascending: the roles' pairs that share a film."""
            if x not in memo:
                films = films_of(x)
                lo, n = first[films], first[films + 1] - first[films]
                at = np.arange(int(n.sum())) + np.repeat(lo - (np.cumsum(n) - n), n)
                ids, cnt = np.unique(actor[at], return_counts=True)
                memo[x] = ids[ids != x], cnt[ids != x]
            return memo[x]

        k = self.k
        out = np.full(len(pairs), 12, np.int64)
        for i, (a, b) in enumerate(pairs.tolist()):
            ia, ca = routes(a)
            ib, cb = routes(b)
            j = np.searchsorted(ia, b)
            n4 = int(ca[j]) if j < len(ia) and ia[j] == b else 0
            if n4 >= k:
                out[i] = 4
                continue
            if len(ia) > len(ib):
                ia, ca, ib, cb = ib, cb, ia, ca
            at = np.minimum(np.searchsorted(ib, ia), len(ib) - 1)
            both = ib[at] == ia
            n8 = int((ca[both] * cb[at[both]]).sum())
            if n4:
                # a route through c that takes one shared film twice is no path
                for f in np.intersect1d(films_of(a), films_of(b)).tolist():
                    cast, r = np.unique(actor[first[f]:first[f + 1]], return_counts=True)
                    ra, rb = r[cast == a].sum(), r[cast == b].sum()
                    n8 -= int(ra * rb * (r[(cast != a) & (cast != b)] ** 2).sum())
            if n4 + n8 >= k:
                out[i] = 8
        return out

    # -- the comparison ------------------------------------------------------------------

    def check(self, out: dict, expect: dict, tag: str = "") -> str | None:
        want = expect["want"]
        paths = out.get("_path_") or []
        hops = out.get(self.spec["blocks"][0] + tag) or []
        if len(paths) != len(want["lengths"]):
            return (f"{self.name}: {len(paths)} paths, the reference "
                    f"{len(want['lengths'])} of {want['lengths']} hops")
        walked = []
        for path in paths:
            try:
                uids, keys = _paths._walk(path)
            except (KeyError, TypeError, ValueError, IndexError) as e:
                return f"{self.name}: a path is unreadable: {e!r}"
            if uids[0] != want["from"] or uids[-1] != want["to"]:
                return f"{self.name}: a path does not run from `from` to `to`"
            if len(set(uids)) != len(uids):
                return f"{self.name}: a uid twice on a path"
            for u, v, key in zip(uids, uids[1:], keys):
                if key not in self.kref.holds(u, v):
                    return f"{self.name}: hop {u:#x} -> {v:#x} is no edge under {key!r}"
            walked.append(tuple(uids))
        lengths = sorted(len(p) - 1 for p in walked)
        if lengths != want["lengths"]:
            return f"{self.name}: paths of {lengths} hops, the reference's {want['lengths']}"
        if len(set(walked)) != len(walked):
            return f"{self.name}: the same path twice"
        named = sorted(n for n in map(self._name, {u for p in walked for u in p})
                       if n is not None)
        try:
            got = sorted(h["name"] for h in hops)
        except (KeyError, TypeError) as e:
            return f"{self.name}: the second block is unreadable: {e!r}"
        if got != named:
            return (f"{self.name}: the second block holds {len(got)} names, "
                    f"the paths' uids have {len(named)}")
        return None

    # -- the answer, from any walker (controls, tests) -------------------------------------

    def render(self, root: int, walker=None) -> dict:
        """The answer as the rule finds it over ``walker.children`` (a
        control's broken graph shows in the paths; a walker's ``numpaths``,
        where it has one, is the k it answers); with none, the reference's."""
        src, dst = self._uids(root)
        if walker is None:
            paths = self.kref.paths(src, dst)
        else:
            k = int(getattr(walker, "numpaths", self.k))
            paths = reference_kpaths.ksearch(
                lambda uids: self._children(walker, uids), self.kref.n, src, dst, k,
                with_paths=True)["paths"]
        out = []
        for path in paths:
            node = None
            for u, v in zip(reversed(path), [None] + list(reversed(path))):
                cur = {"_uid_": hex(u)}
                if node is not None:
                    cur[(self.kref.holds(u, v) or [self.listed[0].lstrip("~")])[0]] = [node]
                node = cur
            out.append(node)
        names = [self._name(u) for u in sorted({u for p in paths for u in p})]
        return {**({"_path_": out} if out else {}),
                self.spec["blocks"][0]: [{"name": n} for n in names if n is not None]}

    def _children(self, walker, uids: np.ndarray):
        """(out-degrees, candidate rows, targets) of ``uids`` over the listed
        predicates as ``walker`` has them: each row's distinct targets,
        ascending (what ``reference_kpaths.ksearch`` takes)."""
        deg = np.zeros(len(uids), np.int64)
        rows, outs = [], []
        for tok in self.listed:
            d, out = walker.children(tok, uids)
            deg += d
            rows.append(np.repeat(np.arange(len(uids)), d))
            outs.append(out)
        rows, outs = np.concatenate(rows), np.concatenate(outs).astype(np.int64)
        key = np.unique((rows << 32) | outs)
        return deg, key >> 32, key & 0xFFFFFFFF
