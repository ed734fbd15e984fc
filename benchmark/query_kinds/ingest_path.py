"""Query kind ``ingest_path``: the path search that has to see a write — the
follower of ``add_film`` in a mix of searches (``traffic/searchwrite.json``).
A class of this kind is DATA (``benchmark/queries/<class>.json``):

    text     the paths text (``query_kinds/paths.py``): two ``var`` blocks by
             ``eq(name, ...)``, ``shortest(from: uid(A), to: uid(B))`` under
             ``listed``, a second block over the path; ``$FROM`` / ``$TO``
             stand for the two NAMES — the written film's LAST newcomer and
             its FIRST (4 hops), or the film itself where the cast is one (2)
    listed   the predicates inside ``shortest``, in the text's order
    blocks   the names of the text's aliasable query blocks
    root     {"pool": "fresh_films", "law": "uniform"}: ``add_film``'s pool,
             so that the warm-up ladder asks for the films it just wrote

The reference is ``reference_paths_rw.WrittenPaths``: the numpy BFS over the
written film's own edges (its component is closed under the listed
predicates).  An answer is correct where it holds ONE path of the reference's
length, no uid twice, every hop rendered under the predicate the reference
walks there, and the second block holds exactly the names of the path's named
nodes.  The uids are the program's to assign and are not compared.

A control that sets ``lost_paths`` on its walker is rendered as a merged
layout that missed the write would answer: no ``_path_``, no name.
"""

from __future__ import annotations

import re

import numpy as np

import reference_paths_rw
import reference_rw
import trafficgen

_walk = trafficgen.load_module("query_kinds", "paths")._walk   # that kind's reading of a ``_path_``


class QueryKind:
    def __init__(self, name: str, spec: dict, world):
        self.name = name
        self.spec = spec
        self.world = world
        self.listed = list(spec["listed"])
        self.written = reference_rw.Written(world.g)
        self.ref = reference_paths_rw.WrittenPaths(self.written, self.listed)
        self._alias = re.compile(r"\b(%s)\(func:" % "|".join(map(re.escape, spec["blocks"])))

    def pool(self) -> np.ndarray:
        return self.written.by_cast

    # -- the request -------------------------------------------------------------------

    def _end_names(self, root: int, tag: str) -> tuple:
        return tuple(self.ref.names(root, tag, [n])[0] for n in self.ref.ends(root))

    def text(self, root: int, tag: str = "") -> str:
        a, b = self._end_names(root, tag)
        t = self.spec["text"].replace("$FROM", a).replace("$TO", b)
        return self._alias.sub(rf"\1{tag}(func:", t) if tag else t

    # -- the reference -----------------------------------------------------------------

    def expect(self, root: int, walker=None) -> dict:
        r = self.ref.search(root)
        return {"edges": r["edges"], "rows": r["rows"], "levels": r["levels"],
                "root": int(root), "want": {"d": r["d"], "keys": r["keys"]},
                # what the film this search follows added to the merged layout
                "path_touch": self.ref.layout_touch(root)}

    # -- the comparison ----------------------------------------------------------------

    def check(self, out: dict, expect: dict, tag: str = "") -> str | None:
        want = expect["want"]
        paths = out.get("_path_") or []
        hops = out.get(self.spec["blocks"][0] + tag) or []
        if len(paths) != 1:
            return (f"{self.name}: {len(paths)} paths, the written film holds one of "
                    f"{want['d']} hops")
        try:
            uids, keys = _walk(paths[0])
        except (KeyError, TypeError, ValueError, IndexError) as e:
            return f"{self.name}: the path is unreadable: {e!r}"
        if len(uids) - 1 != want["d"]:
            return f"{self.name}: {len(uids) - 1} hops, the reference's distance is {want['d']}"
        if len(set(uids)) != len(uids):
            return f"{self.name}: a uid twice on the path"
        if keys != want["keys"]:
            return f"{self.name}: hops rendered under {keys}, the reference walks {want['keys']}"
        named = self.ref.names(expect["root"], tag, self.ref.search(expect["root"])["path"])
        try:
            got = sorted(h["name"] for h in hops)
        except (KeyError, TypeError) as e:
            return f"{self.name}: the second block is unreadable: {e!r}"
        if got != named:
            return f"{self.name}: the second block holds {got}, the path's nodes are named {named}"
        return None

    # -- the answer, from any walker (controls, tests) -----------------------------------

    def render(self, root: int, walker=None) -> dict:
        if getattr(walker, "lost_paths", False):
            return {}
        r = self.ref.search(root)
        node = None
        for n, key in zip(reversed(r["path"]), [None] + r["keys"][::-1]):
            cur = {"_uid_": hex(0x40000000 + n)}
            if node is not None:
                cur[key] = [node]
            node = cur
        return {"_path_": [node], self.spec["blocks"][0]:
                [{"name": x} for x in self.ref.names(root, "", r["path"])]}
