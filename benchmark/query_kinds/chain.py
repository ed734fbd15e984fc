"""Query kind ``chain``: one root entity, then a straight chain of
predicates — the shape of the upstream's co-actor and celebrity fan-out
queries.  A query class of this kind is DATA (``benchmark/queries/<class>.json``):

    text     the GraphQL+- text, ``$ROOT`` standing for the root's index
    root     who may be a root: {"entity", "pool", "from_rank"?, "top"?, "law", "s"?}
             (``from_rank`` r leaves the r - 1 most-cast actors out of the pool;
             ``top`` is the last rank kept)
    walk     the predicates, root outwards
    blocks   the names of the text's query blocks (re-aliased at warm-up)
    checks   what of the answer is compared with the reference:
             {"block", "path", "level", "entity"?, "of"}, where ``of`` is
             "nested" (the objects below ``path``, one per traversed
             (parent object, child) pair, compared as a sorted list WITH
             repeats) or "set" (a ``uid(var)`` block: the unique uids)
    fields   level -> entity whose ``name`` the answer carries there

This file is the kind's interpreter: the text of a root, its reference
walk, the comparison of an answer with it, and — for the controls and the
tests — the answer itself rendered from any walker.
"""

from __future__ import annotations

import re

import numpy as np


class QueryKind:
    def __init__(self, name: str, spec: dict, world):
        self.name = name
        self.spec = spec
        self.world = world
        self.walk = list(spec["walk"])
        self._alias = re.compile(r"\b(%s)\(func:" % "|".join(map(re.escape, spec["blocks"])))

    # -- who can be a root ---------------------------------------------------------

    def pool(self) -> np.ndarray:
        """Root indices this class draws from, in rank order."""
        r = self.spec["root"]
        if r["entity"] != "actor":
            raise ValueError(f"{self.name}: chain roots are actors, not {r['entity']}")
        ranked = self.world.actors_by_cast[int(r.get("from_rank", 1)) - 1:]
        if r["pool"] == "most_cast":
            return ranked[: int(r["top"]) - int(r.get("from_rank", 1)) + 1]
        if r["pool"] == "cast":
            return np.sort(ranked)
        raise ValueError(f"{self.name}: unknown pool {r['pool']!r}")

    # -- the request ---------------------------------------------------------------

    def text(self, root: int, tag: str = "") -> str:
        t = self.spec["text"].replace("$ROOT", str(int(root)))
        return self._alias.sub(rf"\1{tag}(func:", t) if tag else t

    # -- the reference -------------------------------------------------------------

    def _root_uid(self, root: int) -> np.ndarray:
        return np.array([self.world.g.actor_base + int(root)], dtype=np.int64)

    def expect(self, root: int, walker=None) -> dict:
        """What a correct answer for ``root`` holds, and the work it stands
        for: ``edges`` (every level expanded from its uid SET — what a
        traversal has to touch) and ``rows`` (frontier rows read)."""
        w = walker or self.world.walker
        uid = self._root_uid(root)
        levels = w.chain(uid, self.walk)
        nested = None
        want = []
        for c in self.spec["checks"]:
            lv = int(c["level"])
            if c["of"] == "set":
                want.append(levels[lv - 1][1])
            else:
                if nested is None:
                    nested = w.nested(uid, self.walk)
                want.append(np.sort(nested[lv - 1]))
        rows = 1 + sum(len(f) for _, f in levels[:-1])
        return {"edges": sum(n for n, _ in levels), "rows": rows, "want": want}

    # -- the comparison ------------------------------------------------------------

    def check(self, out: dict, expect: dict, tag: str = "") -> str | None:
        """None where the answer says what the reference says, else the
        first difference in words."""
        names = self.world.names
        for c, want in zip(self.spec["checks"], expect["want"]):
            objs = _level_objects(out.get(c["block"] + tag) or [], c["path"])
            if len(objs) != len(want) and c["of"] == "nested":
                return (f"{self.name}: level {c['level']} holds {len(objs)} "
                        f"objects, the reference {len(want)}")
            ent = c.get("entity")
            if ent is None:
                continue
            try:
                got = names.uids(ent, [o["name"] for o in objs])
            except (KeyError, ValueError, IndexError, TypeError) as e:
                return f"{self.name}: level {c['level']} names unreadable: {e!r}"
            got = np.sort(got)
            if c["of"] == "set" and len(got) > 1 and (np.diff(got) == 0).any():
                return f"{self.name}: level {c['level']} repeats a uid in a uid() block"
            if not np.array_equal(got, want):
                return (f"{self.name}: level {c['level']} uids differ from the "
                        f"reference ({len(got)} against {len(want)})")
        return None

    # -- the answer, from any walker (controls, tests) -----------------------------

    def render(self, root: int, walker) -> dict:
        names = self.world.names
        fields = {int(k): v for k, v in self.spec.get("fields", {}).items()}
        uid = self._root_uid(root)
        out = {}
        set_blocks = [c for c in self.spec["checks"] if c["of"] == "set"]
        if set_blocks:
            levels = walker.chain(uid, self.walk)
            for c in set_blocks:
                out[c["block"]] = [
                    {"name": names.name(c["entity"], int(u))}
                    for u in levels[int(c["level"]) - 1][1]
                ]
        nested_blocks = {c["block"] for c in self.spec["checks"] if c["of"] == "nested"}
        if nested_blocks:
            degs, tgts, f = [], [], uid
            for p in self.walk:
                d, f = walker.children(p, f)
                degs.append(d)
                tgts.append(f)
            objs = None
            for lv in range(len(self.walk), 0, -1):
                here = [
                    ({"name": names.name(fields[lv], int(u))} if lv in fields else {})
                    for u in tgts[lv - 1].tolist()
                ]
                if objs is not None:
                    off = np.concatenate(([0], np.cumsum(degs[lv]))).tolist()
                    key = self.walk[lv]
                    for i, o in enumerate(here):
                        kids = objs[off[i]:off[i + 1]]
                        if kids:
                            o[key] = kids
                objs = here
            top = {self.walk[0]: objs} if objs else {}
            for b in nested_blocks:
                out[b] = [top] if top else []
        return out


def _level_objects(node_list, path):
    """All objects at the end of ``path`` below the objects of ``node_list``
    — one object per traversed edge."""
    cur = node_list
    for key in path:
        nxt = []
        for obj in cur:
            nxt.extend(obj.get(key, ()))
        cur = nxt
    return cur
