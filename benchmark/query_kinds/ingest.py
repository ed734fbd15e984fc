"""Query kind ``ingest``: a write of one new film with its cast, and the
read that has to see it.  A class of this kind is DATA
(``benchmark/queries/<class>.json``):

    op       "add_film": ONE ``mutation { set { ... } }`` with blank nodes
             (``reference_rw.Written.quads``: 4 + 3c N-Quads), checked for
             HTTP 200 (by the comparison), the success code and one assigned
             uid for every blank node, all distinct;
             "read_back": the wiki's co-actor text rooted at the film's LAST
             newcomer by name, compared exactly with the reference's answer
    text     the read-back's GraphQL+- text, ``$NAME`` standing for the name
    blocks   the names of the text's query blocks (re-aliased by ``tag``)
    root     {"pool": "fresh_films", "law": "uniform"}: ``reference_rw.POOL``
             fresh film ids, RANKED BY CAST SIZE as the other kinds' pools are
             ranked by size, so that every stretch of the deck writes the same
             shares of small and large casts

The block alias ``tag`` is part of every written name, so the warm-up's and
a re-run window's writes make films of their own and (class, root, tag) has
one right answer.  How to use it: a mix deals ``add_film`` by weight and
names ``read_back`` as its follower (``generators/closed_follow.py``), so a
read-back is only ever sent for a film whose write was acknowledged.
"""

from __future__ import annotations

import re

import numpy as np

import reference_rw


class QueryKind:
    def __init__(self, name: str, spec: dict, world):
        self.name = name
        self.spec = spec
        self.world = world
        self.op = spec["op"]
        self.written = reference_rw.Written(world.g)
        blocks = spec.get("blocks") or []
        self._alias = re.compile(r"\b(%s)\(func:" % "|".join(map(re.escape, blocks))) \
            if blocks else None

    def pool(self) -> np.ndarray:
        return self.written.by_cast

    # -- the request ---------------------------------------------------------------

    def text(self, root: int, tag: str = "") -> str:
        w = self.written
        if self.op == "add_film":
            return "mutation { set {\n" + "\n".join(w.quads(root, tag)) + "\n} }"
        t = self.spec["text"].replace(
            "$NAME", w.actor_name(root, tag, w.cast_size(root)))
        return self._alias.sub(rf"\1{tag}(func:", t) if tag else t

    # -- the reference -------------------------------------------------------------

    def expect(self, root: int, walker=None) -> dict:
        """What a correct answer for ``root`` holds, and the traversal it
        stands for (``edges``, ``rows``: a write traverses nothing; its
        ledger books no edge, and the warm-up holds it to that)."""
        w = getattr(walker, "written", None) or self.written
        if self.op == "add_film":
            return {"edges": 0, "rows": 0, "blanks": w.blanks(root),
                    "touch": w.layout_touch(root)}
        return {**w.read_back_work(root), "root": int(root)}

    # -- the comparison ------------------------------------------------------------

    def check(self, out: dict, expect: dict, tag: str = "") -> str | None:
        if self.op == "add_film":
            if out.get("code") != "Success":
                return f"{self.name}: no success code: {str(out)[:160]}"
            uids = out.get("uids") or {}
            if sorted(uids) != sorted(expect["blanks"]):
                return (f"{self.name}: assigned uids for {sorted(uids)}, the mutation's "
                        f"blank nodes are {sorted(expect['blanks'])}")
            try:
                vals = [int(v, 16) for v in uids.values()]
            except (TypeError, ValueError) as e:
                return f"{self.name}: an assigned uid is unreadable: {e!r}"
            if len(set(vals)) != len(vals) or min(vals) <= 0:
                return f"{self.name}: assigned uids repeat: {uids}"
            return None
        want = _canon(self.written.read_back(expect["root"], tag))
        got = _canon(out.get("q" + tag))
        if got != want:
            return f"{self.name}: the read-back says {str(got)[:200]}, the reference {str(want)[:200]}"
        return None

    # -- the answer, from any walker (controls, tests) -----------------------------

    def render(self, root: int, walker) -> dict:
        w = getattr(walker, "written", None) or self.written
        if self.op == "add_film":
            return {"code": "Success", "message": "Done",
                    "uids": {b: f"0x{0x40000000 + i:x}" for i, b in enumerate(w.blanks(root))}}
        return {"q": w.read_back(root, "")}


def _canon(node):
    """The answer with every list of objects in one order (a film's cast
    comes back in uid order, which a reference that assigns no uid cannot
    know): lists sorted by their rendering, recursively."""
    if isinstance(node, list):
        return sorted((_canon(x) for x in node), key=repr)
    if isinstance(node, dict):
        return {k: _canon(v) for k, v in sorted(node.items())}
    return node
