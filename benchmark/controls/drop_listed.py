"""Control ``drop_listed``: the reference with one guarantee of the
configuration broken — "reads see every loaded quad" — at the size of a whole
arena: every ``starring`` edge is missing, as a path search's merged layout
built from three of its four listed arenas would leave it
(``models/arena.py`` ``PathLayout``), or a chain that walked a predicate
whose arena was never staged.  No film leads to its cast, so no path between
two actors survives; put in the program's place, the comparison has to call
it not correct on every cell that walks ``starring``."""

import reference


def walker(world):
    edges = dict(world.g.edges())
    src, dst = edges["starring"]
    edges["starring"] = (src[:0], dst[:0])
    return reference.Walker(world.g, edges)
