"""Control ``drop_quad``: the reference with one guarantee of the
configuration broken — "reads see every loaded quad".  One ``starring`` and
one ``performance.actor`` edge in every 2,000 is missing, as a stale arena,
a dropped load batch or a capacity that truncates would leave it.  Put in
the program's place, the comparison has to call it not correct."""

import numpy as np

import reference

ONE_IN = 2000


def walker(world):
    edges = dict(world.g.edges())
    for k, pred in enumerate(("starring", "performance.actor")):
        src, dst = edges[pred]
        keep = (np.arange(len(src)) + 7 * k) % ONE_IN != 0
        edges[pred] = (src[keep], dst[keep])
    return reference.Walker(world.g, edges)
