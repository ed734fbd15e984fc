"""Control ``truncate``: the reference with every frontier cut to its first
4,096 uids — what a device expansion with a fixed capacity returns when it
truncates in silence (``ops``: ``expand_csr`` with ``cap`` < the true total).
An approximate answer where the configuration states an exact one."""

import numpy as np

import reference

CAP = 4096


class _Capped(reference.Walker):
    def expand(self, pred, frontier):
        n, out = super().expand(pred, frontier)
        return n, out[:CAP]

    def children(self, pred, parents):
        deg, out = super().children(pred, parents)
        kept = np.minimum(np.cumsum(deg), CAP)
        return np.diff(kept, prepend=0), out[:CAP]


def walker(world):
    return _Capped(world.g)
