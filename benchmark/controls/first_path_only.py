"""Control ``first_path_only``: the reference answering ``numpaths: 1`` where
the text asks for k — the one path a route that ignored ``numpaths`` (or took
the one-path search for a k-path block) would return.  Put in the program's
place on a k-path cell, the comparison has to call it not correct: the
reference finds k paths for nearly every pair of the film graph."""

import reference


class _OnePath(reference.Walker):
    numpaths = 1            # the k that ``query_kinds/kpaths.py`` ``render`` answers


def walker(world):
    return _OnePath(world.g)
