"""Control ``lost_path_write``: the reference with the deployment's guarantee
broken — "every search sent after a write's acknowledgement sees the whole
mutation through the merged layout".  Every ``path_back`` is answered as a
``PathLayout`` that missed the delta would answer it: the two names resolve
(the index took the write), no path joins them — no ``_path_``, an empty
second block; the searches between generated actors and the acks are the true
reference's.  Put in the program's place, the comparison has to call it not
correct."""

import reference


def walker(world):
    w = reference.Walker(world.g)
    w.lost_paths = True        # what query_kinds/ingest_path.py renders from
    return w
