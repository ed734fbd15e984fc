"""Control ``lost_write``: the reference with the deployment's guarantee
broken — "every read sent after the acknowledgement sees the whole
mutation".  Every read-back is rendered without the film's LAST performance,
as an arena, a device layout or an index that missed the delta would leave
it; the traverse classes and the acks are the true reference's.  Put in the
program's place, the comparison has to call it not correct."""

import reference
import reference_rw


class _Lost(reference_rw.Written):
    def read_back(self, k, tag, lost=0):
        return super().read_back(k, tag, lost=1)


def walker(world):
    w = reference.Walker(world.g)
    w.written = _Lost(world.g)     # what query_kinds/ingest.py renders from
    return w
