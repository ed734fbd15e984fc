"""The least bytes a filtered traversal has to move on the device, as a
function of the WORK the request stands for — the reference's count of it
(``reference_filter``: the edges of every level expanded and the frontier
rows read, and the elements of the filter's k-way intersection: the level's
films, the genre's films and the films on or after D) — and of nothing the
program chose: each edge expanded has its target read and written
(2 x 4 B), each frontier row reads its two offsets (2 x 4 B), and each
element of the intersection is read once (4 B).  Where the sets are resolved,
how the intersection runs and what the recheck reads are left out: that is
the point of a share of the roofline, and why it cannot pass 100%.
"""

from __future__ import annotations

BYTES_PER_EDGE = 8
BYTES_PER_ROW = 8
BYTES_PER_ELEMENT = 4


def filter_bytes(edges: float, rows: float, elements: float) -> float:
    return BYTES_PER_EDGE * edges + BYTES_PER_ROW * rows + BYTES_PER_ELEMENT * elements


def roofline_share(edges: float, rows: float, elements: float, busy_s: float,
                   peak_bytes_per_s: float):
    """Percent of the memory roofline: the least time the chip could take for
    these bytes over the time its operations ran.  None where there is
    nothing to read (no request executed, or no busy time) — never 0."""
    if edges + elements <= 0 or busy_s <= 0:
        return None
    return 100.0 * (filter_bytes(edges, rows, elements) / peak_bytes_per_s) / busy_s
