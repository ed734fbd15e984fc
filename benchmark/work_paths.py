"""The least bytes a path search has to move on the device, as a function of
the WORK the search stands for — the reference's count of it
(``reference_paths``: the out-degrees of every uid of every level expanded,
and the levels' sizes) — and of nothing the program chose: whether a level is
swept or gathered, each edge expanded has its target read and that target's
level or parent written (2 x 4 B), and each frontier row reads its two
offsets (2 x 4 B).  A sweep reads far more than this; that is the point of a
share of the roofline, and why it cannot pass 100%.
"""

from __future__ import annotations

BYTES_PER_EDGE = 8
BYTES_PER_ROW = 8


def path_bytes(edges: float, rows: float) -> float:
    return BYTES_PER_EDGE * edges + BYTES_PER_ROW * rows


def roofline_share(edges: float, rows: float, busy_s: float, peak_bytes_per_s: float):
    """Percent of the memory roofline: the least time the chip could take for
    these bytes over the time its operations ran.  None where there is
    nothing to read (no search executed, or no busy time) — never 0."""
    if edges <= 0 or busy_s <= 0:
        return None
    return 100.0 * (path_bytes(edges, rows) / peak_bytes_per_s) / busy_s
