"""The least bytes a k-path search has to move on the device, as a function of
the WORK the search stands for — the reference's count of it
(``reference_kpaths``: the out-degrees of every expanded arrival, the
arrivals, and the prefix uids the simple-path rule reads) — and of nothing
the program chose: each edge of an expanded arrival has its target read and
that target's count read (2 x 4 B), each arrival reads its uid's two offsets
(2 x 4 B), and each prefix uid the rule reads is one parent word (4 B).  The
sorts and list copies the program makes are left out: that is the point of a
share of the roofline, and why it cannot pass 100%.
"""

from __future__ import annotations

BYTES_PER_EDGE = 8
BYTES_PER_ROW = 8
BYTES_PER_STEP = 4


def kpath_bytes(edges: float, rows: float, steps: float) -> float:
    return BYTES_PER_EDGE * edges + BYTES_PER_ROW * rows + BYTES_PER_STEP * steps


def roofline_share(edges: float, rows: float, steps: float, busy_s: float,
                   peak_bytes_per_s: float):
    """Percent of the memory roofline: the least time the chip could take for
    these bytes over the time its operations ran.  None where there is
    nothing to read (no search executed, or no busy time) — never 0."""
    if edges <= 0 or busy_s <= 0:
        return None
    return 100.0 * (kpath_bytes(edges, rows, steps) / peak_bytes_per_s) / busy_s
