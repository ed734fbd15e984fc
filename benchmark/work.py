"""The least bytes a traversal has to move on the device, as a function of
the WORK (edges traversed, frontier rows read) and of nothing the program
chose: whatever kernel or layout does it, each traversed edge's target is
read once and written once (2 x 4 B: uids are int32 on the device), and each
frontier row reads its two CSR offsets (2 x 4 B).
"""

from __future__ import annotations

BYTES_PER_EDGE = 8
BYTES_PER_ROW = 8


def traversal_bytes(edges: float, rows: float) -> float:
    return BYTES_PER_EDGE * edges + BYTES_PER_ROW * rows


def roofline_share(edges: float, rows: float, busy_s: float, peak_bytes_per_s: float,
                   devices: int = 1):
    """Percent of the memory roofline: the least time the chips could take
    for these bytes — all ``devices`` of them moving their share at one
    chip's peak each — over the time their operations ran, ``busy_s`` being
    the mean a chip (``tracered.reduce``).  None where there is nothing to
    read (no device work, or no busy time) — never 0."""
    if edges <= 0 or busy_s <= 0 or devices <= 0:
        return None
    return 100.0 * (traversal_bytes(edges, rows) / (devices * peak_bytes_per_s)) / busy_s
