"""The least bytes a window's writes have to move on the device to bring a
path search's merged layout up to them, as a function of the WORK — the
reference's count of what each acknowledged film adds to a layout of the
listed directions (``reference_paths_rw.WrittenPaths.layout_touch``) — and of
nothing the program chose: a node that holds an edge is one 8-byte row of
offsets written (first and past-the-last slot), an edge slot is 8 bytes
(its target and its source).  Whether the program scatters them, copies the
tables or builds them anew is what a share of the roofline is there to show.
"""

from __future__ import annotations

BYTES_PER_ROW = 8
BYTES_PER_SLOT = 8


def write_bytes(rows: float, slots: float) -> float:
    return BYTES_PER_ROW * rows + BYTES_PER_SLOT * slots
