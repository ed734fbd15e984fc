"""The least bytes a window's writes have to move on the device, as a
function of the WORK — the reference's count of what each acknowledged film
adds to the layouts the mix's reads walk (``reference_rw.Written.layout_touch``)
— and of nothing the program chose: a touched or new layout row is one
32-byte row written, a new overflow chunk another, a new uid -> row entry
4 bytes.  Whether the program scatters them, copies a table or builds it
anew is what a share of the roofline is there to show.
"""

from __future__ import annotations

BYTES_PER_ROW = 32
BYTES_PER_CHUNK = 32
BYTES_PER_LUT_ENTRY = 4


def write_bytes(rows: float, chunks: float, lut: float) -> float:
    return BYTES_PER_ROW * rows + BYTES_PER_CHUNK * chunks + BYTES_PER_LUT_ENTRY * lut
