"""Layer arenas (models/arena.py ``_layouts_take_delta``): of the writes that
reached an arena whose inline layout or LUT is on the device, the share that
built the layout anew from the host mirrors and did not scatter the delta
into the tables that are there — ``dgraph_arena_layout_updates_total{how}``,
window deltas.  Nothing where the program lacks the family or a label, or no
write reached a layout."""


def read(obs):
    grown = obs.delta("dgraph_arena_layout_updates_total")
    if "delta" not in grown or "rebuild" not in grown:
        return None
    total = grown["delta"] + grown["rebuild"]
    return 100.0 * grown["rebuild"] / total if total > 0 else None
