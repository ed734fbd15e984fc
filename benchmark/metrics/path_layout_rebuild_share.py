"""Layer arenas (models/arena.py ``ArenaManager._path_layouts_take``): of the
writes that reached a cached ``PathLayout`` — a path search's merged layout —
the share that built it anew from its arenas (or dropped it for the next
search to build) and did not scatter the delta into the tables that are
there — ``dgraph_path_layout_updates_total{how}``, window deltas.  Nothing
where the program lacks the family or a label, or no write reached a layout."""


def read(obs):
    grown = obs.delta("dgraph_path_layout_updates_total")
    if "delta" not in grown or "rebuild" not in grown:
        return None
    total = grown["delta"] + grown["rebuild"]
    return 100.0 * grown["rebuild"] / total if total > 0 else None
