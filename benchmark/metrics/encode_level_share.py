"""Layer encoder (query/outputnode.py): of the result objects the window's
answers were built from, the share the level encoder laid — a level at a
time — and not a depth-first walk (@normalize, @ignorereflex).
``dgraph_encode_objects_total{path}``, window deltas; nothing where the
program lacks the family or a label, or emitted no object."""


def read(obs):
    grown = obs.delta("dgraph_encode_objects_total")
    if "level" not in grown or "walk" not in grown:
        return None
    total = grown["level"] + grown["walk"]
    return 100.0 * grown["level"] / total if total > 0 else None
