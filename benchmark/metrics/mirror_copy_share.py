"""Layer arenas (models/arena.py ``_take_delta_host``): of the deltas a window's
writes brought to cached arenas' host mirrors, the share that copied the
mirrors whole (a delete, or an edge of a row that was there) and was not
written into the room at their end —
``dgraph_arena_mirror_updates_total{how}``, window deltas: ``copy`` over
``append`` + ``grow`` + ``copy``.  Nothing where the program lacks the family
or a label, or no delta reached a mirror."""


def read(obs):
    grown = obs.delta("dgraph_arena_mirror_updates_total")
    if any(how not in grown for how in ("append", "grow", "copy")):
        return None
    total = grown["append"] + grown["grow"] + grown["copy"]
    return 100.0 * grown["copy"] / total if total > 0 else None
