"""Layer arenas (models/arena.py): bytes an acknowledged write of the window
put on the device to bring the layouts up to it — index vectors, rows and
chunks of a delta, or a whole table of a rebuild
(``dgraph_arena_refresh_h2d_bytes_total`` over
``dgraph_writes_total{result="ok"}``, window deltas; the same bytes are in
``h2d_bytes_per_query``, on the writer's account).  Nothing where the program
lacks either family or acknowledged no write."""


def read(obs):
    grown = obs.delta("dgraph_arena_refresh_h2d_bytes_total")
    ok = obs.delta("dgraph_writes_total").get("ok")
    return sum(grown.values()) / ok if grown and ok else None
