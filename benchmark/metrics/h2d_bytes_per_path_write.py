"""Layer arenas (models/arena.py ``PathLayout``): bytes an acknowledged write
of the window put on the device for path searches' merged layouts — the
index vectors, offset rows and edge slots of a delta, or the three tables of a
rebuild (``dgraph_path_layout_h2d_bytes_total`` over
``dgraph_writes_total{result="ok"}``, window deltas; the same bytes are in
``h2d_bytes_per_query``).  Nothing where the program lacks either family or
acknowledged no write."""


def read(obs):
    grown = obs.delta("dgraph_path_layout_h2d_bytes_total")
    ok = obs.delta("dgraph_writes_total").get("ok")
    return sum(grown.values()) / ok if grown and ok else None
