"""Layer engine / arenas: seconds the backend spent compiling (or reading a
program back from the persistent cache) inside the window
(``dgraph_xla_compile_seconds`` histogram sum, window delta).  ``compiles_in_window``
counts the events; this says whether they were cache reads or cold compiles."""


def read(obs):
    return sum(obs.delta("dgraph_xla_compile_seconds_sum").values())
