"""Layer entry (serve/server.py: HTTP + JSON): the median, over the window's
answered requests, of the client's latency less the server's own
``server_latency.total`` — what the socket, the handler and the JSON encoder
add round the engine.  Host clock on both sides."""

import statistics

import compare


def read(obs):
    over = [
        (r[4] - r[3]) - t
        for r, t in zip(obs.records, compare.server_seconds(obs.tails, "total"))
        if r[5] == 200 and t is not None
    ]
    return 1e3 * statistics.median(over) if over else None
