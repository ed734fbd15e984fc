"""Closed loop: ``clients`` callers, each POSTing the next request of the
mix's one sequence to ``/query`` and taking another when the answer is back — the upstream's own
harness (contrib/freebase/*_test.go: sequential callers).  No rate to find:
a slower server is offered less.

Each caller keeps one HTTP/1.1 connection.  The raw bytes of every answer
are kept and nothing is parsed here: the comparison runs after the window,
so it does not compete with the server for the host's cores.  A request in
flight when the window closes is waited for (its latency counts the wait);
none is sent after the close.
"""

from __future__ import annotations

import http.client
import itertools
import threading
import time
from urllib.parse import urlparse

STRAGGLER_S = 60.0   # how long past the close an answer is waited for


def drive(addr: str, path: str, plan: list, texts, seconds: float, mix: dict) -> dict:
    """Runs the window.  ``plan``: [(class, root)], drawn from in order by
    whichever caller is free (so the window sends a PREFIX of it, whatever
    the callers' luck); ``texts``: (class, root) -> query text; ``mix``: the
    traffic file (``clients``).
    Returns {"t_open", "t_close", "never_answered", "records"}, a record
    being (client, class, root, t_send, t_done, status, body); a request
    that got no answer has status 0 and the error's text as body."""
    u = urlparse(addr)
    start = threading.Event()
    bounds = {}
    clients = int(mix["clients"])
    records = [[] for _ in range(clients)]
    turn = itertools.count()   # next() is atomic under the interpreter lock

    def caller(c: int) -> None:
        conn = http.client.HTTPConnection(u.hostname, u.port, timeout=seconds + STRAGGLER_S)
        out = records[c]
        start.wait()
        close_at = bounds["close"]
        while True:
            t0 = time.monotonic()
            if t0 >= close_at:
                break
            cls, root = plan[next(turn) % len(plan)]
            try:
                conn.request("POST", path, body=texts(cls, root).encode())
                r = conn.getresponse()
                body = r.read()
                status = r.status
            except (OSError, http.client.HTTPException) as e:
                status, body = 0, repr(e).encode()
                conn.close()
                conn = http.client.HTTPConnection(u.hostname, u.port,
                                                  timeout=seconds + STRAGGLER_S)
            out.append((c, cls, root, t0, time.monotonic(), status, body))
        conn.close()

    threads = [threading.Thread(target=caller, args=(c,), name=f"caller-{c}", daemon=True)
               for c in range(clients)]
    for t in threads:
        t.start()
    t_open = time.monotonic()
    bounds["close"] = t_open + seconds
    start.set()
    for t in threads:
        t.join(timeout=max(0.0, bounds["close"] + STRAGGLER_S + 5.0 - time.monotonic()))
    alive = [t.name for t in threads if t.is_alive()]
    return {
        "t_open": t_open, "t_close": bounds["close"], "never_answered": alive,
        "records": [r for per in records for r in per],
    }
