"""Closed loop with followers: ``generators/closed.py``'s loop — ``clients``
callers, one shared sequence, no think time, nothing parsed here — plus ONE
rule, for mixes whose writes are read back:

    a caller whose request of class X was acknowledged with HTTP 200 sends,
    itself and at once, the request (``mix["follow"][X]``, same root) before
    it draws from the sequence again.

So the follower is sent by the same caller, after the ack, never before: what
it reads is what an acknowledged write has to show.  It is a record like any
other (its class is the follower's), compared like any other.  A follower is
sent even where its write's answer came back past the close — it belongs to
its write; nothing is DRAWN after the close.

How to use it: name it as a mix's ``generator`` and give the mix a ``follow``
map; list the follower among ``classes`` with weight 0, after the class it
follows, so that the warm-up ladder knows it and the deck never deals it.

A mix's ``needs`` (counter families the program has to expose) are read at
boot by ``run.set_up``, for a mix of any generator, before the load and the
warm-up ladder; ``drive`` asks again, through the same reader
(``harness.unmet_needs``), before it drives a loop, so none is ever driven at
a program that lacks them.
"""

from __future__ import annotations

import http.client
import itertools
import threading
import time
from urllib.parse import urlparse

import harness

STRAGGLER_S = 60.0   # how long past the close an answer is waited for


def drive(addr: str, path: str, plan: list, texts, seconds: float, mix: dict) -> dict:
    """As ``closed.drive``: {"t_open", "t_close", "never_answered",
    "records"}, a record being (client, class, root, t_send, t_done, status,
    body); a request that got no answer has status 0 and the error's text."""
    u = urlparse(addr)
    why = harness.unmet_needs(addr, mix)
    if why:
        raise RuntimeError(why)
    follow = dict(mix.get("follow") or {})
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
        owed = None            # the follower this caller owes: (class, root)
        while True:
            t0 = time.monotonic()
            if owed is not None:
                (cls, root), owed = owed, None
            elif t0 >= close_at:
                break
            else:
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
            if status == 200 and cls in follow:
                owed = (follow[cls], root)
        conn.close()

    threads = [threading.Thread(target=caller, args=(c,), name=f"caller-{c}", daemon=True)
               for c in range(clients)]
    for t in threads:
        t.start()
    t_open = time.monotonic()
    bounds["close"] = t_open + seconds
    start.set()
    for t in threads:
        t.join(timeout=max(0.0, bounds["close"] + 2 * STRAGGLER_S + 5.0 - time.monotonic()))
    alive = [t.name for t in threads if t.is_alive()]
    return {
        "t_open": t_open, "t_close": bounds["close"], "never_answered": alive,
        "records": [r for per in records for r in per],
    }
