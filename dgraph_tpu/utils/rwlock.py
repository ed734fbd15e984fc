"""Writer-preferring readers-writer lock.

The reference runs every request concurrently (per-request goroutines,
query/query.go:1684-1714) over posting lists guarded by per-list RWMutex
(posting/list.go).  Our read path shares immutable device arenas between
mutations, so the serving layer needs exactly one coarse RW lock: many
concurrent read-only queries, exclusive mutations.  Python's stdlib has no
RW lock; this is the classic two-condition implementation with writer
preference (a waiting writer blocks new readers, so a mutation stream
cannot be starved by a heavy read load).

NOT reentrant, on either side.  A thread already holding the read side
that re-acquires it deadlocks whenever a writer is queued (new readers
block on _writers_waiting) — and the deadlock is load-dependent, so it
would pass quiet tests and hang in production.  acquire_read therefore
tracks holder thread idents and raises RuntimeError on recursive
acquisition instead of deadlocking.

The lock keeps one clock of its own, ``barred_s``: the seconds in which a
writer held or awaited the exclusive side, so a reader arriving then had
to wait.  It is monotone, and the overlap of any interval with the barred
time is the clock's growth across it — two reads, no list of intervals
(sched/cohort.py books a queued request's ``writer`` wait from it).
"""

from __future__ import annotations

import threading
import time
from contextlib import contextmanager


class RWLock:
    def __init__(self) -> None:
        self._cond = threading.Condition()
        self._readers = 0          # active readers
        self._writer = False       # a writer holds the lock
        self._writers_waiting = 0  # writers queued (blocks new readers)
        self._reader_idents: set[int] = set()  # recursive-read detection
        # the readers-barred clock: closed barred seconds, and the start
        # of the open interval (None while readers may enter)
        self._barred_total = 0.0
        self._barred_since: float | None = None

    def _note_barred(self) -> None:
        """Open or close the barred interval (caller holds _cond)."""
        barred = self._writer or self._writers_waiting > 0
        if barred == (self._barred_since is not None):
            return
        now = time.monotonic()
        if barred:
            self._barred_since = now
        else:
            self._barred_total += now - self._barred_since
            self._barred_since = None

    def barred_s(self) -> float:
        """Seconds so far in which new readers were barred, an interval
        still open counted up to this read.  Read under the lock's own
        mutex (held for a few instructions): a read racing the close of
        an interval could otherwise overshoot the total it closes to, and
        the clock would run backwards."""
        with self._cond:
            since = self._barred_since
            now = time.monotonic()
            return self._barred_total + (now - since if since is not None else 0.0)

    def acquire_read(self) -> None:
        ident = threading.get_ident()
        with self._cond:
            if ident in self._reader_idents:
                raise RuntimeError(
                    "recursive RWLock.acquire_read from the same thread "
                    "(would deadlock whenever a writer is queued)"
                )
            while self._writer or self._writers_waiting:
                self._cond.wait()
            self._readers += 1
            self._reader_idents.add(ident)

    def release_read(self) -> None:
        with self._cond:
            self._readers -= 1
            self._reader_idents.discard(threading.get_ident())
            if self._readers == 0:
                self._cond.notify_all()

    def acquire_write(self) -> None:
        with self._cond:
            self._writers_waiting += 1
            self._note_barred()
            try:
                while self._writer or self._readers:
                    self._cond.wait()
                self._writer = True
            finally:
                self._writers_waiting -= 1
                self._note_barred()

    def release_write(self) -> None:
        with self._cond:
            self._writer = False
            self._note_barred()
            self._cond.notify_all()

    @contextmanager
    def read(self):
        self.acquire_read()
        try:
            yield
        finally:
            self.release_read()

    @contextmanager
    def write(self):
        self.acquire_write()
        try:
            yield
        finally:
            self.release_write()
