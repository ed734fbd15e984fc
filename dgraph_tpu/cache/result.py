"""Tier 2: whole-response memoization in front of the cohort scheduler.

The scheduler's singleflight (sched/scheduler.py) already collapses
identical requests that overlap in time; this tier extends the reuse
window from "while a twin is in flight" to "until the next mutation":
``(request key, store version) → the answer's encoded body``.  A
hit skips parsing's downstream entirely — no admission, no cohort
wait, no engine shell, no read-lock acquisition — which under zipf
traffic converts the head of the popularity curve into dict probes.

The UNIT is the body as it is sent: ``json.dumps`` of the answer's
blocks, the bytes that go on the socket, priced at their length.  A
request's blocks are serialised once (``Answer``): the miss hands those
bytes to the cache and to the socket, singleflight twins share them,
and a hit splices the per-request tail (``server_latency``,
``extensions``) round the stored bytes — no tree is kept alive and none
is walked.  Surfaces that need the tree (protobuf, gRPC, subscriptions)
decode the stored body on a hit.

The request key is the serving layer's singleflight key — query text +
canonical (sorted-JSON) variables + debug flag — digested so the cache
holds no unbounded query texts.  Responses that depend on wall-clock
(``math(since(...))``) are detected at parse shape and never cached.

Invalidation is the shared snapshot-version scheme (cache/core.py),
SCOPED since IVM (dgraph_tpu/ivm/): the scheduler keys each entry on
the max last-mutation version over the request's referenced-predicate
footprint (ivm/versions.py::result_version; the global
``store.version`` when the footprint is unknowable or under
``DGRAPH_TPU_IVM=0``), so a mutation only kills the responses that
actually read its predicates; stale entries die logically at the
version advance and are reclaimed by the incremental sweep.

Knobs: ``DGRAPH_TPU_CACHE`` (shared gate),
``DGRAPH_TPU_CACHE_RESULT_BYTES`` (budget, default 32 MiB, 0 disables
this tier only).
"""

from __future__ import annotations

import hashlib
import json
import threading
from typing import Callable, Optional, Tuple

from dgraph_tpu import obs
from dgraph_tpu.cache.core import VersionedLFUCache, env_bytes
from dgraph_tpu.obs import ledger
from dgraph_tpu.utils.metrics import (
    QCACHE_HIT_AGE,
    QCACHE_RESULT_BYTES,
    QCACHE_RESULT_EVENTS,
)

_DEFAULT_BUDGET = 32 << 20


def request_digest(key) -> bytes:
    """Normalized request digest: the serving layer's (text, canonical
    vars, debug) singleflight key, hashed so cache keys are fixed-size."""
    h = hashlib.blake2b(digest_size=16)
    for part in key:
        h.update(repr(part).encode("utf-8", "surrogatepass"))
        h.update(b"\x00")
    return h.digest()


# what the server appends to an answer's blocks, per request
TAIL_KEYS = ("server_latency", "extensions")


def cacheable(parsed) -> bool:
    """A parsed request whose response is a pure function of (query,
    store snapshot): read-only and free of wall-clock math.  Mutations
    never reach the scheduler path, but the guard is cheap and keeps
    this module's contract self-contained.  A block NAMED like one of
    the tail's keys is refused too: its reply is no splice (the tail's
    value takes the block's place, ``Answer.reply``)."""
    if parsed.mutation is not None:
        return False

    def clock_free(mt) -> bool:
        if mt is None:
            return True
        if getattr(mt, "fn", None) == "since":
            return False
        return all(clock_free(c) for c in getattr(mt, "children", ()))

    def walk(q) -> bool:
        if not clock_free(getattr(q, "math_exp", None)):
            return False
        return all(walk(c) for c in q.children)

    return all(
        q.alias not in TAIL_KEYS and q.attr not in TAIL_KEYS and walk(q)
        for q in parsed.queries
    )


class Answer:
    """One execution's answer blocks (what ``run_parsed`` returns, before
    the server appends its tail) in the two forms the surfaces ask for:
    the tree, and the encoded body — ``json.dumps`` of the tree, the
    bytes that go on the socket and the unit the result cache holds.

    Whichever form is missing is made on first use, ONCE: singleflight
    twins are dealt the same Answer, so K twins cost one serialisation,
    and a cache hit is an Answer of the stored bytes that decodes only
    for a surface that needs the tree.  Read-only, like the tree."""

    __slots__ = ("_tree", "_body", "_lock", "_keep")

    def __init__(
        self, tree: Optional[dict] = None, body: Optional[bytes] = None
    ):
        self._tree = tree
        self._body = body
        self._lock = threading.Lock()
        self._keep: Optional[Callable[[bytes], None]] = None

    def tree(self) -> dict:
        tree = self._tree
        if tree is None:
            tree = self._tree = json.loads(self._body)
        return tree

    def body(self) -> bytes:
        body = self._body
        if body is None:
            with self._lock:  # twins wait here for the one encoding
                body = self._body
                keep = None
                if body is None:
                    body = self._body = json.dumps(self._tree).encode()
                    keep, self._keep = self._keep, None
            if keep is not None:
                keep(body)
        return body

    def keep(self, put: Callable[[bytes], None]) -> None:
        """Hand the body to ``put`` (the result cache's) where the
        serialisation happens, whoever pays it: now if it has, else
        from ``body()``.  One is enough: a twin's later ``put`` is
        dropped."""
        with self._lock:
            body = self._body
            if body is None:
                if self._keep is None:
                    self._keep = put
                return
        put(body)

    def reply(self, tail: dict) -> bytes:
        """The response as sent: the body with the per-request ``tail``
        (``TAIL_KEYS``) spliced in before its closing brace — byte for
        byte ``json.dumps({**tree, **tail})``, with no tree needed."""
        tree = self._tree
        if tree is not None and not tail.keys().isdisjoint(tree):
            # a block named like a tail key (never cached, so the tree
            # is at hand): the tail's value in the block's place
            return json.dumps({**tree, **tail}).encode()
        body = self.body()
        if not tail:
            return body
        end = json.dumps(tail).encode()
        if len(body) == 2:  # b"{}": no block, no comma
            return end
        return b"".join((memoryview(body)[:-1], b", ", memoryview(end)[1:]))


class ResultCache:
    """One per server: responses are store-snapshot state."""

    def __init__(self, budget_bytes: Optional[int] = None):
        self._c = VersionedLFUCache(
            budget_bytes=(
                budget_bytes
                if budget_bytes is not None
                else env_bytes(
                    "DGRAPH_TPU_CACHE_RESULT_BYTES", _DEFAULT_BUDGET
                )
            ),
            stats_hook=self._on_event,
        )

    def _on_event(self, event: str, entry) -> None:
        QCACHE_RESULT_EVENTS.add(event)
        QCACHE_RESULT_BYTES.set(self._c.occupancy_bytes)

    @property
    def occupancy_bytes(self) -> int:
        return self._c.occupancy_bytes

    def __len__(self) -> int:
        return len(self._c)

    def hits(self) -> int:
        return QCACHE_RESULT_EVENTS.snapshot().get("hit", 0)

    def get(self, key, version: int) -> Optional[Tuple[bytes, dict]]:
        """(body, stats) for the request ``key`` at ``version``, or None.
        ``stats`` is the execution's engine stats where the entry was
        stored with them (a ``debug`` key), else empty."""
        sp = obs.current_span()
        if sp is None:  # unsampled hot path: probe only
            hit, ev, nb = self._c.get_ev(request_digest(key), version)
        else:
            # sampled: a tier-2 hit is the single most latency-deciding
            # event a request can have — the span says so explicitly
            # (outcome + the stored size)
            with sp.child("cache.result") as cs:
                hit, ev, nb = self._c.get_ev(request_digest(key), version)
                cs.set_attr("outcome", ev)
                if hit is not None:
                    cs.set_attr("bytes", nb)
        led = ledger.current()
        if led is not None:
            # a tier-2 hit is the whole request's account: no engine
            # numbers ever merge in, so the cost story reads "served
            # from cache for free", which is the truth
            led.note_cache("result", ev, nb or 0)
        if hit is None:
            return None
        (body, stats), age = hit
        QCACHE_HIT_AGE.observe(age)
        return body, json.loads(stats) if stats else {}

    def put(
        self, key, version: int, body: bytes, stats: Optional[dict] = None
    ) -> None:
        """Store an answer's encoded ``body`` at its length.  ``stats``
        (the engine's, for a ``debug`` request's latency map) is kept
        encoded beside it and counted the same way."""
        k = request_digest(key)
        # singleflight twins and surfaces race to store one answer: the
        # first did (benign: a double put re-stores the same value)
        if self._c.contains(k, version):
            return
        blob = json.dumps(stats).encode() if stats else b""
        self._c.put(k, version, (body, blob), len(body) + len(blob))
        # admissions and sweeps change occupancy without a get-event
        QCACHE_RESULT_BYTES.set(self._c.occupancy_bytes)

    def clear(self) -> None:
        self._c.clear()
        QCACHE_RESULT_BYTES.set(0)
