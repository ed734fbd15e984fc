"""The comparison that decides ``correct``: every answer the window got,
against the plain reference's walk over the same generated arrays.

Run after the window has closed and the server has ended.  Numbers compared,
each with its limit (all exact, so every limit is 0 — see PERF.md):

``unanswered``        requests with no HTTP 200 answer, or none at all
``wrong``             answers whose JSON says something else than the reference
                      (objects per level, uid multiset per level, uid() sets)
``compared``          answers compared; its limit is a floor: at least 1
``ledger_edges_off``  warm-up answers that were executed (no cache hit, no
                      coalescing, no repair) whose ledger ``edges`` differ
                      from the reference's traversal.  Warm-up calls are
                      sequential; inside the window the scheduler merges
                      hops across callers and books the union's edges to
                      the caller that led the dispatch, so a request's own
                      ledger is not its own there (serve/server.py says so)
``ledger_compared``   how many those were; floor 1

An answer to a text already verified in this run is compared byte for byte
with the verified one (all but the ``server_latency``/``extensions`` tail,
which the server appends last) and parsed in full only where that differs.
"""

from __future__ import annotations

import json

import harness

TAIL = b'"server_latency"'


def split(body: bytes):
    """(head bytes, tail dict): the answer, and what the server appended."""
    at = body.rfind(TAIL)
    if at <= 0:
        return body, {}
    try:
        return body[:at], json.loads(b"{" + body[at:])
    except ValueError:
        return body, {}


def executed(ledger: dict) -> bool:
    """True where the request's own engine shell traversed every hop: no
    cache tier answered any part, no twin's result was shared."""
    return bool(ledger) and not (
        ledger.get("cache_hits") or ledger.get("coalesced") or ledger.get("repairs")
    )


def compare(records, classes: dict, answer_of=None, tag: str = "") -> dict:
    """``records`` as the generator returns them; ``tag`` the block alias
    the window's texts carried.  ``answer_of(class, root)``
    puts a control's answer in the program's place.  Returns the numbers
    compared, the per-record verdicts and what the per-layer readers need:
    {"numbers", "ok": [bool], "expect": [dict|None], "tails": [dict]}."""
    expect_memo, verified = {}, {}
    ok, expects, tails, first_words = [], [], [], []
    n = {"unanswered": 0, "wrong": 0, "compared": 0}
    for rec in records:
        _, cls, root, _, _, status, body = rec
        key = (cls, root)
        if answer_of is not None and status == 200:
            body = answer_of(cls, root)
        if status != 200:
            n["unanswered"] += 1
            ok.append(False)
            expects.append(None)
            tails.append({})
            if len(first_words) < 5:
                first_words.append(f"{cls} root {root}: HTTP {status} {body[:200]!r}")
            continue
        kind = classes[cls]
        exp = expect_memo.get(key)
        if exp is None:
            exp = expect_memo[key] = kind.expect(root)
        head, tail = split(body)
        n["compared"] += 1
        if verified.get(key) == head:
            problem = None
        else:
            try:
                problem = kind.check(json.loads(body), exp, tag)
            except ValueError as e:
                problem = f"{cls}: the answer is no JSON: {e}"
            if problem is None:
                verified[key] = head
        if problem is not None:
            n["wrong"] += 1
            if len(first_words) < 5:
                first_words.append(f"root {root}: {problem}")
        ok.append(problem is None)
        expects.append(exp)
        tails.append(tail)
    return {"numbers": n, "ok": ok, "expect": expects, "tails": tails,
            "first_words": first_words}


def compare_warm(warm_records, classes: dict) -> dict:
    """The warm-up's answers — (class, root, alias tag, body), sent one at a
    time — against the reference: the JSON as in the window, and the
    ledger's ``edges`` of those that were executed."""
    n = {"warm_wrong": 0, "ledger_edges_off": 0, "ledger_compared": 0}
    words = []
    for cls, root, tag, body in warm_records:
        kind = classes[cls]
        exp = kind.expect(root)
        _, tail = split(body)
        try:
            problem = kind.check(json.loads(body), exp, tag)
        except ValueError as e:
            problem = f"{cls}: the answer is no JSON: {e}"
        if problem is not None:
            n["warm_wrong"] += 1
            words.append(f"warm-up root {root}: {problem}")
            continue
        led = (tail.get("extensions") or {}).get("ledger") or {}
        if executed(led):
            n["ledger_compared"] += 1
            if led.get("edges") != exp["edges"]:
                n["ledger_edges_off"] += 1
                words.append(f"warm-up {cls} root {root}: ledger edges "
                             f"{led.get('edges')}, the reference {exp['edges']}")
    return {"numbers": n, "first_words": words[:5]}


LIMITS = {"unanswered": ("max", 0), "wrong": ("max", 0), "compared": ("min", 1),
          "warm_wrong": ("max", 0), "ledger_edges_off": ("max", 0),
          "ledger_compared": ("min", 1)}


def verdict(numbers: dict) -> tuple:
    """(correct, {name: {"value", "limit"}}) — each number beside its limit."""
    shown, good = {}, True
    for name, (side, lim) in LIMITS.items():
        if name not in numbers:
            continue
        v = numbers[name]
        shown[name] = {"value": v, "limit": f"{'<=' if side == 'max' else '>='} {lim}"}
        good &= (v <= lim) if side == "max" else (v >= lim)
    return good, shown


def server_seconds(tails, key: str) -> list:
    """The ``server_latency[key]`` of each answer, in seconds (None where
    the answer carries none)."""
    return [harness.duration_s((t.get("server_latency") or {}).get(key)) for t in tails]
