"""The client-visible latency map {parsing, processing, json}
(query/query.go:102-119).  Sampled request traces are the flight
recorder's (obs/spans.py, ``/debug/traces``); where a request's time
went, stage by stage, is the ledger's (obs/ledger.py STAGES).
"""

from __future__ import annotations

import time


def _fmt_ns(ns: int) -> str:
    """Render a duration the way Go's time.Duration.String does
    (the reference returns e.g. '79.3ms' in latency maps)."""
    if ns < 1_000:
        return f"{ns}ns"
    if ns < 1_000_000:
        us = ns / 1_000
        return f"{us:.6g}µs"
    if ns < 1_000_000_000:
        return f"{ns / 1_000_000:.6g}ms"
    return f"{ns / 1_000_000_000:.6g}s"


class Latency:
    """Per-request stage timing; .to_map() is what goes in the response
    (mirrors query.Latency ToMap, query/query.go:102-119)."""

    def __init__(self):
        self.start = time.perf_counter_ns()
        self.parsing_ns = 0
        self.processing_ns = 0
        self.json_ns = 0

    def _mark(self) -> int:
        now = time.perf_counter_ns()
        elapsed = now - self.start
        self.start = now
        return elapsed

    def record_parsing(self) -> None:
        self.parsing_ns = self._mark()

    def record_processing(self) -> None:
        self.processing_ns = self._mark()

    def record_json(self) -> None:
        self.json_ns = self._mark()

    def total_ns(self) -> int:
        return self.parsing_ns + self.processing_ns + self.json_ns

    def to_map(self) -> dict:
        out = {"total": _fmt_ns(self.total_ns())}
        if self.parsing_ns:
            out["parsing"] = _fmt_ns(self.parsing_ns)
        if self.processing_ns:
            out["processing"] = _fmt_ns(self.processing_ns)
        if self.json_ns:
            out["json"] = _fmt_ns(self.json_ns)
        return out
