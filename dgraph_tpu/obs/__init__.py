"""dgraph_tpu.obs — the end-to-end query flight recorder.

Span-based tracing across scheduler, cache, engine, WAL and peer RPCs,
with W3C ``traceparent`` propagation, head + slow-tail sampling, a
bounded trace ring (``/debug/traces``), a structured slow-query log and
Chrome ``trace_event`` export.  See obs/spans.py for the design and
docs/deploy.md for the operator surface.
"""

from dgraph_tpu.obs import device, ledger  # noqa: F401 — submodule surface
from dgraph_tpu.obs.export import chrome_trace
from dgraph_tpu.obs.spans import (
    NOOP,
    FlightRecorder,
    Sampler,
    Span,
    TraceContext,
    annotation,
    block_ready_ms,
    child,
    configure,
    current_span,
    format_traceparent,
    parse_traceparent,
    server_span,
    span_ns,
    stage,
    start_request,
)


def get_recorder() -> FlightRecorder:
    """The live process recorder (configure() swaps it; always read
    through this or the spans module attribute, never a stale import)."""
    from dgraph_tpu.obs import spans

    return spans.recorder


__all__ = [
    "FlightRecorder",
    "NOOP",
    "Sampler",
    "Span",
    "TraceContext",
    "annotation",
    "block_ready_ms",
    "child",
    "chrome_trace",
    "configure",
    "current_span",
    "format_traceparent",
    "get_recorder",
    "parse_traceparent",
    "server_span",
    "span_ns",
    "stage",
    "start_request",
]
