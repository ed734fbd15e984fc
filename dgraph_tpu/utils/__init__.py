"""Shared infra: metrics, tracing, watermarks, health, config.

Equivalent of the reference's x/ package (x/metrics.go, x/watermark.go,
x/health.go, x/config.go, x/error.go) re-done as plain Python with a
Prometheus text exposition endpoint instead of expvar bridging.
"""

from dgraph_tpu.utils.metrics import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    metrics,
)
from dgraph_tpu.utils.trace import Latency
from dgraph_tpu.utils.watermark import WaterMark
from dgraph_tpu.utils.health import HealthGate
from dgraph_tpu.utils.config import Options

__all__ = [
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "metrics",
    "Latency",
    "WaterMark",
    "HealthGate",
    "Options",
]
