"""Metrics registry with Prometheus text exposition.

Equivalent of x/metrics.go (expvar counters bridged to a Prometheus
collector and served at /debug/prometheus_metrics).  The counter set
mirrors the reference's: posting reads/writes, cache hit/miss, pending
queries/proposals, per-predicate mutation counts (task.go PredicateStats).
"""

from __future__ import annotations

import threading
import time as _time
from typing import Dict, Optional


class Counter:
    """Monotonic counter (expvar.Int analog)."""

    __slots__ = ("name", "_v", "_lock")

    def __init__(self, name: str):
        self.name = name
        self._v = 0
        self._lock = threading.Lock()

    def add(self, n: int = 1) -> None:
        with self._lock:
            self._v += n

    def value(self) -> int:
        return self._v


class Gauge:
    """Settable gauge (expvar.Int used as a gauge in the reference)."""

    __slots__ = ("name", "_v", "_lock")

    def __init__(self, name: str):
        self.name = name
        self._v = 0.0
        self._lock = threading.Lock()

    def set(self, v: float) -> None:
        with self._lock:
            self._v = v

    def add(self, n: float = 1) -> None:
        with self._lock:
            self._v += n

    def value(self) -> float:
        return self._v


class LabeledCounter:
    """Counter family keyed by one label (the per-predicate Map in
    x/metrics.go / task.go:137 PredicateStats)."""

    def __init__(self, name: str, label: str):
        self.name = name
        self.label = label
        self._m: Dict[str, int] = {}
        self._lock = threading.Lock()

    def add(self, key: str, n: int = 1) -> None:
        with self._lock:
            self._m[key] = self._m.get(key, 0) + n

    def snapshot(self) -> Dict[str, int]:
        with self._lock:
            return dict(self._m)


class MultiLabeledCounter:
    """Counter family keyed by a label TUPLE — the resilience layer needs
    ``dgraph_peer_rpc_total{peer,op,outcome}``, and packing three axes
    into one string label would make per-axis aggregation in Prometheus
    impossible."""

    def __init__(self, name: str, labels):
        self.name = name
        self.labels = tuple(labels)
        self._m: Dict[tuple, int] = {}
        self._lock = threading.Lock()

    def add(self, key, n: int = 1) -> None:
        key = tuple(str(k) for k in key)
        if len(key) != len(self.labels):
            raise ValueError(
                f"{self.name}: expected {len(self.labels)} label values, "
                f"got {len(key)}"
            )
        with self._lock:
            self._m[key] = self._m.get(key, 0) + n

    def snapshot(self) -> Dict[tuple, int]:
        with self._lock:
            return dict(self._m)

    def total(self, **want) -> int:
        """Sum over series matching the given label=value filters."""
        idx = {l: i for i, l in enumerate(self.labels)}
        out = 0
        for key, v in self.snapshot().items():
            if all(key[idx[l]] == str(val) for l, val in want.items()):
                out += v
        return out


class FuncGauge:
    """Gauge whose value is computed at scrape time (process uptime,
    anything derived from a live clock).  The callable must be cheap and
    exception-free — it runs inside every exposition pass."""

    __slots__ = ("name", "_fn")

    def __init__(self, name: str, fn):
        self.name = name
        self._fn = fn

    def value(self) -> float:
        return float(self._fn())


class MultiLabeledGauge:
    """Gauge family keyed by a label TUPLE — ``dgraph_build_info`` is
    the canonical user: a constant-1 gauge whose labels carry the
    version/backend identity (the prometheus client_golang BuildInfo
    convention), which a single-label gauge cannot express."""

    def __init__(self, name: str, labels):
        self.name = name
        self.labels = tuple(labels)
        self._m: Dict[tuple, float] = {}
        self._lock = threading.Lock()

    def set(self, key, v: float) -> None:
        key = tuple(str(k) for k in key)
        if len(key) != len(self.labels):
            raise ValueError(
                f"{self.name}: expected {len(self.labels)} label values, "
                f"got {len(key)}"
            )
        with self._lock:
            self._m[key] = float(v)

    def snapshot(self) -> Dict[tuple, float]:
        with self._lock:
            return dict(self._m)


class LabeledGauge:
    """Gauge family keyed by one label (per-peer breaker state)."""

    def __init__(self, name: str, label: str):
        self.name = name
        self.label = label
        self._m: Dict[str, float] = {}
        self._lock = threading.Lock()

    def set(self, key: str, v: float) -> None:
        with self._lock:
            self._m[key] = v

    def value(self, key: str) -> float:
        with self._lock:
            return self._m.get(key, 0.0)

    def snapshot(self) -> Dict[str, float]:
        with self._lock:
            return dict(self._m)


class Histogram:
    """Fixed-bucket histogram with Prometheus `_bucket{le=...}` / `_sum` /
    `_count` exposition (the prometheus client_golang Histogram shape; the
    reference bridges expvar and loses distributions — queue-wait and
    end-to-end latency need percentiles, not means).

    Buckets optionally carry an OpenMetrics EXEMPLAR — the last
    (trace_id, value, wall timestamp) that landed in them — so the
    p99 bucket of ``dgraph_query_latency_seconds`` links straight to a
    trace in the flight-recorder ring (``/debug/traces/<id>``).
    Exemplars render only in the OpenMetrics exposition
    (``openmetrics_text``); the classic text format has no syntax for
    them."""

    __slots__ = (
        "name", "buckets", "_counts", "_sum", "_count", "_exemplars",
        "_lock",
    )

    def __init__(self, name: str, buckets):
        self.name = name
        self.buckets = tuple(sorted(float(b) for b in buckets))
        if not self.buckets:
            raise ValueError("histogram needs at least one bucket bound")
        # per-bucket (non-cumulative) counts; +Inf bucket is the tail slot
        self._counts = [0] * (len(self.buckets) + 1)
        # per-bucket last exemplar: (trace_id, value, wall_ts) or None
        self._exemplars = [None] * (len(self.buckets) + 1)
        self._sum = 0.0
        self._count = 0
        self._lock = threading.Lock()

    def observe(self, v: float, trace_id: Optional[str] = None) -> None:
        from bisect import bisect_left

        i = bisect_left(self.buckets, v)
        with self._lock:
            self._counts[i] += 1
            self._sum += v
            self._count += 1
            if trace_id:
                import time as _t

                # wall timestamp STORED, never used in interval math —
                # OpenMetrics exemplar timestamps are epoch seconds
                self._exemplars[i] = (trace_id, v, _t.time())

    def exemplars(self):
        """Per-bucket (trace_id, value, wall_ts) snapshot, aligned with
        buckets + [+Inf]."""
        with self._lock:
            return list(self._exemplars)

    def snapshot(self):
        """(cumulative bucket counts aligned with self.buckets + [+Inf],
        sum, count) — one consistent view."""
        with self._lock:
            counts = list(self._counts)
            s, c = self._sum, self._count
        cum = []
        run = 0
        for n in counts:
            run += n
            cum.append(run)
        return cum, s, c

    def count(self) -> int:
        return self._count

    def mean(self) -> float:
        with self._lock:
            return self._sum / self._count if self._count else 0.0


class LabeledHistogram:
    """Histogram family keyed by one label (per-tenant latency needs
    percentiles PER TENANT, and packing the tenant into the metric name
    would break every aggregation).  Series are created on first
    observe; the key space is BOUNDED (``max_series``) because label
    values may come from client input — the overflow tail collapses
    into one ``overflow`` series (the SAME sentinel qos.metric_label
    uses for counters, so latency and shed series for overflow
    tenants line up on a dashboard) instead of minting unbounded
    exposition lines."""

    def __init__(self, name: str, label: str, buckets, max_series: int = 64):
        self.name = name
        self.label = label
        self.buckets = tuple(sorted(float(b) for b in buckets))
        self.max_series = max_series
        self._m: Dict[str, Histogram] = {}
        self._lock = threading.Lock()

    def _get(self, key: str) -> Histogram:
        with self._lock:
            h = self._m.get(key)
            if h is None:
                if len(self._m) >= self.max_series:
                    key = "overflow"
                    h = self._m.get(key)
                if h is None:
                    h = self._m[key] = Histogram(self.name, self.buckets)
            return h

    def observe(self, key: str, v: float, trace_id: Optional[str] = None) -> None:
        self._get(str(key)).observe(v, trace_id=trace_id)

    def histogram(self, key: str) -> Optional[Histogram]:
        with self._lock:
            return self._m.get(key)

    def snapshot(self) -> Dict[str, tuple]:
        with self._lock:
            items = list(self._m.items())
        return {k: h.snapshot() for k, h in items}


class MetricsRegistry:
    def __init__(self):
        self._lock = threading.Lock()
        self._counters: Dict[str, Counter] = {}
        self._gauges: Dict[str, Gauge] = {}
        self._func_gauges: Dict[str, FuncGauge] = {}
        self._labeled: Dict[str, LabeledCounter] = {}
        self._multilabeled: Dict[str, MultiLabeledCounter] = {}
        self._labeled_gauges: Dict[str, LabeledGauge] = {}
        self._multilabeled_gauges: Dict[str, MultiLabeledGauge] = {}
        self._histograms: Dict[str, Histogram] = {}
        self._labeled_histograms: Dict[str, LabeledHistogram] = {}

    def counter(self, name: str) -> Counter:
        with self._lock:
            c = self._counters.get(name)
            if c is None:
                c = self._counters[name] = Counter(name)
            return c

    def gauge(self, name: str) -> Gauge:
        with self._lock:
            g = self._gauges.get(name)
            if g is None:
                g = self._gauges[name] = Gauge(name)
            return g

    def func_gauge(self, name: str, fn) -> FuncGauge:
        with self._lock:
            g = self._func_gauges.get(name)
            if g is None:
                g = self._func_gauges[name] = FuncGauge(name, fn)
            return g

    def labeled(self, name: str, label: str = "predicate") -> LabeledCounter:
        with self._lock:
            l = self._labeled.get(name)
            if l is None:
                l = self._labeled[name] = LabeledCounter(name, label)
            return l

    def multilabeled(self, name: str, labels) -> MultiLabeledCounter:
        with self._lock:
            c = self._multilabeled.get(name)
            if c is None:
                c = self._multilabeled[name] = MultiLabeledCounter(name, labels)
            return c

    def labeled_gauge(self, name: str, label: str) -> LabeledGauge:
        with self._lock:
            g = self._labeled_gauges.get(name)
            if g is None:
                g = self._labeled_gauges[name] = LabeledGauge(name, label)
            return g

    def multilabeled_gauge(self, name: str, labels) -> MultiLabeledGauge:
        with self._lock:
            g = self._multilabeled_gauges.get(name)
            if g is None:
                g = self._multilabeled_gauges[name] = MultiLabeledGauge(
                    name, labels
                )
            return g

    def histogram(self, name: str, buckets) -> Histogram:
        with self._lock:
            h = self._histograms.get(name)
            if h is None:
                h = self._histograms[name] = Histogram(name, buckets)
            return h

    def labeled_histogram(
        self, name: str, label: str, buckets
    ) -> LabeledHistogram:
        with self._lock:
            h = self._labeled_histograms.get(name)
            if h is None:
                h = self._labeled_histograms[name] = LabeledHistogram(
                    name, label, buckets
                )
            return h

    def prometheus_text(self) -> str:
        """Prometheus text exposition format (the collector at
        x/metrics.go:119 re-done natively)."""
        lines = []
        with self._lock:
            counters = list(self._counters.values())
            gauges = list(self._gauges.values())
            func_gauges = list(self._func_gauges.values())
            labeled = list(self._labeled.values())
            multilabeled = list(self._multilabeled.values())
            labeled_gauges = list(self._labeled_gauges.values())
            multilabeled_gauges = list(self._multilabeled_gauges.values())
            histograms = list(self._histograms.values())
            labeled_histograms = list(self._labeled_histograms.values())

        def _esc(s: str) -> str:
            return s.replace("\\", "\\\\").replace('"', '\\"')

        for c in sorted(counters, key=lambda c: c.name):
            lines.append(f"# TYPE {c.name} counter")
            lines.append(f"{c.name} {c.value()}")
        for g in sorted(gauges, key=lambda g: g.name):
            lines.append(f"# TYPE {g.name} gauge")
            lines.append(f"{g.name} {g.value()}")
        for fg in sorted(func_gauges, key=lambda g: g.name):
            lines.append(f"# TYPE {fg.name} gauge")
            lines.append(f"{fg.name} {fg.value():g}")
        for l in sorted(labeled, key=lambda l: l.name):
            lines.append(f"# TYPE {l.name} counter")
            for k, v in sorted(l.snapshot().items()):
                lines.append(f'{l.name}{{{l.label}="{_esc(k)}"}} {v}')
        for ml in sorted(multilabeled, key=lambda m: m.name):
            lines.append(f"# TYPE {ml.name} counter")
            for key, v in sorted(ml.snapshot().items()):
                pairs = ",".join(
                    f'{lab}="{_esc(val)}"' for lab, val in zip(ml.labels, key)
                )
                lines.append(f"{ml.name}{{{pairs}}} {v}")
        for lg in sorted(labeled_gauges, key=lambda g: g.name):
            lines.append(f"# TYPE {lg.name} gauge")
            for k, v in sorted(lg.snapshot().items()):
                lines.append(f'{lg.name}{{{lg.label}="{_esc(k)}"}} {v:g}')
        for mg in sorted(multilabeled_gauges, key=lambda g: g.name):
            lines.append(f"# TYPE {mg.name} gauge")
            for key, v in sorted(mg.snapshot().items()):
                pairs = ",".join(
                    f'{lab}="{_esc(val)}"' for lab, val in zip(mg.labels, key)
                )
                lines.append(f"{mg.name}{{{pairs}}} {v:g}")
        for h in sorted(histograms, key=lambda h: h.name):
            cum, s, c = h.snapshot()
            lines.append(f"# TYPE {h.name} histogram")
            for b, n in zip(h.buckets, cum):
                lines.append(f'{h.name}_bucket{{le="{b:g}"}} {n}')
            lines.append(f'{h.name}_bucket{{le="+Inf"}} {c}')
            lines.append(f"{h.name}_sum {s:g}")
            lines.append(f"{h.name}_count {c}")
        for lh in sorted(labeled_histograms, key=lambda h: h.name):
            lines.append(f"# TYPE {lh.name} histogram")
            for key, (cum, s, c) in sorted(lh.snapshot().items()):
                kq = _esc(key)
                for b, n in zip(lh.buckets, cum):
                    lines.append(
                        f'{lh.name}_bucket{{{lh.label}="{kq}",le="{b:g}"}} {n}'
                    )
                lines.append(
                    f'{lh.name}_bucket{{{lh.label}="{kq}",le="+Inf"}} {c}'
                )
                lines.append(f'{lh.name}_sum{{{lh.label}="{kq}"}} {s:g}')
                lines.append(f'{lh.name}_count{{{lh.label}="{kq}"}} {c}')
        return "\n".join(lines) + "\n"

    def openmetrics_text(self) -> str:
        """OpenMetrics exposition: the classic body plus histogram
        bucket EXEMPLARS (``# {trace_id="..."} value timestamp``) and
        the mandatory ``# EOF`` terminator.  Served when a scraper
        negotiates ``application/openmetrics-text`` on /metrics —
        exemplars are how ``dgraph_query_latency_seconds`` buckets link
        to live traces in the flight-recorder ring.  Series names match
        the classic exposition exactly (no ``_total`` re-suffixing), so
        dashboards keep working across the negotiation boundary."""
        classic = self.prometheus_text()
        with self._lock:
            histograms = list(self._histograms.values())
        # keyed by the bucket-line PREFIX (name + le label), never the
        # count: the classic body and the exemplar snapshot are taken at
        # different instants, and a concurrent observe() between them
        # must not strip exemplars from every bucket it bumped
        ex_by_prefix: Dict[str, str] = {}
        for h in histograms:
            exemplars = h.exemplars()
            bounds = [f"{b:g}" for b in h.buckets] + ["+Inf"]
            for bound, ex in zip(bounds, exemplars):
                if ex is None:
                    continue
                trace_id, v, ts = ex
                ex_by_prefix[f'{h.name}_bucket{{le="{bound}"}} '] = (
                    f' # {{trace_id="{trace_id}"}} {v:g} {ts:.3f}'
                )
        out = []
        for line in classic.splitlines():
            if "_bucket{" in line:
                cut = line.index("} ") + 2
                suffix = ex_by_prefix.get(line[:cut])
                if suffix is not None:
                    line += suffix
            out.append(line)
        out.append("# EOF")
        return "\n".join(out) + "\n"


# Global registry with the reference's standard counter set pre-named
# (x/metrics.go:27-58); components fetch these by name.
metrics = MetricsRegistry()

POSTING_READS = metrics.counter("dgraph_posting_reads_total")
POSTING_WRITES = metrics.counter("dgraph_posting_writes_total")
CACHE_HIT = metrics.counter("dgraph_cache_hits_total")
CACHE_MISS = metrics.counter("dgraph_cache_miss_total")
PENDING_QUERIES = metrics.gauge("dgraph_pending_queries")
PENDING_PROPOSALS = metrics.gauge("dgraph_pending_proposals")
NUM_QUERIES = metrics.counter("dgraph_num_queries_total")
NUM_MUTATIONS = metrics.counter("dgraph_num_mutations_total")
ARENA_BYTES = metrics.gauge("dgraph_arena_bytes")
NUM_GRPC_RUNS = metrics.counter("dgraph_grpc_runs_total")
NUM_GRPC_RAFT = metrics.counter("dgraph_grpc_raft_frames_total")
MAX_PL_LENGTH = metrics.gauge("dgraph_max_posting_list_length")
PREDICATE_STATS = metrics.labeled("dgraph_predicate_mutations_total")

# latency bucket ladder shared by the serving histograms (seconds):
# sub-ms through 10s, roughly ×2.5 steps — the client_golang DefBuckets
_LATENCY_BUCKETS = (
    0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
    0.25, 0.5, 1.0, 2.5, 5.0, 10.0,
)

TENANT_LATENCY = metrics.labeled_histogram(
    "dgraph_tenant_query_latency_seconds", "tenant", _LATENCY_BUCKETS
)

# cohort scheduler surface (sched/scheduler.py): how full cohorts ride,
# why they flushed, how long requests queued, end-to-end query latency
QUERY_LATENCY = metrics.histogram(
    "dgraph_query_latency_seconds", _LATENCY_BUCKETS
)
SCHED_QUEUE_WAIT = metrics.histogram(
    "dgraph_sched_queue_wait_seconds", _LATENCY_BUCKETS
)
SCHED_COHORT_OCCUPANCY = metrics.histogram(
    "dgraph_sched_cohort_occupancy", (1, 2, 4, 8, 16, 32, 64, 128)
)
SCHED_FLUSHES = metrics.labeled("dgraph_sched_flushes_total", label="reason")
SCHED_SHED = metrics.labeled("dgraph_sched_shed_total", label="reason")
SCHED_MERGED_HOPS = metrics.counter("dgraph_sched_merged_hops_total")
SCHED_COALESCED = metrics.counter("dgraph_sched_coalesced_requests_total")
SCHED_QUEUE_DEPTH = metrics.gauge("dgraph_sched_queue_depth")

# multi-tenant QoS surface (sched/qos.py): every cancelled query lands
# in QUERY_CANCELLED with {reason ∈ deadline/disconnect/admin, tenant};
# per-tenant sheds (quota / overload / deadline) in TENANT_SHED; and
# per-tenant end-to-end latency percentiles in TENANT_LATENCY (bounded
# series — tenant names are client input, the tail collapses to
# "overflow").  Alert on a victim tenant's p99 and on any tenant's
# quota-shed rate: sustained quota sheds mean the tenant's envelope is
# too small OR an antagonist is being correctly contained.
QUERY_CANCELLED = metrics.multilabeled(
    "dgraph_query_cancelled_total", ("reason", "tenant")
)
TENANT_SHED = metrics.multilabeled(
    "dgraph_tenant_shed_total", ("tenant", "reason")
)

# segmented dataflow execution (sched/segments.py, PR 18): the fused
# drivers emit bounded k-step program segments with a scheduler yield
# point at every seam.  SEGMENT_DISPATCHES counts segmented driver
# invocations per driver; SEGMENT_YIELDS counts seams that actually
# yielded (cancel / early_exit — preemptions are counted by the
# histogram below); SEGMENT_PREEMPT_US is how long a higher-priority
# cohort waited for the running query's next segment boundary — the
# PREEMPTION LATENCY, bounded by one segment's dispatch.  Alert when
# its p99 approaches a whole monolithic program: segmentation has
# stopped engaging (planner mispricing or DGRAPH_TPU_SEGMENT=0 left
# pinned after an incident).
SEGMENT_DISPATCHES = metrics.labeled(
    "dgraph_segment_dispatches_total", label="driver"
)
SEGMENT_YIELDS = metrics.labeled(
    "dgraph_segment_yields_total", label="reason"
)
SEGMENT_PREEMPT_US = metrics.histogram(
    "dgraph_segment_preempt_us",
    (100.0, 500.0, 1000.0, 5000.0, 25000.0, 100000.0, 500000.0, 2000000.0),
)

# two-tier query cache surface (dgraph_tpu/cache/): per-tier event
# counters (hit / miss / stale / evicted / rejected), occupancy-bytes
# gauges, and the shared hit-age histogram — hit age tells an operator
# directly how long results live between mutations (a warm cache with
# young hits = churny store; old hits = the zipf head paying off)
QCACHE_HOP_EVENTS = metrics.labeled(
    "dgraph_qcache_hop_events_total", label="event"
)
QCACHE_RESULT_EVENTS = metrics.labeled(
    "dgraph_qcache_result_events_total", label="event"
)
QCACHE_HOP_BYTES = metrics.gauge("dgraph_qcache_hop_bytes")
QCACHE_RESULT_BYTES = metrics.gauge("dgraph_qcache_result_bytes")
QCACHE_HIT_AGE = metrics.histogram(
    "dgraph_qcache_hit_age_seconds",
    (0.001, 0.01, 0.1, 1.0, 10.0, 60.0, 600.0),
)

# deliberately-swallowed exceptions (graftlint: swallowed-exception).
# Some drops are correct — a raft frame to a downed peer retries via the
# next heartbeat — but "correct to drop" never means "correct to drop
# invisibly": a peer that is down for an hour shows up here as a rate an
# operator can alert on, instead of as silence.
SWALLOWED_EXC = metrics.labeled(
    "dgraph_swallowed_exceptions_total", label="site"
)

# expected-donation fallbacks (utils/jaxdiag.py): JAX's "donated buffers
# were not usable" warning, swallowed ONLY at contract-checked sites
# (analysis/programs.py declares which carry may go unaliased) and
# counted here instead of vanishing — on a backend that used to alias,
# a nonzero rate is a donation regression to chase, not noise.
DONATION_FALLBACK = metrics.labeled(
    "dgraph_donation_fallback_total", label="site"
)


# resilience layer (cluster/peerclient.py, utils/failpoints.py): every
# peer RPC lands in PEER_RPC as {peer, op, outcome} — outcome "ok",
# "http_error" (peer responded with an application error: alive),
# "unavailable" (retries/budget exhausted), "open" (shed by the circuit
# breaker without touching the network).  Alert on the unavailable/open
# rate per peer; BREAKER_STATE is the at-a-glance gauge (0 closed,
# 1 half-open, 2 open), one series per "peer:op" because breakers are
# scoped per (peer, op) — a broken snapshot endpoint must stay visible
# while raft heartbeats to the same peer succeed.
PEER_RPC = metrics.multilabeled(
    "dgraph_peer_rpc_total", ("peer", "op", "outcome")
)
PEER_RPC_ATTEMPTS = metrics.histogram(
    "dgraph_peer_rpc_attempts", (1, 2, 3, 4, 6, 8)
)
PEER_BACKOFF = metrics.histogram(
    "dgraph_peer_backoff_seconds",
    (0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.0),
)
BREAKER_STATE = metrics.labeled_gauge(
    "dgraph_peer_breaker_state", label="peer"
)
BREAKER_TRANSITIONS = metrics.multilabeled(
    "dgraph_peer_breaker_transitions_total", ("peer", "op", "to")
)
DEGRADED_READS = metrics.counter("dgraph_degraded_reads_total")
RAFT_DROPPED = metrics.labeled(
    "dgraph_raft_frames_dropped_total", label="peer"
)
FAILPOINTS_FIRED = metrics.labeled(
    "dgraph_failpoints_fired_total", label="site"
)

# storage plane (models/wal.py, models/durability.py): disk faults flip
# the node read-only (dgraph_storage_readonly 1) until the re-arm probe
# clears it; every fault is counted per site so an operator can tell a
# journal-append fault from a snapshot-compaction fault.  Recovery
# gauges describe the LAST boot replay (the observability line's
# machine-readable twin); WAL gauges + snapshot age say whether the
# background snapshotter is keeping the log bounded; the group-commit
# pair's ratio (writes / syncs) is the fsync batching factor under
# --sync.
STORAGE_ERRORS = metrics.labeled(
    "dgraph_storage_errors_total", label="site"
)
STORAGE_READONLY = metrics.gauge("dgraph_storage_readonly")
RECOVERY_RECORDS = metrics.gauge("dgraph_recovery_records")
RECOVERY_TORN_BYTES = metrics.gauge("dgraph_recovery_torn_bytes")
RECOVERY_SECONDS = metrics.gauge("dgraph_recovery_seconds")
SNAPSHOT_AGE = metrics.gauge("dgraph_snapshot_age_seconds")
SNAPSHOTS = metrics.counter("dgraph_snapshots_total")
WAL_BYTES = metrics.gauge("dgraph_wal_bytes")

# graftcheck tier 3 (analysis/witness.py): field states the armed
# Eraser lockset witness is tracking — its own coverage proof.  Zero
# under an armed tier-1 run means the instrumentation regressed (the
# annotated classes stopped being exercised), not that the tree is
# race-free.  Unarmed serving paths never touch it.
RACE_WITNESS_FIELDS = metrics.counter("dgraph_race_witness_fields_total")
WAL_SEGMENTS = metrics.gauge("dgraph_wal_sealed_segments")
GROUP_COMMIT_SYNCS = metrics.counter("dgraph_group_commit_syncs_total")
GROUP_COMMIT_WRITES = metrics.counter("dgraph_group_commit_writes_total")


# flight recorder (dgraph_tpu/obs/): SPANS_RECORDED counts every Span
# object constructed — the overhead guard's proof that the unsampled
# hot path allocates none (tests assert a ZERO delta at ratio 0, a
# property a tracemalloc probe could only suggest); TRACES_RECORDED is
# the ring intake rate; SLOW_QUERIES counts tail-sampled offenders
# (DGRAPH_TPU_SLOW_MS) independently of head sampling.
SPANS_RECORDED = metrics.counter("dgraph_trace_spans_total")
TRACES_RECORDED = metrics.counter("dgraph_traces_recorded_total")
SLOW_QUERIES = metrics.counter("dgraph_slow_queries_total")


# measured-cost adaptive planner (query/planner.py): every route
# decision is counted per (kind, route) — kind ∈ chain/expand/kway, the
# join tier keeps its own dgraph_join_route_total below — and every
# post-hoc check that catches the model on the wrong side of a
# break-even lands in MISPREDICT{kind}.  Alert on the mispredict RATE
# (mispredicts / decisions): a sustained rise means the persisted
# calibration no longer matches the hardware — re-run the
# micro-calibration pass (docs/deploy.md "Adaptive planner").
PLANNER_DECISIONS = metrics.multilabeled(
    "dgraph_planner_decisions_total", ("kind", "route")
)
PLANNER_MISPREDICTS = metrics.labeled(
    "dgraph_planner_mispredict_total", label="kind"
)
PLANNER_CALIBRATIONS = metrics.counter("dgraph_planner_calibrations_total")


# MXU join tier (ops/spgemm.py + query/joinplan.py): every per-query
# route decision (mxu generic-join vs pairwise expansion) and every
# size-gated k-way intersection's host-vs-device choice is counted, so
# a bench run — or an operator staring at /debug/store — can explain
# exactly which tier served which shape (the chain_reject discipline,
# applied to join routing).
JOIN_ROUTES = metrics.labeled("dgraph_join_route_total", label="route")
KWAY_INTERSECTS = metrics.labeled(
    "dgraph_kway_intersect_total", label="route"
)
JOIN_TILE_BUILDS = metrics.counter("dgraph_join_tile_builds_total")
# cumulative bytes densified (a counter, not an occupancy gauge: tiles
# die with their arena under the HBM budget, and live occupancy is
# already visible through the arena-bytes accounting)
JOIN_TILE_BYTES = metrics.counter("dgraph_join_tile_built_bytes_total")


# incremental view maintenance (dgraph_tpu/ivm/): the delta stream's
# publication rate by event kind (edge/pred/epoch) and its overflow
# losses; every repair-vs-rebuild outcome per derived-view kind
# (hop-cache entries, tile blocks) with the edge volume the repair path
# absorbed.  A rising hop:rebuild share means writes are outpacing the
# repair gate — check /debug/planner's "repair" decisions.
IVM_DELTAS = metrics.labeled("dgraph_ivm_deltas_total", label="kind")
IVM_STREAM_DROPPED = metrics.counter("dgraph_ivm_stream_dropped_total")
IVM_REPAIRS = metrics.multilabeled(
    "dgraph_ivm_repairs_total", ("kind", "outcome")
)
IVM_REPAIR_EDGES = metrics.counter("dgraph_ivm_repair_edges_total")


# live-query subscriptions (dgraph_tpu/ivm/subs.py): active
# registrations, re-evaluations run, events by disposition (push =
# changed result delivered / skip = re-evaluated but unchanged /
# lagged = a slow consumer's queue overflowed and dropped its oldest),
# and registration sheds by reason (quota/cap/parse).
SUBS_ACTIVE = metrics.gauge("dgraph_subscription_active")
SUBS_EVALS = metrics.counter("dgraph_subscription_evals_total")
SUBS_EVENTS = metrics.labeled(
    "dgraph_subscription_events_total", label="kind"
)
SUBS_SHED = metrics.labeled(
    "dgraph_subscription_shed_total", label="reason"
)


# per-query resource ledger (obs/ledger.py): the serving-path cost
# accounting the SLO layer aggregates.  EDGES_TRAVERSED{tenant} makes
# the BASELINE north-star metric (edges traversed per second) a live
# per-tenant series instead of a bench artifact; LEDGER_HOPS{route}
# counts hop dispatches by the route the expander took
# (cache/merged/mesh/host/resident/csr/chain/path/mxu/empty) and
# LEDGER_HOP_EDGES{route} the edges those hops traversed — which route
# actually carries a deployment's traffic, in the unit users pay for;
# LEDGER_STAGE_US{stage} accumulates, in integer microseconds, the
# coarse host/device/device_sync route times AND the stage catalogue of
# obs/ledger.py STAGES (parse, queue, ..., http_write), which are there
# at zero from boot; LEDGER_BYTES{dir} the staged h2d/d2h bytes and
# cache-hit payload bytes.  LEDGERS_CREATED counts Ledger STRUCTS
# constructed — the pooled-struct twin of dgraph_trace_spans_total:
# tests assert a zero delta across warm requests, so "one pooled struct
# per request, zero allocations" is a counter-proved property, not a
# hope.
EDGES_TRAVERSED = metrics.labeled(
    "dgraph_edges_traversed_total", label="tenant"
)
LEDGER_HOPS = metrics.labeled("dgraph_ledger_hops_total", label="route")
LEDGER_HOP_EDGES = metrics.labeled(
    "dgraph_ledger_hop_edges_total", label="route"
)
LEDGER_STAGE_US = metrics.labeled(
    "dgraph_ledger_stage_us_total", label="stage"
)
LEDGER_BYTES = metrics.labeled("dgraph_ledger_bytes_total", label="dir")
LEDGERS_CREATED = metrics.counter("dgraph_ledger_structs_total")

# path search (query/shortest.py): PATH_SEARCHES{route} counts shortest
# blocks by the route that answered them — "device" (ops/bfs.py: unit
# cost, one path) or "host" (the Dijkstra: numpaths > 1, a weight facet,
# a decorated child); PATH_LEVELS the levels the device route expanded
# and PATH_FRONTIER_ROWS the uids in them, so rows / levels is a level's
# mean width; PATH_LEVEL_WAYS{way} the same levels by what did them —
# "gather" (from the frontier list) or "sweep" (every edge of the layout;
# ops/bfs.py chooses per level).  Their edges ride
# LEDGER_HOP_EDGES{route="path"}.  Every label is there at zero from boot.
PATH_SEARCHES = metrics.labeled("dgraph_path_searches_total", label="route")
PATH_LEVELS = metrics.counter("dgraph_path_levels_total")
PATH_FRONTIER_ROWS = metrics.counter("dgraph_path_frontier_rows_total")
PATH_LEVEL_WAYS = metrics.labeled("dgraph_path_level_ways_total", label="way")
for _r in ("device", "host"):
    PATH_SEARCHES.add(_r, 0)
for _w in ("gather", "sweep"):
    PATH_LEVEL_WAYS.add(_w, 0)
LEDGER_HOP_EDGES.add("path", 0)

# result encoder (query/outputnode.py): result objects emitted, by the
# path that built them — "level" (a level at a time, the general
# encoder) or "walk" (depth first: @normalize, @ignorereflex).  One
# increment a block; both labels at zero from boot.
ENCODE_OBJECTS = metrics.labeled("dgraph_encode_objects_total", label="path")


# device telemetry (obs/device.py + models/arena.py): HBM residency
# under the ArenaManager budget (resident/budget gauges — headroom is
# the difference, computed in PromQL, not stored), dense join-tile
# residency, arena LRU evictions, bounded program-cache occupancy per
# kind (tile sets), and XLA compile events
# via jax.monitoring (count + seconds as a histogram, so compile storms
# show up as a rate AND a duration distribution).
HBM_RESIDENT_BYTES = metrics.gauge("dgraph_hbm_resident_bytes")
# resident arenas (models/arena.py ResidentArena): how each write reached
# the pinned buffers — "merge" = the on-device delta merge (only the delta
# pairs crossed h2d), "reseed" = a structural change re-uploaded the CSR
RESIDENT_EPOCHS = metrics.labeled("dgraph_resident_epochs_total", label="how")
# a write reaching an arena whose inline layout or LUT is on the device:
# "delta" = the touched rows, new chunks and new LUT entries were scattered
# into the tables that are there, "rebuild" = the layout was built anew from
# the host mirrors (a bucket outgrown, rows renumbered, a large delta)
ARENA_LAYOUT_UPDATES = metrics.labeled(
    "dgraph_arena_layout_updates_total", label="how"
)
for _how in ("delta", "rebuild"):
    ARENA_LAYOUT_UPDATES.add(_how, 0)
# a delta taken into an arena's host mirrors (models/arena.py
# ``_take_delta_host``), one count an ``apply_delta``: "append" = rows and
# edges past all the arena held, written into the room at the end of the
# mirrors' buffers, O(delta); "grow" = the same after a buffer was made or had
# run out (one copy of that mirror); "copy" = a delete, or an edge of a row
# that was there: the mirrors copied once through ``_merge``, O(rows + edges)
ARENA_MIRROR_UPDATES = metrics.labeled(
    "dgraph_arena_mirror_updates_total", label="how"
)
for _how in ("append", "grow", "copy"):
    ARENA_MIRROR_UPDATES.add(_how, 0)
# bytes those updates and rebuilds put on the device (they are in
# dgraph_ledger_bytes_total{dir="h2d"} too, on the writer's account)
ARENA_REFRESH_H2D_BYTES = metrics.counter("dgraph_arena_refresh_h2d_bytes_total")
# a write reaching a cached PathLayout (a path search's merged layout,
# models/arena.py): "delta" = the new uids' offset rows and edge slots were
# scattered into the tables that are there (``PathLayout.take_delta``),
# "rebuild" = the layout was built anew from its arenas by the writer, or
# dropped with an arena that was, for the next search to build
PATH_LAYOUT_UPDATES = metrics.labeled(
    "dgraph_path_layout_updates_total", label="how"
)
for _how in ("delta", "rebuild"):
    PATH_LAYOUT_UPDATES.add(_how, 0)
# bytes a PathLayout put on the device: a build's three tables, a delta's
# index vectors, rows and slots (in dgraph_ledger_bytes_total{dir="h2d"} too)
PATH_LAYOUT_H2D_BYTES = metrics.counter("dgraph_path_layout_h2d_bytes_total")
# the write path (serve/server.py run_query): mutations by outcome, and the
# N-Quads the acknowledged ones set or deleted
WRITES = metrics.labeled("dgraph_writes_total", label="result")
for _r in ("ok", "error"):
    WRITES.add(_r, 0)
WRITE_QUADS = metrics.counter("dgraph_write_quads_total")
HBM_BUDGET_BYTES = metrics.gauge("dgraph_hbm_budget_bytes")
HBM_TILE_BYTES = metrics.gauge("dgraph_hbm_tile_bytes")
ARENA_EVICTIONS = metrics.counter("dgraph_arena_evictions_total")
PROGRAM_CACHE_ENTRIES = metrics.labeled_gauge(
    "dgraph_program_cache_entries", label="kind"
)
XLA_COMPILES = metrics.counter("dgraph_xla_compiles_total")
# programs read back from JAX's persistent compilation cache: each also
# counts in dgraph_xla_compiles_total (the backend-compile bracket closes
# round a read too), so compiles less reads = cold compiles
XLA_CACHE_READS = metrics.counter("dgraph_xla_cache_reads_total")
XLA_COMPILE_SECONDS = metrics.histogram(
    "dgraph_xla_compile_seconds",
    (0.01, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0),
)


# device fault domain (utils/devguard.py): DEVICE_STATE mirrors the
# breaker-gauge convention (0 healthy, 1 suspect, 2 sick), one series
# per fault domain ("device" = the default backend's dispatch plane,
# "mesh" = the multi-chip collective plane — a lost mesh chip must not
# brand single-device dispatch sick).  Every classified fault lands in
# DEVICE_FAULTS{kind ∈ hang/oom/transient/sick}; every hot failover the
# sick path took in DEVICE_FAILOVER{route ∈ host/unsharded/evict_retry}.
# Alert on the failover RATE: a sustained nonzero rate means queries
# are being served correct-but-slower off the host mirrors while the
# device re-proves itself.  DEVICE_PROBES counts half-open re-admission
# probes by outcome (ok/fail).
DEVICE_STATE = metrics.labeled_gauge(
    "dgraph_device_state", label="domain"
)
DEVICE_FAULTS = metrics.labeled(
    "dgraph_device_faults_total", label="kind"
)
DEVICE_FAILOVER = metrics.labeled(
    "dgraph_device_failover_total", label="route"
)
DEVICE_PROBES = metrics.labeled(
    "dgraph_device_probes_total", label="outcome"
)

# elastic mesh fault domain (mesh/fault.py, PR 20): MESH_EPOCH is the
# epoch fence every dispatched mesh program carries (the MeshPlan
# version at the last re-shard) — it moves exactly when the serving
# sub-mesh does.  MESH_CHIPS_HEALTHY vs the boot width is the capacity
# headline (8→7 = one chip evicted, still sharded; the plane only
# degrades to unsharded when it hits 0 or latches whole-plane sick).
# MESH_RESHARD counts epoch flips by cause (loss / rejoin / manual) and
# MESH_RESHARD_SECONDS is the drain window each flip cost — plan
# rebalance + stale-shard drop + gauge/epoch publication; queries keep
# serving through it, resuming at their next segment seam.
# QUERY_RESUMED counts in-flight queries that drained their carry to
# host and resumed under a new plan (reason ∈ epoch/loss/hang): a
# sustained rate with no matching reshards means a flapping chip is
# churning epochs — see the docs/deploy.md runbook.
MESH_EPOCH = metrics.gauge("dgraph_mesh_epoch")
MESH_CHIPS_HEALTHY = metrics.gauge("dgraph_mesh_chips_healthy")
MESH_RESHARD = metrics.labeled(
    "dgraph_mesh_reshard_total", label="reason"
)
MESH_RESHARD_SECONDS = metrics.histogram(
    "dgraph_mesh_reshard_seconds",
    (0.001, 0.005, 0.025, 0.1, 0.5, 2.0, 10.0, 60.0),
)
QUERY_RESUMED = metrics.labeled(
    "dgraph_query_resumed_total", label="reason"
)


# build identity + liveness: BUILD_INFO is the constant-1 gauge whose
# labels carry what is running (the client_golang BuildInfo
# convention; obs/device.py stamps it once the backend is known), and
# UPTIME computes seconds-since-import at scrape time — a FuncGauge,
# so no background thread exists just to tick a number.
BUILD_INFO = metrics.multilabeled_gauge(
    "dgraph_build_info", ("version", "backend", "jax")
)
_PROCESS_START = _time.monotonic()
UPTIME_SECONDS = metrics.func_gauge(
    "dgraph_uptime_seconds",
    lambda: _time.monotonic() - _PROCESS_START,
)


def note_swallowed(site: str, exc: BaseException) -> None:
    """Count an intentionally-dropped exception at ``site`` (a short
    dotted location like ``transport.grpc_send``).  The exception TYPE
    rides in the label so a sudden shift (OSError → ValueError) is
    visible without logs."""
    SWALLOWED_EXC.add(f"{site}:{type(exc).__name__}")
