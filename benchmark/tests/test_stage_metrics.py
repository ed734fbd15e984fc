"""The stage readers (``metrics/*_ms.py``, ``*_bytes_per_query.py``,
``cohort_occupancy.py``, ``request_accounted_share.py``,
``cold_compiles_in_window.py``) on a made-up window: each gives the number
worked out by hand, and ``None`` — never 0 — where the program lacks the
family or the label it reads (a parent commit)."""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

import stagecount  # noqa: E402
import trafficgen  # noqa: E402
from run import Observed  # noqa: E402

STAGE_US = {          # microseconds the window added, by stage
    "parse": 2_000, "result_cache": 6_000, "queue": 300_000, "merge_wait": 20_000,
    "plan": 18_000, "host_expand": 4_000, "h2d": 50_000, "dispatch": 30_000,
    "fetch": 400_000, "convert": 60_000, "assemble": 90_000, "encode": 150_000,
    "handoff": 70_000, "http_write": 80_000,
    "device": 999_999, "host": 999_999,     # coarse labels: read by no stage reader
}
QUERIES = 10          # requests the program counted, all answered
WALL_S = 1.5          # dgraph_query_latency_seconds_sum over the window


def window(drop=()):
    """An ``Observed`` whose counters grew by the figures above over a
    window that started from other figures; ``drop`` names families or
    ``family{label}`` the program does not have."""
    grown = {
        "dgraph_ledger_stage_us_total": dict(STAGE_US),
        "dgraph_num_queries_total": {"": QUERIES},
        "dgraph_query_latency_seconds_sum": {"": WALL_S},
        "dgraph_ledger_bytes_total": {"h2d": 40_960, "d2h": 503_316_480, "cache_hit": 7},
        "dgraph_sched_cohort_occupancy_sum": {"": 18.0},
        "dgraph_sched_cohort_occupancy_count": {"": 8.0},
        "dgraph_xla_compiles_total": {"": 5.0},
        "dgraph_xla_cache_reads_total": {"": 3.0},
    }
    before, after = {}, {}
    for fam, labels in grown.items():
        if fam in drop:
            continue
        for label, v in labels.items():
            if f"{fam}{{{label}}}" in drop:
                continue
            before.setdefault(fam, {})[label] = 1_000.0
            after.setdefault(fam, {})[label] = 1_000.0 + v
    return Observed(counters_before=before, counters_after=after,
                    answered=[None] * QUERIES)


def read(name, obs):
    return trafficgen.load_module("metrics", name).read(obs)


@pytest.mark.parametrize("name, by_hand", [
    ("sched_queue_wait_ms", (300_000 + 20_000) / 1e3 / 10),     # 32.0
    ("handoff_ms", 7.0),
    ("plan_ms", (2_000 + 18_000) / 1e3 / 10),                   # 2.0
    ("h2d_ms", 5.0),
    ("dispatch_ms", 3.0),
    ("fetch_ms", 40.0),
    ("convert_ms", 6.0),
    ("assemble_ms", 9.0),
    ("host_expand_ms", 0.4),
    ("encode_ms", 15.0),
    ("result_cache_ms", 0.6),
    ("http_write_ms", 8.0),
    ("cohort_occupancy", 18.0 / 8.0),                           # 2.25
    ("h2d_bytes_per_query", 4_096.0),
    ("d2h_bytes_per_query", 50_331_648.0),
    # every stage but http_write: 1,200,000 us of 1.5 s
    ("request_accounted_share", 80.0),
    ("cold_compiles_in_window", 2.0),
])
def test_reader_gives_the_number_worked_out_by_hand(name, by_hand):
    assert read(name, window()) == pytest.approx(by_hand, rel=1e-12)


@pytest.mark.parametrize("name, lacks", [
    ("sched_queue_wait_ms", "dgraph_ledger_stage_us_total{merge_wait}"),
    ("handoff_ms", "dgraph_ledger_stage_us_total{handoff}"),
    ("plan_ms", "dgraph_ledger_stage_us_total{plan}"),
    ("h2d_ms", "dgraph_ledger_stage_us_total"),
    ("dispatch_ms", "dgraph_ledger_stage_us_total{dispatch}"),
    ("fetch_ms", "dgraph_ledger_stage_us_total{fetch}"),
    ("convert_ms", "dgraph_ledger_stage_us_total{convert}"),
    ("assemble_ms", "dgraph_ledger_stage_us_total{assemble}"),
    ("host_expand_ms", "dgraph_ledger_stage_us_total{host_expand}"),
    ("encode_ms", "dgraph_ledger_stage_us_total{encode}"),
    ("result_cache_ms", "dgraph_ledger_stage_us_total{result_cache}"),
    ("http_write_ms", "dgraph_ledger_stage_us_total{http_write}"),
    ("fetch_ms", "dgraph_num_queries_total"),
    ("cohort_occupancy", "dgraph_sched_cohort_occupancy_count"),
    ("h2d_bytes_per_query", "dgraph_ledger_bytes_total{h2d}"),
    ("d2h_bytes_per_query", "dgraph_ledger_bytes_total"),
    ("request_accounted_share", "dgraph_ledger_stage_us_total{assemble}"),
    ("request_accounted_share", "dgraph_query_latency_seconds_sum"),
    ("cold_compiles_in_window", "dgraph_xla_cache_reads_total"),
])
def test_reader_gives_nothing_without_its_family(name, lacks):
    assert read(name, window(drop={lacks})) is None


def test_the_parents_counters_read_as_nothing():
    """The parent of the PR that added the stages has the family with its
    coarse labels only: every stage reader returns None there."""
    obs = window(drop={f"dgraph_ledger_stage_us_total{{{s}}}" for s in STAGE_US
                       if s not in ("device", "host")}
                 | {"dgraph_xla_cache_reads_total"})
    for name in ("sched_queue_wait_ms", "handoff_ms", "plan_ms", "h2d_ms", "dispatch_ms",
                 "fetch_ms", "convert_ms", "assemble_ms", "host_expand_ms", "encode_ms",
                 "result_cache_ms", "http_write_ms", "request_accounted_share",
                 "cold_compiles_in_window"):
        assert read(name, obs) is None, name
    assert read("cohort_occupancy", obs) == 2.25


def test_the_share_counts_exactly_the_stages_inside_the_requests_clock():
    assert set(stagecount.IN_REQUEST) == set(STAGE_US) - {"http_write", "device", "host"}
