"""``metrics/path_sweep_share.py`` on a made-up window, by the rule of
``test_stage_metrics.py``: the number worked out by hand, and ``None`` —
never 0 — where the program lacks the family or a label (a parent commit)
or no level ran."""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

import trafficgen  # noqa: E402
from run import Observed  # noqa: E402

FAMILY = "dgraph_path_level_ways_total"


def window(grown):
    """An ``Observed`` whose level counter grew by ``grown`` ({way: n}) over
    a window that started from other figures."""
    before = {FAMILY: {way: 700.0 for way in grown}} if grown is not None else {}
    after = {FAMILY: {way: 700.0 + n for way, n in grown.items()}} if grown is not None else {}
    before["dgraph_path_searches_total"] = {"device": 10.0, "host": 0.0}
    after["dgraph_path_searches_total"] = {"device": 15.0, "host": 0.0}
    return Observed(counters_before=before, counters_after=after, answered=[None] * 5)


def read(obs):
    return trafficgen.load_module("metrics", "path_sweep_share").read(obs)


@pytest.mark.parametrize("grown, by_hand", [
    ({"gather": 37, "sweep": 3}, 7.5),        # 3 sweeps of 40 levels
    ({"gather": 840, "sweep": 0}, 0.0),
    ({"gather": 0, "sweep": 12}, 100.0),
])
def test_reader_gives_the_share_worked_out_by_hand(grown, by_hand):
    assert read(window(grown)) == pytest.approx(by_hand, rel=1e-12)


@pytest.mark.parametrize("grown", [
    None,                       # the parent: no such family
    {"gather": 9},              # a label missing
    {"sweep": 9},
    {"gather": 0, "sweep": 0},  # a window of result-cache hits: no level ran
], ids=["no_family", "no_sweep", "no_gather", "no_level"])
def test_reader_gives_nothing_without_something_to_read(grown):
    assert read(window(grown)) is None
