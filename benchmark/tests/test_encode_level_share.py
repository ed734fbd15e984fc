"""``metrics/encode_level_share.py`` on a made-up window, by the rule of
``test_stage_metrics.py``: the number worked out by hand, and ``None`` —
never 0 — where the program lacks the family or a label (a parent commit)
or emitted nothing."""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

import trafficgen  # noqa: E402
from run import Observed  # noqa: E402

FAMILY = "dgraph_encode_objects_total"


def window(grown):
    """An ``Observed`` whose encoder counter grew by ``grown`` ({path: n})
    over a window that started from other figures."""
    before = {FAMILY: {path: 5_000.0 for path in grown}} if grown is not None else {}
    after = {FAMILY: {path: 5_000.0 + n for path, n in grown.items()}} if grown is not None else {}
    before["dgraph_num_queries_total"] = {"": 10.0}
    after["dgraph_num_queries_total"] = {"": 20.0}
    return Observed(counters_before=before, counters_after=after, answered=[None] * 10)


def read(obs):
    return trafficgen.load_module("metrics", "encode_level_share").read(obs)


@pytest.mark.parametrize("grown, by_hand", [
    ({"level": 750_000, "walk": 250_000}, 75.0),
    ({"level": 1_234_567, "walk": 0}, 100.0),
    ({"level": 0, "walk": 40}, 0.0),
])
def test_reader_gives_the_share_worked_out_by_hand(grown, by_hand):
    assert read(window(grown)) == pytest.approx(by_hand, rel=1e-12)


@pytest.mark.parametrize("grown", [
    None,                       # the parent: no such family
    {"level": 9},               # a label missing
    {"walk": 9},
    {"level": 0, "walk": 0},    # a window of result-cache hits: nothing encoded
], ids=["no_family", "no_walk", "no_level", "nothing_emitted"])
def test_reader_gives_nothing_without_something_to_read(grown):
    assert read(window(grown)) is None
