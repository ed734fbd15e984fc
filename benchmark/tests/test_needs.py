"""A mix's ``needs`` are read at boot (CPU, no chip; ``python -m pytest
benchmark/tests/test_needs.py -q``; the cases that start a whole server
through ``run.py`` are marked ``slow``):

- ``harness.lacking`` counts a family as exposed where the exposition holds
  its ``# TYPE`` line or any sample, and no other family's;
- a mix whose ``needs`` name a family no program has gives ``no result``
  with the reason, before the loader starts and within 30 s of boot;
- every mix of ``BENCHMARK.json`` that declares ``needs`` passes the check
  at boot on this tree.
"""

import json
import os
import re
import sys
import time

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

import harness  # noqa: E402
import run  # noqa: E402
import trafficgen  # noqa: E402

EXPOSITION = """\
# TYPE dgraph_num_queries_total counter
dgraph_num_queries_total 12
# TYPE dgraph_writes_total counter
# TYPE dgraph_path_searches_total counter
dgraph_path_searches_total{route="device"} 3
dgraph_untyped_total 1
# TYPE dgraph_query_latency_seconds histogram
dgraph_query_latency_seconds_bucket{le="+Inf"} 12
dgraph_query_latency_seconds_sum 0.5
dgraph_query_latency_seconds_count 12
"""


@pytest.mark.parametrize("family, exposed", [
    ("dgraph_num_queries_total", True),        # its # TYPE line and a sample
    ("dgraph_writes_total", True),             # a labelled counter with no sample yet: # TYPE alone
    ("dgraph_path_searches_total", True),      # a labelled sample
    ("dgraph_untyped_total", True),            # a sample with no # TYPE line
    ("dgraph_query_latency_seconds", True),    # a histogram by its # TYPE line
    ("dgraph_query_latency_seconds_sum", True),
    ("dgraph_writes", False),                  # a prefix of a family is no family
    ("dgraph_num_queries", False),
    ("num_queries_total", False),              # nor is a suffix
    ("counter", False),
    ("dgraph_no_such_family_total", False),
])
def test_a_family_is_exposed_by_its_type_line_or_a_sample(family, exposed):
    assert harness.lacking(EXPOSITION, [family]) == ([] if exposed else [family])


def test_lacking_keeps_the_order_of_the_needs():
    needs = ["dgraph_b_total", "dgraph_writes_total", "dgraph_a_total"]
    assert harness.lacking(EXPOSITION, needs) == ["dgraph_b_total", "dgraph_a_total"]
    assert harness.lacking(EXPOSITION, []) == []


class _LoaderStarted(Exception):
    pass


def _boot_only(monkeypatch, workload, needs=None):
    """``run.main`` on the CPU for ``workload`` at a rehearsal's size, with
    the loader replaced by a raise: the run ends at the load, or before it.
    ``needs`` replaces the mix's own.  Returns (rc, the times the server
    answered healthy, the time ``run.main`` returned, the loader's calls)."""
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    calls, healthy_at = [], []
    real_json, real_wait = trafficgen.load_json, harness.Server.wait_healthy

    def load_json(*parts):
        d = real_json(*parts)
        if needs is not None and parts[0] == "traffic":
            d = {**d, "needs": needs}
        return d

    def wait_healthy(self, *a, **kw):
        out = real_wait(self, *a, **kw)
        healthy_at.append(time.monotonic())
        return out

    def start_loader(*a, **kw):
        calls.append(a)
        raise _LoaderStarted("the loader was started")

    monkeypatch.setattr(trafficgen, "load_json", load_json)
    monkeypatch.setattr(harness.Server, "wait_healthy", wait_healthy)
    monkeypatch.setattr(harness, "start_loader", start_loader)
    rc = run.main(["--workload", workload, "--seed", "2200000341", "--seconds", "2",
                   "--trace", "0", "--quads", "60000"])
    return rc, healthy_at, time.monotonic(), calls


@pytest.mark.slow
def test_a_mix_whose_needs_no_program_has_ends_before_the_load(monkeypatch, capfd):
    rc, healthy_at, t_end, calls = _boot_only(
        monkeypatch, "film-q4.traverse", needs=["dgraph_writes_total", "dgraph_no_such_family_total"])
    out, err = capfd.readouterr()
    assert rc == 1, err[-2000:]
    assert out.strip() == ""
    assert err.strip().splitlines()[-1] == (
        "no result: the server exposes no ['dgraph_no_such_family_total']: "
        "this mix needs a program that has them")
    assert calls == [] and "Traceback" not in err
    assert len(healthy_at) == 1 and t_end - healthy_at[0] < 30


with open(os.path.join(harness.CHECKOUT, "BENCHMARK.json")) as _f:
    CELLS_WITH_NEEDS = [w["name"] for w in json.load(_f)["workloads"]
                        if trafficgen.load_json("traffic", w["traffic"] + ".json").get("needs")]


def test_the_write_mixes_declare_needs():
    assert {"film-q4-rw.readwrite", "film-q4-paths-rw.searchwrite"} <= set(CELLS_WITH_NEEDS)


@pytest.mark.slow
@pytest.mark.parametrize("workload", CELLS_WITH_NEEDS)
def test_a_mixs_needs_pass_at_boot_on_this_tree(monkeypatch, capfd, workload):
    rc, healthy_at, _, calls = _boot_only(monkeypatch, workload)
    _, err = capfd.readouterr()
    assert len(calls) == 1, err[-2000:]          # the check passed: the load was next
    assert rc == 1 and "exposes no" not in err
    assert re.search(r"no result: _LoaderStarted: the loader was started", err), err[-2000:]
