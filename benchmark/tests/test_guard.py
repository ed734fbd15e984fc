"""The device guard is judged over the window, not over the set-up (CPU, no
chip; ``python -m pytest benchmark/tests/test_guard.py -q``; the cases that
start a whole server through ``run.py`` are marked ``slow``):

- ``harness.unfit`` given the guard read at the window's open reports a
  fault, a wedged dispatch or a failover counted since, and a domain that
  was sick at the open or is at the close; what was counted before is not
  its reason, whatever it was;
- through ``run.py`` with a device fault planted at the fused chain's
  dispatch (``DGRAPH_TPU_FAILPOINTS``): a fault of the warm-up is printed as
  set-up's and leaves the window fit, faults inside the window make it unfit.
"""

import os
import re
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import harness  # noqa: E402
import run  # noqa: E402


def _serve(monkeypatch, faults=None, wedged=0, state="healthy", failovers=None):
    """``harness``'s reads of a made-up server on one TPU chip whose device
    guard reads as given."""
    dev = {"backend": "tpu", "device_kind": "TPU v5 lite", "devices": 1,
           "guard": {"domains": {"device": {"state": state, "faults": dict(faults or {}),
                                            "wedged_workers": wedged}}}}

    def http_json(addr, path, body=None):
        return dev if path == "/debug/device" else {"calibration": {"source": "measured"}}

    monkeypatch.setattr(harness, "http_json", http_json)
    monkeypatch.setattr(harness, "counters", lambda addr: {
        "dgraph_device_failover_total": {"host": 0.0, "unsharded": 0.0, **(failovers or {})}})


def test_the_guard_reads_nonzero_counts_and_sick_domains(monkeypatch):
    _serve(monkeypatch, faults={"transient": 2}, wedged=1, state="sick", failovers={"host": 3.0})
    assert harness.guard("x") == {
        "counts": {"failover host": 3.0, "device fault transient": 2, "device wedged": 1},
        "sick": {"device": "sick"}}
    _serve(monkeypatch)
    assert harness.guard("x") == {"counts": {}, "sick": {}}


@pytest.mark.parametrize("at_open, now, reason", [
    # what the set-up counted stays set-up's: nothing more in the window
    (dict(faults={"hang": 1}, wedged=1, failovers={"host": 4.0}),
     dict(faults={"hang": 1}, wedged=1, failovers={"host": 4.0}), None),
    (dict(), dict(), None),
    # counted in the window
    (dict(faults={"hang": 1}), dict(faults={"hang": 2}), "device guard in the window: {'device fault hang': 1}"),
    (dict(), dict(faults={"oom": 1}), "device guard in the window: {'device fault oom': 1}"),
    (dict(wedged=1), dict(wedged=2), "device guard in the window: {'device wedged': 1}"),
    (dict(failovers={"host": 4.0}), dict(failovers={"host": 5.0}),
     "device guard in the window: {'failover host': 1.0}"),
    # sick at the close, or at the open
    (dict(faults={"hang": 1}), dict(faults={"hang": 1}, state="sick"), "guard domain device at its close: sick"),
    (dict(faults={"hang": 1}, state="sick"), dict(faults={"hang": 1}),
     "guard domain device at the window's open: sick"),
])
def test_unfit_judges_the_guard_by_what_the_window_added(monkeypatch, at_open, now, reason):
    _serve(monkeypatch, **at_open)
    since = harness.guard("x")
    _serve(monkeypatch, **now)
    assert harness.unfit("x", 1, since=since) == ([reason] if reason else [])


def test_without_a_window_unfit_reads_the_platform_alone(monkeypatch):
    _serve(monkeypatch, faults={"hang": 3}, state="sick", failovers={"host": 9.0})
    assert harness.unfit("x", 1) == []
    assert harness.unfit("x", 4) == ["1 chips, the cell asks for 4"]


def _rehearse(monkeypatch, failpoints):
    """``run.main`` on the CPU for the traverse cell at a rehearsal's size
    with ``failpoints`` armed in the server; returns (rc, the guard's
    reasons ``unfit`` gave after the window)."""
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setenv("DGRAPH_TPU_FAILPOINTS", failpoints)
    monkeypatch.setenv("DGRAPH_TPU_FAILPOINT_SEED", "7")
    said, real = [], harness.unfit

    def unfit(addr, chips, since=None):
        why = real(addr, chips, since)
        if since is not None:
            said.append([w for w in why if "guard" in w])    # not the CPU's own reasons
        return why

    monkeypatch.setattr(harness, "unfit", unfit)
    rc = run.main(["--workload", "film-q4.traverse", "--seed", "2200000341", "--seconds", "4",
                   "--trace", "0", "--quads", "200000"])
    return rc, said


@pytest.mark.slow
def test_a_fault_of_the_warm_up_leaves_the_window_fit(monkeypatch, capfd):
    rc, said = _rehearse(monkeypatch, "device.chain=error(n=1)")
    _, err = capfd.readouterr()
    assert rc == 3, err[-3000:]                       # a rehearsal, correct
    assert said == [[]], err[-3000:]
    assert re.search(r"device guard in set-up, before any window: \{.*'device fault transient': 1", err)
    assert re.search(r'"guard_in_set_up": \{[^}]*"device fault transient": 1', err)


@pytest.mark.slow
def test_faults_inside_the_window_make_it_unfit(monkeypatch, capfd):
    rc, said = _rehearse(monkeypatch, "device.chain=error(p=0.2)")
    _, err = capfd.readouterr()
    assert rc == 3, err[-3000:]                       # failed-over answers are still right
    assert len(said) == 1 and said[0], err[-3000:]
    assert said[0][0].startswith("device guard in the window: {"), said
    assert "'device fault transient'" in said[0][0]
