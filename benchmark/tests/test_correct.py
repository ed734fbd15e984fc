"""The benchmark's own tests of what decides ``correct`` (run by hand or with
``python -m pytest benchmark/tests -q``; on the CPU, at a size a test run can
hold).  None of them is part of a benchmark run.

- each control — the reference with one stated guarantee broken, put in the
  program's place — has to come out NOT correct, on three seeds;
- the plain reference, put in the program's place, has to come out correct
  (the comparison does not fail sound answers);
- a run driven end to end past the harness's look for a chip, with the timed
  path broken underneath (an answer altered where it is produced), has to
  come out NOT correct, and the same run without the fault correct.

The other faults the contract lists (a state returned unchanged, half a
batch left out) are a training step's: a cell that serves reads has neither.
The exchange between chips left out is a mesh's: no cell runs one yet, and
``test_yardstick.py`` plants it on four virtual devices (``faults/no_exchange.py``).
"""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

import compare  # noqa: E402
import filmgen  # noqa: E402
import trafficgen  # noqa: E402
from run import World  # noqa: E402

QUADS = 120_000
SEEDS = (11, 2_200_000_123, 3_000_000_007)


def _window(seed, n=160):
    """The first ``n`` requests the cell's own mix deals for ``seed``, as
    records with no body yet."""
    world = World(filmgen.generate(QUADS, seed))
    mix = trafficgen.load_json("traffic", "traverse.json")
    classes = trafficgen.load_classes(mix, world)
    plan = trafficgen.deal(mix, classes, seed)
    return world, classes, [(0, cls, root, 0.0, 0.0, 200, b"") for cls, root in plan[:n]]


def _judge(world, classes, records, walker):
    def answer_of(cls, root):
        return json.dumps({**classes[cls].render(root, walker), "server_latency": {}}).encode()

    numbers = compare.compare(records, classes, answer_of=answer_of)["numbers"]
    return compare.verdict(numbers)[0], numbers


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("control", ["drop_quad", "truncate"])
def test_control_is_not_correct(control, seed):
    world, classes, records = _window(seed)
    broken = trafficgen.load_module("controls", control).walker(world)
    ok, numbers = _judge(world, classes, records, broken)
    assert not ok and numbers["wrong"] >= 1, numbers


@pytest.mark.parametrize("seed", SEEDS)
def test_reference_in_the_programs_place_is_correct(seed):
    world, classes, records = _window(seed)
    ok, numbers = _judge(world, classes, records, world.walker)
    assert ok and numbers["compared"] == len(records), numbers


def _run(*extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", "film-q4.traverse",
         "--seed", "5", "--seconds", "3", "--trace", "0", "--quads", str(QUADS), *extra],
        env=env, capture_output=True, text=True, timeout=600,
    )
    return r


def test_planted_fault_in_the_timed_path_is_not_correct():
    """``--quads`` takes the run past the look for a chip; a rehearsal exits
    3 where the comparison says correct and 4 where it says not."""
    r = _run("--fault", "drop_leaf")
    assert r.returncode == 4, r.stderr[-2000:]
    assert "compared wrong: 0 " not in r.stderr
    assert r.stdout.strip() == ""          # a rehearsal prints no result line


def test_same_run_without_the_fault_is_correct():
    r = _run()
    assert r.returncode == 3, r.stderr[-2000:]
    assert "compared wrong: 0 " in r.stderr
