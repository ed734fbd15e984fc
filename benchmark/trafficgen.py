"""One general traffic generator: reads a mix (``traffic/<name>.json``) and
the query classes it names (``queries/<class>.json``), and deals the run's
requests from the seed.

The requests form ONE sequence that all callers draw from in turn.  It is
not sampled: classes are scheduled by their weights and each class walks its
law's quantiles by the golden ratio, so every stretch of the sequence — and a
window consumes only a stretch — holds the same shares of classes and sizes.
Every class's walk starts at its law's median (``U0``) for EVERY seed, so all
seeds ask for the same ranks; the seed shuffles each ``BLOCK`` neighbours and
deals the graph (who holds which rank).  Two seeds differ in order and in who
is who, not in the sizes they ask for: the seed-to-seed spread of a cell is
then process noise, not a difference of work (PERF.md section 4 gives the
spreads of a seed-drawn deck beside).
"""

from __future__ import annotations

import importlib.util
import json
import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def load_json(*parts) -> dict:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_module(folder: str, name: str):
    """``benchmark/<folder>/<name>.py``, found by the name a data file gives."""
    path = os.path.join(HERE, folder, name + ".py")
    spec = importlib.util.spec_from_file_location(f"bench_{folder}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_classes(mix: dict, world) -> dict:
    """{class name: its kind's interpreter} for the classes of a mix."""
    out = {}
    for c in mix["classes"]:
        spec = load_json("queries", c["class"] + ".json")
        kind = load_module("query_kinds", spec["kind"])
        out[c["class"]] = kind.QueryKind(c["class"], spec, world)
    return out


GOLDEN = 0.6180339887498949   # frac(U0 + k * GOLDEN) fills [0, 1) evenly in EVERY prefix
U0 = 0.5                      # where every class's walk starts: its law's median
BLOCK = 16                    # requests shuffled among themselves by the seed


def inverse_cdf(law: dict, pool_size: int):
    """u in [0, 1) -> a position in a pool of ``pool_size`` ranks under a law."""
    if law["law"] == "zipf":
        w = np.arange(1, pool_size + 1, dtype=np.float64) ** -float(law.get("s", 1.0))
        cdf = np.cumsum(w) / w.sum()
        return lambda u: np.minimum(np.searchsorted(cdf, u, side="right"), pool_size - 1)
    if law["law"] == "uniform":
        return lambda u: np.minimum((u * pool_size).astype(np.int64), pool_size - 1)
    raise ValueError(f"unknown law {law['law']!r}")


def class_schedule(weights, length: int) -> np.ndarray:
    """Which class each of ``length`` requests is of: at every step the class
    furthest behind its weight, so every prefix holds each class within one
    request of its share."""
    w = np.asarray(weights, dtype=np.float64)
    w = w / w.sum()
    given = np.zeros(len(w))
    out = np.empty(length, dtype=np.int64)
    for i in range(length):
        c = int(np.argmax(w * (i + 1) - given))
        out[i] = c
        given[c] += 1
    return out


def deal(mix: dict, classes: dict, seed: int) -> list:
    """[(class name, root), ...]: the ONE sequence the mix's callers draw
    from, ``mix["deck"]`` long.  Classes follow ``class_schedule``; a class's
    k-th request takes the root at quantile frac(U0 + k * GOLDEN) of its law,
    so every stretch of the sequence asks for the same sizes in the same
    shares, and every seed for the same ranks: a walk that started where the
    seed said met the few giant roots of a pool within a window for one seed
    in six and not for the others (PERF.md).  The seed shuffles every
    ``BLOCK`` consecutive requests among themselves, and makes the graph."""
    names = [c["class"] for c in mix["classes"]]
    n = int(mix["deck"])
    rng = np.random.default_rng([int(seed), 0x6465636B])
    which = class_schedule([c["weight"] for c in mix["classes"]], n)
    roots = np.empty(n, dtype=np.int64)
    for ci, name in enumerate(names):
        at = np.flatnonzero(which == ci)
        pool = classes[name].pool()
        u = (U0 + np.arange(len(at)) * GOLDEN) % 1.0
        roots[at] = pool[inverse_cdf(classes[name].spec["root"], len(pool))(u)]
    order = np.arange(n)
    for lo in range(0, n, BLOCK):
        order[lo:lo + BLOCK] = rng.permutation(order[lo:lo + BLOCK])
    return [(names[int(which[i])], int(roots[i])) for i in order]
