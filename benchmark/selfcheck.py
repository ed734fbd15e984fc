#!/usr/bin/env python3
"""Checks of the yardstick itself, run by hand: ``python3 benchmark/selfcheck.py``.
No server, no chip, no JAX.

1. The reduction from a trace to ``busy_s``, ``device_idle_share``,
   ``traversal_roofline`` and ``breakdown``, on a trace recorded on the chip
   and kept in ``fixtures/trace_v5e.json.gz`` (a cut of one traced run of
   ``film-q4.traverse``), against numbers worked out here the slow way; on
   a hand-made trace whose answer is known by construction; and on one of
   four device planes (a mesh): busy, operations and gaps are a chip's mean,
   the roofline is held against four chips' bandwidth, and the mesh's and
   the path search's edges count as the device's.
2. The bytes function.
3. The percentile and rate arithmetic on a made-up window that holds a
   stall: the stall has to move the 95th percentile (``latency_p95_ms``) and ``edges_per_s``.
4. The traffic generator: every stretch of a seed's sequence holds the same
   shares of classes and sizes.
"""

from __future__ import annotations

import gzip
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import harness  # noqa: E402
import stats  # noqa: E402
import tracered  # noqa: E402
import trafficgen  # noqa: E402
import work  # noqa: E402

FAILED = []


def check(ok: bool, what: str, **detail) -> None:
    print(("ok    " if ok else "FAILED") + " " + what + (f"  {detail}" if detail and not ok else ""))
    if not ok:
        FAILED.append(what)


def close(a: float, b: float, rel: float = 1e-9) -> bool:
    return abs(a - b) <= rel * max(abs(a), abs(b), 1e-30)


def handmade_trace() -> dict:
    """Two chips over a 10 ms extent.  Chip 0: ops at [1,3) and [2,4) ms
    (overlapping: busy 3 ms) and [6,7) ms (busy 1 ms).  Chip 1: one op at
    [0,2) ms.  The host: 'encode' spans [4,6) ms, 'wait' spans [7,10) ms."""
    ms = 1e6
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": [["gather", 1 * ms, 2 * ms], ["sort", 2 * ms, 2 * ms],
                                           ["gather", 6 * ms, 1 * ms]]},
            {"name": "Steps", "events": [["step", 0.0, 10 * ms]]},
        ]},
        {"name": "/device:TPU:1", "lines": [
            {"name": "XLA Ops", "events": [["gather", 0.0, 2 * ms]]},
        ]},
        {"name": "/host:CPU", "lines": [
            {"name": "python", "events": [["encode", 4 * ms, 2 * ms], ["wait", 7 * ms, 3 * ms],
                                          ["tick", 4.5 * ms, 0.1 * ms]]},
        ]},
    ]}


def check_reduction() -> None:
    r = tracered.reduce(handmade_trace(), window_s=0.010)
    check(r["devices"] == 2, "hand-made trace: two device planes found")
    check(close(r["busy_s"], (0.004 + 0.002) / 2),
          "hand-made trace: busy is the union per chip, averaged (a 'Steps' line is not an op)",
          got=r["busy_s"])
    ops = dict(r["device_ops"])
    check(close(ops["gather"], (0.003 + 0.002) / 2) and close(ops["sort"], 0.002 / 2),
          "hand-made trace: device_ops sums each name, per chip", got=ops)
    gaps = dict(r["idle_gaps"])
    # chip 0 idles [0,1) [4,6) [7,10); chip 1 idles [2,10): 'wait' overlaps that one most
    check(close(gaps.get("encode", 0), 0.002 / 2) and close(gaps.get("wait", 0), (0.003 + 0.008) / 2),
          "hand-made trace: gaps go to the host event that overlaps them most", got=gaps)
    check(close(sum(gaps.values()) + r["busy_s"], 0.010),
          "hand-made trace: busy + idle gaps = the window")

    share = work.roofline_share(edges=1e6, rows=1e5, busy_s=r["busy_s"], peak_bytes_per_s=819e9)
    check(close(share, 100 * ((8e6 + 8e5) / 819e9) / 0.003),
          "traversal_roofline from the hand-made trace", got=share)
    check(work.roofline_share(0, 0, r["busy_s"], 819e9) is None
          and work.roofline_share(1e6, 1e5, 0.0, 819e9) is None,
          "a roofline share with nothing to read is nothing, not 0")

    empty = tracered.reduce({"planes": [{"name": "/host:CPU", "lines": [
        {"name": "python", "events": [["x", 0.0, 5.0]]}]}]}, window_s=1.0)
    check(empty["busy_s"] == 0.0 and empty["devices"] == 0,
          "a trace with no device plane reads busy 0 on 0 devices (the run then fails)")

    path = os.path.join(HERE, "fixtures", "trace_v5e.json.gz")
    with gzip.open(path, "rt") as f:
        rec = json.load(f)
    r = tracered.reduce(rec)
    dev = tracered.device_lines(rec)
    check(len(dev) == 1, "recorded trace: one TPU plane with an 'XLA Ops' line", got=list(dev))
    events = next(iter(dev.values()))
    # the slow way: mark every nanosecond-bucket an op covers
    lo = min(e[1] for e in events)
    hi = max(e[1] + e[2] for e in events)
    step = max(1.0, (hi - lo) / 2_000_000)
    covered = bytearray(int((hi - lo) / step) + 2)
    for _, s, d in events:
        a, b = int((s - lo) / step), int((s + d - lo) / step)
        covered[a:b + 1] = b"\x01" * (b + 1 - a)
    rough = sum(covered) * step / 1e9
    check(abs(r["busy_s"] - rough) <= 0.02 * rough + 2 * step * len(events) / 1e9,
          "recorded trace: busy_s agrees with a bucket count", got=r["busy_s"], want=rough)
    check(0 < r["busy_s"] <= r["window_s"], "recorded trace: 0 < busy <= window",
          busy=r["busy_s"], window=r["window_s"])
    check(len(r["device_ops"]) <= 10 and len(r["idle_gaps"]) <= 10
          and all(v > 0 for _, v in r["device_ops"]),
          "recorded trace: breakdown lists at most ten of each, all above 0")
    check(close(sum(v for _, v in r["idle_gaps"]) + r["busy_s"], r["window_s"], 1e-6),
          "recorded trace: busy + idle gaps = the trace's extent")
    print(f"      recorded trace: busy {r['busy_s']:.6f} s of {r['window_s']:.6f} s; "
          f"top op {r['device_ops'][0]}; longest gap {r['idle_gaps'][0]}")


def four_plane_trace() -> dict:
    """Four chips over a 20 ms extent.  Chip n runs 'exchange' at [n, n+2)
    ms and 'expand' at [10, 10+n+1) ms: busy 3, 4, 5, 6 ms.  The host:
    'dgraph.plan' [0, 10) ms, 'dgraph.encode' [10, 20) ms."""
    ms = 1e6
    planes = [{"name": f"/device:TPU:{n}", "lines": [
        {"name": "XLA Ops", "events": [["exchange", n * ms, 2 * ms], ["expand", 10 * ms, (n + 1) * ms]]},
        {"name": "XLA Modules", "events": [["jit_run", 0.0, 20 * ms]]},
    ]} for n in range(4)]
    planes.append({"name": "/host:CPU", "lines": [{"name": "worker", "events": [
        ["dgraph.plan", 0.0, 10 * ms], ["dgraph.encode", 10 * ms, 10 * ms]]}]})
    return {"planes": planes}


def check_mesh_reduction() -> None:
    r = tracered.reduce(four_plane_trace(), window_s=0.020)
    check(r["devices"] == 4 and close(r["busy_s"], (3 + 4 + 5 + 6) / 4 / 1e3),
          "four-plane trace: busy is the mean a chip", got=r)
    ops, gaps = dict(r["device_ops"]), dict(r["idle_gaps"])
    check(close(ops["exchange"], 0.002) and close(ops["expand"], (1 + 2 + 3 + 4) / 4 / 1e3),
          "four-plane trace: device_ops are a chip's seconds", got=ops)
    check(close(gaps["dgraph.plan"], 0.008) and close(gaps["dgraph.encode"], (9 + 8 + 7 + 6) / 4 / 1e3),
          "four-plane trace: idle gaps are a chip's seconds, by the host event over them", got=gaps)
    check(close(sum(gaps.values()) + r["busy_s"], 0.020), "four-plane trace: busy + idle gaps = the window")
    one = work.roofline_share(1e6, 1e5, r["busy_s"], 819e9)
    four = work.roofline_share(1e6, 1e5, r["busy_s"], 819e9, devices=r["devices"])
    check(close(four, one / 4) and close(four, 100 * (8.8e6 / (4 * 819e9)) / 0.0045),
          "traversal_roofline on four planes: a quarter of the one-plane share for the same bytes and busy time",
          got=(one, four))
    split = harness.route_split({"mesh": 900.0, "path": 50.0, "chain": 30.0, "host": 15.0, "cache": 5.0})
    check(split == (980.0, 1000.0), "route_split: mesh, path and chain edges are the device's", got=split)


def check_bytes() -> None:
    check(work.traversal_bytes(10, 3) == 8 * 10 + 8 * 3, "traversal_bytes: 8 B an edge, 8 B a row")


def check_window_arithmetic() -> None:
    # 8 callers, a 10 s window, every request 500 ms and 2,000 edges: 160 requests ...
    lat = [0.5] * (8 * 20)
    p95, rate = stats.percentile(lat, 95), stats.rate(2000 * len(lat), 10.0)
    # ... and the same window with one 2 s stall: each caller has one request
    # of 2.5 s and completes 15 others, so 8 of 128 requests are slow
    lat_s = [0.5] * (8 * 15) + [2.5] * 8
    p95_s, rate_s = stats.percentile(lat_s, 95), stats.rate(2000 * len(lat_s), 10.0)
    check(close(p95, 0.5) and close(stats.percentile(lat, 50), 0.5),
          "steady window: p50 = p95 = 500 ms")
    check(close(p95_s, 2.5) and close(stats.percentile(lat_s, 50), 0.5), "a stall moves the 95th percentile (no trimming, no chunking)",
          steady=p95, stalled=p95_s)
    check(close(rate_s, 0.8 * rate), "a stall moves edges_per_s (all the window's seconds count)",
          steady=rate, stalled=rate_s)
    check(close(stats.percentile([1, 2, 3, 4], 50), 2.5) and close(stats.percentile([5], 95), 5),
          "percentile interpolates between order statistics")
    check(close(stats.spread([10, 11, 12, 13, 14, 15]), (14.25 - 10.75) / 12.5),
          "spread: inter-quartile distance over the median, statistics.quantiles' rule")


def check_deal() -> None:
    class Pool:
        def __init__(self, n, spec):
            self.n, self.spec = n, spec

        def pool(self):
            import numpy as np

            return np.arange(int(self.spec["root"].get("top", self.n)))

    mix = trafficgen.load_json("traffic", "traverse.json")
    classes = {c["class"]: Pool(5000, trafficgen.load_json("queries", c["class"] + ".json"))
               for c in mix["classes"]}
    a = trafficgen.deal(mix, classes, 1)
    b = trafficgen.deal(mix, classes, 2_999_999_999)
    check(len(a) == mix["deck"] and a != b, "a seed deals the whole deck, in its own order")
    h1000 = sum(1 / r for r in range(1, 1001))
    worst_class, worst_top = 0.0, 0.0
    for plan in (a, b):
        for lo in range(0, 4000, 250):          # any stretch a window might consume
            part = plan[lo:lo + 400]
            n_hot = sum(1 for cls, _ in part if cls == "hot_actor4")
            top = sum(1 for cls, root in part if cls == "hot_actor4" and root == 0)
            worst_class = max(worst_class, abs(n_hot - 0.5 * len(part)))
            worst_top = max(worst_top, abs(top - n_hot / h1000))
    check(worst_class <= 1 + trafficgen.BLOCK, "every stretch of 400 holds the classes in their weights",
          got=worst_class)
    check(worst_top <= 3, "every stretch of 400 sends the pool's first rank its Zipf share (1/H(1000)) within 3",
          got=worst_top)


def main() -> int:
    check_reduction()
    check_mesh_reduction()
    check_bytes()
    check_window_arithmetic()
    check_deal()
    print("selfcheck:", "FAILED: " + "; ".join(FAILED) if FAILED else "all passed")
    return 1 if FAILED else 0


if __name__ == "__main__":
    sys.exit(main())
