"""From a profiler trace to numbers: device busy time, the operations that
took most of it, and the idle gaps by what the host was doing.

Two steps, so that the second can be checked on a recorded trace
(``selfcheck.py``, ``fixtures/``) with nothing but Python:

``load_xplane(path)``  the profiler's ``.xplane.pb`` -> a plain structure
                       ``{"planes": [{"name", "lines": [{"name", "events":
                       [[name, start_ns, dur_ns], ...]}]}]}``
``reduce(trace, window_s)``  that structure -> the numbers.

A device plane is one named ``/device:TPU:<n>``; its ``XLA Ops`` line holds
one event per operation the chip ran.  Busy time is the UNION of those
intervals per chip, averaged over the chips.  The program puts no spans of
its own on the profiler's clock yet (PERF.md), so a gap is attributed to the
host-plane event that overlaps it most — as far as the trace allows.
"""

from __future__ import annotations

import glob
import os
import re

import numpy as np

OPS_LINE = "XLA Ops"
GAPS_ATTRIBUTED = 200          # the longest gaps get a name; the rest are summed
TOP = 10


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load_xplane(path: str) -> dict:
    """Reads the trace with JAX's own reader.  Called only after the server
    child has ended, and held to the CPU: the parent never reaches for the
    chip."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from jax.profiler import ProfileData

    planes = []
    for p in ProfileData.from_file(path).planes:
        lines = []
        for ln in p.lines:
            ev = [[e.name, float(e.start_ns), float(e.duration_ns)] for e in ln.events]
            if ev:
                lines.append({"name": ln.name, "events": ev})
        planes.append({"name": p.name, "lines": lines})
    return {"planes": planes}


def device_lines(trace: dict, rehearsal: bool = False) -> dict:
    """{device name: [events]} of the lines that hold device operations.  On
    a CPU rehearsal there is no device plane: the CPU client's worker
    threads stand in, so that the control flow runs — never for a result."""
    out = {}
    for p in trace["planes"]:
        if p["name"].startswith("/device:TPU:"):
            for ln in p["lines"]:
                if ln["name"] == OPS_LINE:
                    out[p["name"]] = ln["events"]
    if not out and rehearsal:
        ev = [
            e for p in trace["planes"] if p["name"].startswith("/host:")
            for ln in p["lines"] if ln["name"].startswith("tf_XLAPjRtCpuClient")
            for e in ln["events"] if e[2] > 0
        ]
        if ev:
            out["/host:CPU (rehearsal)"] = ev
    return out


def host_events(trace: dict) -> list:
    return [
        e for p in trace["planes"] if p["name"].startswith("/host:")
        for ln in p["lines"] if not ln["name"].startswith("tf_XLAPjRtCpuClient")
        for e in ln["events"] if e[2] > 0
    ]


_HLO = re.compile(r"^%(\S+) = \(?([a-z0-9]+\[[0-9,]*\])")


def short(name: str) -> str:
    """An operation's name as the trace gives it, cut to what tells it
    apart: '%fusion.3 = s32[2097152]{...} fusion(...)' -> 'fusion.3 s32[2097152]'."""
    m = _HLO.match(name)
    return f"{m.group(1)} {m.group(2)}" if m else name[:80]


def union(events) -> tuple:
    """(busy ns, [(start, end)] merged) of a list of [name, start, dur]."""
    iv = sorted((e[1], e[1] + e[2]) for e in events if e[2] > 0)
    merged = []
    for s, t in iv:
        if merged and s <= merged[-1][1]:
            if t > merged[-1][1]:
                merged[-1][1] = t
        else:
            merged.append([s, t])
    return sum(t - s for s, t in merged), merged


def extent(trace: dict) -> tuple:
    lo, hi = np.inf, -np.inf
    for p in trace["planes"]:
        for ln in p["lines"]:
            for _, s, d in ln["events"]:
                lo, hi = min(lo, s), max(hi, s + d)
    return lo, hi


def reduce(trace: dict, window_s: float | None = None, rehearsal: bool = False) -> dict:
    """``busy_s`` (averaged over the chips), ``window_s`` (as given: the
    host's clock from the profiler's start to its stop; else the trace's own
    extent), ``device_ops`` and ``idle_gaps`` (at most ten [name, seconds]
    each), and ``devices`` traced."""
    dev = device_lines(trace, rehearsal)
    lo, hi = extent(trace)
    if window_s is None:
        window_s = (hi - lo) / 1e9 if hi > lo else 0.0
    if not dev:
        return {"busy_s": 0.0, "window_s": window_s, "devices": 0,
                "device_ops": [], "idle_gaps": []}
    busy, by_op, gaps = [], {}, []
    for events in dev.values():
        b, merged = union(events)
        busy.append(b / 1e9)
        for name, _, d in events:
            by_op[name] = by_op.get(name, 0.0) + d / 1e9
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    n = len(dev)
    by_short = {}
    for name, secs in by_op.items():
        by_short[short(name)] = by_short.get(short(name), 0.0) + secs
    ops = sorted(by_short.items(), key=lambda kv: -kv[1])[:TOP]
    return {
        "busy_s": sum(busy) / n,
        "window_s": window_s,
        "devices": n,
        "device_ops": [[k, v / n] for k, v in ops],
        "idle_gaps": _attribute(gaps, host_events(trace), n),
    }


def _attribute(gaps, host, n_dev: int) -> list:
    """[[what the host was doing, idle seconds]]: each of the longest gaps
    goes to the host event that overlaps it most — of those within a tenth
    of the most, the shortest, so the most specific — and the rest to one
    row of their own."""
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])
    named, rest = gaps[:GAPS_ATTRIBUTED], gaps[GAPS_ATTRIBUTED:]
    out = {}
    if host:
        hs = np.array([e[1] for e in host])
        he = hs + np.array([e[2] for e in host])
    for g0, g1 in named:
        label = "no host event in the trace"
        if host:
            ov = np.minimum(he, g1) - np.maximum(hs, g0)
            best = ov.max()
            if best > 0:
                cand = np.flatnonzero(ov >= 0.9 * best)
                label = short(host[int(cand[np.argmin((he - hs)[cand])])][0])
        out[label] = out.get(label, 0.0) + (g1 - g0) / 1e9
    if rest:
        key = f"gaps shorter than {(rest[0][1] - rest[0][0]) / 1e6:.3g} ms"
        out[key] = sum(g1 - g0 for g0, g1 in rest) / 1e9
    top = sorted(out.items(), key=lambda kv: -kv[1])[:TOP]
    return [[k, v / n_dev] for k, v in top]
