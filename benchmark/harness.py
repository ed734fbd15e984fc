"""The parts of a run that touch the system under test: the one child that
owns the chip, the loader call, HTTP, and the readers of the program's
counters.  Copied from ``chip_smoke.py`` (PR 21), which ran them on the chip.
The parent process never imports JAX.
"""

from __future__ import annotations

import json
import os
import re
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(BENCH_DIR)
# the labels of dgraph_ledger_hop_edges_total that are the device's work in the
# program as it stands (utils/metrics.py lists all ten): the per-level program
# (`resident` on a TPU, `csr` elsewhere), hops merged into one (`merged`), the
# fused chain, the MXU tier, the path search's BFS and the mesh executor.
# `cache`, `host` and `empty` are the host's.
DEVICE_ROUTES = ("resident", "csr", "merged", "chain", "mxu", "path", "mesh")
HTTP_TIMEOUT_S = 1100.0


def free_port() -> int:
    """A free TCP port whose +1000 twin (the default gRPC listener) is free
    as well."""
    for _ in range(64):
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
        if port + 1000 > 65535:
            continue
        with socket.socket() as s2:
            try:
                s2.bind(("127.0.0.1", port + 1000))
            except OSError:
                continue
        return port
    raise RuntimeError("no free port pair found")


def child_env() -> dict:
    """The parent's environment, with the checkout importable — nothing
    forced: no DGRAPH_TPU_* knob, no JAX_PLATFORMS."""
    env = dict(os.environ)
    env["PYTHONPATH"] = CHECKOUT + os.pathsep + env.get("PYTHONPATH", "")
    return env


def http(addr: str, path: str, body: str | None = None, timeout=HTTP_TIMEOUT_S) -> str:
    req = urllib.request.Request(
        addr + path, data=body.encode() if body is not None else None
    )
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.read().decode()


def http_json(addr, path, body=None):
    return json.loads(http(addr, path, body))


class Server:
    """The one child that owns the chip: ``python -m dgraph_tpu.cli.server``
    with default settings and a fresh postings directory, run from the work
    directory.  ``wrapper_args`` starts it through ``server_child.py``
    instead (the profiler hook of a traced run, a planted fault of a test);
    the wrapper calls the same ``main`` with the same arguments."""

    def __init__(self, workdir: str, wrapper_args: list | None = None):
        self.port = free_port()
        self.addr = f"http://127.0.0.1:{self.port}"
        self.log_path = os.path.join(workdir, "server.log")
        self._log = open(self.log_path, "wb")
        entry = (
            [os.path.join(BENCH_DIR, "server_child.py"), *wrapper_args, "--"]
            if wrapper_args is not None
            else ["-m", "dgraph_tpu.cli.server"]
        )
        self.proc = subprocess.Popen(
            [sys.executable, *entry,
             "--p", os.path.join(workdir, "p"), "--port", str(self.port)],
            cwd=workdir, env=child_env(), stdout=self._log, stderr=subprocess.STDOUT,
        )

    def wait_healthy(self, timeout_s: float = 300.0) -> float:
        t0 = time.monotonic()
        while time.monotonic() - t0 < timeout_s:
            if self.proc.poll() is not None:
                raise RuntimeError(
                    f"server exited with code {self.proc.returncode} during "
                    f"boot:\n{self.log_tail()}"
                )
            try:
                if http(self.addr, "/health", timeout=2.0).strip() == "OK":
                    return time.monotonic() - t0
            except (urllib.error.URLError, OSError):
                pass
            time.sleep(0.1)
        raise RuntimeError(f"server not healthy after {timeout_s}s:\n{self.log_tail()}")

    def log_tail(self, n: int = 4000) -> str:
        self._log.flush()
        with open(self.log_path, "rb") as f:
            f.seek(0, os.SEEK_END)
            f.seek(max(0, f.tell() - n))
            return f.read().decode(errors="replace")

    def stop(self, grace_s: float = 120.0) -> None:
        """Asks the server to shut down and waits ``grace_s`` for it; one
        that is still there (a profiler stop that does not return) is killed."""
        if self.proc.poll() is None:
            try:
                http(self.addr, "/admin/shutdown", timeout=10.0)
                self.proc.wait(timeout=grace_s)
            except (urllib.error.URLError, OSError, subprocess.TimeoutExpired):
                pass
        if self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait()
        self._log.close()


def start_loader(workdir: str, addr: str, rdf: str, schema: str) -> subprocess.Popen:
    """``python -m dgraph_tpu.cli.loader`` as users run it; the caller
    ``communicate()``s and reads 'loaded N quads' from its output."""
    return subprocess.Popen(
        [sys.executable, "-m", "dgraph_tpu.cli.loader", "-r", rdf, "-s", schema,
         "-d", addr, "--batch", "100000"],
        cwd=workdir, env=child_env(), stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True,
    )


# -- the program's counters ----------------------------------------------------------


_SAMPLE = re.compile(r"^(\w+)(?:\{(.*)\})?\s+([0-9.eE+-]+)$")
_ONE_LABEL = re.compile(r"^\w+=\"([^\"]*)\"$")


def parse_metrics(text: str) -> dict:
    """{family: {label: sample}} of a Prometheus exposition: the label is
    the value of a family's one label, '' where it has none, and the whole
    label string where it has several."""
    out = {}
    for line in text.splitlines():
        m = _SAMPLE.match(line)
        if not m:
            continue
        one = _ONE_LABEL.match(m.group(2) or "")
        key = one.group(1) if one else (m.group(2) or "")
        out.setdefault(m.group(1), {})[key] = float(m.group(3))
    return out


def counters(addr: str) -> dict:
    """Every family the program exposes, as ``parse_metrics`` reads it."""
    return parse_metrics(http(addr, "/debug/prometheus_metrics"))


def delta(before: dict, after: dict, family: str) -> dict:
    """{label: after - before} of one family between two ``counters()``."""
    b, a = before.get(family, {}), after.get(family, {})
    return {k: a[k] - b.get(k, 0.0) for k in a}


def route_split(by_route: dict) -> tuple:
    """(edges on device routes, edges on all routes) of a
    ``dgraph_ledger_hop_edges_total`` delta."""
    return sum(by_route.get(r, 0.0) for r in DEVICE_ROUTES), sum(by_route.values())


_UNIT_S = {"ns": 1e-9, "us": 1e-6, "µs": 1e-6, "ms": 1e-3, "s": 1.0}


def duration_s(text) -> float | None:
    """Seconds of a ``server_latency`` duration ('79.3ms', Go's rendering)."""
    m = re.match(r"^([0-9.eE+-]+)(ns|us|µs|ms|s)$", text or "")
    return float(m.group(1)) * _UNIT_S[m.group(2)] if m else None


# -- is the device doing the work -----------------------------------------------------


def device_identity(dev: dict) -> dict:
    return {"platform": dev["backend"], "kind": dev["device_kind"], "count": dev["devices"]}


def memory_peak_bytes(dev: dict) -> int | None:
    peaks = [
        (v or {}).get("peak_bytes_in_use") for v in (dev.get("memory") or {}).values()
    ]
    peaks = [p for p in peaks if p is not None]
    return int(max(peaks)) if peaks else None


def unfit(addr: str, chips: int) -> list:
    """Why this server's answers may not be reported as the chip's: no TPU,
    too few chips, a device failover, a guard domain not healthy, or a
    calibration taken from priors.  Empty where all is well."""
    dev = http_json(addr, "/debug/device")
    plan = http_json(addr, "/debug/planner")
    why = []
    if dev["backend"] != "tpu" or not dev["device_kind"]:
        why.append(f"the server runs on {dev['backend']!r}, not a TPU")
    if dev["devices"] < chips:
        why.append(f"{dev['devices']} chips, the cell asks for {chips}")
    failovers = counters(addr).get("dgraph_device_failover_total", {})
    if any(v != 0 for v in failovers.values()):
        why.append(f"device failover: {failovers}")
    for name, st in dev["guard"]["domains"].items():
        if st["state"] != "healthy" or st["faults"] or st["wedged_workers"]:
            why.append(f"guard domain {name}: {st['state']}")
    if plan["calibration"]["source"] == "prior":
        why.append("the planner's calibration is a prior, not measured on this backend")
    return why
