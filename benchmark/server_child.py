#!/usr/bin/env python3
"""The benchmark's wrapper round the server child: ``dgraph_tpu.cli.server``'s
``main`` unchanged, with what only the process that holds the chip can do.

    server_child.py [--control DIR] [--fault NAME] -- <server arguments>

``--control DIR``: a thread watches DIR for ``trace.start`` and starts
``jax.profiler.start_trace(DIR/trace)``; on ``trace.stop`` it stops it.  Each
step is acknowledged by a file (``trace.started`` / ``trace.stopped``, JSON
with the host's clock round the call) written under a temporary name and
renamed, so the parent never reads half a file.  The program has no profiler
hook of its own (PERF.md, Open questions).

``--fault NAME``: imports ``benchmark/tests/faults/NAME.py`` and calls its
``install()`` before the server starts — the benchmark's own tests plant a
fault in the timed path this way and see ``correct`` come out false.  No
benchmark run passes it.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
POLL_S = 0.02


def _ack(path: str, obj: dict) -> None:
    with open(path + ".tmp", "w") as f:
        json.dump(obj, f)
    os.replace(path + ".tmp", path)


def _watch(control: str) -> None:
    def wait_for(name: str) -> None:
        p = os.path.join(control, name)
        while not os.path.exists(p):
            time.sleep(POLL_S)

    wait_for("trace.start")
    # not before: imported at the thread's start it raced the main thread's own
    # imports for numpy's module lock, and one boot in some dozens died of it
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0   # the host's Python frames would swamp the file
    opts.host_tracer_level = 2     # 1 holds as many events (measured, PR 33): the runtime's stay
    t0 = time.time_ns()
    try:
        jax.profiler.start_trace(os.path.join(control, "trace"), profiler_options=opts)
    except Exception as e:  # noqa: BLE001 — the parent reads why and gives no result
        _ack(os.path.join(control, "trace.started"), {"error": repr(e)})
        return
    t1 = time.time_ns()
    _ack(os.path.join(control, "trace.started"), {"call_ns": t0, "return_ns": t1})
    wait_for("trace.stop")
    t2 = time.time_ns()
    try:
        jax.profiler.stop_trace()
    except Exception as e:  # noqa: BLE001
        _ack(os.path.join(control, "trace.stopped"), {"error": repr(e)})
        return
    t3 = time.time_ns()
    _ack(os.path.join(control, "trace.stopped"),
         {"call_ns": t2, "return_ns": t3, "traced_s": (t2 - t1) / 1e9})


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    cut = argv.index("--")
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--control", default="")
    ap.add_argument("--fault", default="")
    args = ap.parse_args(argv[:cut])
    if args.fault:
        spec = importlib.util.spec_from_file_location(
            "bench_fault", os.path.join(HERE, "tests", "faults", args.fault + ".py")
        )
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        mod.install()
    if args.control:
        os.makedirs(args.control, exist_ok=True)
        threading.Thread(target=_watch, args=(args.control,), daemon=True,
                         name="bench-trace-hook").start()
    from dgraph_tpu.cli import server

    return server.main(argv[cut + 1:])


if __name__ == "__main__":
    sys.exit(main())
