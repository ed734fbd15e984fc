#!/usr/bin/env python3
"""The shared-clock check of the stage catalogue (PR 26), by hand:

    python3 benchmark/stagecheck.py <tag> --workload film-q4.traverse --seed 7 --seconds 51 --trace 1

runs ``run.py`` unchanged (same arguments after ``<tag>``) and writes, beside
its result, ``chiprun_out/<tag>.stages.json``: (a) the total duration and the
count of every ``dgraph.*`` event on the host planes of the trace the run took
through ``server_child.py`` and (b) the window's growth of the program's stage,
byte, query and latency counters, with the means of ``server_latency.total``
and of the client's latency less it.  Per stage, (a) over (b) is 1.00 where
the program's spans and its counters sit on one clock (PERF.md section 6).
No benchmark run uses this file; it edits none and patches two names in its
own process only.
"""

from __future__ import annotations

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import compare  # noqa: E402
import harness  # noqa: E402
import run  # noqa: E402
import tracered  # noqa: E402

FAMILIES = ("dgraph_ledger_stage_us_total", "dgraph_num_queries_total",
            "dgraph_query_latency_seconds_sum", "dgraph_ledger_bytes_total",
            "dgraph_xla_compiles_total", "dgraph_xla_cache_reads_total")


def main(argv) -> int:
    tag, rest = argv[0], argv[1:]
    out_dir = os.path.join(harness.CHECKOUT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    dump = {}
    reduce_ = tracered.reduce

    def reduce(trace, window_s=None, rehearsal=False):
        total, count = {}, {}
        for p in trace["planes"]:
            if p["name"].startswith("/host:"):
                for ln in p["lines"]:
                    for name, _, dur in ln["events"]:
                        if name.startswith("dgraph."):
                            total[name] = total.get(name, 0.0) + dur
                            count[name] = count.get(name, 0) + 1
        dump["trace_total_ns"], dump["trace_count"] = total, count
        return reduce_(trace, window_s=window_s, rehearsal=rehearsal)

    class Observed(run.Observed):
        def __init__(self, **kw):
            super().__init__(**kw)
            for fam in FAMILIES:
                dump[fam] = self.delta(fam)
            total = compare.server_seconds(kw["tails"], "total")
            over = [(r[4] - r[3]) - t for r, t in zip(kw["records"], total)
                    if r[5] == 200 and t is not None]
            seen = [t for t in total if t is not None]
            dump["server_total_mean_ms"] = 1e3 * sum(seen) / max(1, len(seen))
            dump["entry_overhead_mean_ms"] = 1e3 * sum(over) / max(1, len(over))
            dump["answered"] = len(kw["answered"])
            with open(os.path.join(out_dir, tag + ".stages.json"), "w") as f:
                json.dump(dump, f)

    tracered.reduce, run.Observed = reduce, Observed
    return run.main(rest)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
