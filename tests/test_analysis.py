"""graftcheck's own tests: golden-bad fixtures (each rule must flag its
canonical bug), a clean-tree gate (the shipped package must carry zero
findings and a cycle-free lock graph), and load-bearing proofs for the
runtime halves — the witness recorder must catch a seeded lock-order
inversion, the budget plugin must fail a seeded recompile storm, and
the compiled hop program must be implicit-transfer-free under
jax.transfer_guard."""

import textwrap
import threading

import numpy as np
import pytest

from dgraph_tpu.analysis.framework import check_source, run_rules
from dgraph_tpu.analysis.lockorder import build_lock_graph, check_lock_order
from dgraph_tpu.analysis.rules import (
    ALL_RULES,
    HostSyncInJit,
    NakedAtomicWrite,
    NakedPeerRpc,
    NakedRouteThreshold,
    NakedStageTiming,
    RecompileHazard,
    SwallowedException,
    UncheckedHopLoop,
    WallClockDuration,
)
from dgraph_tpu.analysis import witness as witness_mod

pytest_plugins = ["pytester"]


def _ids(findings):
    return [f.rule for f in findings]


# ------------------------------------------------------ golden bad fixtures

def test_host_sync_item_in_jit_flagged():
    src = textwrap.dedent("""
        import jax

        @jax.jit
        def f(x):
            return x.sum().item()
    """)
    assert _ids(check_source(src, [HostSyncInJit()])) == ["host-sync-in-jit"]


def test_host_sync_np_asarray_in_scan_body_flagged():
    src = textwrap.dedent("""
        import numpy as np
        from jax import lax

        def step(carry, x):
            bad = np.asarray(x)
            return carry, bad

        def drive(xs):
            return lax.scan(step, 0, xs)
    """)
    assert _ids(
        check_source(src, [HostSyncInJit()])
    ) == ["host-sync-in-jit"]


def test_host_sync_in_fori_cond_while_bodies_flagged():
    # the traced callee sits at DIFFERENT positions per combinator:
    # fori_loop's body is arg 2, cond's branches are args 1-2,
    # while_loop traces both cond_fun and body_fun
    src = textwrap.dedent("""
        from jax import lax

        def body(i, x):
            return x + x.mean().item()

        def t(x):
            return x

        def f(x):
            bad = bool(x)
            return x

        def wcond(x):
            return x.sum().item() > 0

        def drive(n, x, p):
            a = lax.fori_loop(0, n, body, x)
            b = lax.cond(p, t, f, x)
            c = lax.while_loop(wcond, t, x)
            return a, b, c
    """)
    findings = check_source(src, [HostSyncInJit()])
    # body's .item(), the false-branch's bool(x) (branch params are
    # traced), and wcond's .item()
    assert len(findings) == 3
    assert {f.line for f in findings} == {5, 11, 15}


def test_host_sync_bool_of_traced_param_flagged():
    src = textwrap.dedent("""
        import jax

        @jax.jit
        def f(x):
            if bool(x):
                return x
            return -x
    """)
    assert _ids(check_source(src, [HostSyncInJit()])) == ["host-sync-in-jit"]


def test_host_sync_static_args_not_flagged():
    # int()/bool() on a static_argnames parameter is a Python value —
    # exactly how engine.py's packed expand programs use `cap`
    src = textwrap.dedent("""
        import jax
        from functools import partial

        @partial(jax.jit, static_argnames=("cap",))
        def f(x, cap):
            return x[: int(cap)]
    """)
    assert check_source(src, [HostSyncInJit()]) == []


def test_host_sync_outside_trace_not_flagged():
    src = textwrap.dedent("""
        import numpy as np

        def host_fn(x):
            return np.asarray(x).item()
    """)
    assert check_source(src, [HostSyncInJit()]) == []


def test_recompile_jit_in_loop_flagged():
    src = textwrap.dedent("""
        import jax

        def run(xs):
            out = []
            for x in xs:
                out.append(jax.jit(lambda v: v + 1)(x))
            return out
    """)
    findings = check_source(src, [RecompileHazard()])
    assert "recompile-hazard" in _ids(findings)


def test_recompile_inline_invocation_flagged():
    src = textwrap.dedent("""
        import jax

        def f(g, x):
            return jax.jit(g)(x)
    """)
    assert _ids(check_source(src, [RecompileHazard()])) == ["recompile-hazard"]


def test_recompile_module_level_jit_not_flagged():
    src = textwrap.dedent("""
        import jax

        def _make():
            @jax.jit
            def run(x):
                return x * 2
            return run

        _cached = _make()
    """)
    assert check_source(src, [RecompileHazard()]) == []


def test_wallclock_deadline_math_flagged():
    src = textwrap.dedent("""
        import time

        def wait(timeout):
            deadline = time.time() + timeout
            while time.time() < deadline:
                pass
    """)
    findings = check_source(src, [WallClockDuration()])
    assert _ids(findings) == ["wallclock-duration", "wallclock-duration"]


def test_wallclock_duration_via_names_flagged():
    src = textwrap.dedent("""
        import time

        def rate(n):
            t0 = time.time()
            work()
            return n / (time.time() - t0)
    """)
    assert "wallclock-duration" in _ids(
        check_source(src, [WallClockDuration()])
    )


def test_wallclock_timestamp_not_flagged():
    # producing a timestamp is what wall clock is FOR
    src = textwrap.dedent("""
        import time

        def stamp(record):
            record["created_at"] = time.time()
            return record
    """)
    assert check_source(src, [WallClockDuration()]) == []


def test_wallclock_monotonic_not_flagged():
    src = textwrap.dedent("""
        import time

        def wait(timeout):
            deadline = time.monotonic() + timeout
            while time.monotonic() < deadline:
                pass
    """)
    assert check_source(src, [WallClockDuration()]) == []


def test_swallowed_broad_except_pass_flagged():
    src = textwrap.dedent("""
        def f():
            try:
                g()
            except Exception:
                pass
    """)
    assert _ids(
        check_source(src, [SwallowedException()])
    ) == ["swallowed-exception"]


def test_naked_peer_rpc_urlopen_peer_flagged_anywhere():
    src = textwrap.dedent("""
        from dgraph_tpu.cluster.transport import urlopen_peer

        def fetch(req, auth):
            with urlopen_peer(req, 5, auth) as resp:
                return resp.read()
    """)
    assert _ids(
        check_source(src, [NakedPeerRpc()], path="dgraph_tpu/serve/foo.py")
    ) == ["naked-peer-rpc"]


def test_naked_peer_rpc_channel_call_flagged_in_cluster():
    src = textwrap.dedent("""
        def send(channel, payload):
            rpc = channel.unary_unary("/protos.Worker/RaftMessage")
            return rpc(payload, timeout=2.0)
    """)
    assert _ids(
        check_source(
            src, [NakedPeerRpc()], path="dgraph_tpu/cluster/newtransport.py"
        )
    ) == ["naked-peer-rpc"]


def test_naked_peer_rpc_clean_counterexamples():
    # the funnel itself is the one legitimate home of both call forms
    inside = textwrap.dedent("""
        def call(self, req, channel, payload, auth):
            with urlopen_peer(req, 5, auth) as resp:
                resp.read()
            return channel.unary_unary("/m")(payload)
    """)
    assert check_source(
        inside, [NakedPeerRpc()], path="dgraph_tpu/cluster/peerclient.py"
    ) == []
    # routing THROUGH the funnel is clean anywhere
    routed = textwrap.dedent("""
        def forward(self, peer, req):
            with self.peerclient.urlopen(peer, req, op="forward", budget=5) as r:
                return r.read()
    """)
    assert check_source(
        routed, [NakedPeerRpc()], path="dgraph_tpu/cluster/service.py"
    ) == []
    # a raw channel RPC on the PUBLIC client surface is out of scope
    client_side = textwrap.dedent("""
        def probe(channel):
            return channel.unary_unary("/protos.Dgraph/CheckVersion")(b"")
    """)
    assert check_source(
        client_side, [NakedPeerRpc()], path="dgraph_tpu/serve/grpc_server.py"
    ) == []


def test_naked_atomic_write_os_replace_flagged():
    src = textwrap.dedent("""
        import os

        def persist(path, blob):
            tmp = path + ".tmp"
            with open(tmp, "wb") as f:
                f.write(blob)
            os.replace(tmp, path)
    """)
    assert _ids(
        check_source(src, [NakedAtomicWrite()], path="dgraph_tpu/models/x.py")
    ) == ["naked-atomic-write"]


def test_naked_atomic_write_imported_rename_flagged():
    # `from os import replace` must not slip past the dotted-name check
    src = textwrap.dedent("""
        from os import replace as _rp

        def persist(tmp, path):
            _rp(tmp, path)
    """)
    assert _ids(
        check_source(src, [NakedAtomicWrite()], path="dgraph_tpu/cli/x.py")
    ) == ["naked-atomic-write"]


def test_naked_atomic_write_clean_counterexamples():
    # the helper itself is the one legitimate home of the raw call
    inside = textwrap.dedent("""
        import os

        def atomic_write_file(path, data):
            tmp = path + ".tmp"
            with open(tmp, "wb") as f:
                f.write(data)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, path)
    """)
    assert check_source(
        inside, [NakedAtomicWrite()], path="dgraph_tpu/utils/atomicio.py"
    ) == []
    # routing THROUGH the helper is clean anywhere
    routed = textwrap.dedent("""
        from dgraph_tpu.utils.atomicio import atomic_write_file

        def persist(path, blob):
            atomic_write_file(path, blob, site="raft.hardstate")
    """)
    assert check_source(
        routed, [NakedAtomicWrite()], path="dgraph_tpu/cluster/raft.py"
    ) == []
    # a str.replace() call is not a rename
    strings = textwrap.dedent("""
        def norm(s):
            return s.replace("a", "b")
    """)
    assert check_source(
        strings, [NakedAtomicWrite()], path="dgraph_tpu/gql/x.py"
    ) == []
    # pragma'd deliberate site (rename of an already-fully-synced file)
    sealed = textwrap.dedent("""
        import os

        def seal(path, seg):
            os.replace(path, seg)  # graftlint: ignore[naked-atomic-write]
    """)
    assert check_source(
        sealed, [NakedAtomicWrite()], path="dgraph_tpu/models/wal.py"
    ) == []


def test_naked_stage_timing_bracketing_flagged_in_serving_dirs():
    # the canonical bug: t0 = perf_counter() ... elapsed = pc() - t0
    src = textwrap.dedent("""
        import time as _time

        def expand(self, rows):
            t0 = _time.perf_counter()
            out = do_expand(rows)
            self.stats["ms"] += (_time.perf_counter() - t0) * 1e3
            return out
    """)
    assert _ids(
        check_source(
            src, [NakedStageTiming()], path="dgraph_tpu/query/newexec.py"
        )
    ) == ["naked-stage-timing"]
    # direct-call form without an intermediate name
    inline = textwrap.dedent("""
        import time

        def handle(self):
            start = time.perf_counter_ns()
            serve()
            return time.perf_counter_ns() - start
    """)
    assert _ids(
        check_source(
            inline, [NakedStageTiming()], path="dgraph_tpu/serve/handler.py"
        )
    ) == ["naked-stage-timing"]


def test_naked_stage_timing_counterexamples_clean():
    # the span API is the sanctioned home of the raw clock reads
    inside = textwrap.dedent("""
        import time

        class _Stage:
            def __enter__(self):
                self.t0 = time.perf_counter()
                return self

            def __exit__(self, et, ev, tb):
                self.stats[self.key] += (time.perf_counter() - self.t0) * 1e3
    """)
    assert check_source(
        inside, [NakedStageTiming()], path="dgraph_tpu/obs/spans.py"
    ) == []
    # utils/trace.py (the legacy Latency marks) is exempt by design
    assert check_source(
        inside, [NakedStageTiming()], path="dgraph_tpu/utils/trace.py"
    ) == []
    # routing THROUGH obs.stage is clean in the serving tree
    routed = textwrap.dedent("""
        from dgraph_tpu import obs

        def expand(self, rows):
            with obs.stage(self.stats, "device_expand_ms"):
                return do_expand(rows)
    """)
    assert check_source(
        routed, [NakedStageTiming()], path="dgraph_tpu/query/engine.py"
    ) == []
    # outside the serving dirs the rule does not apply (models/, ops/
    # own their micro-bench timing)
    bench = textwrap.dedent("""
        import time

        def measure():
            t0 = time.perf_counter()
            work()
            return time.perf_counter() - t0
    """)
    assert check_source(
        bench, [NakedStageTiming()], path="dgraph_tpu/models/arena.py"
    ) == []
    # monotonic() deadline logic is wallclock-rule territory, not this
    deadline = textwrap.dedent("""
        import time

        def wait(timeout):
            deadline = time.monotonic() + timeout
            return deadline - time.monotonic()
    """)
    assert check_source(
        deadline, [NakedStageTiming()], path="dgraph_tpu/sched/scheduler.py"
    ) == []


def test_naked_stage_timing_pragma_with_why():
    src = textwrap.dedent("""
        import time

        def profile(self):
            t0 = time.perf_counter()
            run()
            # offline profiling harness, never in the serving path
            # graftlint: ignore[naked-stage-timing]
            return time.perf_counter() - t0
    """)
    assert check_source(
        src, [NakedStageTiming()], path="dgraph_tpu/query/profiler.py"
    ) == []


def test_naked_route_threshold_env_read_flagged():
    # the PR-10 origin story: a DGRAPH_TPU_* env read growing a new magic
    # threshold inside the routing layers
    src = textwrap.dedent("""
        import os

        def gate():
            return int(os.environ.get("DGRAPH_TPU_NEW_ROUTE_MIN", 262144))
    """)
    assert _ids(
        check_source(
            src, [NakedRouteThreshold()], path="dgraph_tpu/query/newroute.py"
        )
    ) == ["naked-route-threshold"]
    # os.getenv spelling too, and ops/ is in scope
    src2 = textwrap.dedent("""
        import os

        def gate():
            return os.getenv("DGRAPH_TPU_KERNEL_PICK", "auto")
    """)
    assert _ids(
        check_source(
            src2, [NakedRouteThreshold()], path="dgraph_tpu/ops/newkernel.py"
        )
    ) == ["naked-route-threshold"]


def test_naked_route_threshold_literal_compare_flagged():
    # both historical spellings: the bare decimal and the shifted literal
    src = textwrap.dedent("""
        def pick(est_total, capc):
            if est_total < 262144:
                return "host"
            if capc > 1 << 21:
                return "abort"
            return "device"
    """)
    findings = check_source(
        src, [NakedRouteThreshold()], path="dgraph_tpu/query/route.py"
    )
    assert _ids(findings) == ["naked-route-threshold"] * 2


def test_naked_route_threshold_counterexamples_clean():
    # named thresholds from planconfig / the planner are the fix
    routed = textwrap.dedent("""
        from dgraph_tpu.utils import planconfig

        def pick(est_total):
            if est_total < planconfig.chain_threshold():
                return "host"
            return "device"
    """)
    assert check_source(
        routed, [NakedRouteThreshold()], path="dgraph_tpu/query/route.py"
    ) == []
    # small literals (capacities, buckets, lane widths) are not gates
    small = textwrap.dedent("""
        def bucketed(n):
            if n < 4096:
                return 4096
            return n
    """)
    assert check_source(
        small, [NakedRouteThreshold()], path="dgraph_tpu/ops/kern.py"
    ) == []
    # outside query//ops/ the rule does not apply (models/ owns its own
    # budgets; serve/ reads its knobs through its gates)
    outside = textwrap.dedent("""
        import os

        def budget():
            return int(os.environ.get("DGRAPH_TPU_ARENA_BUDGET", 262144))
    """)
    assert check_source(
        outside, [NakedRouteThreshold()], path="dgraph_tpu/models/arena.py"
    ) == []
    # the pragma escape hatch carries the WHY
    pragmad = textwrap.dedent("""
        def sanity(cap):
            # jit-cache hard stop, not a route gate
            # graftlint: ignore[naked-route-threshold]
            assert cap < 16777216
    """)
    assert check_source(
        pragmad, [NakedRouteThreshold()], path="dgraph_tpu/ops/kern.py"
    ) == []


def test_unchecked_hop_loop_flagged():
    # the PR-11 origin story: a per-level expansion loop that never
    # checkpoints the request's CancelToken — a cancelled query keeps
    # dispatching hops here
    src = textwrap.dedent("""
        def run_levels(engine, levels, src, resolver):
            for child in levels:
                engine._exec_child(child, src, resolver, {}, {})
    """)
    assert _ids(
        check_source(
            src, [UncheckedHopLoop()], path="dgraph_tpu/query/newpath.py"
        )
    ) == ["unchecked-hop-loop"]
    # the local-wrapper shape (shortest.py's lazy expander): a bare
    # expand() call in a search loop is the same seam
    src2 = textwrap.dedent("""
        def search(expand, heap):
            while heap:
                u = heap.pop()
                expand(u)
    """)
    assert _ids(
        check_source(
            src2, [UncheckedHopLoop()], path="dgraph_tpu/query/walk.py"
        )
    ) == ["unchecked-hop-loop"]


def test_unchecked_hop_loop_counterexamples_clean():
    # the fix: a checkpoint inside the loop (method or token form)
    checked = textwrap.dedent("""
        def run_levels(engine, levels, src, resolver):
            for child in levels:
                engine.checkpoint()
                engine._exec_child(child, src, resolver, {}, {})

        def probe_tokens(self, idx, toks):
            for t in toks:
                self.cancel_token.check()
                self._expand_rows(idx.csr, [t])
    """)
    assert check_source(
        checked, [UncheckedHopLoop()], path="dgraph_tpu/query/newpath.py"
    ) == []
    # a loop that never touches the dispatch seam is not a hop loop
    plain = textwrap.dedent("""
        def tally(children):
            total = 0
            for c in children:
                total += len(c.values)
            return total
    """)
    assert check_source(
        plain, [UncheckedHopLoop()], path="dgraph_tpu/query/enc.py"
    ) == []
    # outside query/ the rule does not apply: ops/ loops run inside
    # jitted programs where a checkpoint is impossible by design
    outside = textwrap.dedent("""
        def kernel(ce, fronts):
            for f in fronts:
                ce.expand(f)
    """)
    assert check_source(
        outside, [UncheckedHopLoop()], path="dgraph_tpu/ops/kern.py"
    ) == []
    # pragma escape hatch with the WHY
    pragmad = textwrap.dedent("""
        def replay(engine, levels, src, resolver):
            # replay of an already-admitted fixture: no live client
            # graftlint: ignore[unchecked-hop-loop]
            for child in levels:
                engine._exec_child(child, src, resolver, {}, {})
    """)
    assert check_source(
        pragmad, [UncheckedHopLoop()], path="dgraph_tpu/query/fixture.py"
    ) == []


def test_unchecked_segment_loop_flagged_in_all_driver_layers():
    """PR 18: a loop re-dispatching a program segment without a seam
    probe is flagged — including in ops/ and mesh/, where the plain
    hop-loop rule is exempt (segment loops are HOST loops between
    bounded programs, exactly where a yield point is possible)."""
    bad = textwrap.dedent("""
        def run_segments(carry, n, k):
            lo = 0
            while lo < n:
                carry = _dispatch_segment(carry, lo, min(lo + k, n))
                lo += k
            return carry
    """)
    for path in (
        "dgraph_tpu/ops/batch.py",
        "dgraph_tpu/query/chain.py",
        "dgraph_tpu/mesh/executor.py",
    ):
        assert _ids(
            check_source(bad, [UncheckedHopLoop()], path=path)
        ) == ["unchecked-hop-loop"], path
    # the method-call shape is the same seam
    bad2 = textwrap.dedent("""
        def run(self, parts):
            for lo, hi in parts:
                self._dispatch_segment(lo, hi)
    """)
    assert _ids(
        check_source(bad2, [UncheckedHopLoop()], path="dgraph_tpu/ops/x.py")
    ) == ["unchecked-hop-loop"]


def test_unchecked_segment_loop_counterexamples_clean():
    # the fix: a segments.seam() yield point between dispatches
    seamed = textwrap.dedent("""
        from dgraph_tpu.sched import segments

        def run_segments(carry, n, k):
            lo = 0
            while lo < n:
                if lo:
                    segments.seam("chain")
                carry = _dispatch_segment(carry, lo, min(lo + k, n))
                lo += k
            return carry
    """)
    assert check_source(
        seamed, [UncheckedHopLoop()], path="dgraph_tpu/ops/batch.py"
    ) == []
    # a direct token probe between dispatches also satisfies the rule
    tokened = textwrap.dedent("""
        def run_segments(self, parts):
            for lo, hi in parts:
                self.cancel_token.check()
                self._dispatch_segment(lo, hi)
    """)
    assert check_source(
        tokened, [UncheckedHopLoop()], path="dgraph_tpu/mesh/executor.py"
    ) == []
    # ordinary ops/ dispatch loops stay exempt: only the segment-carry
    # convention opts a loop in outside query/
    plain = textwrap.dedent("""
        def kernel(ce, fronts):
            for f in fronts:
                ce.expand(f)
    """)
    assert check_source(
        plain, [UncheckedHopLoop()], path="dgraph_tpu/ops/kern.py"
    ) == []
    # pragma escape hatch with the WHY
    pragmad = textwrap.dedent("""
        def replay_segments(carry, parts):
            # offline fixture replay: no live client, nothing queued
            # graftlint: ignore[unchecked-hop-loop]
            for lo, hi in parts:
                carry = _dispatch_segment(carry, lo, hi)
            return carry
    """)
    assert check_source(
        pragmad, [UncheckedHopLoop()], path="dgraph_tpu/query/fixture.py"
    ) == []


def test_unregistered_metric_flagged():
    """Golden-bad: a dgraph_* series with no docs/deploy.md catalog row
    must be flagged — and the catalog is pinned for the test so the
    verdict cannot drift with the doc."""
    from dgraph_tpu.analysis.rules import UnregisteredMetric

    UnregisteredMetric.catalog_override = {"dgraph_num_queries_total"}
    try:
        bad = textwrap.dedent("""
            from dgraph_tpu.utils.metrics import metrics

            ROGUE = metrics.counter("dgraph_totally_new_series_total")
            ROGUE_H = metrics.histogram("dgraph_rogue_seconds", (0.1, 1))
            ROGUE_KW = metrics.counter(name="dgraph_kwarg_series_total")
        """)
        assert _ids(check_source(bad, [UnregisteredMetric()])) == [
            "unregistered-metric", "unregistered-metric",
            "unregistered-metric",
        ]
        # counterexample: a cataloged series is clean, and non-dgraph
        # names (third-party prefixes) are out of scope
        good = textwrap.dedent("""
            from dgraph_tpu.utils.metrics import metrics

            NQ = metrics.counter("dgraph_num_queries_total")
            OTHER = metrics.counter("python_gc_collections_total")
        """)
        assert check_source(good, [UnregisteredMetric()]) == []
        # pragma escape hatch with the WHY
        pragmad = textwrap.dedent("""
            from dgraph_tpu.utils.metrics import metrics

            # internal-only A/B probe, removed with the experiment
            # graftlint: ignore[unregistered-metric]
            EXP = metrics.counter("dgraph_experiment_total")
        """)
        assert check_source(pragmad, [UnregisteredMetric()]) == []
    finally:
        UnregisteredMetric.catalog_override = None


def test_unregistered_metric_real_catalog_parses():
    """The real deploy.md catalog section must parse to a non-trivial
    set containing the anchor series (guards against a doc refactor
    silently emptying the rule's ground truth)."""
    from dgraph_tpu.analysis.rules import UnregisteredMetric

    UnregisteredMetric._catalog_cache = None
    cat = UnregisteredMetric.catalog()
    assert "dgraph_num_queries_total" in cat
    assert "dgraph_edges_traversed_total" in cat
    assert len(cat) > 40


def test_unchecked_hop_loop_nested_checkpoint_covers_outer():
    # a checkpoint in the innermost loop satisfies every enclosing loop
    # (the outer iteration cannot advance without passing through it)
    src = textwrap.dedent("""
        def walk(engine, parents, templates, src, resolver):
            while parents:
                for tmpl in templates:
                    engine.checkpoint()
                    engine._exec_child(tmpl, src, resolver, {}, {})
                parents = parents[1:]
    """)
    assert check_source(
        src, [UncheckedHopLoop()], path="dgraph_tpu/query/walk2.py"
    ) == []


def test_swallowed_narrow_or_counted_not_flagged():
    src = textwrap.dedent("""
        def f():
            try:
                g()
            except OSError:
                pass  # narrow: peer down, heartbeat retries
            try:
                g()
            except Exception as e:
                note_swallowed("site", e)
    """)
    assert check_source(src, [SwallowedException()]) == []


def test_pragma_suppression():
    src = textwrap.dedent("""
        import time

        def wait(timeout):
            # graftlint: ignore[wallclock-duration]
            deadline = time.time() + timeout
            return deadline
    """)
    assert check_source(src, [WallClockDuration()]) == []


def test_fingerprint_stable_across_line_moves():
    src1 = "def f():\n    try:\n        g()\n    except Exception:\n        pass\n"
    src2 = "# moved down\n\n" + src1
    (f1,) = check_source(src1, [SwallowedException()])
    (f2,) = check_source(src2, [SwallowedException()])
    assert f1.line != f2.line
    assert f1.fingerprint == f2.fingerprint


# ----------------------------------------------------------- shipped tree

def _pkg_root():
    import dgraph_tpu
    from pathlib import Path

    return Path(dgraph_tpu.__file__).resolve().parent


def test_shipped_tree_is_clean():
    """The whole point: the suite ships running clean with an EMPTY
    baseline, so any new finding is a regression, not noise."""
    root = _pkg_root()
    findings = run_rules(
        [str(root)], ALL_RULES, repo_root=str(root.parent),
        exclude=("dgraph_tpu/analysis/",),
    )
    assert findings == [], "\n".join(f.render() for f in findings)


def test_shipped_lock_graph_cycle_free():
    root = _pkg_root()
    graph, problems = check_lock_order(
        [str(root)], repo_root=str(root.parent),
        exclude=("dgraph_tpu/analysis/",),
    )
    assert problems == [], "\n".join(problems)
    # sanity: the pass actually sees the repo's locks (19 locking
    # modules; if this collapses the extractor broke, not the repo)
    assert len(graph.classes) >= 15
    assert len(graph.edges) >= 3


def test_static_lockorder_catches_seeded_cycle(tmp_path):
    (tmp_path / "mod.py").write_text(textwrap.dedent("""
        import threading

        class S:
            def __init__(self):
                self._a = threading.Lock()
                self._b = threading.Lock()

            def one(self):
                with self._a:
                    with self._b:
                        pass

            def two(self):
                with self._b:
                    with self._a:
                        pass
    """))
    _graph, problems = check_lock_order(
        [str(tmp_path)], repo_root=str(tmp_path)
    )
    assert any("cycle" in p for p in problems), problems


def test_static_lockorder_call_propagation(tmp_path):
    # held lock -> lock acquired inside a same-class callee
    (tmp_path / "mod.py").write_text(textwrap.dedent("""
        import threading

        class S:
            def __init__(self):
                self._a = threading.Lock()
                self._b = threading.Lock()

            def outer(self):
                with self._a:
                    self.inner()

            def inner(self):
                with self._b:
                    pass
    """))
    graph = build_lock_graph([str(tmp_path)], repo_root=str(tmp_path))
    assert ("mod.S._a", "mod.S._b") in graph.edges


def test_static_lockorder_ignores_deferred_closures(tmp_path):
    """A closure DEFINED under a lock runs later, possibly without it —
    its acquisitions must not be attributed to the enclosing hold."""
    (tmp_path / "mod.py").write_text(textwrap.dedent("""
        import threading

        class S:
            def __init__(self):
                self._a = threading.Lock()
                self._b = threading.Lock()

            def outer(self):
                with self._a:
                    self._cb = lambda: self.later()

                def deferred():
                    self.later()
                with self._a:
                    self._worker = deferred

            def later(self):
                with self._b:
                    pass
    """))
    graph = build_lock_graph([str(tmp_path)], repo_root=str(tmp_path))
    assert ("mod.S._a", "mod.S._b") not in graph.edges


def test_static_lockorder_self_nesting_on_plain_lock(tmp_path):
    (tmp_path / "mod.py").write_text(textwrap.dedent("""
        import threading

        class S:
            def __init__(self):
                self._a = threading.Lock()

            def bad(self):
                with self._a:
                    with self._a:
                        pass
    """))
    _graph, problems = check_lock_order(
        [str(tmp_path)], repo_root=str(tmp_path)
    )
    assert any("self-nesting" in p for p in problems), problems


# ----------------------------------------------------------------- CLI

_CLI_BAD = {
    "host-sync-in-jit": (
        "import jax\n\n@jax.jit\ndef f(x):\n    return x.sum().item()\n"
    ),
    "recompile-hazard": (
        "import jax\n\ndef f(g, x):\n    return jax.jit(g)(x)\n"
    ),
    "wallclock-duration": (
        "import time\n\ndef f(t):\n    return time.time() + t\n"
    ),
    "swallowed-exception": (
        "def f():\n    try:\n        g()\n    except Exception:\n        pass\n"
    ),
    "naked-peer-rpc": (
        "from dgraph_tpu.cluster.transport import urlopen_peer\n\n"
        "def f(req, auth):\n    return urlopen_peer(req, 5, auth)\n"
    ),
    "naked-atomic-write": (
        "import os\n\ndef f(tmp, path):\n    os.replace(tmp, path)\n"
    ),
    "naked-resident-transfer": (
        "import numpy as np\n\n"
        "def f(arena):\n"
        "    ra = arena.resident()\n"
        "    return np.asarray(ra.dst)\n"
    ),
    "naked-collective": (
        "import jax\n\n"
        'def f(t):\n    return jax.lax.psum(t, "model")\n'
    ),
}


@pytest.mark.parametrize("rule", sorted(_CLI_BAD))
def test_cli_exits_nonzero_on_golden_bad(rule, tmp_path):
    from dgraph_tpu.analysis.__main__ import main

    bad = tmp_path / "bad.py"
    bad.write_text(_CLI_BAD[rule])
    assert main([str(bad)]) == 1


def test_cli_exits_zero_on_shipped_tree_and_baseline_roundtrip(tmp_path):
    from dgraph_tpu.analysis.__main__ import main

    # acceptance: clean on the shipped tree with an EMPTY baseline
    assert main([]) == 0
    # the baseline workflow: adopt standing debt, then run clean
    bad = tmp_path / "bad.py"
    bad.write_text(_CLI_BAD["wallclock-duration"])
    base = tmp_path / "baseline.json"
    assert main([str(bad), "--write-baseline", str(base)]) == 0
    assert main([str(bad), "--baseline", str(base)]) == 0
    # a NEW finding is not hidden by the old baseline
    bad.write_text(
        _CLI_BAD["wallclock-duration"]
        + "\ndef g():\n    try:\n        f(1)\n    except Exception:\n        pass\n"
    )
    assert main([str(bad), "--baseline", str(base)]) == 1


def test_baseline_is_a_multiset(tmp_path):
    """Two IDENTICAL offending lines share a fingerprint; a baseline
    that accepted one must not hide a second, newly-added duplicate."""
    from dgraph_tpu.analysis.__main__ import main

    one = "def f():\n    try:\n        g()\n    except Exception:\n        pass\n"
    bad = tmp_path / "bad.py"
    bad.write_text(one)
    base = tmp_path / "baseline.json"
    assert main([str(bad), "--write-baseline", str(base)]) == 0
    assert main([str(bad), "--baseline", str(base)]) == 0
    bad.write_text(one + "\n\ndef h():\n    try:\n        g()\n    except Exception:\n        pass\n")
    assert main([str(bad), "--baseline", str(base)]) == 1


# ------------------------------------------------- runtime witness recorder

def test_witness_catches_seeded_inversion():
    w = witness_mod.Witness()
    a = witness_mod._WLock(w, "lock.A", threading.Lock())
    b = witness_mod._WLock(w, "lock.B", threading.Lock())
    # thread 1 order: A then B
    with a:
        with b:
            pass
    assert w.inversions() == []
    # thread 2 order: B then A — never overlapping, so no deadlock HAPPENS,
    # but the order disagreement is already provable
    done = []

    def t2():
        with b:
            with a:
                done.append(True)

    th = threading.Thread(target=t2)
    th.start()
    th.join()
    assert done
    inv = w.inversions()
    assert len(inv) == 1 and "inversion" in inv[0]
    assert "lock.A" in inv[0] and "lock.B" in inv[0]


def test_witness_catches_same_class_instance_inversion():
    """Two INSTANCES of one lock class (same construction site — e.g.
    two VersionedLFUCache locks) taken in opposite orders is the classic
    ABBA the class-level table cannot see; instance serials catch it."""
    w = witness_mod.Witness()
    proxy = witness_mod._ThreadingProxy(w)
    a, b = proxy.Lock(), proxy.Lock()  # same creation site = same class
    assert a._name == b._name
    with a:
        with b:
            pass

    def rev():
        with b:
            with a:
                pass

    th = threading.Thread(target=rev)
    th.start()
    th.join()
    inv = w.inversions()
    assert len(inv) == 1 and "two instances" in inv[0], inv


def test_witness_rlock_recursion_is_not_an_inversion():
    w = witness_mod.Witness()
    r = witness_mod._WLock(w, "lock.R", threading.RLock())
    with r:
        with r:
            pass
    assert w.inversions() == []


def test_witness_condition_direct_acquire_is_seen():
    """threading.Condition binds acquire/release as INSTANCE attrs of
    the inner lock; the wrapper must rebind them or direct
    cond.acquire() calls would be invisible to the recorder."""
    w = witness_mod.Witness()
    cond = witness_mod._WCondition(w, "lock.cond")
    other = witness_mod._WLock(w, "lock.other", threading.Lock())
    cond.acquire()
    with other:
        pass
    cond.release()
    assert ("lock.cond", "lock.other") in w.edges()


def test_witness_condition_wait_releases_hold():
    """While a thread waits on a condition it does NOT hold it — an
    acquisition made by another thread during the wait must not create
    a (cond -> other) order edge for the waiter."""
    w = witness_mod.Witness()
    cond = witness_mod._WCondition(w, "lock.cond")
    other = witness_mod._WLock(w, "lock.other", threading.Lock())
    started = threading.Event()
    results = []

    def waiter():
        with cond:
            started.set()
            cond.wait(timeout=5)
            results.append("woke")

    th = threading.Thread(target=waiter)
    th.start()
    started.wait(5)
    # wake the waiter while independently holding `other` in THIS thread,
    # then take the reverse order; neither may produce an inversion
    with other:
        with cond:
            cond.notify_all()
    th.join(5)
    assert results == ["woke"]
    assert w.inversions() == []
    # the waiter's post-wait reacquire happened while holding nothing
    assert ("lock.cond", "lock.other") not in w.edges()


def test_witness_is_armed_for_the_suite():
    """Acceptance: the witness is load-bearing during tier-1 — locks
    created by dgraph_tpu modules are wrapper objects feeding the global
    recorder, and the run so far is inversion-free."""
    import os

    if os.environ.get("DGRAPH_TPU_WITNESS", "1") == "0":
        pytest.skip("witness disabled via DGRAPH_TPU_WITNESS=0")
    w = witness_mod.current()
    assert w is not None and w.active
    # a lock constructed by an armed module is witnessed (re-arm after
    # the import: THIS test may be the first to pull the module in when
    # run standalone; under full tier-1 the per-test re-arm covers it)
    from dgraph_tpu.cache.core import VersionedLFUCache

    witness_mod.arm()
    c = VersionedLFUCache(1 << 16)
    assert isinstance(c._lock, witness_mod._WLock)
    assert w.inversions() == [], "\n".join(w.inversions())


def test_witness_sees_real_engine_lock_order():
    """Drive the real serving path under the armed witness: scheduler
    cond, engine RW lock, arena cache lock and hop-cache lock all fire;
    the observed order table must stay inversion-free."""
    import os

    if os.environ.get("DGRAPH_TPU_WITNESS", "1") == "0":
        pytest.skip("witness disabled via DGRAPH_TPU_WITNESS=0")
    from dgraph_tpu import gql
    from dgraph_tpu.models import PostingStore
    from dgraph_tpu.sched.scheduler import CohortScheduler
    from dgraph_tpu.serve.server import DgraphServer

    store = PostingStore()
    store.apply_schema("friend: [uid] .")
    for i in range(1, 6):
        store.set_edge("friend", i, 1 + (i % 5))
    srv = DgraphServer(store)
    sched = CohortScheduler(srv, flush_ms=1.0)
    errors = []
    try:
        parsed = gql.parse(
            "{ q(func: uid(0x1)) { uid friend { uid } } }", None
        )

        def client():
            try:
                out, _stats = sched.run(parsed)
                assert out["q"], out
            except Exception as e:  # surfaced below; join() can't raise
                errors.append(e)

        threads = [threading.Thread(target=client) for _ in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(10)
    finally:
        sched.stop()
    assert errors == []
    w = witness_mod.current()
    assert w.inversions() == [], "\n".join(w.inversions())


# ------------------------------------------------- compile-count budgets

def test_budget_plugin_counts_compiles():
    import jax
    import jax.numpy as jnp

    from dgraph_tpu.analysis.pytest_budget import (
        compile_count,
        install_compile_counter,
    )

    install_compile_counter()
    before = compile_count()

    @jax.jit
    def f(x):
        return x * 3 + 1

    f(jnp.ones(7))   # compiles
    mid = compile_count()
    f(jnp.ones(7))   # cache hit: no new program
    assert mid > before
    assert compile_count() == mid


def test_budget_plugin_catches_seeded_recompile(pytester):
    """Acceptance: a seeded recompile storm must BUST a budget — run a
    mini pytest session wired exactly like tier-1's conftest and assert
    the violating test fails with the budget error."""
    pytester.makeconftest(textwrap.dedent("""
        from dgraph_tpu.analysis.pytest_budget import (
            budget_plugin_configure,
            pytest_runtest_call,  # noqa: F401 — hook by import
        )

        def pytest_configure(config):
            budget_plugin_configure(config)
    """))
    pytester.makepyfile(textwrap.dedent("""
        import jax
        import jax.numpy as jnp
        import pytest

        @pytest.mark.compile_budget(1)
        def test_seeded_recompile_storm():
            # jit-in-a-loop over changing shapes: the exact bug class
            # the recompile-hazard lint + these budgets exist for
            for n in (3, 4, 5, 6):
                jax.jit(lambda x: x * 2)(jnp.ones(n))
    """))
    result = pytester.runpytest_inprocess("-q", "-p", "no:cacheprovider")
    result.assert_outcomes(failed=1)
    result.stdout.fnmatch_lines(["*CompileBudgetExceeded*"])


def test_budget_resolution_order(pytester):
    """Marker beats budgets.json; generous budgets pass."""
    pytester.makeconftest(textwrap.dedent("""
        from dgraph_tpu.analysis.pytest_budget import (
            budget_plugin_configure,
            pytest_runtest_call,  # noqa: F401
        )

        def pytest_configure(config):
            budget_plugin_configure(config)
    """))
    pytester.makepyfile(textwrap.dedent("""
        import jax
        import jax.numpy as jnp
        import pytest

        @pytest.mark.compile_budget(None)
        def test_unlimited_marker():
            for n in (11, 12, 13):
                jax.jit(lambda x: x + 1)(jnp.ones(n))
    """))
    result = pytester.runpytest_inprocess("-q", "-p", "no:cacheprovider")
    result.assert_outcomes(passed=1)


# ------------------------------------------------- transfer-guard invariant

@pytest.mark.transfer_guard("disallow")
def test_hop_program_is_implicit_transfer_free():
    """The issue's invariant, stated as a test: handed device-resident
    arguments, the compiled hop-expansion program performs ZERO implicit
    host↔device transfers (no hidden .item()/np.asarray inside the
    traced body).  The transfer_guard marker makes JAX raise on any
    implicit transfer for the whole test body."""
    import jax

    from dgraph_tpu.query.engine import _packed_expand_csr

    # tiny CSR: 3 nodes, edges 0->{1,2}, 1->{2}; staging is EXPLICIT
    # device_put (allowed under the guard — the rule is no *implicit*
    # transfers), exactly how a transfer-disciplined dispatch looks
    offsets = jax.device_put(np.asarray([0, 2, 3, 3], dtype=np.int32))
    dst = jax.device_put(np.asarray([1, 2, 2], dtype=np.int32))
    rows = jax.device_put(np.asarray([0, 1], dtype=np.int32))
    packed = _packed_expand_csr(offsets, dst, rows, 4)
    packed.block_until_ready()  # execution, not just trace, stays clean
    # fetching the result is an EXPLICIT transfer — allowed under the
    # guard, and the engine's np.asarray fetch happens outside dispatch
    got = jax.device_get(packed)
    assert got[:3].tolist() == [1, 2, 2]


def test_transfer_guard_marker_is_load_bearing():
    """Prove the marker machinery actually trips on a violation (a
    Python bool() on a device value forces an implicit transfer)."""
    import jax
    import jax.numpy as jnp

    x = jnp.arange(4)
    with jax.transfer_guard("disallow"):
        with pytest.raises(Exception, match="[Dd]isallowed"):
            bool(x[0] > 1)


# ------------------------------------- rule: unregistered-program-factory

def test_unregistered_program_factory_flagged():
    """Golden-bad: every jit/pallas_call construction spelling in
    dgraph_tpu/ must be flagged when its site is not in the registry —
    decorator, partial-decorator, module-level assign, factory-return,
    method, and pallas_call."""
    from dgraph_tpu.analysis.rules import UnregisteredProgramFactory

    UnregisteredProgramFactory.coverage_override = set()
    try:
        bad = textwrap.dedent("""
            from functools import partial
            import jax
            from jax.experimental import pallas as pl

            @jax.jit
            def plain(x):
                return x + 1

            @partial(jax.jit, static_argnames=("cap",))
            def with_static(x, cap):
                return x[:cap]

            batched = jax.jit(jax.vmap(lambda a: a * 2))

            def factory(n):
                def fn(x):
                    return x * n
                return jax.jit(fn)

            class Expander:
                def _build(self):
                    return jax.jit(lambda m: m)

            def kernel_entry(x):
                return pl.pallas_call(_kernel, grid=(1,))(x)

            curried = partial(jax.jit, static_argnames=("desc",))(plain)
        """)
        found = check_source(
            bad, [UnregisteredProgramFactory()],
            path="dgraph_tpu/ops/fake.py",
        )
        assert _ids(found) == ["unregistered-program-factory"] * 7
        sites = {f.message.split("`")[1] for f in found}
        assert sites == {
            "dgraph_tpu/ops/fake.py::plain",
            "dgraph_tpu/ops/fake.py::with_static",
            "dgraph_tpu/ops/fake.py::batched",
            "dgraph_tpu/ops/fake.py::factory",
            "dgraph_tpu/ops/fake.py::Expander._build",
            "dgraph_tpu/ops/fake.py::kernel_entry",
            "dgraph_tpu/ops/fake.py::curried",
        }
    finally:
        UnregisteredProgramFactory.coverage_override = None


def test_unregistered_program_factory_counterexamples_clean():
    """Registered sites, non-package paths, and non-constructions (a
    bare jax.jit reference, jnp math) are all clean; pragma works."""
    from dgraph_tpu.analysis.rules import UnregisteredProgramFactory

    src = textwrap.dedent("""
        import jax

        @jax.jit
        def registered(x):
            return x + 1

        HANDLE = jax.jit          # a reference, not a construction
        y = jax.vmap(lambda a: a) # vmap alone compiles nothing
    """)
    UnregisteredProgramFactory.coverage_override = {
        "dgraph_tpu/ops/fake.py::registered"
    }
    try:
        assert check_source(
            src, [UnregisteredProgramFactory()],
            path="dgraph_tpu/ops/fake.py",
        ) == []
        # outside the package: the rule is scoped to dgraph_tpu/
        UnregisteredProgramFactory.coverage_override = set()
        assert check_source(
            src, [UnregisteredProgramFactory()], path="scripts/tool.py"
        ) == []
        pragmad = textwrap.dedent("""
            import jax

            # graftlint: ignore[unregistered-program-factory]
            @jax.jit
            def oneoff(x):
                return x
        """)
        assert check_source(
            pragmad, [UnregisteredProgramFactory()],
            path="dgraph_tpu/ops/fake.py",
        ) == []
    finally:
        UnregisteredProgramFactory.coverage_override = None


def test_naked_collective_flagged_outside_mesh_dirs():
    """Golden-bad: every collective spelling (module-dotted, lax-dotted,
    bare import) outside dgraph_tpu/mesh/ and dgraph_tpu/parallel/ is
    flagged — cross-chip exchange grown in the engine layers ships no
    placement invariance, no exchange-bytes attribution, no contract."""
    from dgraph_tpu.analysis.rules import NakedCollective

    bad = textwrap.dedent("""
        import jax
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        def hop(mesh, f):
            fn = shard_map(lambda x: x, mesh=mesh, in_specs=(P(),),
                           out_specs=P())
            return fn(f)

        def combine(t):
            g = jax.lax.all_gather(t, "model")
            s = jax.lax.psum(t, "model")
            return jax.lax.ppermute(g, "model", [(0, 1)]), s
    """)
    found = check_source(
        bad, [NakedCollective()], path="dgraph_tpu/query/engine.py"
    )
    assert _ids(found) == ["naked-collective"] * 4
    names = {f.message.split("`")[1] for f in found}
    assert names == {
        "shard_map", "jax.lax.all_gather", "jax.lax.psum",
        "jax.lax.ppermute",
    }


def test_naked_collective_counterexamples_clean():
    """The sanctioned homes are exempt; collective-free mesh USAGE
    (calling a built step, reading mesh.shape) is clean anywhere; the
    pragma escape hatch carries the WHY."""
    from dgraph_tpu.analysis.rules import NakedCollective

    homed = textwrap.dedent("""
        import jax
        from jax import shard_map

        def step(mesh, t):
            fn = shard_map(lambda x: x, mesh=mesh, in_specs=(),
                           out_specs=())
            return jax.lax.psum(t, "model")
    """)
    for home in (
        "dgraph_tpu/mesh/programs.py", "dgraph_tpu/parallel/mesh.py"
    ):
        assert check_source(homed, [NakedCollective()], path=home) == []
    usage = textwrap.dedent("""
        from dgraph_tpu.mesh.programs import mesh_multi_hop_step

        def run(mesh, sa, f, cap, hops):
            step = mesh_multi_hop_step(mesh, cap, hops)
            width = int(mesh.shape["model"])
            return step(sa.src, sa.offsets, sa.dst, f), width
    """)
    assert check_source(
        usage, [NakedCollective()], path="dgraph_tpu/query/chain.py"
    ) == []
    pragmad = textwrap.dedent("""
        import jax

        def debug_sum(t):
            # offline mesh-debug harness, never on the serving path
            # graftlint: ignore[naked-collective]
            return jax.lax.psum(t, "model")
    """)
    assert check_source(
        pragmad, [NakedCollective()], path="dgraph_tpu/utils/meshdbg.py"
    ) == []


def test_program_factory_live_coverage_names_real_sites():
    """The production acceptance set comes from the live registry and
    must contain the load-bearing kernels and the documented
    exemptions (a rename on either side surfaces here, not in CI)."""
    from dgraph_tpu.analysis.rules import UnregisteredProgramFactory

    cov = UnregisteredProgramFactory.coverage()
    for key in (
        "dgraph_tpu/ops/sets.py::intersect_many",
        "dgraph_tpu/ops/batch.py::_multi_hop_jit",
        "dgraph_tpu/ops/spgemm.py::run_mask_chain",
        "dgraph_tpu/ops/pallas_gather.py::gather_pallas_packed",
        "dgraph_tpu/query/chain.py::_run_fused",
        "dgraph_tpu/utils/calibrate.py::measure.gather",
    ):
        assert key in cov, key


# ------------------------------------------------------- naked-device-sync

def test_naked_device_sync_flags_host_level_sync_points():
    from dgraph_tpu.analysis.rules import NakedDeviceSync

    src = textwrap.dedent("""
        import jax
        import numpy as np

        def serve_hop(program, rows):
            dev = program(rows)
            dev.block_until_ready()
            jax.block_until_ready(dev)
            return int(dev.sum().item())
    """)
    findings = check_source(
        src, [NakedDeviceSync()], path="dgraph_tpu/query/newexec.py"
    )
    assert [f.rule for f in findings] == ["naked-device-sync"] * 3


def test_naked_device_sync_scoped_to_serving_dirs():
    from dgraph_tpu.analysis.rules import NakedDeviceSync

    src = "def f(x):\n    return x.block_until_ready()\n"
    # utils/ (devguard's home) and obs/ (block_ready_ms) are exempt by
    # scoping; the four serving layers are covered
    assert check_source(
        src, [NakedDeviceSync()], path="dgraph_tpu/utils/devguard.py"
    ) == []
    assert check_source(
        src, [NakedDeviceSync()], path="dgraph_tpu/obs/spans.py"
    ) == []
    for d in ("query", "ops", "parallel", "sched"):
        got = check_source(
            src, [NakedDeviceSync()], path=f"dgraph_tpu/{d}/x.py"
        )
        assert [f.rule for f in got] == ["naked-device-sync"], d


def test_naked_device_sync_counterexamples_not_flagged():
    from dgraph_tpu.analysis.rules import NakedDeviceSync

    src = textwrap.dedent("""
        import jax
        from dgraph_tpu import obs
        from dgraph_tpu.utils import devguard

        def guarded_hop(program, rows):
            # the sanctioned spellings: the guard's watchdog bracket and
            # the span-attributed block helper
            res = devguard.get().run("device.hop", lambda: program(rows))
            obs.block_ready_ms(res)
            return res

        @jax.jit
        def traced(x):
            # in-jit sync points belong to host-sync-in-jit, not this
            # rule (one finding per bug class)
            return x.sum().item()
    """)
    assert check_source(
        src, [NakedDeviceSync()], path="dgraph_tpu/ops/newkernel.py"
    ) == []


def test_naked_device_sync_pragma_suppresses_with_why():
    from dgraph_tpu.analysis.rules import NakedDeviceSync

    src = textwrap.dedent("""
        def host_count(counts_np):
            # a host numpy scalar, no device involved
            return counts_np.sum().item()  # graftlint: ignore[naked-device-sync]
    """)
    assert check_source(
        src, [NakedDeviceSync()], path="dgraph_tpu/query/x.py"
    ) == []


def test_naked_device_sync_ships_clean_on_tree():
    from dgraph_tpu.analysis.rules import NakedDeviceSync
    from pathlib import Path

    root = Path(__file__).resolve().parents[1]
    findings = run_rules(
        [str(root / "dgraph_tpu")], [NakedDeviceSync()],
        repo_root=str(root),
    )
    assert findings == [], [f"{f.path}:{f.line}" for f in findings]


# ------------------------------------------- tier 3: static escape analysis

from dgraph_tpu.analysis.escape import (  # noqa: E402
    RULE_ESCAPE,
    RULE_GLOBAL,
    RULE_WHY,
    check_escape_source,
    check_escapes,
)
from dgraph_tpu.analysis.lockorder import discover_thread_entries  # noqa: E402


def test_escape_two_thread_unlocked_write_flagged():
    """The golden bad: a field written by a spawned thread AND a public
    method, neither under a lock."""
    src = textwrap.dedent("""
        import threading

        class Pump:
            def __init__(self):
                self.count = 0
                threading.Thread(target=self._run, daemon=True).start()

            def _run(self):
                while True:
                    self.count += 1

            def poke(self):
                self.count = 0
    """)
    findings = check_escape_source(src)
    assert [f.rule for f in findings] == [RULE_ESCAPE]
    assert "count" in findings[0].message


def test_escape_locked_writes_clean():
    src = textwrap.dedent("""
        import threading

        class Pump:
            def __init__(self):
                self._lock = threading.Lock()
                self.count = 0
                threading.Thread(target=self._run, daemon=True).start()

            def _run(self):
                with self._lock:
                    self.count += 1

            def poke(self):
                with self._lock:
                    self.count = 0
    """)
    assert check_escape_source(src) == []


def test_escape_single_root_clean():
    """A field only the spawned thread writes (init writes are
    happens-before the spawn and stripped) is single-writer."""
    src = textwrap.dedent("""
        import threading

        class Pump:
            def __init__(self):
                self.count = 0
                threading.Thread(target=self._run, daemon=True).start()

            def _run(self):
                self.count += 1
    """)
    assert check_escape_source(src) == []


def test_escape_caller_holds_lock_clean():
    """The `caller holds self._lock` discipline: a private helper whose
    every call site is under the lock inherits the lock scope (the
    devguard _set_state shape)."""
    src = textwrap.dedent("""
        import threading

        class Guard:
            def __init__(self):
                self._lock = threading.Lock()
                self.state = "ok"
                threading.Thread(target=self._probe, daemon=True).start()

            def _set_state(self, s):
                self.state = s

            def _probe(self):
                with self._lock:
                    self._set_state("degraded")

            def readmit(self):
                with self._lock:
                    self._set_state("ok")
    """)
    assert check_escape_source(src) == []


def test_escape_pragma_sanctions_with_why_and_flags_without():
    base = textwrap.dedent("""
        import threading

        class Flag:
            def __init__(self):
                self.done = False
                threading.Thread(target=self._run, daemon=True).start()

            def _run(self):
                {pragma}
                self.done = True

            def stop(self):
                self.done = False
    """)
    why = base.format(
        pragma="# graftlint: shared[done] GIL-atomic bool handshake, "
        "single store each side"
    )
    assert check_escape_source(why) == []
    bare = base.format(pragma="# graftlint: shared[done]")
    rules = sorted(f.rule for f in check_escape_source(bare))
    # sanctioned (no thread-escape) but the missing WHY is itself flagged
    assert rules == [RULE_WHY]


def test_escape_executor_submit_is_a_thread_root():
    """Satellite: ThreadPoolExecutor.submit and bound-method
    Thread(target=self.x) feed one shared entry model — submit inside a
    loop counts as many threads, so one method alone races with itself."""
    src = textwrap.dedent("""
        from concurrent.futures import ThreadPoolExecutor

        class Fan:
            def __init__(self):
                self.done = 0
                self._ex = ThreadPoolExecutor(4)

            def kick(self):
                for _ in range(4):
                    self._ex.submit(self._work)

            def _work(self):
                self.done += 1
    """)
    findings = check_escape_source(src)
    assert [f.rule for f in findings] == [RULE_ESCAPE]
    assert "done" in findings[0].message


def test_escape_conn_handler_instances_exempt_globals_still_flagged():
    """Per-connection handler instances are single-threaded (fresh
    instance per request) — but a module global they write is shared
    across every concurrent connection."""
    src = textwrap.dedent("""
        from http.server import BaseHTTPRequestHandler

        HITS = 0

        class H(BaseHTTPRequestHandler):
            def do_GET(self):
                global HITS
                HITS += 1           # global-escape: concurrent handlers
                self.body = b"ok"   # instance attr: per-connection, fine
    """)
    findings = check_escape_source(src)
    assert [f.rule for f in findings] == [RULE_GLOBAL]
    assert "HITS" in findings[0].message


def test_escape_seeded_scheduler_adapt_shape():
    """Regression seed for the PR-19 scheduler fix: two flush workers
    (loop-spawned) rebinding adaptive knobs unlocked was the shipped
    bug; the same stores under the condvar are the shipped fix."""
    bug = textwrap.dedent("""
        import threading

        class Sched:
            def __init__(self, n):
                self._cond = threading.Condition()
                self.max_batch = 8
                for _ in range(n):
                    threading.Thread(target=self._worker).start()

            def _worker(self):
                self._adapt()

            def _adapt(self):
                self.max_batch = 16
    """)
    findings = check_escape_source(bug)
    assert [f.rule for f in findings] == [RULE_ESCAPE]
    assert "max_batch" in findings[0].message
    fixed = bug.replace(
        "        self.max_batch = 16",
        "        with self._cond:\n"
        "            self.max_batch = 16",
    )
    assert fixed != bug
    assert check_escape_source(fixed) == []


def test_thread_entry_discovery_spellings():
    import ast as _ast

    src = textwrap.dedent("""
        import threading
        from concurrent.futures import ThreadPoolExecutor

        def loose():
            pass

        class S:
            def __init__(self):
                threading.Thread(target=self._run).start()
                threading.Timer(1.0, self._tick).start()
                with ThreadPoolExecutor(2) as ex:
                    ex.submit(self._job)

            def _run(self): pass
            def _tick(self): pass
            def _job(self): pass

        # graftlint: thread-entry
        def marked():
            pass
    """)
    entries = discover_thread_entries(
        _ast.parse(src), "m", "m.py", src.splitlines()
    )
    quals = {e.qual: e.kind for e in entries}
    assert quals["m.S._run"] == "thread"
    assert quals["m.S._tick"] == "timer"
    assert quals["m.S._job"] == "executor"
    assert quals["m.marked"] == "pragma"
    assert "m.loose" not in quals


def test_races_cli_nonzero_on_golden_bad_zero_on_tree(tmp_path):
    from dgraph_tpu.analysis.__main__ import main

    bad = tmp_path / "bad.py"
    bad.write_text(textwrap.dedent("""
        import threading

        class P:
            def __init__(self):
                self.n = 0
                threading.Thread(target=self.run).start()

            def run(self):
                self.n += 1

            def poke(self):
                self.n = 2
    """))
    assert main(["--races", str(bad)]) == 1
    # acceptance: the shipped tree is clean with the EMPTY manifest
    assert main(["--races"]) == 0


def test_races_manifest_roundtrip(tmp_path):
    """--write-shared adopts standing findings as a multiset baseline;
    a NEW finding is not hidden behind it."""
    from dgraph_tpu.analysis.__main__ import main

    bad = tmp_path / "bad.py"
    one = textwrap.dedent("""
        import threading

        class P:
            def __init__(self):
                self.n = 0
                threading.Thread(target=self.run).start()

            def run(self):
                self.n += 1

            def poke(self):
                self.n = 2
    """)
    bad.write_text(one)
    manifest = tmp_path / "shared.json"
    assert main(["--races", str(bad), "--write-shared", str(manifest)]) == 0
    assert main(["--races", str(bad), "--shared-manifest", str(manifest)]) == 0
    bad.write_text(one + textwrap.dedent("""
        class Q:
            def __init__(self):
                self.m = 0
                threading.Thread(target=self.run).start()

            def run(self):
                self.m += 1

            def poke(self):
                self.m = 2
    """))
    assert main(
        ["--races", str(bad), "--shared-manifest", str(manifest)]
    ) == 1


# --------------------------------------- tier 3: Eraser lockset witness

class _Obj:
    """A bare field-state carrier for driving note_field_write directly."""


def _in_thread(fn):
    th = threading.Thread(target=fn)
    th.start()
    th.join()


def test_lockset_witness_catches_seeded_two_thread_race():
    w = witness_mod.Witness()
    o = _Obj()
    w.note_field_write(o, "x")          # this thread: Virgin -> Exclusive
    _in_thread(lambda: w.note_field_write(o, "x"))  # hand-off: tolerated
    assert w.races() == []
    w.note_field_write(o, "x")          # ping-pong back: the race
    races = w.races()
    assert len(races) == 1 and "_Obj.x" in races[0]
    assert "EMPTY lockset" in races[0]
    # one report per field, not one per write
    _in_thread(lambda: w.note_field_write(o, "x"))
    assert len(w.races()) == 1


def test_lockset_witness_single_writer_handoff_exempt():
    """Init-then-publish: creator writes, one worker takes over and
    keeps writing.  No alternation back — silent, even with no lock."""
    w = witness_mod.Witness()
    o = _Obj()
    w.note_field_write(o, "x")
    w.note_field_write(o, "x")

    def worker():
        for _ in range(3):
            w.note_field_write(o, "x")

    _in_thread(worker)
    assert w.races() == []


def test_lockset_witness_refines_to_common_lock():
    """Writers sharing a lock stay clean indefinitely; a third writer
    OUTSIDE the lock empties the intersection and is reported."""
    w = witness_mod.Witness()
    lk = witness_mod._WLock(w, "lock.L", threading.Lock())
    o = _Obj()

    def locked_write():
        with lk:
            w.note_field_write(o, "x")

    locked_write()
    _in_thread(locked_write)
    locked_write()
    _in_thread(locked_write)
    assert w.races() == []
    _in_thread(lambda: w.note_field_write(o, "x"))
    races = w.races()
    assert len(races) == 1 and "_Obj.x" in races[0]


def test_lockset_witness_reset_fields_is_an_epoch():
    """reset_fields asserts a happens-before edge (ledger activation,
    request completion): the ping-pong that would otherwise report is
    split into two clean single-writer epochs."""
    w = witness_mod.Witness()
    o = _Obj()
    w.note_field_write(o, "x")
    _in_thread(lambda: w.note_field_write(o, "x"))
    w.reset_fields(o)
    w.note_field_write(o, "x")
    _in_thread(lambda: w.note_field_write(o, "x"))
    assert w.races() == []


def test_race_instrumentation_is_arm_time_only(monkeypatch):
    """Unarmed classes carry only the frozenset — no __setattr__ in the
    class dict, no per-write work.  _instrument_one_class installs the
    wrapper, writes feed the active witness, and the uninstrumented
    original stays restorable."""

    class Box:
        __race_fields__ = frozenset({"v"})

        def __init__(self):
            self.v = 0

    assert "__setattr__" not in vars(Box)  # unarmed: nothing installed
    fresh = witness_mod.Witness()
    monkeypatch.setattr(witness_mod, "_global", fresh)
    witness_mod._instrument_one_class(Box)
    assert vars(Box).get("_race_instrumented") is True
    witness_mod._instrument_one_class(Box)  # idempotent
    b = Box()
    _in_thread(lambda: setattr(b, "v", 1))  # hand-off
    b.v = 2                                 # ping-pong: race
    races = fresh.races()
    assert len(races) == 1 and "Box.v" in races[0]


def test_shipped_race_annotations_are_instrumented_and_consistent():
    """The suite runs with the witness armed (conftest): every shipped
    __race_fields__ class must actually be wrapped, and every annotated
    name must be a real slot where __slots__ is declared (a typo'd name
    would silently witness nothing)."""
    if not witness_mod.races_enabled() or witness_mod.current() is None:
        pytest.skip("witness disarmed for this run")
    from dgraph_tpu.cluster.peerclient import _PeerState
    from dgraph_tpu.ivm.deltas import DeltaStream
    from dgraph_tpu.obs.ledger import Ledger
    from dgraph_tpu.sched.qos import CancelToken
    from dgraph_tpu.sched.scheduler import CohortScheduler
    from dgraph_tpu.models.arena import ArenaManager
    from dgraph_tpu.utils.devguard import DeviceGuard, _Job

    # re-arm: when this file runs alone, the imports above happened
    # AFTER the per-test arm — the same lazy-import window the conftest
    # re-arm comment describes
    witness_mod.arm()
    for cls in (
        ArenaManager,
        _PeerState, DeltaStream, Ledger, CancelToken,
        CohortScheduler, DeviceGuard, _Job,
    ):
        assert vars(cls).get("_race_instrumented") is True, cls
        slots = getattr(cls, "__slots__", None)
        if slots is not None:
            missing = set(cls.__race_fields__) - set(slots)
            assert not missing, (cls, missing)
            assert "_race_serial" in slots, cls
