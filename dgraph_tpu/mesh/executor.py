"""MeshExecutor: the engine-facing entry points of the mesh plane.

``parallel/mesh.py`` exposes raw kernels; the executor is what the
serving path actually calls — one-hop sharded expansion behind
``DeviceExpander`` (query/engine.py::_mesh_expand) and the fused
multi-hop scan behind ``chain`` (query/chain.py::_try_mesh_chain).
Both entry points carry the full serving contract the kernels alone
don't:

- **fault domain**: every dispatch runs under the ``"mesh"`` device
  guard (utils/devguard.py).  With the elastic fault domain active
  (mesh/fault.py) a CHIP-attributed fault evicts that chip, re-shards
  the plan onto the survivors and the executor RETRIES under the new
  epoch (bounded by ``DGRAPH_TPU_MESH_RESUME_RETRIES``) — the route
  stays mesh on the surviving sub-mesh.  Un-attributed faults keep the
  PR 15 path: ``DeviceFaultError`` propagates and the caller re-plans
  the level/chain unsharded.
- **epoch fence + drain-and-resume**: a segmented multi-hop captures
  the fault domain's fence (epoch, mesh) at plan time and re-checks it
  at every ``segments.seam()``.  On a flip — another query's chip loss,
  or a staged rejoin cutting over — the query's carry is already
  mirrored on the host (each segment's fetched ``fs[-1]`` row IS the
  donated carry's value), so it re-fetches the sharded arena at the new
  width and resumes byte-identically: placement is byte-invisible
  (mesh/plan.py) and every sub-mesh program is pinned value-for-value
  against the unsharded scan driver.  A wedged collective
  (``DeviceHangError``) mid-query latches the plane and the remaining
  hops complete on that same unsharded driver from the host carry.
- **ledger attribution**: wall time inside mesh programs, the mesh
  width it ran on (per-chip time under SPMD = wall × width), and the
  estimated cross-chip exchange payload land on the request's ledger
  (obs/ledger.py ``mesh_ms``/``mesh_chips``/``exchange_bytes``).
- **placement**: sharded arenas come via ``ArenaManager.sharded_csr``,
  which applies the ``MeshPlan`` roll — the executor never sees an
  unplaced arena.
"""

from __future__ import annotations

import time
from typing import Tuple

import numpy as np

from dgraph_tpu import obs, ops
from dgraph_tpu.obs import ledger as _ledger
from dgraph_tpu.utils import devguard


class MeshExecutor:
    """Serving-path executor over one ArenaManager's mesh.

    Cheap to construct (holds no device state of its own — the sharded
    arenas and compiled steps are the manager's/module caches' assets);
    ArenaManager memoizes one per manager (``mesh_executor()``)."""

    def __init__(self, arenas):
        self.arenas = arenas  # models/arena.py::ArenaManager

    @property
    def mesh(self):
        return self.arenas.mesh

    @property
    def width(self) -> int:
        """Model-axis width — the chips one dispatch spans."""
        m = self.mesh
        return int(m.shape["model"]) if m is not None else 1

    def allowed(self) -> bool:
        """May the mesh domain be dispatched to right now (devguard
        latch + half-open probe)?"""
        return self._guard().allowed()

    def _guard(self) -> devguard.DeviceGuard:
        """The plane guard, fault-sink re-attached when the elastic
        domain is live (devguard.reset_for_tests builds fresh guards)."""
        dom = self.arenas.mesh_fault
        if dom is not None:
            return dom.plane_guard()
        return devguard.get("mesh")

    def _retries(self) -> int:
        if self.arenas.mesh_fault is None:
            return 0
        from dgraph_tpu.mesh.fault import resume_retries

        return resume_retries()

    def _chip_retryable(self, e: BaseException) -> bool:
        """A fault the elastic domain already attributed to ONE chip:
        its sink re-sharded the plan synchronously before the raise, so
        a retry dispatches the surviving sub-mesh — the route stays
        mesh.  Hangs/sick-latch are never chip-attributable."""
        if self.arenas.mesh_fault is None:
            return False
        if isinstance(e, (devguard.DeviceHangError, devguard.DeviceSickError)):
            return False
        return devguard.chip_of(e) is not None

    def _note_degraded(self, stats: dict, resumed: int = 0) -> None:
        """Stamp per-request sub-mesh disclosure: results are
        byte-identical, capacity is not (engine.run_parsed lifts this
        into the response's ``degraded.mesh``, the PR 5 discipline)."""
        dom = self.arenas.mesh_fault
        if dom is None:
            return
        info = dom.degraded_info()
        if resumed or info["chips_healthy"] < info["chips_total"]:
            info["resumed"] = resumed + (
                stats.get("mesh_degraded", {}).get("resumed", 0)
            )
            stats["mesh_degraded"] = info

    # -- entry points --------------------------------------------------------

    def expand(
        self, attr: str, reverse: bool, src: np.ndarray, cap: int, stats: dict
    ) -> Tuple[np.ndarray, np.ndarray]:
        """One engine-level sharded expansion (the route:mesh leaf).
        Returns (out, seg_ptr) byte-identical to the single-device
        expand.  A chip-attributed fault re-shards and retries on the
        surviving sub-mesh (reads are idempotent — the dispatch either
        returned or it didn't); anything else raises
        ``devguard.DeviceFaultError`` so the caller re-plans unsharded."""
        from dgraph_tpu.parallel.mesh import _fcap_bucket, sharded_expand_segments
        from dgraph_tpu.sched import segments

        dom = self.arenas.mesh_fault
        retries = self._retries()
        resumed = 0
        t0 = time.perf_counter()
        while True:
            sharded = self.arenas.sharded_csr(attr, reverse=reverse)
            if dom is not None:
                dom.note_shape("expand", cap, _fcap_bucket(len(src)))

            def _dispatch():
                with obs.stage(stats, "device_expand_ms"):
                    return sharded_expand_segments(
                        self.mesh, sharded, src, cap
                    )

            try:
                if not devguard.enabled():
                    out, seg_ptr = _dispatch()
                else:
                    out, seg_ptr = self._guard().run("mesh.expand", _dispatch)
                break
            except devguard.DeviceFaultError as e:
                if retries <= 0 or not self._chip_retryable(e):
                    raise
                retries -= 1
                resumed += 1
                segments.resume("mesh", "loss")
        self._charge(
            h2d=int(src.nbytes),
            d2h=int(out.nbytes + seg_ptr.nbytes),
            cap=cap,
            hops=1,
            wall_ms=(time.perf_counter() - t0) * 1e3,
        )
        self._note_degraded(stats, resumed)
        return out, seg_ptr

    def multi_hop(
        self,
        attr: str,
        reverse: bool,
        src: np.ndarray,
        n_hops: int,
        cap: int,
        stats: dict,
    ) -> Tuple[np.ndarray, np.ndarray]:
        """The fused multi-hop chain over the mesh: ONE compiled program
        whose cross-chip frontier exchange happens between scan levels
        on the interconnect (mesh/programs.py), no host round trip per
        hop.  Returns (frontiers int64-convertible int32[n_hops, cap],
        totals int32[n_hops]) — per-level sorted-unique-padded
        frontiers matching the unsharded scan driver (ops.multi_hop
        with track_visited=False) value-for-value.

        Raises ``devguard.DeviceFaultError`` under the guard exactly
        like :meth:`expand` when the fault cannot be owned by one chip;
        the chain then declines the fused path and the per-level ladder
        (which re-plans unsharded on the latched domain) takes over."""
        from dgraph_tpu.sched import segments

        n_hops = int(n_hops)
        # segmented dataflow (PR 18): k hops of the mesh scan per
        # dispatched program, the in-program exchange untouched inside a
        # segment, the ``final`` frontier output threaded (device-
        # resident) between segments with a scheduler yield point at
        # every seam.  mesh_multi_hop_step's lru_cache bounds the
        # segment programs: fixed k compiles the k-hop step and at most
        # one remainder per cap bucket.
        seg_k = segments.plan(n_hops, cap, "mesh")
        t0 = time.perf_counter()
        resumed = [0]
        if 0 < seg_k < n_hops:
            fs, totals = self._run_segmented(
                attr, reverse, src, n_hops, cap, seg_k, stats, resumed
            )
        else:
            fs, totals = self._run_monolithic(
                attr, reverse, src, n_hops, cap, stats, resumed
            )
        self._charge(
            h2d=cap * 4,
            d2h=int(fs.nbytes + totals.nbytes),
            cap=cap,
            hops=n_hops,
            wall_ms=(time.perf_counter() - t0) * 1e3,
        )
        self._note_degraded(stats, resumed[0])
        return fs, totals

    # -- dispatch strategies --------------------------------------------------

    def _run_monolithic(
        self, attr, reverse, src, n_hops, cap, stats, resumed
    ):
        from dgraph_tpu.mesh.programs import mesh_multi_hop_step
        from dgraph_tpu.parallel.mesh import put_replicated
        from dgraph_tpu.sched import segments
        from dgraph_tpu.utils.failpoints import fail

        dom = self.arenas.mesh_fault
        retries = self._retries()
        while True:
            sharded = self.arenas.sharded_csr(attr, reverse=reverse)
            step = mesh_multi_hop_step(self.mesh, cap, n_hops)
            if dom is not None:
                dom.note_shape("hop", cap, n_hops)

            def _dispatch():
                # the chip-loss probe of the PR 15 chaos suite fires on
                # the guard's worker, same as the one-hop kernel path
                fail.point("device.mesh")
                f = put_replicated(
                    self.mesh, ops.pad_to(np.asarray(src, dtype=np.int64), cap)
                )
                with obs.stage(stats, "chain_ms"):
                    fs, totals, _final = step(
                        sharded.src, sharded.offsets, sharded.dst, f
                    )
                    return np.asarray(fs), np.asarray(totals)

            try:
                if not devguard.enabled():
                    return _dispatch()
                return self._guard().run("mesh.multi_hop", _dispatch)
            except devguard.DeviceFaultError as e:
                if retries <= 0 or not self._chip_retryable(e):
                    raise
                # the sink already evicted the chip and re-sharded: loop
                # re-fetches the arena at the new width and re-dispatches
                # the whole (idempotent) read on the surviving sub-mesh
                retries -= 1
                resumed[0] += 1
                segments.resume("mesh", "loss")

    def _run_segmented(
        self, attr, reverse, src, n_hops, cap, seg_k, stats, resumed
    ):
        from dgraph_tpu.mesh.programs import mesh_multi_hop_step
        from dgraph_tpu.parallel.mesh import put_replicated
        from dgraph_tpu.sched import segments
        from dgraph_tpu.utils.failpoints import fail

        dom = self.arenas.mesh_fault
        retries = self._retries()
        sharded = self.arenas.sharded_csr(attr, reverse=reverse)
        fence = dom.fence() if dom is not None else None
        # the host mirror of the donated carry: the padded seed before
        # the first segment, then each fetched segment's fs[-1] row
        # (== the donated final frontier, value-for-value) — so a drain
        # never fetches the donated device buffer at all
        f_host = ops.pad_to(np.asarray(src, dtype=np.int64), cap)
        f = put_replicated(self.mesh, f_host)
        fs_parts, tot_parts = [], []
        done = 0
        while done < n_hops:
            if done:
                segments.seam("mesh")
                if dom is not None and dom.fence() != fence:
                    # epoch flipped between segments (another query's
                    # chip loss, or a staged rejoin cutting over): drain
                    # — the carry already lives in f_host — and resume
                    # under the new sub-mesh's plan
                    sharded, fence, f = self._replan(
                        attr, reverse, f_host, dom
                    )
                    resumed[0] += 1
                    segments.resume("mesh", "epoch")
            hops = min(seg_k, n_hops - done)
            if dom is not None:
                dom.note_shape("hop", cap, hops)

            def _dispatch_segment(f=f, hops=hops, sharded=sharded):
                fail.point("device.mesh")
                sstep = mesh_multi_hop_step(self.mesh, cap, hops)
                with obs.stage(stats, "chain_ms"):
                    sfs, stot, final = sstep(
                        sharded.src, sharded.offsets, sharded.dst, f
                    )
                    return np.asarray(sfs), np.asarray(stot), final

            try:
                if not devguard.enabled():
                    sfs, stot, f = _dispatch_segment()
                else:
                    sfs, stot, f = self._guard().run(
                        "mesh.multi_hop", _dispatch_segment
                    )
            except devguard.DeviceFaultError as e:
                if (
                    dom is not None
                    and isinstance(
                        e,
                        (devguard.DeviceHangError, devguard.DeviceSickError),
                    )
                ):
                    # wedged collective / plane latched mid-query: no
                    # chip to blame, the mesh is gone for now — finish
                    # the remaining hops on the unsharded scan driver
                    # from the host carry (its byte-parity twin) and
                    # disclose the failover
                    sfs, stot = self._finish_unsharded(
                        attr, reverse, f_host, n_hops - done, cap, stats
                    )
                    fs_parts.append(sfs)
                    tot_parts.append(stot)
                    resumed[0] += 1
                    segments.resume("mesh", "hang")
                    devguard.count_failover("unsharded", stats, "mesh")
                    break
                if retries <= 0 or not self._chip_retryable(e):
                    raise
                retries -= 1
                sharded, fence, f = self._replan(
                    attr, reverse, f_host, dom
                )
                resumed[0] += 1
                segments.resume("mesh", "loss")
                continue  # retry THIS segment on the surviving sub-mesh
            fs_parts.append(sfs)
            tot_parts.append(stot)
            done += hops
            f_host = np.asarray(sfs[-1])
            if done < n_hops and sfs[-1][0] == ops.SENT:
                # drained frontier: the remaining hops are all-SENT
                # rows / zero totals on every chip — synthesize and
                # stop dispatching
                segments.early_exit("mesh")
                r = n_hops - done
                fs_parts.append(np.full((r, cap), ops.SENT, sfs.dtype))
                tot_parts.append(np.zeros((r,), stot.dtype))
                break
        return np.concatenate(fs_parts), np.concatenate(tot_parts)

    def _replan(self, attr, reverse, f_host, dom):
        """Drain-and-resume bookkeeping: re-fetch the sharded arena
        under the new epoch's plan (new width ⇒ sharded_csr rebuilds —
        the survivor re-seed path) and rebuild the device carry from
        its host mirror."""
        from dgraph_tpu.parallel.mesh import put_replicated

        dom.note_drain(1)
        try:
            sharded = self.arenas.sharded_csr(attr, reverse=reverse)
            fence = dom.fence()
            f = put_replicated(self.mesh, f_host)
        finally:
            dom.note_drain(-1)
        return sharded, fence, f

    def _finish_unsharded(self, attr, reverse, f_host, hops, cap, stats):
        """Complete a drained query's remaining hops on the unsharded
        lax.scan driver — ``ops.multi_hop`` is the exact driver the mesh
        program is pinned byte-identical against, fed the same
        sorted-unique-padded carry, so the stitched result is
        indistinguishable from an all-mesh run.  Same universe
        convention as the chain scan path (max src uid)."""
        import jax.numpy as jnp

        a = self.arenas.reverse(attr) if reverse else self.arenas.data(attr)
        a.ensure_device()
        universe = int(a.h_src[-1]) if a.n_rows else 0
        lut = a.lut(universe)
        f = jnp.asarray(np.asarray(f_host, dtype=np.int32))
        vis = jnp.full((cap,), ops.SENT, dtype=jnp.int32)
        with obs.stage(stats, "chain_ms"):
            fs, totals, _vis = ops.multi_hop(
                a.offsets, a.dst, f, vis, hops, cap, lut=lut
            )
        return np.asarray(fs), np.asarray(totals)

    # -- attribution ---------------------------------------------------------

    def _charge(
        self, h2d: int, d2h: int, cap: int, hops: int, wall_ms: float
    ) -> None:
        led = _ledger.current()
        if led is None:
            return
        from dgraph_tpu.mesh.programs import exchange_bytes_per_hop

        led.bytes_h2d += h2d
        led.bytes_d2h += d2h
        led.exchange_bytes += exchange_bytes_per_hop(self.mesh, cap) * hops
        led.mesh_ms += wall_ms
        led.mesh_chips = max(led.mesh_chips, self.width)
