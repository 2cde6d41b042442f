"""GF-DiT serving engine: binds the control plane to real executors.

Wall-clock serving over the thread backend — arrivals release on
schedule, policies make elastic layout/reallocation/preemption decisions,
workers run real JAX compute with GFC sequence parallelism, and migration
happens at layout changes.  The serving loop itself is the SAME
:class:`~repro.core.event_loop.EventLoop` that drives the simulator —
only the :class:`~repro.core.event_loop.Clock` differs (paper §5.5 claim,
validated by benchmarks/sim_fidelity.py).
"""
from __future__ import annotations

import dataclasses
import logging
from typing import Optional

from repro.configs.base import ModelConfig
from repro.core.cost_model import CostModel
from repro.core.event_loop import EventLoop, WallClock
from repro.core.executor import ThreadBackend
from repro.core.gfc import GroupFreeComm
from repro.core.scheduler import ControlPlane, Policy
from repro.core.trajectory import Request, as_topology
from repro.diffusion.adapters import convert_request
from repro.diffusion.pipeline import DiTPipeline


class ServingEngine:
    def __init__(self, cfg: ModelConfig, policy: Policy, num_ranks,
                 cost: Optional[CostModel] = None, seed: int = 0,
                 cache_interval: Optional[int] = None,
                 injector=None, snapshot_interval: Optional[int] = None,
                 snapshot_dir=None, failure_recovery: bool = True,
                 telemetry=None, pipeline: Optional[DiTPipeline] = None):
        # `num_ranks` accepts a bare rank count (back-compat: synthesizes
        # a one-host topology) or a ClusterTopology (DESIGN.md §10);
        # spanning GFC groups then run hierarchical collectives.
        # `cache_interval` enables the cross-step feature cache
        # (DESIGN.md §11): denoise steps reuse stale remote KV shards
        # for up to interval-1 steps between full refresh gathers
        # (interval=1 refreshes every step — bit-exact outputs).
        # `pipeline` serves with an existing pipeline's weights (same
        # cfg) instead of initializing new ones from `seed`.
        topo = as_topology(num_ranks)
        self.cfg = cfg
        self.topology = topo
        if pipeline is None:
            pipeline = DiTPipeline(cfg, seed=seed)
        assert pipeline.cfg == cfg, "pipeline was built for another cfg"
        self.pipeline = pipeline
        self.comm = GroupFreeComm(topo.num_ranks, topology=topo)
        # telemetry plane (DESIGN.md §15): one instance observes the
        # whole stack — control plane decisions/timelines, GFC
        # registration latency, and the regions of the workers' tasks,
        # steps and collectives
        self.comm.telemetry = telemetry
        self.pipeline.telemetry = telemetry
        self.backend = ThreadBackend(self.pipeline, topo.num_ranks,
                                     comm=self.comm)
        self.cp = ControlPlane(topo, policy, cost or CostModel(),
                               self.backend,
                               cache_interval=cache_interval,
                               injector=injector,
                               snapshot_interval=snapshot_interval,
                               snapshot_dir=snapshot_dir,
                               failure_recovery=failure_recovery,
                               telemetry=telemetry)

    # ------------------------------------------------------------------
    def serve(self, requests: list[Request], *, time_scale: float = 1.0,
              timeout: float = 300.0) -> dict:
        """Run requests to completion; arrivals release at
        ``request.arrival * time_scale`` wall seconds.

        Caller-owned ``Request`` objects are never mutated: the engine
        serves private copies (same ids, so ``result_pixels`` still
        resolves against the originals).
        """
        served = [dataclasses.replace(r, arrival=r.arrival * time_scale,
                                      deadline=(r.deadline * time_scale
                                                if r.deadline is not None
                                                else None),
                                      task_ids=[], done_time=None,
                                      failed=False)
                  for r in requests]
        graphs = [(r, convert_request(r, self.cfg))
                  for r in sorted(served, key=lambda r: r.arrival)]
        # start the clock only after CPU-side graph construction so
        # early arrivals do not release late
        clock = WallClock()
        self.backend.t0 = clock.t0
        if self.cp.telemetry is not None:
            # anchor the wall overlay streams (recorded in absolute
            # monotonic time from worker threads) to plane-relative time
            self.cp.telemetry.t0 = clock.t0
        for r, g in graphs:
            self.cp.submit(r, g)
        EventLoop(self.cp, clock).run(until=timeout)
        if self.backend.errors:
            raise RuntimeError("worker errors:\n"
                               + "\n".join(self.backend.errors[:3]))
        # wall-clock timeout: requests still in flight when the loop gave
        # up are explicitly FAILED in the returned metrics (and logged),
        # never reported as silently in-flight
        unfinished = sorted(
            rid for rid, req in self.cp.requests.items()
            if req.done_time is None and not req.failed)
        if unfinished:
            logging.getLogger(__name__).warning(
                "serve timed out at %.1fs with %d unfinished requests: %s",
                timeout, len(unfinished), ", ".join(unfinished))
            for rid in unfinished:
                self.cp._fail_request(rid, "serve-timeout")
        if self.cp.telemetry is not None:
            # end-of-run watermark: whatever the sinks still buffer is
            # flushed out-of-process before the caller reads metrics
            # (DESIGN.md §16); sinks stay attached for post-run exports
            self.cp.telemetry.flush_sinks()
        m = self.cp.metrics()
        m["timed_out_requests"] = unfinished
        return m

    def result_pixels(self, request: Request):
        g = self.cp.graphs[request.id]
        for a in g.artifacts.values():
            if a.role == "output" and a.data:
                for rank_data in a.data.values():
                    if "pixels" in rank_data:
                        return rank_data["pixels"]
        return None

    def shutdown(self):
        self.backend.shutdown()
        if self.cp.telemetry is not None:
            self.cp.telemetry.close_sinks()
