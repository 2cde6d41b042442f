"""Drive the served path for one run: build the engine, warm up, measure
one window, finish the steps in flight, and keep the records.

The program is driven through its own entry points: ``ServingEngine``
with the ``elastic`` policy, ``ControlPlane.submit`` with
``convert_request``, and the shared ``EventLoop`` on a ``WallClock``.
``ServingEngine.serve`` is never called: its timeout fails requests that
are merely in flight.  Host spans come from wrappers that this module
installs on the instances (``schedule_point``, ``execute`` by task kind,
``all_gather`` and the clock's wait), each a
``jax.profiler.TraceAnnotation`` named ``bench.*``.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib
import time
from typing import Optional

import jax

from gfbench import reference
from gfbench.traffic import Planned


def program_config(conf: dict):
    """The program's config named by a configuration file, with the
    file's changes and the kernel path on; refused where a key of the
    file's ``model`` table is on neither the config nor its ``dit``, or
    holds another value there."""
    prog = conf["program"]
    base = getattr(importlib.import_module(prog["module"]), prog["name"])
    changes = dict(prog.get("changes", {}))
    if "dit" in changes:
        changes["dit"] = dataclasses.replace(base.dit, **changes["dit"])
    cfg = base.with_(use_pallas=True, **changes)
    want = conf["model"]
    lacks = [k for k in want
             if not hasattr(cfg, k) and not hasattr(cfg.dit, k)]
    if lacks:
        raise ValueError(f"program config has no {lacks}")
    got = {k: getattr(cfg if hasattr(cfg, k) else cfg.dit, k)
           for k in want}
    if got != want:
        raise ValueError(f"program config {got} is not the file's {want}")
    return cfg


class Recorder:
    """Host spans and step completions of one run, in monotonic time; a
    denoise step's rows are those its request runs under ``arch``."""

    def __init__(self, arch):
        self.arch = arch
        self.spans: list = []           # (name, t0, t1)
        self.completions: list = []     # dicts, plane time
        self.hold = False               # policy dispatches nothing

    def span(self, name_of, fn):
        spans = self.spans

        def wrapped(*args, **kwargs):
            name, meta = name_of(*args)
            t0 = time.monotonic()
            try:
                with jax.profiler.TraceAnnotation(name, **meta):
                    return fn(*args, **kwargs)
            finally:
                spans.append((name, t0, time.monotonic()))
        return wrapped

    def install(self, eng, clock):
        cp = eng.cp
        cp.schedule_point = self.span(lambda: ("bench.schedule_point", {}),
                                      cp.schedule_point)
        eng.pipeline.execute = self.span(
            functools.partial(_exec_span, self.arch), eng.pipeline.execute)
        eng.comm.all_gather = self.span(lambda *a: ("bench.all_gather", {}),
                                        eng.comm.all_gather)
        clock.wait = self.span(lambda *a: ("bench.clock_wait", {}),
                               clock.wait)
        schedule = cp.policy.schedule
        cp.policy.schedule = lambda view: [] if self.hold else schedule(view)
        on_completion = cp.on_completion
        done = self.completions

        def record(c):
            entry = cp.running.get(c.task_id)
            if entry is not None:
                task, layout = entry
                req = cp.requests[task.request_id]
                done.append({
                    "task": task.id, "req": task.request_id,
                    "kind": task.kind, "step": task.step_index,
                    "tokens": task.meta.get("tokens"),
                    "rows": len(self.arch.rows(req.guidance)),
                    "degree": layout.degree,
                    "start": c.finish_time - c.duration,
                    "finish": c.finish_time, "duration": c.duration,
                    "failed": bool(c.failed_ranks)})
            on_completion(c)
        cp.on_completion = record


def _exec_span(arch, task, layout, rank, comm, graph, *rest):
    """``bench.exec.<kind>``; a denoise step carries its request's token
    count, the rows this rank's group runs (the architecture's rows for
    the request, split over the layout's CFG branches) and its SP
    degree."""
    if task.kind != "denoise":
        return f"bench.exec.{task.kind}", {}
    rows = len(arch.rows(graph.request.guidance)) // layout.cfg
    return "bench.exec.denoise", {"tokens": task.meta["tokens"],
                                  "rows": rows, "degree": layout.degree}


def build(conf: dict, seed: int, ranks: int, telemetry=None):
    """The engine, with the program's weights made from the seed and its
    adaLN gates and output head livened by the program's ``liven``."""
    from repro.core.policies import make_policy
    from repro.serving.cache_demo import liven
    from repro.serving.engine import ServingEngine
    cfg = program_config(conf)
    model_seed, liven_seed = reference.weight_seeds(seed)
    eng = ServingEngine(cfg, make_policy("elastic", ranks), ranks,
                        seed=model_seed, telemetry=telemetry)
    liven(eng.pipeline, seed=liven_seed, scale=conf["liven_scale"])
    jax.block_until_ready(eng.pipeline.weights(0))
    return eng


def _request(p: Planned, model: str, arrival: float):
    from repro.core.trajectory import Request
    return Request(p.id, model, p.height, p.width, frames=p.frames,
                   steps=p.steps, arrival=arrival, size_class=p.cls,
                   guidance=p.guidance)


def _serve_all(eng, loop, clock, planned, model):
    """Serve requests due now to completion (set-up only)."""
    from repro.diffusion.adapters import convert_request
    reqs = [_request(p, model, clock.now()) for p in planned]
    for r in reqs:
        eng.cp.submit(r, convert_request(r, eng.cfg))
    loop.run()
    bad = [r.id for r in reqs if r.done_time is None or r.failed]
    if bad or eng.backend.errors:
        raise RuntimeError(f"warm-up did not finish {bad}: "
                           f"{eng.backend.errors[:1]}")


def warm_up(eng, loop, clock, warm: list, model: str):
    """Serve the warm set twice: the first pass compiles (or loads from
    the persistent cache) every shape; the cost model then forgets those
    durations and the second pass calibrates it on warm ones, as a
    long-running server's would be."""
    _serve_all(eng, loop, clock, warm, model)
    eng.cp.cost.calibration.clear()
    eng.cp.cost.pack_calibration.clear()
    _serve_all(eng, loop, clock,
               [dataclasses.replace(p, id=p.id + "-2") for p in warm], model)


def run_window(eng, rec: Recorder, clock, planned: list, model: str,
               seconds: float, *, drain_s: float,
               trace_dir: Optional[str] = None, trace_s: float = 10.0,
               lead_s: float = 0.05) -> dict:
    """Submit the planned requests, measure ``seconds``, then dispatch
    nothing new and finish the steps in flight, waiting at most
    ``drain_s``.

    Returns plane times: the window's edges, when waiting stopped, and
    the traced span (or None)."""
    from repro.core.event_loop import EventLoop
    from repro.diffusion.adapters import convert_request
    cp = eng.cp
    loop = EventLoop(cp, clock)
    reqs = [_request(p, model, 0.0) for p in planned]
    graphs = [convert_request(r, eng.cfg) for r in reqs]
    w0 = clock.now() + lead_s
    for p, r, g in zip(planned, reqs, graphs):
        r.arrival = w0 + p.due
        cp.submit(r, g)
    w1 = w0 + seconds
    traced = None
    if trace_dir is None:
        loop.run(until=w1)
    else:
        t_on = w1 - min(trace_s, seconds / 2)
        loop.run(until=t_on)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        t_on = clock.now()
        with jax.profiler.TraceAnnotation("bench.traced_window"):
            loop.run(until=w1)
        traced = (t_on, clock.now())
    rec.hold = True
    while cp.running and clock.now() < w1 + drain_s:
        for c in cp.backend.poll():
            cp.on_completion(c)
    stop = clock.now()
    if trace_dir is not None:
        jax.profiler.stop_trace()
    return {"w0": w0, "w1": w1, "stop": stop, "traced": traced}


def request_records(cp, ids) -> dict:
    """Per request: due time, completion and failure, in plane time."""
    out = {}
    for rid in ids:
        r = cp.requests[rid]
        out[rid] = {"cls": r.size_class, "due": r.arrival,
                    "done": r.done_time, "failed": r.failed}
    return out


def peak_bytes(devices) -> int:
    return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
