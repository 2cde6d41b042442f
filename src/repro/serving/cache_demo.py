"""Cross-backend feature-cache demonstration (DESIGN.md §11).

A deterministic single-request scenario on 4 ranks that drives every
layer of the cross-step feature cache on BOTH execution backends:

* denoise step 0 runs on ranks (0, 1) and **refreshes** the cache (full
  KV all-gather, snapshot stored);
* step 1 **hits**: stale remote shards + fresh local K/V, no collective;
* a mid-trace same-degree **Reallocate** onto ranks (2, 3) takes effect
  at step 2 — the warm snapshot **migrates** through the ordinary
  layout-aware migration planner and step 2 is a ``hit+mig``;
* step 3 exhausts the staleness window (``CACHE_INTERVAL = 3``) and
  refreshes on the new ranks; steps 4-5 hit again.

All decisions are scripted from *structure* (task kind and step index),
and the cache hit/refresh/migrate calls are made by the control plane
itself, so the virtual-clock simulator and the wall-clock thread runtime
produce identical :func:`~repro.core.scheduler.trace_signature`
projections — cache decisions included.

The wall leg additionally validates the cache's numerics:

* ``cache_interval=1`` (refresh every step) is **bit-exact** with the
  non-cached runtime;
* the stale-reuse run's decoded pixels stay within the relative-L2
  error budget of the exact output (§11 accuracy contract);
* a no-Reallocate control run at the same interval produces pixels
  **bit-identical** to the reallocated run — the only way that holds is
  if migration moved the warm snapshot bit-identically;
* a ``use_pallas=True`` leg (the fused fast path, DESIGN.md §12) yields
  a **bit-identical** trace signature — kernels change numerics within
  tolerance, never the schedule — and pixels inside the kernel budget.

Used by tests/test_cache_backends.py, benchmarks/sim_fidelity.py, and
benchmarks/policies_e2e.py (--only cache error leg).
"""
from __future__ import annotations

import dataclasses

import numpy as np

from repro.core.cost_model import CostModel
from repro.core.scheduler import (ControlPlane, Dispatch, Policy,
                                  Reallocate, trace_signature)
from repro.core.simulator import SimBackend
from repro.core.trajectory import ExecutionLayout, Request
from repro.diffusion.adapters import convert_request
from repro.serving.engine import ServingEngine

RES = 128                    # 64 latent tokens: small, fast
STEPS = 6
CACHE_INTERVAL = 3           # refresh every 3rd step
NUM_RANKS = 4
SHIFT_STEP = 2               # first denoise step on the new rank set

LAYOUT_A = ExecutionLayout((0, 1))
LAYOUT_B = ExecutionLayout((2, 3))


class CacheScriptPolicy(Policy):
    """Structural script: denoise on ``LAYOUT_A`` until ``SHIFT_STEP``,
    with a single same-degree Reallocate onto ``LAYOUT_B`` issued at the
    last A-step's dispatch (the plane auto-dispatches the pinned rest of
    the chain); encode/decode single-rank.  ``shift=False`` is the
    control variant that stays on ``LAYOUT_A`` for the whole chain."""
    name = "cache-script"

    def __init__(self, shift: bool = True):
        self.shift = shift

    def schedule(self, view):
        out = []
        for t, req, g in sorted(view.ready,
                                key=lambda x: (x[1].id, x[0].step_index)):
            if t.kind in ("encode", "decode"):
                if 0 in view.free_ranks:
                    out.append(Dispatch(t.id, ExecutionLayout((0,))))
            elif req.id in view.pinned:
                continue        # the plane auto-dispatches pinned steps
            elif all(r in view.free_ranks for r in LAYOUT_A.ranks):
                out.append(Dispatch(t.id, LAYOUT_A))
                if self.shift and t.step_index == SHIFT_STEP - 1:
                    # same-degree re-pin: takes effect at the next
                    # boundary and MIGRATES the warm cache (§11)
                    out.append(Reallocate(req.id, LAYOUT_B))
        return out


def scenario_requests() -> list[Request]:
    return [Request(id="cache", model="dit-image", height=RES, width=RES,
                    frames=1, steps=STEPS, arrival=0.0)]


def cache_modes(events: list[dict]) -> list[tuple]:
    """(step, mode) per denoise dispatch, in dispatch order."""
    return [(e["step"], e.get("cache")) for e in events
            if e["ev"] == "dispatch" and e["kind"] == "denoise"]


def liven(pipeline, seed: int = 123, scale: float = 0.05):
    """Replace the block family's zero-init modulation leaves (adaLN-Zero
    gates) and output head (its ``gate_leaves``) with small fixed-seed
    values, drawn in that order.  An untrained DiT gates its attention
    output by exactly zero, so stale-KV reuse would be vacuously exact —
    livening the gates makes the error-budget claim a real measurement
    while keeping every leg of the demo deterministic (same seed, same
    perturbation, every engine)."""
    import jax
    from repro.models import dit
    key = jax.random.PRNGKey(seed)
    for path in dit.family(pipeline.cfg).gate_leaves:
        tree = pipeline.dit_params
        for name in path[:-1]:
            tree = tree[name]
        key, k = jax.random.split(key)
        arr = tree[path[-1]]
        tree[path[-1]] = scale * jax.random.normal(k, arr.shape, arr.dtype)


def run_wall(cfg, reqs, *, cache_interval, shift: bool = True) -> dict:
    eng = ServingEngine(cfg, CacheScriptPolicy(shift=shift), NUM_RANKS,
                        cost=CostModel(), cache_interval=cache_interval)
    liven(eng.pipeline)
    metrics = eng.serve(reqs, timeout=240)
    out = {
        "metrics": metrics,
        "events": list(eng.cp.events),
        "signature": trace_signature(eng.cp.events),
        "modes": cache_modes(eng.cp.events),
        "pixels": {r.id: eng.result_pixels(r) for r in reqs},
    }
    eng.shutdown()
    return out


def run_sim(cfg, reqs, *, cache_interval) -> dict:
    cost = CostModel()
    cp = ControlPlane(NUM_RANKS, CacheScriptPolicy(), cost,
                      SimBackend(cost), cache_interval=cache_interval)
    for r in reqs:
        r = dataclasses.replace(r, task_ids=[])
        cp.submit(r, convert_request(r, cfg))
    cp.run()
    return {
        "metrics": cp.metrics(),
        "events": list(cp.events),
        "signature": trace_signature(cp.events),
        "modes": cache_modes(cp.events),
        "migrated_bytes": cp.backend.migrated_bytes,
    }


def rel_l2(a: np.ndarray, b: np.ndarray) -> float:
    denom = float(np.linalg.norm(b))
    return float(np.linalg.norm(a - b)) / max(denom, 1e-12)


def run_demo(cfg=None) -> dict:
    """Run the scenario on both backends plus the numeric control legs
    and compare traces, cache decisions, and pixels."""
    if cfg is None:
        from repro.configs.dit_models import DIT_IMAGE
        cfg = DIT_IMAGE.reduced()
    reqs = scenario_requests()
    sim = run_sim(cfg, reqs, cache_interval=CACHE_INTERVAL)
    wall = run_wall(cfg, reqs, cache_interval=CACHE_INTERVAL)
    # numeric controls (wall only; the simulator has no pixels)
    exact = run_wall(cfg, reqs, cache_interval=None)
    exact1 = run_wall(cfg, reqs, cache_interval=1)
    stay = run_wall(cfg, reqs, cache_interval=CACHE_INTERVAL, shift=False)
    # Pallas fast-path leg (DESIGN.md §12): same scenario with the fused
    # kernels on — the control plane must make the identical decisions
    # (bit-identical trace signature; scheduling never reads activations)
    # and the decoded pixels must track the jnp cached leg within the
    # kernel tolerance budget.
    pallas = run_wall(cfg.with_(use_pallas=True), reqs,
                      cache_interval=CACHE_INTERVAL)
    rid = reqs[0].id
    px, px_exact = wall["pixels"][rid], exact["pixels"][rid]
    px_pallas = pallas["pixels"][rid]
    return {
        "wall": wall,
        "sim": sim,
        "trace_match": wall["signature"] == sim["signature"],
        "modes": wall["modes"],
        # cache_interval=1 == non-cached path, bit for bit
        "interval1_exact": bool(
            px_exact is not None and exact1["pixels"][rid] is not None
            and np.array_equal(exact1["pixels"][rid], px_exact)),
        # stale reuse stays inside the §11 error budget
        "rel_l2_err": (rel_l2(px, px_exact)
                       if px is not None and px_exact is not None
                       else float("inf")),
        # the same-degree Reallocate moved the warm snapshot
        # bit-identically: the shifted and stay-put cached runs agree
        # bit for bit (same refresh schedule, same snapshot bytes)
        "migration_bitexact": bool(
            px is not None and stay["pixels"][rid] is not None
            and np.array_equal(px, stay["pixels"][rid])),
        "sim_migrated_bytes": sim["migrated_bytes"],
        # fast-path contract (§12): fused kernels change numerics within
        # tolerance only — never the schedule
        "pallas_trace_match": wall["signature"] == pallas["signature"],
        "pallas_modes": pallas["modes"],
        "pallas_rel_l2": (rel_l2(px_pallas, px)
                          if px is not None and px_pallas is not None
                          else float("inf")),
    }


def pixel_error_report(cfg=None, interval: int = CACHE_INTERVAL) -> dict:
    """Small wall-clock error probe for benchmarks: serve the scripted
    scenario cached (``interval``) and uncached, report the relative-L2
    pixel error and the interval-1 bit-exactness bit."""
    if cfg is None:
        from repro.configs.dit_models import DIT_IMAGE
        cfg = DIT_IMAGE.reduced()
    reqs = scenario_requests()
    exact = run_wall(cfg, reqs, cache_interval=None)
    exact1 = run_wall(cfg, reqs, cache_interval=1)
    cached = run_wall(cfg, reqs, cache_interval=interval)
    rid = reqs[0].id
    px_exact = exact["pixels"][rid]
    px1, px = exact1["pixels"][rid], cached["pixels"][rid]
    # a timed-out leg reports a failed measurement, not a traceback
    ok = px_exact is not None
    return {
        "cache_interval": interval,
        "rel_l2_err": (rel_l2(px, px_exact)
                       if ok and px is not None else float("inf")),
        "interval1_exact": bool(ok and px1 is not None
                                and np.array_equal(px1, px_exact)),
        "hits": sum(1 for _, m in cached["modes"]
                    if m and m.startswith("hit")),
        "refreshes": sum(1 for _, m in cached["modes"]
                         if m == "refresh"),
    }
