"""Telemetry plane tests (DESIGN.md §15).

Covers the two §15 contracts on the simulator backend — the disabled
path leaves control-plane traces byte-identical (modulo process-global
ids), and the enabled path's streams are well-formed — plus the
Perfetto export shape, the GFC latency histogram, and the
``ControlPlane.metrics()`` edge cases (empty run, all-failed run, and
the unfinished-counts-as-violation SLO rule the serving timeout path
relies on) — and, on the thread backend under the profiler, the
program's ``gfdit.*`` regions: in the trace with their stats, nested
as a step nests, and absent when telemetry is off.  Cross-backend
telemetry identity on REAL serving runs is gated in
tests/test_elastic_backends.py / tests/test_hybrid_shapes.py and
benchmarks/telemetry_suite.py.
"""
from __future__ import annotations

import json
import math
from pathlib import Path

import jax
import pytest

from repro.configs.dit_models import DIT_IMAGE
from repro.core.cost_model import CostModel
from repro.core.policies import make_policy
from repro.core.scheduler import (ControlPlane, Dispatch, PackedDispatch,
                                  Policy, trace_signature)
from repro.core.simulator import SimBackend
from repro.core.telemetry import (RANK_STATES, Telemetry, _sanitize)
from repro.core.trajectory import ClusterTopology, ExecutionLayout, Request
from repro.diffusion.adapters import convert_request
from repro.serving.engine import ServingEngine

CFG = DIT_IMAGE.reduced()
TOPO = ClusterTopology(num_hosts=2, ranks_per_host=2)


def _request(i: int, deadline=None) -> Request:
    return Request(id=f"r{i}", model="dit-image", height=128, width=128,
                   frames=1, steps=4, arrival=i * 0.2, deadline=deadline)


def _run(telemetry=None, n: int = 6, jitter: float = 0.0,
         until: float = float("inf")) -> ControlPlane:
    cost = CostModel()
    cp = ControlPlane(TOPO, make_policy("elastic", TOPO.num_ranks), cost,
                      SimBackend(cost, jitter=jitter),
                      telemetry=telemetry)
    for i in range(n):
        r = _request(i, deadline=i * 0.2 + 30.0)
        cp.submit(r, convert_request(r, CFG))
    cp.run(until=until)
    return cp


def _strip_ids(events):
    """Task/artifact ids come from process-global counters, so two runs
    in one process never match raw; everything else must."""
    out = []
    for e in events:
        e = dict(e)
        for k in ("task", "tasks", "victims", "lost"):
            e.pop(k, None)
        out.append(e)
    return out


# ---------------------------------------------------------------------------
# contract 1: zero perturbation when disabled (and when enabled)
# ---------------------------------------------------------------------------

def test_telemetry_does_not_perturb_the_trace():
    off = _run(telemetry=None)
    on = _run(telemetry=Telemetry())
    assert trace_signature(off.events) == trace_signature(on.events)
    assert _strip_ids(off.events) == _strip_ids(on.events)


def test_disabled_plane_has_no_telemetry_state():
    cp = _run(telemetry=None)
    assert cp.telemetry is None
    assert cp.cache.telemetry is None


# ---------------------------------------------------------------------------
# stream shape
# ---------------------------------------------------------------------------

def test_rank_timelines_well_formed():
    tel = Telemetry()
    _run(telemetry=tel)
    assert sorted(tel.rank_states) == list(range(TOPO.num_ranks))
    for r, seq in tel.rank_states.items():
        t0, s0, _ = seq[0]
        assert (t0, s0) == (0.0, "idle")
        times = [t for t, _, _ in seq]
        assert times == sorted(times)
        states = [s for _, s, _ in seq]
        assert set(states) <= set(RANK_STATES)
        # consecutive idle/dead entries are deduped
        for a, b in zip(states, states[1:]):
            assert not (a == b and a in ("idle", "dead"))


def test_utilization_and_goodput_bounds():
    tel = Telemetry()
    cp = _run(telemetry=tel)
    s = tel.summary()
    assert 0.0 < s["rank_utilization"] <= 1.0
    for u in s["utilization_per_rank"].values():
        assert 0.0 <= u <= 1.0
    assert s["completed"] == cp.metrics()["completed"]
    assert s["goodput_per_rank"] == pytest.approx(
        s["completed"] / (TOPO.num_ranks * s["makespan_s"]))


def test_decisions_match_dispatches_and_carry_explanations():
    tel = Telemetry()
    cp = _run(telemetry=tel)
    dispatches = [e for e in cp.events if e["ev"] == "dispatch"]
    recs = [d for d in tel.decisions if d["action"] == "dispatch"]
    assert len(recs) == len(dispatches)
    # ElasticPolicy stages an explanation for every dispatch it emits
    for d in recs:
        ex = d["explanation"]
        assert ex is not None and "why" in ex
        assert all(isinstance(a, dict) for a in ex.get("alternatives", []))


def test_lifecycle_spans_pair_and_terminate():
    tel = Telemetry()
    _run(telemetry=tel)
    for rid, seq in tel.lifecycle.items():
        phases = [p for _, p, _ in seq]
        assert phases[0] == "queued"
        assert phases[-1] == "done"
        assert phases.count("step_start") == phases.count("step_end")


def test_cost_accuracy_stream():
    tel = Telemetry()
    _run(telemetry=tel)                 # jitter-free: estimates are exact
    assert tel.cost_stream
    assert all(s["rel_err"] == 0.0 for s in tel.cost_stream)
    tel2 = Telemetry()
    _run(telemetry=tel2, jitter=0.2)    # jittered: observed != predicted
    assert any(s["rel_err"] > 0.0 for s in tel2.cost_stream)
    for cell in tel2.cost_cells.values():
        assert cell["n"] >= 1 and cell["rel_err"] >= 0.0


# ---------------------------------------------------------------------------
# identity projection
# ---------------------------------------------------------------------------

def test_sanitize_drops_volatile_fields():
    rec = {"t": 1.25, "task": "task-9", "kind": "denoise", "step": 3,
           "metrics": {"eta": 0.5}, "lost": ["a-1"], "pack": "p-7",
           "ranks": [0, 1], "score": 0.125}
    san = _sanitize(rec)
    assert san == {"kind": "denoise", "step": 3, "pack": True,
                   "ranks": (0, 1)}


def test_clock_independent_projection_is_json_stable():
    tel = Telemetry()
    _run(telemetry=tel)
    ci = tel.clock_independent()
    assert set(ci) == {"rank_states", "decisions", "lifecycle"}
    # round-trips through repr-equality (no floats, no ids anywhere)
    flat = repr(ci)
    assert "task-" not in flat
    assert not any(ch in flat for ch in ("e-0", "e+0"))


# ---------------------------------------------------------------------------
# Perfetto export
# ---------------------------------------------------------------------------

def test_perfetto_export_valid(tmp_path):
    tel = Telemetry()
    _run(telemetry=tel)
    path = tmp_path / "trace.json"
    tel.perfetto(str(path))
    doc = json.loads(path.read_text())
    evs = doc["traceEvents"]
    assert evs
    phases = {e["ph"] for e in evs}
    assert "M" in phases and "X" in phases
    meta_names = {e["name"] for e in evs if e["ph"] == "M"}
    assert {"process_name", "thread_name"} <= meta_names
    hosts = {e["args"]["name"] for e in evs
             if e["ph"] == "M" and e["name"] == "process_name"}
    assert any(h.startswith("host") for h in hosts)
    for e in evs:
        assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
        if e["ph"] == "X":
            assert e["dur"] >= 0.0 and math.isfinite(e["ts"])
    # rank slices: pid = host of the rank, tid = rank
    rank_x = [e for e in evs if e["ph"] == "X"
              and e["pid"] <= TOPO.num_hosts - 1]
    assert rank_x
    for e in rank_x:
        assert e["pid"] == TOPO.host_of(e["tid"])


# ---------------------------------------------------------------------------
# GFC histogram + staging
# ---------------------------------------------------------------------------

def test_gfc_histogram_and_percentiles():
    tel = Telemetry()
    for us in (3, 3, 3, 50, 900):
        tel.gfc_register(us * 1e-6)
    hist = tel.gfc_histogram()
    assert sum(hist.values()) == 5
    assert hist["4us"] == 3          # 3us samples land in the (2,4] bucket
    pct = tel.gfc_percentiles()
    assert pct["n"] == 5
    assert pct["p50_us"] == pytest.approx(3.0)
    # floor-index selection: p99 of 5 samples is the 4th order statistic
    assert pct["p99_us"] == pytest.approx(50.0)
    tel.gfc_register(900e-6)  # a 6th sample pushes p99 to the tail
    assert tel.gfc_percentiles()["p99_us"] == pytest.approx(900.0)
    assert tel.summary()["gfc"]["n"] == 6


def test_staged_explanations_cleared_per_schedule_point():
    tel = Telemetry()
    tel.stage("dispatch", "t-1", {"why": "stale"})
    tel.begin_schedule()                     # new schedule point: cleared
    ev = {"t": 0.0, "ev": "dispatch", "task": "t-1", "req": "r",
          "kind": "denoise", "step": 0, "ranks": [0]}
    tel.record_action("dispatch", ev, key="t-1")
    assert tel.decisions[-1]["explanation"] is None


# ---------------------------------------------------------------------------
# ControlPlane.metrics() edge cases
# ---------------------------------------------------------------------------

def _empty_plane():
    cost = CostModel()
    return ControlPlane(TOPO, make_policy("elastic", TOPO.num_ranks),
                        cost, SimBackend(cost))


def test_metrics_empty_run():
    cp = _empty_plane()
    cp.run()
    m = cp.metrics()
    assert m["completed"] == 0 and m["failed"] == 0
    assert m["slo_attainment"] == 1.0
    assert m["throughput_rps"] == 0.0 and m["makespan_s"] == 0.0
    assert math.isnan(m["mean_latency_s"])
    assert math.isnan(m["p95_latency_s"])


def test_metrics_all_failed_run():
    cp = _empty_plane()
    for i in range(3):
        r = _request(i, deadline=i * 0.2 + 30.0)
        cp.submit(r, convert_request(r, CFG))
    for rid in list(cp.requests):
        cp._fail_request(rid, "test")
    m = cp.metrics()
    assert m["completed"] == 0 and m["failed"] == 3
    assert m["slo_attainment"] == 0.0
    assert m["throughput_rps"] == 0.0
    assert math.isnan(m["mean_latency_s"])


def test_metrics_unfinished_counts_as_slo_violation():
    # the serve-timeout path (engine.serve) relies on this §6.1 rule:
    # an unfinished request is BOTH a failure and an SLO violation,
    # even when its deadline has not yet passed
    cp = _run(n=4, until=0.5)           # cut the virtual clock mid-run
    m = cp.metrics()
    unfinished = sum(1 for r in cp.requests.values()
                     if r.done_time is None)
    assert unfinished >= 1
    done_late = sum(1 for r in cp.requests.values()
                    if r.done_time is not None and r.deadline is not None
                    and r.done_time > r.deadline)
    expect = 1.0 - (unfinished + done_late) / len(cp.requests)
    assert m["slo_attainment"] == pytest.approx(expect)
    assert m["failed"] == unfinished


# ---------------------------------------------------------------------------
# program regions on the profiler's clock (thread backend)
# ---------------------------------------------------------------------------

TASK_STATS = {"task", "seq", "step", "tokens", "rows", "degree", "rank"}
#: every gfdit.* region a degree-2 guided + unguided run enters, with
#: the stats it must carry
REGION_STATS = {
    "gfdit.loop.wait": {"completions"},
    "gfdit.plane.schedule": {"ready", "actions"},
    "gfdit.plane.complete": {"task", "seq", "wait_us"},
    "gfdit.exec.dispatch": {"task", "seq", "degree"},
    "gfdit.task.encode": TASK_STATS,
    "gfdit.task.denoise": TASK_STATS,
    "gfdit.task.decode": TASK_STATS,
    "gfdit.step.inputs": set(),
    "gfdit.step.forward": {"layers"},
    "gfdit.step.update": set(),
    "gfdit.step.fetch": {"bytes"},
    "gfdit.gfc.all_gather": {"rank", "group", "bytes"},
    "gfdit.migrate": {"rank", "bytes"},
}


class SpTwo(Policy):
    """Encode and decode on one rank, denoise at SP degree 2: the
    latent migrates onto two ranks and each step all-gathers K/V."""
    name = "sp-two"

    def schedule(self, view):
        out, free = [], list(view.free_ranks)
        for t, _, _ in sorted(view.ready, key=lambda x: x[0].id):
            k = 2 if t.kind == "denoise" else 1
            if len(free) < k:
                break
            out.append(Dispatch(t.id, ExecutionLayout(tuple(free[:k]))))
            free = free[k:]
        return out


def _serve_two(telemetry):
    eng = ServingEngine(CFG, SpTwo(), 2, telemetry=telemetry)
    reqs = [Request(id=f"{name}0", model="dit-image", height=64, width=64,
                    frames=1, steps=2, arrival=0.0, guidance=g)
            for name, g in (("g", 4.5), ("u", None))]
    try:
        m = eng.serve(reqs, timeout=120.0)
    finally:
        eng.shutdown()
    assert m["completed"] == 2
    return eng


def _profiled_regions(trace_dir) -> list:
    """The trace's gfdit.* host events: (name, thread, start, end,
    stats); a thread is its plane and line (one line per thread)."""
    from jax.profiler import ProfileData
    path = sorted(Path(trace_dir).rglob("*.xplane.pb"))[-1]
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith("gfdit."):
                    out.append((e.name, (plane.name, i), e.start_ns,
                                e.start_ns + e.duration_ns, dict(e.stats)))
    return out


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    tel = Telemetry()
    trace_dir = tmp_path_factory.mktemp("profile")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    with jax.profiler.trace(str(trace_dir), profiler_options=options):
        _serve_two(tel)
    return tel, _profiled_regions(trace_dir)


def test_regions_reach_the_profiler_with_numeric_stats(served):
    _, regions = served
    seen: dict = {}
    for name, _, a, b, stats in regions:
        assert b >= a
        assert all(isinstance(v, (int, float)) for v in stats.values())
        seen.setdefault(name, []).append(stats)
    assert set(REGION_STATS) <= set(seen)
    for name, want in REGION_STATS.items():
        for stats in seen[name]:
            assert want <= set(stats), (name, stats)
    assert all(s["wait_us"] >= 0 for s in seen["gfdit.plane.complete"])
    assert {s["rows"] for s in seen["gfdit.task.denoise"]} == {1, 2}
    assert {s["degree"] for s in seen["gfdit.task.denoise"]} == {2}
    assert all(s["layers"] == CFG.num_layers
               for s in seen["gfdit.step.forward"])
    assert any(s["actions"] >= 1 for s in seen["gfdit.plane.schedule"])


def test_step_regions_nest_inside_their_task(served):
    _, regions = served
    tasks = [r for r in regions if r[0] == "gfdit.task.denoise"]
    # 2 requests x 2 steps, each on both ranks of its layout
    assert len(tasks) == 8
    for name in ("gfdit.step.inputs", "gfdit.step.forward",
                 "gfdit.step.update", "gfdit.step.fetch"):
        steps = [r for r in regions if r[0] == name]
        assert len(steps) == len(tasks)
        for _, thread, a, b, _ in steps:
            assert sum(1 for _, th, ta, tb, _ in tasks
                       if th == thread and ta <= a and b <= tb) == 1
    # inside one task the phases run in order
    for _, thread, ta, tb, _ in tasks:
        order = sorted((a, name) for name, th, a, b, _ in regions
                       if th == thread and ta <= a and b <= tb
                       and name.startswith("gfdit.step."))
        assert [n for _, n in order] == [
            "gfdit.step.inputs", "gfdit.step.forward", "gfdit.step.update",
            "gfdit.step.fetch"]


def test_plane_completions_name_the_tasks_that_ran(served):
    _, regions = served
    ran = {(s["task"], s["seq"]) for name, *_, s in regions
           if name == "gfdit.task.denoise"}
    handled = {(s["task"], s["seq"]) for name, *_, s in regions
               if name == "gfdit.plane.complete"}
    assert ran and ran <= handled
    dispatched = {(s["task"], s["seq"]) for name, *_, s in regions
                  if name == "gfdit.exec.dispatch"}
    assert ran <= dispatched


def test_regions_in_overlay_and_perfetto(served):
    tel, regions = served
    names = [name for name, *_ in tel.overlay]
    assert sorted(names) == sorted(name for name, *_ in regions)
    for name, t, t_end, stats in tel.overlay:
        assert 0.0 <= t <= t_end
    evs = tel.perfetto()["traceEvents"]
    cp_pid = next(e["pid"] for e in evs if e["ph"] == "M"
                  and e["args"].get("name") == "control-plane")
    region_x = [e for e in evs if e.get("cat") == "region"]
    assert len(region_x) == len(tel.overlay)
    for e in region_x:
        rank = e["args"].get("rank")
        assert (e["pid"], e["tid"]) == ((cp_pid, 0) if rank is None
                                        else (0, rank))


class PackBoth(Policy):
    """One rank: encodes first, then both requests' denoise steps as one
    pack, then the decodes."""
    name = "pack-both"

    def schedule(self, view):
        if 0 not in view.free_ranks or not view.ready:
            return []
        ready = sorted((t for t, _, _ in view.ready),
                       key=lambda t: (t.kind != "encode", t.id))
        steps = [t.id for t in ready if t.kind == "denoise"]
        one = ExecutionLayout((0,))
        if ready[0].kind != "encode" and len(steps) == 2:
            return [PackedDispatch(tuple(steps), one)]
        return [Dispatch(ready[0].id, one)]


def test_a_pack_runs_as_one_task_region():
    tel = Telemetry()
    eng = ServingEngine(CFG, PackBoth(), 1, telemetry=tel)
    reqs = [Request(id=f"p{i}", model="dit-image", height=64, width=64,
                    frames=1, steps=2, arrival=0.0) for i in range(2)]
    try:
        assert eng.serve(reqs, timeout=120.0)["completed"] == 2
    finally:
        eng.shutdown()
    tasks = [st for name, _, _, st in tel.overlay
             if name == "gfdit.task.denoise"]
    assert [st["rows"] for st in tasks] == [2, 2]
    packs = {st["task"] for st in tasks}
    assert packs <= {st["task"] for name, _, _, st in tel.overlay
                     if name == "gfdit.exec.dispatch"}
    fetched = [st["bytes"] for name, _, _, st in tel.overlay
               if name == "gfdit.step.fetch"]
    assert len(fetched) == 2 and len(set(fetched)) == 1


def test_no_region_is_entered_with_telemetry_off(monkeypatch):
    made = []

    class Counting(jax.profiler.TraceAnnotation):
        def __init__(self, *args, **kwargs):
            made.append(args[0])
            super().__init__(*args, **kwargs)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Counting)
    _serve_two(None)
    assert made == []
    _serve_two(Telemetry())
    assert made
