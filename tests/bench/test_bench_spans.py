"""The program's ``gfdit.*`` spans in a reduced trace: the idle split and
the numbers read from them, on a hand-made trace with known answers and
on one video step recorded on a TPU v5e with the spans kept; and the
benchmark's existing reduction, which must read the same from a trace
whether or not such spans are mixed into its host events."""
import copy
import gzip
import json

import pytest

from conftest import FIXTURE
from gfbench import spans, spec, trace

PEAK = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
# the models the recorded traces were served with: the image trace's
# 1024 px guided, the video trace's video-batch-1c (Wan2.2 at 4 layers)
RECORDED_MODEL = {"num_layers": 28, "d_model": 1536, "num_heads": 24,
                  "head_dim": 64, "d_ff": 6144, "patch_size": 2,
                  "in_channels": 16, "cond_dim": 1024}
VIDEO_MODEL = {"num_layers": 4, "d_model": 3072, "num_heads": 24,
               "head_dim": 128, "d_ff": 14336, "patch_size": 2,
               "in_channels": 48, "cond_dim": 4096, "latent_frames": 21}
# readers of the accepted benchmark
READERS = ("schedule_ms_per_s", "denoise_ms_per_ktoken.tput", "step_mfu",
           "flash_roofline", "adaln_roofline", "device_idle_share")


def _hand():
    """Window 0-1000 ns: two denoise tasks and a third cut by the
    window's end, with the loop and plane between them; device ops in
    and between the tasks."""
    def task(a, b):
        return ["gfdit.task.denoise", a, b - a, {"task": 1, "seq": 1}]

    def sched(a, actions):
        return ["gfdit.plane.schedule", a, 10, {"ready": 1,
                                                "actions": actions}]
    return {
        "host": [["bench.traced_window", 0, 1000, {}],
                 task(0, 400),
                 ["gfdit.step.inputs", 0, 20, {}],
                 ["gfdit.step.forward", 20, 280, {"layers": 28}],
                 ["gfdit.step.update", 300, 20, {}],
                 ["gfdit.step.fetch", 320, 80, {"bytes": 64}],
                 ["gfdit.plane.complete", 420, 10, {"task": 1, "seq": 1,
                                                    "wait_us": 15.0}],
                 sched(430, 1),
                 ["gfdit.loop.wait", 440, 160, {"completions": 0}],
                 sched(600, 0),
                 task(610, 950),
                 ["gfdit.step.forward", 630, 200, {"layers": 28}],
                 ["gfdit.step.fetch", 850, 100, {"bytes": 64}],
                 ["gfdit.plane.complete", 960, 10, {"task": 1, "seq": 1,
                                                    "wait_us": 25.0}],
                 sched(970, 0),
                 task(985, 1200),
                 ["gfdit.step.forward", 990, 110, {"layers": 28}]],
        "device": [["fusion.1", "jit_a", 50, 100],
                   ["fusion.2", "jit_a", 200, 80],
                   ["copy", "jit_b", 350, 40],
                   ["fusion.3", "jit_c", 420, 20],
                   ["fusion.1", "jit_a", 700, 100],
                   ["copy", "jit_b", 860, 40]],
    }


def _run(tr, model=RECORDED_MODEL):
    return {"trace": {"events": tr, "span": trace.window(tr)},
            "model": model, "text_len": 77, "peak": PEAK,
            "arch": "adaln-cross-swiglu", "root": str(spec.ROOT),
            "window": {"w0": 0.0, "w1": 1.0, "seconds": 1.0},
            "steps": [], "spans": []}


def _without_program_spans(tr):
    return {"device": tr["device"],
            "host": [h for h in tr["host"] if not h[0].startswith("gfdit.")]}


def test_bench_idle_split_partitions_device_idle():
    run = _run(_hand())
    split = spans.idle_split(run)
    # idle 620 ns: 210 under a forward, 225 with no task open, 185 in
    # tasks outside their forward
    assert split == pytest.approx({"idle": 62.0, "forward": 21.0,
                                   "between": 22.5, "in_task": 18.5})
    idle = spec.reader("device_idle_share")(run)
    assert split["forward"] + split["between"] + split["in_task"] == \
        pytest.approx(idle)
    # the op at 420-440 runs while no task is open
    assert spans.busy_outside_tasks_share(run) == pytest.approx(
        100 * 20 / 380)


def test_bench_idle_split_starts_at_the_first_task():
    """The window opens mid-step: that step's task began before the
    profiler did, so it has no span; the split starts at the next task."""
    tr = _hand()
    tr["host"] = [h for h in tr["host"] if h[0] == "bench.traced_window"
                  or h[1] >= 400]
    split = spans.idle_split(_run(tr))
    # over 610-1000 (390 ns): idle 90 + 60 + 100 = 250, under a forward
    # 70 + 30 + 10 = 110, under no task 950-985 = 35
    assert split["idle"] == pytest.approx(100 * 250 / 390)
    assert split["forward"] == pytest.approx(100 * 110 / 390)
    assert split["between"] == pytest.approx(100 * 35 / 390)
    assert split["in_task"] == pytest.approx(100 * 105 / 390)
    assert spans.busy_outside_tasks_share(_run(tr)) == 0.0


def test_bench_span_numbers():
    run = _run(_hand())
    # forwards wholly inside: 280 and 200 ns
    assert spans.forward_dispatch_ms(run) == pytest.approx(240e-6)
    assert spans.completion_wait_ms(run) == pytest.approx(0.020)
    assert spans.schedule_useful_share(run) == pytest.approx(100 / 3)


def test_bench_spans_absent_read_none():
    for run in (_run(_without_program_spans(_hand())), {"trace": None}):
        assert spans.idle_split(run) is None
        assert spans.busy_outside_tasks_share(run) is None
        assert spans.forward_dispatch_ms(run) is None
        assert spans.completion_wait_ms(run) is None
        assert spans.schedule_useful_share(run) is None


def _with_program_spans(tr):
    """The recorded trace with ``gfdit.*`` spans where the program would
    open them: a task and its forward over each executed step, a
    schedule point and a loop wait over the benchmark's own."""
    out = copy.deepcopy(tr)
    names = {"bench.exec.denoise": ("gfdit.task.denoise",
                                    "gfdit.step.forward"),
             "bench.schedule_point": ("gfdit.plane.schedule",),
             "bench.clock_wait": ("gfdit.loop.wait",)}
    for name, s, d, _ in tr["host"]:
        for i, span in enumerate(names.get(name, ())):
            out["host"].append([span, s + i, d - 2 * i,
                                {"task": 7, "seq": 1, "actions": 0}])
    return out


def _load(name):
    with gzip.open(FIXTURE / name) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def recorded():
    return _load("trace-v5e-image-step.json.gz")


@pytest.fixture(scope="module")
def video_step():
    """One video-batch-1c denoise step and 30 ms on each side, recorded
    on a TPU v5e with the program's spans kept."""
    return _load("trace-v5e-video-step-spans.json.gz")


def _same_reduction(plain, mixed, model):
    a, b = trace.window(plain)
    assert trace.window(mixed) == (a, b)
    assert trace.busy_intervals(mixed, a, b) == \
        trace.busy_intervals(plain, a, b)
    assert trace.top_ops(mixed, a, b) == trace.top_ops(plain, a, b)
    assert trace.idle_gaps(mixed, a, b, n=10 ** 6) == \
        trace.idle_gaps(plain, a, b, n=10 ** 6)
    assert trace.denoise_spans(mixed, a, b) == \
        trace.denoise_spans(plain, a, b)
    for name in READERS:
        read = spec.reader(name)
        assert read(_run(mixed, model)) == read(_run(plain, model)), name


def test_bench_reduction_ignores_program_spans(recorded):
    mixed = _with_program_spans(recorded)
    assert len(mixed["host"]) > len(recorded["host"])
    _same_reduction(recorded, mixed, RECORDED_MODEL)
    assert spans.idle_split(_run(recorded)) is None
    split = spans.idle_split(_run(mixed))
    assert split["forward"] + split["between"] + split["in_task"] == \
        pytest.approx(split["idle"])


def test_bench_recorded_spans(video_step):
    plain = _without_program_spans(video_step)
    _same_reduction(plain, video_step, VIDEO_MODEL)
    run = _run(video_step, VIDEO_MODEL)
    a, b = trace.window(video_step)
    first = min(s for name, s, _, _ in video_step["host"]
                if name == "gfdit.task.denoise" and s >= a)
    split = spans.idle_split(run)
    assert split["forward"] + split["between"] + split["in_task"] == \
        pytest.approx(split["idle"])
    assert split["idle"] == pytest.approx(
        100 * (1 - trace.busy_ns(video_step, first, b) / (b - first)))
    # one clock: the device's ops lie inside the program's task spans
    assert spans.busy_outside_tasks_share(run) < 1.0
    forward = spans.forward_dispatch_ms(run)
    assert 100 < forward < spans.whole(
        video_step, "gfdit.task.denoise", a, b)[0][0] * 1e-6
    assert spans.completion_wait_ms(run) >= 0
    assert 0 < spans.schedule_useful_share(run) < 100
