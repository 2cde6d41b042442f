"""The reduction from a trace to busy and idle time, the breakdown and
the kernels' roofline shares: on a hand-made trace with known answers,
and on a small trace recorded on a TPU v5e kept beside this file."""
import gzip
import json

import pytest

from conftest import FIXTURE
from gfbench import flops, spec, trace

PEAK = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
ARCH = spec.arch("adaln-cross-swiglu")
MODEL = {"num_layers": 1, "d_model": 128, "num_heads": 2, "head_dim": 64,
         "d_ff": 256, "patch_size": 2, "in_channels": 16, "cond_dim": 64}


def _hand():
    """Window 0-1000 ns; two denoise steps; device ops with one gap
    during a clock wait and one during scheduling."""
    return {
        "host": [["bench.traced_window", 0, 1000, {}],
                 ["bench.exec.denoise", 0, 400, {"tokens": 256, "rows": 2}],
                 ["bench.clock_wait", 400, 200, {}],
                 ["bench.exec.denoise", 600, 350, {"tokens": 256,
                                                    "rows": 2}],
                 ["bench.schedule_point", 950, 50, {}]],
        "device": [["fusion.1", "jit_dot_general", 0, 100],
                   ["custom-call", "jit_flash_attention", 100, 200],
                   ["fusion.2", "jit_adaln_modulate", 250, 100],
                   ["custom-call", "jit_flash_attention", 600, 300],
                   ["copy", "jit_adaln_modulate", 900, 20]],
    }


def test_bench_busy_idle_and_breakdown():
    tr = _hand()
    a, b = trace.window(tr)
    assert (a, b) == (0, 1000)
    assert trace.busy_intervals(tr, a, b) == [[0, 350], [600, 920]]
    assert trace.busy_ns(tr, a, b) == 670
    gaps = trace.idle_gaps(tr, a, b)
    assert [g[0] for g in gaps] == ["bench.clock_wait",
                                    "bench.schedule_point"]
    assert [g[1] for g in gaps] == pytest.approx([250e-9, 80e-9])
    ops = dict((k, v) for k, v in trace.top_ops(tr, a, b))
    assert ops["jit_flash_attention:custom-call"] == pytest.approx(500e-9)
    assert ops["jit_adaln_modulate:fusion.2"] == pytest.approx(100e-9)


def test_bench_roofline_share_over_traced_steps():
    tr = _hand()
    run = {"trace": {"events": tr, "span": (0, 1000)}, "model": MODEL,
           "text_len": 77, "peak": PEAK}
    share = trace.roofline_share(
        run, ARCH.step_flash, lambda mod, name: "flash" in mod, "flash")
    least = 2 * flops.least_time(*ARCH.step_flash(MODEL, 256, 2, 77),
                                 PEAK)[0]
    assert share == pytest.approx(100 * least / 500e-9)
    spans = trace.denoise_spans(tr, 0, 1000)
    assert trace.kernel_ns(tr, spans, lambda m, n: "adaln" in m) == 120


def test_bench_nothing_traced_reads_none():
    run = {"trace": None}
    assert trace.roofline_share(run, ARCH.step_flash,
                                lambda m, n: True, "x") is None
    tr = {"host": [["bench.traced_window", 0, 10, {}]], "device": []}
    run = {"trace": {"events": tr, "span": (0, 10)}, "model": MODEL,
           "text_len": 77, "peak": PEAK}
    assert trace.roofline_share(run, ARCH.step_flash,
                                lambda m, n: True, "x") is None


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(FIXTURE / "trace-v5e-image-step.json.gz") as f:
        return json.load(f)


def test_bench_recorded_trace_reduces(recorded):
    tr = recorded
    a, b = trace.window(tr)
    busy = trace.busy_ns(tr, a, b)
    assert 0.5 < busy / (b - a) < 1.0
    ops = trace.top_ops(tr, a, b)
    assert ops[0][0] == "jit_flash_attention:flash_attention.1"
    assert sum(v for _, v in trace.top_ops(tr, a, b, n=10 ** 6)) == \
        pytest.approx(sum(min(s + d, b) - max(s, a) for _, _, s, d
                          in tr["device"] if s + d > a and s < b) * 1e-9)
    gaps = trace.idle_gaps(tr, a, b)
    assert gaps and all(name.startswith("bench.") for name, _ in gaps)
    assert sum(d for _, d in trace.idle_gaps(tr, a, b, n=10 ** 6)) == \
        pytest.approx((b - a - busy) * 1e-9)


# the model the recorded trace was served with: 1024 px, guided
RECORDED_MODEL = {"num_layers": 28, "d_model": 1536, "num_heads": 24,
                  "head_dim": 64, "d_ff": 6144, "patch_size": 2,
                  "in_channels": 16, "cond_dim": 1024}


def test_bench_recorded_trace_rooflines(recorded):
    run = {"trace": {"events": recorded, "span": trace.window(recorded)},
           "model": RECORDED_MODEL, "text_len": 77, "peak": PEAK}
    assert len(trace.denoise_spans(recorded, *run["trace"]["span"])) == 1
    flash = trace.roofline_share(
        run, ARCH.step_flash,
        lambda mod, name: name.startswith("flash_attention"), "flash")
    adaln = trace.roofline_share(
        run, ARCH.step_adaln,
        lambda mod, name: name.startswith("adaln_modulate"), "adaln")
    assert 0 < flash < 100 and 0 < adaln < 100
