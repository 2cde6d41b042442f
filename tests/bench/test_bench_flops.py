"""Operation and byte counts of the configurations' architecture against
hand counts at both configurations' widths."""
import json

import pytest

from conftest import REPO
from gfbench import flops, spec, traffic

ARCH = spec.arch("adaln-cross-swiglu")


def _model(name):
    with open(REPO / "bench" / "configs" / f"{name}.json") as f:
        return json.load(f)["model"]


def test_bench_image_step_hand_count():
    m = _model("pixart-sigma-xl2-1024")
    n, b, lt, d, dff = 4096, 2, 77, 1152, 4608
    assert traffic.tokens(m, {"height": 1024, "width": 1024,
                              "frames": 1}) == n
    per_token_layer = 8 * d * d + 4 * d * d + 6 * d * dff   # self, cross q/o, MLP
    attn = 4 * n * d + 4 * lt * d                           # QK^T and PV
    body = 28 * b * n * (per_token_layer + attn)
    got = ARCH.step_flops(m, n, b, lt)
    assert got == pytest.approx(15.40e12, rel=2e-3)
    assert body / got == pytest.approx(1.0, abs=5e-3)
    flash_fl, _ = ARCH.step_flash(m, n, b, lt)
    assert flash_fl / got == pytest.approx(0.285, abs=0.005)


def test_bench_video_step_hand_count():
    m = _model("wan2.2-ti2v-5b-4l")
    n, d, dff = 11440, 3072, 14336
    assert traffic.tokens(m, {"height": 352, "width": 640, "frames": 49}) == n
    per_tok = ARCH.step_flops(m, n, 1, 77) / (4 * n)
    proj_mlp = 8 * d * d + 4 * d * d + 6 * d * dff
    assert proj_mlp == pytest.approx(377.5e6, rel=1e-3)
    assert 4 * n * d == pytest.approx(140.6e6, rel=1e-3)
    flash_fl, _ = ARCH.step_flash(m, n, 1, 77)
    assert flash_fl / (4 * n * per_tok) == pytest.approx(0.27, abs=0.01)
    assert per_tok == pytest.approx(377.5e6 + 140.6e6, rel=5e-3)


@pytest.mark.parametrize("hd,nq,ratio", [
    (64, 4096, 2.0), (72, 4096, 128 / 72),
    (128, 11440, (11520 / 11440) ** 2)])
def test_bench_padded_against_algorithmic_flash(hd, nq, ratio):
    padded = flops.padded_flash_flops(1, 24, nq, nq, hd)
    assert padded / flops.flash_flops(1, 24, nq, nq, hd) == \
        pytest.approx(ratio, rel=1e-9)


def test_bench_bytes_and_roofline():
    m = _model("pixart-sigma-xl2-1024")
    peak = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    t, bound = flops.least_time(*ARCH.step_flash(m, 4096, 2, 77), peak)
    assert bound == "compute"
    fl, by = ARCH.step_adaln(m, 4096, 2)
    assert by == 4 * (28 * 12 + 2) * 2 * 4096 * 1152 + 4 * (28 * 6 + 2) * 2 * 1152
    assert flops.least_time(fl, by, peak)[1] == "memory"
    assert flops.flash_bytes(1, 2, 3, 5, 7) == 4 * 2 * 7 * (6 + 10)
