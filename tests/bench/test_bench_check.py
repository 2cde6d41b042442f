"""The numbers that decide ``correct``, on hand-made outputs: the
velocity and the state a step produced against the reference's, the
control's state held in bfloat16, and the verdict over every number."""
import numpy as np
import pytest

from gfbench import check, spec


class _Ref:
    """A stand-in reference: velocity rows fixed by hand, merged and
    faulted by the benchmarked configurations' architecture."""
    conf = {"flow_shift": 3.0}
    arch = spec.arch("adaln-cross-swiglu")

    def __init__(self, rows):
        self.rows = np.asarray(rows, np.float32)

    def step(self, it, dtype):
        return self.rows, 0.9, 0.8


def _item(x_in, x_out, guidance=None):
    return {"what": "step", "req": "r", "step": 0, "steps": 50,
            "guidance": guidance, "x_in": np.asarray(x_in, np.float32),
            "x_out": np.asarray(x_out, np.float32)}


def test_bench_sound_step_reads_zero():
    v = np.array([[[1.0, -2.0], [0.5, 3.0]]])
    x = np.array([[0.3, 0.1], [-0.2, 0.4]])
    got = check.gaps(_Ref(v), [_item(x, x + np.float32(0.8 - 0.9) * v[0])])
    assert got["step_gap"] == pytest.approx(0.0, abs=1e-6)
    assert got["state_gap"] == pytest.approx(0.0, abs=1e-6)


def test_bench_guided_merge_and_faults():
    v = np.array([[[1.0, 2.0]], [[0.5, 1.0]]])     # cond, uncond
    x = np.array([[1.0, 1.0]])
    merged = v[1] + 4.0 * (v[0] - v[1])
    ref = _Ref(v)
    sound = _item(x, x - np.float32(0.1) * merged, guidance=4.0)
    assert check.gaps(ref, [sound])["step_gap"] == pytest.approx(0, abs=1e-6)
    f = check.fault_readings(ref, [sound])
    assert f["unchanged_state.step_gap"] == 1.0
    x_ref = x - 0.1 * merged
    assert f["unchanged_state.state_gap"] == pytest.approx(
        np.linalg.norm(x - x_ref) / np.linalg.norm(x_ref))
    assert f["half_batch.step_gap"] == pytest.approx(
        np.linalg.norm(v[0] - merged) / np.linalg.norm(merged))


def test_bench_control_state_is_held_in_bfloat16():
    x = np.linspace(-1.0, 1.0, 64, dtype=np.float32)
    got = check._control_state(x, np.zeros_like(x), 0.9, 0.8)
    assert 0 < np.abs(got - x).max() <= 2 ** -8


@pytest.mark.parametrize("found,ok", [
    ({"step_gap": 0.01, "state_gap": 1e-4}, True),
    ({"step_gap": 0.01, "state_gap": 1e-2}, False),
    ({"state_gap": 1e-4}, False)])
def test_bench_verdict(found, ok):
    limits = {"step_gap": 0.02, "state_gap": 1e-3, "encode_gap": 1e-3,
              "latent0_gap": 0.0}
    got, table = check.verdict(found, limits)
    assert got is ok
    assert set(table) == set(found)
