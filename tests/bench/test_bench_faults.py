"""``correct`` on a whole run of a CPU-sized cell: a sound run is correct
and the control (the bfloat16 reference in the program's place) is
rejected; a run with the timed path broken underneath is not correct.

The run is driven past the harness's look for a chip, through the same
``measure`` the benchmark's command calls.  The faults are planted in
the program for the test only: a step that returns its state unchanged,
half of the guided batch left out, and an answer (the latent a step
produces) altered where it is produced.  The cells run on one chip, so no
exchange between chips can be left out."""
import time
from pathlib import Path

import jax.numpy as jnp
import pytest

from conftest import FIXTURE
from gfbench import bench, check, spec

SEED = 3000000011


def _measure(tmp_path, control=False):
    cell = spec.resolve(spec.load(FIXTURE), "tiny-batch", root=FIXTURE)
    return bench.measure(cell, SEED, 2.0, False, Path(tmp_path),
                         spec.peaks()["TPU v5 lite"], time.monotonic(), [],
                         control=control)


def test_bench_sound_run_correct_and_control_rejected(tmp_path):
    res = _measure(tmp_path, control=True)
    assert res["correct"], res["checks"]
    assert res["attempted"] == 1 and res["failed"] == 0
    assert set(check.NUMBERS) == set(res["checks"])
    limits = {k: lim for k, (_, lim) in res["checks"].items()}
    ok, table = check.verdict(res["control"], limits)
    assert not ok, table
    assert res["control_correct"] is False
    assert all(res["checks"][k][0] < res["control"][k] for k in
               ("step_gap", "state_gap", "encode_gap", "latent0_gap"))
    assert res["control"]["state_gap"] > limits["state_gap"]


def _unchanged(x, v, s0, s1):
    return x


def _half_batch(fn):
    def wrapped(params, x, t, txt, *a, **kw):
        if x.shape[0] == 2:
            v = fn(params, x[:1], t[:1], txt[:1], *a, **kw)
            return jnp.concatenate([v, v])
        return fn(params, x, t, txt, *a, **kw)
    return wrapped


def _altered(fn):
    def wrapped(*a, **kw):
        return fn(*a, **kw).at[:4].add(0.5)
    return wrapped


@pytest.mark.parametrize("fault", ["unchanged_state", "half_batch",
                                   "altered_answer"])
def test_bench_broken_path_is_not_correct(fault, tmp_path, monkeypatch):
    from repro.diffusion import schedule
    from repro.models import dit
    if fault == "unchanged_state":
        monkeypatch.setattr(schedule, "flow_step", _unchanged)
    elif fault == "half_batch":
        monkeypatch.setattr(dit, "forward_sp_tokens",
                            _half_batch(dit.forward_sp_tokens))
    else:
        monkeypatch.setattr(schedule, "flow_step",
                            _altered(schedule.flow_step))
    res = _measure(tmp_path)
    assert not res["correct"], res["checks"]
