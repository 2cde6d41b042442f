"""Real-runtime serving tests: SP equivalence through the full engine,
elastic layout changes with migration, failure recovery."""
import numpy as np
import pytest

from repro.configs.dit_models import DIT_IMAGE
from repro.core.policies import make_policy
from repro.core.scheduler import Decision, Policy
from repro.core.trajectory import ExecutionLayout, Request
from repro.serving.engine import ServingEngine


class FixedSP(Policy):
    name = "fixed-sp"

    def __init__(self, k):
        self.k = k

    def schedule(self, view):
        out, free = [], list(view.free_ranks)
        for t, req, g in sorted(view.ready, key=lambda x: x[0].id):
            k = 1 if t.kind in ("encode", "decode") else self.k
            if len(free) < k:
                break
            out.append(Decision(t.id, ExecutionLayout(tuple(free[:k]))))
            free = free[k:]
        return out


class AlternatingSP(Policy):
    """Forces a layout change at every denoise boundary -> migration on
    every step (stress test for §5.3)."""
    name = "alternating"

    def schedule(self, view):
        out, free = [], list(view.free_ranks)
        for t, req, g in sorted(view.ready, key=lambda x: x[0].id):
            if t.kind == "denoise":
                k = 2 if t.step_index % 2 == 0 else 4
                # also rotate which ranks, so data must move
                ranks = tuple(free[-k:]) if t.step_index % 2 else \
                    tuple(free[:k])
            else:
                k = 1
                ranks = tuple(free[:1])
            if len(free) < k:
                break
            out.append(Decision(t.id, ExecutionLayout(ranks)))
            free = [r for r in free if r not in ranks]
        return out


def _request(rid="r0", res=128, steps=3):
    return Request(id=rid, model="dit-image", height=res, width=res,
                   frames=1, steps=steps, arrival=0.0)


@pytest.fixture(scope="module")
def cfg():
    return DIT_IMAGE.reduced()


def _run(cfg, policy, req):
    eng = ServingEngine(cfg, policy, num_ranks=4, seed=0)
    eng.serve([req], timeout=240)
    px = eng.result_pixels(req)
    eng.shutdown()
    return px


def test_sp_degrees_bitwise_equal(cfg):
    """SP1 == SP2 == SP4 pixels: GFC + SP denoise + migration correct."""
    px1 = _run(cfg, FixedSP(1), _request())
    px2 = _run(cfg, FixedSP(2), _request())
    px4 = _run(cfg, FixedSP(4), _request())
    assert px1 is not None
    np.testing.assert_array_equal(px1, px2)
    np.testing.assert_array_equal(px1, px4)


def test_elastic_layout_changes_preserve_output(cfg):
    """Changing group size AND membership at every trajectory boundary
    (migration on every step) must not change the result."""
    ref = _run(cfg, FixedSP(1), _request(steps=4))
    alt = _run(cfg, AlternatingSP(), _request(steps=4))
    np.testing.assert_allclose(ref, alt, atol=1e-5)


def test_multi_request_edf_serving(cfg):
    eng = ServingEngine(cfg, make_policy("edf", 4), num_ranks=4, seed=0)
    reqs = [_request(f"r{i}", res=128, steps=2) for i in range(4)]
    for i, r in enumerate(reqs):
        r.arrival = 0.05 * i
        r.deadline = 300.0
    m = eng.serve(reqs, timeout=300)
    assert m["completed"] == 4
    for r in reqs:
        assert eng.result_pixels(r) is not None
    eng.shutdown()


def test_gfc_descriptor_count_grows_with_layout_churn(cfg):
    """Elastic serving registers many dynamic groups; each must be
    metadata-only (no comm state)."""
    eng = ServingEngine(cfg, AlternatingSP(), num_ranks=4, seed=0)
    eng.serve([_request(steps=4)], timeout=240)
    regs = eng.comm.stats["registrations"]
    per_reg_us = eng.comm.stats["reg_seconds"] / max(regs, 1) * 1e6
    eng.shutdown()
    assert regs >= 4
    assert per_reg_us < 1000.0      # paper: ~60 us


def test_every_rank_shares_the_one_device():
    """Ranks wrap over the local devices: with one device every rank
    computes on it, and the weights are placed there once."""
    import jax
    from repro.core.executor import rank_device
    from repro.diffusion.pipeline import DiTPipeline
    (dev,) = jax.local_devices()
    assert {rank_device(r) for r in range(8)} == {dev}
    pipe = DiTPipeline(DIT_IMAGE.reduced())
    placed = pipe.weights(0)
    assert all(pipe.weights(r) is placed for r in range(4))
    assert all(dev in leaf.devices() for leaf in jax.tree.leaves(placed))
