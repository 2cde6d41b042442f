"""FLUX.1-dev on the served path at a CPU size (models/flux.py, DESIGN.md
§18): the step walks a double- and a single-stream segment of compiled
layer programs; SP degrees 2 and 4 gather only the image K/V and agree
with degree 1; a guided request is one row everywhere the control plane
and the executor count rows; packs of guided requests match solo steps;
and the §11 cache-hit splice, which assumes no text rows ahead of the
image rows, refuses a two-stream model."""
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.dit_models import DIT_IMAGE, DIT_VIDEO, FLUX1_DEV
from repro.core.cost_model import CostModel
from repro.core.gfc import GroupFreeComm
from repro.core.policies import make_policy
from repro.core.scheduler import (ControlPlane, Dispatch, PackedDispatch,
                                  Policy)
from repro.core.simulator import SimBackend
from repro.core.telemetry import Telemetry
from repro.core.trajectory import ExecutionLayout, Request
from repro.diffusion.adapters import convert_request
from repro.diffusion.feature_cache import FeatureCachePlane, cache_artifact
from repro.diffusion.pipeline import DiTPipeline
from repro.kernels import ops
from repro.models import dit
from repro.serving.cache_demo import liven
from repro.serving.engine import ServingEngine
from test_serving_engine import FixedSP

# a name of its own, so no other test in the process has built these
# programs already; 1 double + 2 single blocks, 2 heads of 32, 77 text
# tokens, RoPE axes 4/14/14
CFG = FLUX1_DEV.reduced(num_layers=1, d_model=64, num_heads=2,
                        num_kv_heads=2, head_dim=32, d_ff=256).with_(
    name="flux-tiny-tests")
JNP = CFG.with_(use_pallas=False)
GRID = (1, 8, 8)            # 128 px: 8 x 8 patches
N = 64
SEGMENTS = 6                # head, double pre/post, single pre/post, tail
TOL = dict(atol=1e-5, rtol=1e-5)


@pytest.fixture(scope="module")
def params():
    pipe = DiTPipeline(CFG, seed=0)
    liven(pipe)
    return pipe.dit_params


def _inputs(seed, batch=1):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    tok = jax.random.normal(ks[0], (batch, N, 64))
    t = jax.random.uniform(ks[1], (batch,), minval=0.0, maxval=1000.0)
    txt = jax.random.normal(ks[2], (batch, CFG.dit.text_len,
                                    CFG.dit.cond_dim))
    return tok, t, txt, jnp.full((batch,), 3.5)


def _forward(params, cfg, tok, t, txt, g, *, off=0, grid=GRID,
             kv_gather=lambda k, v, layer: (k, v)):
    return np.asarray(dit.forward_sp_tokens(
        params, tok, t, txt, cfg, pos_offset=off, n_total=N,
        kv_gather=kv_gather, guidance=g, grids=(grid,) * tok.shape[0]))


def test_config_is_flux_dev_at_its_published_widths():
    c = FLUX1_DEV
    assert (c.num_layers, c.dit.num_single_layers) == (19, 38)
    assert (c.d_model, c.num_heads, c.head_dim, c.d_ff) == \
        (3072, 24, 128, 12288)
    assert c.dit.rope_axes == (16, 56, 56) and sum(c.dit.rope_axes) == 128
    assert c.dit.patch_size ** 2 * c.dit.in_channels == 64
    assert (c.dit.cond_dim, c.dit.pooled_dim, c.dit.text_len) == \
        (4096, 768, 512)
    assert c.dit.guidance_embeds and c.dit.flow_shift == 3.1582
    assert dit.segments(c) == (("double", 19), ("single", 38))
    assert dit.segments(DIT_IMAGE) == (("adaln", 28),)


def test_one_shape_builds_each_program_once(params):
    before = dit.builds()
    _forward(params, CFG, *_inputs(1))
    assert dit.builds() - before == SEGMENTS
    for seed in (2, 3):
        _forward(params, CFG, *_inputs(seed))
    assert dit.builds() - before == SEGMENTS


def test_kernel_path_matches_the_jnp_path(params):
    tok, t, txt, g = _inputs(4, batch=2)
    got = _forward(params, CFG, tok, t, txt, g)
    want = _forward(params, JNP, tok, t, txt, g)
    assert np.abs(want).max() > 0.01
    np.testing.assert_allclose(got, want, **TOL)


def test_guidance_and_positions_are_inputs(params):
    tok, t, txt, g = _inputs(5)
    base = _forward(params, JNP, tok, t, txt, g)
    assert not np.allclose(base, _forward(params, JNP, tok, t, txt, g * 2))
    # the same tokens laid out as 4 x 16 patches sit at other positions
    assert not np.allclose(base, _forward(params, JNP, tok, t, txt, g,
                                          grid=(1, 4, 16)))


@pytest.mark.parametrize("degree", [2, 4])
def test_sp_degrees_gather_image_kv_and_match_degree_1(params, degree):
    """Each rank holds the text rows and its image shard; the gather
    concatenates the image K/V only."""
    tok, t, txt, g = _inputs(6)
    want = _forward(params, CFG, tok, t, txt, g)
    comm = GroupFreeComm(degree)
    desc = comm.register_group(tuple(range(degree)))
    size = N // degree
    out, errors, seen = {}, [], []

    def rank(r):
        def gather(k, v, layer):
            seen.append(k.shape[1])
            return (jnp.asarray(comm.all_gather(desc, r, np.asarray(k),
                                                axis=1)),
                    jnp.asarray(comm.all_gather(desc, r, np.asarray(v),
                                                axis=1)))
        try:
            out[r] = _forward(params, CFG, tok[:, r * size:(r + 1) * size],
                              t, txt, g, off=r * size, kv_gather=gather)
        except Exception as e:   # noqa: BLE001 - reported below
            errors.append(e)

    threads = [threading.Thread(target=rank, args=(r,))
               for r in range(degree)]
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=120)
    assert not errors and not any(th.is_alive() for th in threads), errors
    assert set(seen) == {size}                  # no text rows gathered
    assert len(seen) == degree * 3              # one gather a layer
    np.testing.assert_allclose(
        np.concatenate([out[r] for r in range(degree)], axis=1), want,
        **TOL)


def test_cache_hit_splice_refuses_a_two_stream_model(params):
    tok, t, txt, g = _inputs(7)

    def spliced(k, v, layer):
        return ops.SplicedKV(k, v, k, v, 0)

    with pytest.raises(NotImplementedError, match="cache hit"):
        _forward(params, CFG, tok, t, txt, g, kv_gather=spliced)


def _flux_request(rid, guidance=3.5, steps=2, height=128, width=128):
    return Request(id=rid, model="flux1-dev", height=height, width=width,
                   frames=1, steps=steps, arrival=0.0, guidance=guidance)


def test_cfg_branch_count_is_decided_from_the_model():
    flux, pixart = _flux_request("f"), _flux_request("p")
    g_flux = convert_request(flux, CFG)
    g_pixart = convert_request(pixart, DIT_IMAGE.reduced())
    assert (flux.cfg_branches, pixart.cfg_branches) == (1, 2)
    unguided = _flux_request("u", guidance=None)
    convert_request(unguided, DIT_IMAGE.reduced())
    assert unguided.cfg_branches == 1

    def text_fields(g):
        return next(a for a in g.artifacts.values()
                    if a.role == "text_embeds").fields
    assert set(text_fields(g_flux)) == {"embeds"}
    assert text_fields(g_flux)["embeds"].global_shape == (77, 64)
    assert set(text_fields(g_pixart)) == {"embeds", "embeds_uncond"}
    # FLUX's layers take no §11 snapshot, so its graph declares no cache
    assert cache_artifact(g_flux) is None
    assert len(cache_artifact(g_pixart).fields) == 2 * 2    # k, v a layer
    # every denoise task and the decode carry the latent's one shape
    video = Request(id="v", model="dit-video", height=64, width=128,
                    frames=9, steps=2, arrival=0.0)
    for g, shape in ((g_pixart, (1, 16, 16, 16)),
                     (convert_request(video, DIT_VIDEO.reduced()),
                      (3, 8, 16, 16))):
        tasks = [t for t in g.tasks.values() if t.kind != "encode"]
        assert {t.kind for t in tasks} == {"denoise", "decode"}
        assert {t.meta["latent_shape"] for t in tasks} == {shape}


def test_cache_plane_never_stamps_a_flux_hit():
    """Under the feature cache a FLUX step at degree 2 is planned as no
    cache mode, so no worker is handed a hit its layers cannot take."""
    plane = FeatureCachePlane(interval=4)
    lay = ExecutionLayout((0, 1))
    for rid, cfg, want in (("f", CFG, None),
                           ("p", DIT_IMAGE.reduced(), "refresh")):
        g = convert_request(_flux_request(rid, guidance=None), cfg)
        steps = sorted((t for t in g.tasks.values() if t.kind == "denoise"),
                       key=lambda t: t.step_index)
        modes = [(plane.stamp(t, lay, g) or {}).get("mode") for t in steps]
        assert modes[0] == want
        if want is None:
            assert modes == [None] * len(steps)
            assert not any("cache" in t.meta for t in steps)


class _Null(Policy):
    name = "null"

    def schedule(self, view):
        return []


def _plane(reqs, cfg):
    """A simulated plane with each request's encode done."""
    cost = CostModel()
    cp = ControlPlane(4, _Null(), cost, SimBackend(cost))
    for r in reqs:
        cp.submit(r, convert_request(r, cfg))
    for g in cp.graphs.values():
        enc = next(t for t in g.tasks.values() if t.kind == "encode")
        assert cp.apply(Dispatch(enc.id, ExecutionLayout((0,))))
        for c in cp.backend.poll():
            cp.on_completion(c)
    return cp


def _denoise(cp, rid):
    return next(t for t in cp.graphs[rid].ready_tasks()
                if t.kind == "denoise")


def test_guided_flux_is_one_row_to_the_control_plane():
    split = ExecutionLayout((0, 1, 2, 3), cfg=2)
    cp = _plane([_flux_request("a"), _flux_request("b"),
                 _flux_request("c", guidance=None)], CFG)
    ta, tb = _denoise(cp, "a"), _denoise(cp, "b")
    req = cp.requests["a"]
    # no cfg x sp shape is offered or accepted
    assert not ControlPlane._shape_ok(split, req)
    assert not cp.apply(Dispatch(ta.id, split))
    req.deadline = 1.0                  # tight: shapes would be searched
    policy = make_policy("elastic-hybrid", 4)
    assert policy._need_shape(cp._view(), req, cp.graphs["a"])[1] == 1
    # priced as one row: the same as the request unguided
    assert cp.cost.request_remaining("flux1-dev", cp.graphs["a"], 2) == \
        cp.cost.request_remaining("flux1-dev", cp.graphs["c"], 2)
    # guided one-row steps of one scale may share a batched call, also
    # on transposed grids of one token count (64 x 256, 256 x 64 px)
    assert cp.apply(PackedDispatch((ta.id, tb.id), ExecutionLayout((0,))))
    cp3 = _plane([_flux_request("w", height=64, width=256),
                  _flux_request("t", height=256, width=64)], CFG)
    assert cp3.apply(PackedDispatch(
        (_denoise(cp3, "w").id, _denoise(cp3, "t").id),
        ExecutionLayout((0,))))
    # a guided two-row model's requests may not
    cp2 = _plane([_flux_request("a"), _flux_request("b")],
                 DIT_IMAGE.reduced())
    assert not cp2.apply(PackedDispatch(
        (_denoise(cp2, "a").id, _denoise(cp2, "b").id),
        ExecutionLayout((0,))))


def test_guided_flux_request_serves_one_row():
    tel = Telemetry()
    eng = ServingEngine(CFG.with_(name="flux-tiny-engine"), FixedSP(2), 2,
                        telemetry=tel)
    req = _flux_request("s0", steps=3)
    try:
        assert eng.serve([req], timeout=120.0)["completed"] == 1
        assert np.isfinite(eng.result_pixels(req)).all()
    finally:
        eng.shutdown()
    enc = next(t for t in eng.cp.graphs["s0"].tasks.values()
               if t.kind == "encode")
    txt = eng.cp.graphs["s0"].artifacts[enc.outputs[0]]
    assert all(set(d) == {"embeds"} for d in txt.data.values())
    tasks = [s for n, _, _, s in tel.overlay if n == "gfdit.task.denoise"]
    assert tasks and all(s["rows"] == 1 and s["degree"] == 2
                         for s in tasks)
    fwd = [s for n, _, _, s in tel.overlay if n == "gfdit.step.forward"]
    assert all((s["layers"], s["double"], s["single"]) == (3, 1, 2)
               for s in fwd)
    assert [s["builds"] for s in fwd][-2:] == [0, 0]


def _prepped(pipe, comm, rid, guidance, height=128, width=128):
    lay = ExecutionLayout((0,))
    req = _flux_request(rid, guidance=guidance, height=height, width=width)
    g = convert_request(req, CFG)
    enc = next(t for t in g.tasks.values() if t.kind == "encode")
    for aid in enc.outputs:
        g.artifacts[aid].data = {0: {}}
    pipe.execute(enc, lay, 0, comm, g, comm.register_group((0,)))
    for aid in enc.outputs:
        g.artifacts[aid].materialized = True
        g.artifacts[aid].layout = lay
    d0 = next(t for t in g.tasks.values()
              if t.kind == "denoise" and t.step_index == 0)
    for aid in d0.outputs:
        g.artifacts[aid].data = {0: {}}
    return g, d0


@pytest.mark.parametrize("members", [
    {"pa": (3.5, 128, 128), "pb": (3.5, 128, 128), "pc": (None, 128, 128)},
    # 64 x 256 and 256 x 64 px: 64 tokens each, on grids (1, 4, 16) and
    # (1, 16, 4); each row keeps its own grid's positions
    {"wide": (3.5, 64, 256), "tall": (3.5, 256, 64)},
], ids=["one-grid", "transposed-grids"])
def test_packed_guided_steps_match_solo_steps(members):
    pipe = DiTPipeline(CFG, seed=0)
    liven(pipe)
    comm = GroupFreeComm(1)
    lay = ExecutionLayout((0,))
    solo = {}
    for rid, args in members.items():
        graph, d0 = _prepped(pipe, comm, rid, *args)
        pipe.execute(d0, lay, 0, comm, graph, comm.register_group((0,)))
        solo[rid] = graph.artifacts[d0.outputs[0]].data[0]["latent"].copy()
    packed = [(d0, graph) for graph, d0 in
              (_prepped(pipe, comm, rid, *args)
               for rid, args in members.items())]
    pipe.execute_packed(packed, lay, 0, comm, comm.register_group((0,)))
    for d0, graph in packed:
        np.testing.assert_allclose(
            graph.artifacts[d0.outputs[0]].data[0]["latent"],
            solo[d0.request_id], **TOL)

