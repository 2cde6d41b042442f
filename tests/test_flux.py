"""FLUX.1-dev on the served path at a CPU size (models/flux.py, DESIGN.md
§18): the step walks a double- and a single-stream segment of compiled
layer programs; SP degrees 2 and 4 gather only the image K/V and agree
with degree 1; a guided request is one row everywhere the control plane
and the executor count rows; packs of guided requests match solo steps;
and the §11 cache-hit splice, which assumes no text rows ahead of the
image rows, refuses a two-stream model."""
import functools
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
from repro.models import dit, flux
from repro.models.dit_parts import layer_of, mod_norm
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


def _layer_inputs(params, cfg, seed):
    """The state and (vec, cos, sin) a block's programs take, from the
    head at degree 1."""
    tok, t, txt, g = _inputs(seed, batch=2)
    return flux.head(params, tok, t, txt, cfg, pos_offset=0, n_total=N,
                     guidance=g, grids=(GRID,) * 2)


def test_linear1_pieces_are_the_drawn_weight_by_columns(params):
    """q, k, v and the MLP input read column blocks of the one drawn
    ``linear1``, which together are that weight and bias to the bit."""
    blocks = params["single"]
    d, f = CFG.d_model, CFG.d_ff
    assert blocks["linear1_w"].shape[1:] == (d, 3 * d + f)
    for i in range(CFG.dit.num_single_layers):
        pieces = flux._linear1(blocks, i, CFG)
        assert [w.shape for w, _ in pieces] == [(d, d)] * 3 + [(d, f)]
        np.testing.assert_array_equal(
            np.concatenate([w for w, _ in pieces], axis=1),
            blocks["linear1_w"][i])
        np.testing.assert_array_equal(
            np.concatenate([b for _, b in pieces]), blocks["linear1_b"][i])


def _fused_single_pre(blocks, i, x, vec, cos, sin, cfg):
    """The single block's ``pre`` as one (D, 3D + F) matmul whose output
    is then sliced into q, k, v and the MLP input."""
    p = layer_of(blocks, i)
    lt, d3 = cfg.dit.text_len, 3 * cfg.d_model
    mods = jax.nn.silu(vec) @ p["mod_w"] + p["mod_b"]
    sh, sc, _ = jnp.split(mods, 3, axis=-1)
    h = mod_norm(x, sh, sc, up=cfg.use_pallas)
    y = h @ p["linear1_w"] + p["linear1_b"]
    q, k, v = flux._qkv(y[..., :d3], p, cfg, cos, sin)
    return mods, q, y[..., d3:], k[:, :lt], v[:, :lt], k[:, lt:], v[:, lt:]


@pytest.mark.parametrize("cfg", [CFG, JNP], ids=["kernel", "jnp"])
def test_single_block_matches_the_fused_linear1(params, cfg):
    x, (vec, cos, sin) = _layer_inputs(params, cfg, 11)
    blocks = params["single"]
    with jax.default_matmul_precision("highest"):
        for i in range(cfg.dit.num_single_layers):
            want = _fused_single_pre(blocks, i, x, vec, cos, sin, cfg)
            got = flux._single_pre(blocks, i, x, vec, cos, sin, cfg=cfg)
            for a, b in zip(got, want):
                assert a.shape == b.shape
                np.testing.assert_allclose(a, b, **TOL)
            out = flux.single_layer(blocks, i, x, (vec, cos, sin),
                                    lambda k, v, layer: (k, v), i, cfg)
            np.testing.assert_allclose(
                out, flux._single_post(blocks, i, x, *want, cfg=cfg), **TOL)
            assert np.abs(np.asarray(out - x)).max() > 0.01
            x = out


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs nested in it."""
    for eqn in jaxpr.eqns:
        subs = [getattr(v, "jaxpr", v) for v in eqn.params.values()
                if hasattr(v, "eqns") or hasattr(getattr(v, "jaxpr", None),
                                                  "eqns")]
        if subs:
            for sub in subs:
                yield from _eqns(sub)
        else:
            yield eqn


@pytest.mark.parametrize("cfg", [CFG, JNP], ids=["kernel", "jnp"])
def test_single_pre_never_slices_linear1s_output(params, cfg):
    """No value of ``linear1``'s whole width 3D + F is computed in
    ``_single_pre``: the stacked weight and bias are only read, a column
    block at a time, by the dynamic slices feeding the four matmuls."""
    x, (vec, cos, sin) = _layer_inputs(params, cfg, 12)
    wide = 3 * cfg.d_model + cfg.d_ff
    closed = jax.make_jaxpr(functools.partial(flux._single_pre, cfg=cfg))(
        params["single"], 0, x, vec, cos, sin)
    reads = []
    for eqn in _eqns(closed.jaxpr):
        assert all(v.aval.shape[-1:] != (wide,) for v in eqn.outvars), eqn
        if any(v.aval.shape[-1:] == (wide,) for v in eqn.invars
               if hasattr(v, "aval")):
            assert eqn.primitive.name == "dynamic_slice", eqn
            reads.append(eqn.outvars[0].aval.shape[-1])
    d, f = cfg.d_model, cfg.d_ff
    assert sorted(reads) == sorted([d, d, d, f] * 2)   # weight and bias
    dots = [e.outvars[0].aval.shape[-1] for e in _eqns(closed.jaxpr)
            if e.primitive.name == "dot_general"
            and e.outvars[0].aval.ndim == 3]
    assert sorted(dots) == [d, d, d, f]


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

