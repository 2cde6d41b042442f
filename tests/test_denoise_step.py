"""The pipeline's one denoise step (``DiTPipeline.execute`` on a denoise
task) at each step shape it runs, against ``schedule.flow_step`` applied
to a velocity from ``dit.forward_sp_tokens`` called directly, with the
guidance merge written out here: a guided request's two rows at degree 1,
an unguided solo step, FLUX's guidance as an input, and a §11 refresh
followed by a hit at degree 2 over GFC threads on the jnp and the Pallas
attention paths.  Also: the jnp splice of a hit equals materializing the
splice and running the plain ``post``, bit for bit."""
import threading

import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.dit_models import DIT_IMAGE, FLUX1_DEV
from repro.core.gfc import GroupFreeComm
from repro.core.trajectory import ExecutionLayout, Request
from repro.diffusion import schedule
from repro.diffusion.adapters import convert_request
from repro.diffusion.feature_cache import cache_artifact
from repro.diffusion.pipeline import DiTPipeline
from repro.kernels import ops
from repro.models import dit
from repro.serving.cache_demo import liven

IMAGE = DIT_IMAGE.reduced()
FLUX = FLUX1_DEV.reduced(num_layers=1, d_model=64, num_heads=2,
                         num_kv_heads=2, head_dim=32, d_ff=256)
# PixArt's head_dim: dividing the scores by sqrt(72) and multiplying them
# by 72 ** -0.5 round differently, so a splice that attends other than
# ``post`` does cannot pass for it bit for bit
SPLICE = IMAGE.with_(head_dim=72)
N = 64                  # 128 px: 8 x 8 patches
GRID = (1, 8, 8)
# the kernel tests' tolerance, kernel against oracle (test_kernels.py)
TOL = dict(atol=1e-5, rtol=1e-5)


def _local(k, v, layer):
    return k, v


def _prepared(cfg, req, ranks):
    """A pipeline, a converted graph with its encode run over ``ranks``,
    and the graph's denoise tasks in step order."""
    pipe = DiTPipeline(cfg, seed=0)
    liven(pipe)               # non-zero gates and head: a real output
    comm = GroupFreeComm(len(ranks))
    graph = convert_request(req, cfg)
    for art in graph.artifacts.values():
        art.data = {r: {} for r in ranks}
    enc = next(t for t in graph.tasks.values() if t.kind == "encode")
    lay = ExecutionLayout(ranks)
    pipe.execute(enc, lay, ranks[0], comm, graph, comm.register_group(ranks))
    steps = sorted((t for t in graph.tasks.values() if t.kind == "denoise"),
                   key=lambda t: t.step_index)
    return pipe, comm, graph, lay, steps


def _run(pipe, comm, graph, lay, task):
    """The step on every rank of ``lay``, one thread a rank; returns the
    step's input latent and its output, each whole (N, patch_dim)."""
    desc = comm.register_group(lay.ranks)
    errors = []

    def rank(r):
        try:
            pipe.execute(task, lay, r, comm, graph, desc)
        except Exception as e:   # noqa: BLE001 - reported below
            errors.append(e)

    threads = [threading.Thread(target=rank, args=(r,)) for r in lay.ranks]
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=120)
    assert not errors and not any(th.is_alive() for th in threads), errors

    def whole(aid):
        data = graph.artifacts[aid].data
        return np.concatenate([data[r]["latent"] for r in lay.ranks])
    return whole(task.inputs[1]), whole(task.outputs[0])


def _sigmas(cfg, task, steps):
    s = schedule.flow_sigmas(steps, cfg.dit.flow_shift)
    i = task.step_index
    return float(s[i]), float(s[i + 1]) if i + 1 < steps else 0.0


def _solo(cfg, req):
    """One degree-1 step: (pipeline's latent, the direct one, exact?)."""
    pipe, comm, graph, lay, steps = _prepared(cfg, req, (0,))
    x, got = _run(pipe, comm, graph, lay, steps[0])
    s_now, s_next = _sigmas(cfg, steps[0], req.steps)
    embeds = graph.artifacts[steps[0].inputs[0]].data[0]
    rows = ["embeds"] + (["embeds_uncond"] if req.cfg_branches == 2 else [])
    guidance = None
    if cfg.dit.guidance_embeds:
        guidance = jnp.array([req.guidance] * len(rows), jnp.float32)
    t = schedule.timestep_of_sigma(s_now)
    v = dit.forward_sp_tokens(
        pipe.dit_params, jnp.stack([jnp.asarray(x)] * len(rows)),
        jnp.array([t] * len(rows), jnp.float32),
        jnp.stack([jnp.asarray(embeds[r]) for r in rows]), cfg,
        pos_offset=0, n_total=N, kv_gather=_local, guidance=guidance,
        grids=(GRID,) * len(rows))
    vel = v[0]
    if req.cfg_branches == 2:
        vel = v[1] + float(req.guidance) * (v[0] - v[1])
    want = schedule.flow_step(jnp.asarray(x), vel, s_now, s_next)
    return [(got, np.asarray(want), True)]


def _refresh_then_hit(cfg, req):
    """A §11 refresh then a hit at degree 2: each step's latent against
    the model run at degree 1 (the refresh, recording each layer's K/V)
    and per shard on the recorded K/V with the shard's fresh rows spliced
    in (the hit)."""
    pipe, comm, graph, lay, steps = _prepared(cfg, req, (0, 1))
    art = cache_artifact(graph).id
    for task, mode in zip(steps, ("refresh", "hit")):
        task.meta["cache"] = {"mode": mode, "migrate": False, "art": art}
    embeds = jnp.asarray(graph.artifacts[steps[0].inputs[0]]
                         .data[0]["embeds"])[None]
    out, stale = [], {}

    def record(k, v, layer):
        stale[layer] = (np.asarray(k), np.asarray(v))
        return k, v

    half = N // 2
    for task in steps[:2]:
        x, got = _run(pipe, comm, graph, lay, task)
        s_now, s_next = _sigmas(cfg, task, req.steps)
        t = jnp.array([schedule.timestep_of_sigma(s_now)], jnp.float32)
        if task.meta["cache"]["mode"] == "refresh":
            v = dit.forward_sp_tokens(
                pipe.dit_params, jnp.asarray(x)[None], t, embeds, cfg,
                pos_offset=0, n_total=N, kv_gather=record)[0]
        else:
            parts = []
            for off in (0, half):
                def spliced(k, v, layer, off=off):
                    K, V = (a.copy() for a in stale[layer])
                    K[:, off:off + half] = np.asarray(k)
                    V[:, off:off + half] = np.asarray(v)
                    return jnp.asarray(K), jnp.asarray(V)
                parts.append(dit.forward_sp_tokens(
                    pipe.dit_params, jnp.asarray(x[off:off + half])[None],
                    t, embeds, cfg, pos_offset=off, n_total=N,
                    kv_gather=spliced)[0])
            v = jnp.concatenate(parts)
        want = schedule.flow_step(jnp.asarray(x), v, s_now, s_next)
        # degree 2 against degree 1: other matmul shapes, so not bitwise
        out.append((got, np.asarray(want), False))
    return out


def _image(guidance=None):
    return Request(id="step", model="dit-image", height=128, width=128,
                   frames=1, steps=3, arrival=0.0, guidance=guidance)


CASES = {
    "guided-cfg1": (_solo, IMAGE, _image(guidance=4.5)),
    "unguided-solo": (_solo, IMAGE, _image()),
    "flux-embedded-guidance": (
        _solo, FLUX, Request(id="step", model="flux1-dev", height=128,
                             width=128, frames=1, steps=3, arrival=0.0,
                             guidance=3.5)),
    "cache-refresh-hit-jnp": (_refresh_then_hit, IMAGE, _image()),
    "cache-refresh-hit-pallas": (_refresh_then_hit,
                                 IMAGE.with_(use_pallas=True), _image()),
}


@pytest.mark.parametrize("case", list(CASES))
def test_step_matches_the_model_called_directly(case):
    run, cfg, req = CASES[case]
    for got, want, exact in run(cfg, req):
        assert np.abs(want).max() > 0.01
        if exact:
            np.testing.assert_array_equal(got, want)
        else:
            np.testing.assert_allclose(got, want, **TOL)


def test_jnp_splice_equals_materializing_it_first():
    """A ``SplicedKV`` on the jnp path (the in-program update in
    ``_post_spliced``) against the splice written into host copies of
    the stale K/V and the plain ``post``."""
    pipe = DiTPipeline(SPLICE, seed=0)
    liven(pipe)
    rng = np.random.default_rng(0)
    patch = SPLICE.dit.patch_size ** 2 * SPLICE.dit.in_channels
    tok = jnp.asarray(rng.standard_normal((1, N, patch), np.float32))
    t = jnp.array([500.0], jnp.float32)
    txt = jnp.asarray(rng.standard_normal((1, 77, SPLICE.dit.cond_dim),
                                          np.float32))
    stale = {}

    def record(k, v, layer):
        stale[layer] = (np.asarray(k), np.asarray(v))
        return k, v
    dit.forward_sp_tokens(pipe.dit_params, tok, t, txt, SPLICE,
                          pos_offset=0, n_total=N, kv_gather=record)

    off = N // 2
    shard = tok[:, off:] * 0.5         # this step's tokens differ

    def spliced(k, v, layer):
        return ops.SplicedKV(jnp.asarray(stale[layer][0]),
                             jnp.asarray(stale[layer][1]), k, v, off)

    def materialized(k, v, layer):
        K, V = (a.copy() for a in stale[layer])
        K[:, off:] = np.asarray(k)
        V[:, off:] = np.asarray(v)
        return jnp.asarray(K), jnp.asarray(V)

    got, want = (np.asarray(dit.forward_sp_tokens(
        pipe.dit_params, shard, t, txt, SPLICE, pos_offset=off, n_total=N,
        kv_gather=g)) for g in (spliced, materialized))
    assert np.abs(want).max() > 0.01
    np.testing.assert_array_equal(got, want)
