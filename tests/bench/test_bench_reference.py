"""The plain reference against the program at a CPU size: the weights it
and the configuration's architecture derive from the seed are the
program's, and the architecture's velocity and the text encoder agree
with the served path's."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import FIXTURE
from gfbench import reference as R
from gfbench import serve, spec

SEED = 3000000007
ARCH = spec.arch("adaln-cross-swiglu")


@pytest.fixture(scope="module")
def conf():
    with open(FIXTURE / "bench" / "configs" / "tiny.json") as f:
        return json.load(f)


@pytest.fixture(scope="module")
def program(conf):
    from repro.diffusion.pipeline import DiTPipeline
    from repro.serving.cache_demo import liven
    cfg = serve.program_config(conf).with_(use_pallas=False)
    model_seed, liven_seed = R.weight_seeds(SEED)
    pipe = DiTPipeline(cfg, seed=model_seed)
    liven(pipe, seed=liven_seed, scale=conf["liven_scale"])
    return cfg, pipe


def _leaves(tree):
    return {jax.tree_util.keystr(k): v for k, v in
            jax.tree_util.tree_flatten_with_path(tree)[0]}


def test_bench_reference_weights_are_the_programs(conf, program):
    _, pipe = program
    dit, txt = R.make_weights(conf, SEED, ARCH)
    got, want = _leaves(dit), _leaves(pipe.dit_params)
    assert set(got) == set(want)
    prog_txt = dict(pipe.txt_params)
    prog_txt = {"tok": prog_txt["embed"]["tok"], "blocks": {
        k: prog_txt["blocks"][k] for k in ("attn", "mlp")}}
    for mine, theirs in ((dit, pipe.dit_params), (txt, prog_txt)):
        a, b = _leaves(mine), _leaves(theirs)
        assert set(a) == set(b)
        for k in a:
            # the same draws; fusing the scale into the draw under jit
            # may move a value by one rounding
            np.testing.assert_allclose(np.asarray(a[k]), np.asarray(b[k]),
                                       rtol=1e-6, atol=1e-9, err_msg=k)


def test_bench_reference_forward_matches_program(conf, program):
    from repro.models import dit, text_encoder
    cfg, pipe = program
    dw, tw = R.make_weights(conf, SEED, ARCH)
    te = conf["text_encoder"]
    toks = R.prompt_tokens("s1-r0001", te)
    emb = text_encoder.encode(pipe.txt_params, toks, pipe.txt_cfg,
                              dtype=jnp.float32)
    emb_ref = R.encode(tw, toks, te["rope_theta"], te["norm_eps"])
    assert R.rel_l2(emb, emb_ref) < 1e-5
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 64, 64))
    t = jnp.array([700.0, 700.0])
    txt = jnp.concatenate([emb, emb * 0.5])
    got = dit.forward_sp_tokens(pipe.dit_params, x, t, txt, cfg,
                                pos_offset=0, n_total=64,
                                kv_gather=lambda k, v, i: (k, v))
    assert R.rel_l2(got, ARCH.velocity(dw, x, t, txt, None)) < 1e-5


def test_bench_reference_inputs_match_program():
    n_tok, pd, sigma0 = 64, 64, float(R.flow_sigmas(4, 3.0)[0])
    from repro.diffusion import schedule
    np.testing.assert_allclose(R.flow_sigmas(50, 3.0),
                               schedule.flow_sigmas(50), rtol=0)
    lat = R.initial_latent("s9-b000", n_tok, pd, sigma0)
    assert lat.shape == (n_tok, pd) and np.isfinite(lat).all()
    assert np.std(lat) == pytest.approx(sigma0, rel=0.1)
