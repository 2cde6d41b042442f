"""FLUX.1-dev's plain reference (``bench/archs/flux-double-single.py``)
against the program at a CPU size, the configuration file against the
program's config, and the two block readers on a hand-made trace."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import FIXTURE, REPO
from gfbench import check, flops, serve, spec, traffic
from gfbench import reference as R

SEED = 3000000007
ARCH = spec.arch("flux-double-single")
PEAK = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _conf(path):
    with open(path) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def conf():
    return _conf(FIXTURE / "bench" / "configs" / "tiny-flux.json")


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


def test_bench_flux_reference_weights_are_the_programs(conf, program):
    _, pipe = program
    dit, _ = R.make_weights(conf, SEED, ARCH)
    # the RoPE frequencies are a buffer: the program computes them in
    # its head from the config
    assert set(dit.pop("rope")) == {"freqs", "axis"}
    got, want = _leaves(dit), _leaves(pipe.dit_params)
    assert set(got) == set(want)
    for k in got:
        np.testing.assert_allclose(np.asarray(got[k]), np.asarray(want[k]),
                                   rtol=1e-6, atol=1e-9, err_msg=k)


@pytest.mark.parametrize("grid,guidance", [((1, 8, 8), 3.5),
                                           ((1, 4, 16), 3.5),
                                           ((1, 8, 8), None)])
def test_bench_flux_forward_matches_program(conf, program, grid, guidance):
    from repro.models import dit
    cfg, pipe = program
    dw, _ = R.make_weights(conf, SEED, ARCH)
    n = grid[1] * grid[2]
    x = jax.random.normal(jax.random.PRNGKey(1), (2, n, 64))
    t = jnp.array([700.0, 300.0])
    txt = jax.random.normal(jax.random.PRNGKey(2), (2, 16, 64))
    g = jnp.full((2,), 1.0 if guidance is None else guidance)
    with jax.default_matmul_precision("highest"):
        got = dit.forward_sp_tokens(pipe.dit_params, x, t, txt, cfg,
                                    pos_offset=0, n_total=n,
                                    kv_gather=lambda k, v, i: (k, v),
                                    guidance=g, grids=(grid,) * 2)
        want = ARCH._velocity(dw, x, t, g, txt, grid)
        if grid[1] == grid[2]:
            np.testing.assert_array_equal(
                want, ARCH.velocity(dw, x, t, txt, guidance))
    assert R.rel_l2(got, want) < 1e-5
    assert np.abs(np.asarray(want)).max() > 0.01


def test_bench_flux_guided_step_is_one_row(conf):
    ref = check.Reference(conf, SEED, ARCH)
    rid, steps, n_tok = "s1-b000", 3, 64
    sigma0 = float(R.flow_sigmas(steps, conf["flow_shift"])[0])
    x_in = R.initial_latent(rid, n_tok, 64, sigma0)
    it = {"what": "step", "req": rid, "step": 0, "steps": steps,
          "guidance": 3.5, "x_in": x_in}
    with jax.default_matmul_precision("highest"):
        v, s_now, s_next = ref.step(it, jnp.float32)
        other = ref.step(dict(it, guidance=1.5), jnp.float32)[0]
    assert v.shape == (1, n_tok, 64) and ARCH.rows(3.5) == ("cond",)
    assert not any(uncond for _, uncond, _ in ref._embeds)
    assert R.rel_l2(other, v) > 0.01        # the scale is an input
    it["x_out"] = (x_in + np.float32(s_next - s_now) * v[0]).astype(
        np.float32)
    found = check.gaps(ref, [it])
    assert found["step_gap"] < 1e-4 and found["state_gap"] < 1e-6
    with pytest.raises(ValueError, match="square"):
        ARCH.velocity(ref.dit, jnp.zeros((1, 32, 64)), jnp.zeros((1,)),
                      jnp.zeros((1, 16, 64)), 3.5)


def test_bench_flux_config_is_the_programs():
    """The benchmarked file: every width FLUX.1-dev's, the cut in depth
    only, the program's config equal to the file's model table, and the
    batch the generator makes at the v5e's peak."""
    conf = _conf(REPO / "bench" / "configs" / "flux1-dev-4d8s.json")
    m, pub = conf["model"], conf["published"]
    cfg = serve.program_config(conf)
    assert (cfg.num_layers, cfg.dit.num_single_layers) == (4, 8)
    assert conf["reduced"] == ["num_layers", "num_single_layers"]
    assert (pub["num_layers"], pub["num_single_layers"]) == (19, 38)
    assert m["d_model"] == pub["num_attention_heads"] \
        * pub["attention_head_dim"] == 3072
    assert m["d_ff"] == pub["mlp_ratio"] * m["d_model"]
    assert m["patch_size"] ** 2 * m["in_channels"] == pub["in_channels"]
    assert (m["cond_dim"], m["pooled_dim"]) == \
        (pub["joint_attention_dim"], pub["pooled_projection_dim"])
    assert list(cfg.dit.rope_axes) == conf["rope_axes"] == \
        pub["axes_dims_rope"]
    assert m["text_len"] == conf["text_encoder"]["prompt_len"] == \
        pub["max_sequence_length"] == 512
    assert m["flow_shift"] == conf["flow_shift"] == \
        pytest.approx(np.exp(1.15), abs=1e-4)
    table = spec.load()
    cell = spec.resolve(table, "flux-batch-1c")
    assert cell["arch"] is ARCH and cell["chips"] == 1
    assert {x["name"] for x in cell["per_layer"]} >= {
        "double_block_roofline", "single_block_roofline",
        "flash_roofline", "adaln_roofline", "step_mfu"}
    planned = traffic.generate(cell["mix"], m, ARCH, PEAK, 51.0, 2 ** 31 + 5,
                               512)
    assert len(planned) == 26
    assert all(p.guidance == 3.5 and (p.height, p.width) == (1024, 1024)
               for p in planned)
    assert ARCH.step_flops(m, 4096, 1, 512) == pytest.approx(15.67e12,
                                                             rel=1e-3)


# a step of one double and one single block at 256 image tokens, a text
# of 16: the ops of the double programs (flash among them), of the single
# programs, and an adaLN call in the tail
MODEL = {"num_layers": 1, "num_single_layers": 1, "d_model": 128,
         "num_heads": 2, "head_dim": 64, "d_ff": 512, "patch_size": 2,
         "in_channels": 16, "cond_dim": 64, "pooled_dim": 32,
         "guidance_embeds": True}


def _run(arch):
    tr = {"host": [["bench.traced_window", 0, 1000, {}],
                   ["bench.exec.denoise", 0, 900, {"tokens": 256,
                                                   "rows": 1}]],
          "device": [["fusion.1", "jit__double_pre", 0, 100],
                     ["adaln_modulate.1", "jit__double_pre", 100, 20],
                     ["flash_attention.1", "jit__double_post", 120, 200],
                     ["fusion.2", "jit__double_post", 320, 80],
                     ["fusion.3", "jit__single_pre", 400, 150],
                     ["flash_attention.2", "jit__single_post", 550, 200],
                     ["fusion.4", "jit__single_post", 750, 50],
                     ["adaln_modulate.2", "jit__flux_tail", 800, 30]]}
    return {"trace": {"events": tr, "span": (0, 1000)}, "model": MODEL,
            "text_len": 16, "peak": PEAK, "arch": arch,
            "root": str(REPO)}


@pytest.mark.parametrize("name,kernel,took", [
    ("double_block_roofline", "double_block", 200e-9),
    ("single_block_roofline", "single_block", 200e-9),
    ("flash_roofline", "flash_attention", 400e-9),
    ("adaln_roofline", "adaln_modulate", 50e-9)])
def test_bench_flux_readers(name, kernel, took):
    run = _run("flux-double-single")
    least = flops.least_time(*ARCH.KERNELS[kernel](MODEL, 256, 1, 16),
                             PEAK)[0]
    assert spec.reader(name)(run) == pytest.approx(100 * least / took)


def test_bench_block_readers_find_nothing_in_the_adaln_block():
    run = _run("adaln-cross-swiglu")
    assert spec.reader("double_block_roofline")(run) is None
    assert spec.reader("single_block_roofline")(run) is None


def test_bench_flux_counts_add_up():
    """The blocks' non-attention work and flash make up a step's matmul
    and attention operations, with the head and tail left over."""
    m = dict(MODEL, num_layers=4, num_single_layers=8)
    n, lt = 4096, 512
    parts = sum(ARCH.KERNELS[k](m, n, 1, lt)[0]
                for k in ("double_block", "single_block", "flash_attention"))
    step = ARCH.step_flops(m, n, 1, lt)
    # the blocks' counts add elementwise work (QK norms, RoPE, GELU,
    # adaLN) that step_flops leaves out
    assert 0.99 * step < parts < 1.1 * step
    fl, by = ARCH.KERNELS["flash_attention"](m, n, 1, lt)
    assert fl == 12 * flops.flash_flops(1, 2, n + lt, n + lt, 64)
    assert by == 12 * flops.flash_bytes(1, 2, n + lt, n + lt, 64)
