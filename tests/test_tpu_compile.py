"""Ahead-of-time compiles of the served path's Pallas kernels for a TPU
v5e, at dit-image's served shapes (fp32, 24 heads x 64 padded to 128
lanes by ``ops._pad_qkv``, d_model 1536), with no chip attached.

Interpret mode hides what the chip's compiler refuses (VMEM overflow,
block shapes the TPU lowering rejects, in-kernel gathers); these compile
with the TPU compiler that ships with JAX.  The topology is described in
a module fixture, never at import, so every xdist worker collects the
same tests and only the worker running this file loads the TPU library.
"""
import re

import jax
import jax.numpy as jnp
import pytest

from repro.compile_cache import CHECKOUT, compile_cache_dir
from repro.kernels import ops

HEADS, HEAD_DIM, D_MODEL = 24, 64, 1536


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:   # noqa: BLE001 - no TPU compiler here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip cannot be read back from the
        # persistent cache here, so keep it out of the cache
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        yield SingleDeviceSharding(topo.devices[0])
        jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture
def compiled_kernels(monkeypatch):
    """Steer the wrappers onto the compiled (non-interpret) kernels, as
    on a TPU backend."""
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)


def _compile(fn, sharding, kernel, *shapes):
    """Compile ``fn`` for the described chip; the Pallas kernel must be
    in the program as a custom call named ``kernel`` (``kernel.N`` is
    the op name a profiler trace gives it, which the benchmark's
    roofline readers match)."""
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=sharding)
            for s in shapes]
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    calls = re.findall(r"%([\w.-]+) = \S+ custom-call\(.*"
                       r"custom_call_target=\"tpu_custom_call\"", hlo)
    assert calls and all(re.fullmatch(rf"{kernel}\.\d+", c)
                         for c in calls), calls


@pytest.mark.parametrize("n_q,n_kv", [
    (4096, 4096),       # 1024 px, SP degree 1
    (9216, 9216),       # 1536 px, SP degree 1
    (1024, 4096),       # 1024 px query shard at SP degree 4
])
def test_flash_attention_compiles(one_chip, compiled_kernels, n_q, n_kv):
    _compile(lambda q, k, v: ops.attention(q, k, v, use_pallas=True),
             one_chip, "flash_attention", (1, n_q, HEADS, HEAD_DIM),
             (1, n_kv, HEADS, HEAD_DIM), (1, n_kv, HEADS, HEAD_DIM))


@pytest.mark.parametrize("b,n,h,d", [
    (2, 4096, 16, 72),      # image-batch-1c: guided PixArt, d padded to 128
    (1, 11440, 24, 128),    # video-batch-1c: padded to 11,520 rows
    (1, 4608, 24, 128),     # flux-batch-1c: 4,096 image + 512 text rows
], ids=["image", "video", "flux"])
def test_flash_attention_compiles_at_cell_tiles(one_chip, compiled_kernels,
                                                b, n, h, d):
    """Self-attention at each benchmark cell's shape, with the tiles
    ``flash_tiles`` picks there: a tile past the VMEM limit fails here."""
    _compile(lambda q, k, v: ops.attention(q, k, v, use_pallas=True),
             one_chip, "flash_attention", *[(b, n, h, d)] * 3)


@pytest.mark.parametrize("batch", [1, 2])    # B=2: batched CFG and packs
def test_adaln_full_fusion_compiles(one_chip, compiled_kernels, batch):
    n = 4096
    _compile(lambda x, sh, sc, g, r: ops.fused_adaln(x, sh, sc, g, r,
                                                     use_pallas=True),
             one_chip, "adaln_modulate", (batch, n, D_MODEL), (batch, D_MODEL),
             (batch, D_MODEL), (batch, D_MODEL), (batch, n, D_MODEL))


@pytest.mark.parametrize("degree", [2, 4])
def test_splice_attention_compiles(one_chip, compiled_kernels, degree):
    n = 4096
    local = n // degree
    kv = (1, n, HEADS, HEAD_DIM)
    fresh = (1, local, HEADS, HEAD_DIM)
    _compile(lambda q, ks, vs, kf, vf: ops.splice_attention(
        q, ks, vs, kf, vf, offset=local, use_pallas=True),
        one_chip, "flash_attention", fresh, kv, kv, fresh, fresh)


def test_compile_cache_dir_from_environment():
    """The cache goes where JAX_COMPILATION_CACHE_DIR says; unset, to a
    fixed directory in the checkout."""
    assert compile_cache_dir({"JAX_COMPILATION_CACHE_DIR": "/x"}) is None
    assert compile_cache_dir({}) == str(CHECKOUT / ".jax_cache")
    assert compile_cache_dir({}) == compile_cache_dir({})


# the served step's layer programs (models/dit.py) at the benchmark's
# widths: PixArt-Sigma XL/2 at 1024 px (B=2 for batched CFG, 4,096
# tokens, 16 heads of 72) and Wan2.2 TI2V-5B's widths at 720p 49 frames
# (B=1, 11,440 tokens, 24 heads of 128), float32 as served
HBM_BYTES = 16e9
TEXT_LEN = 77


def _served_config(which):
    from dataclasses import replace
    from repro.configs.dit_models import DIT_IMAGE, DIT_VIDEO
    if which == "pixart":
        cfg = DIT_IMAGE.with_(d_model=1152, num_heads=16, num_kv_heads=16,
                              head_dim=72, d_ff=4608,
                              dit=replace(DIT_IMAGE.dit, in_channels=4,
                                          cond_dim=4096))
        return cfg.with_(use_pallas=True), 2, 4096
    cfg = DIT_VIDEO.with_(num_layers=4, d_ff=14336,
                          dit=replace(DIT_VIDEO.dit, in_channels=48,
                                      cond_dim=4096))
    return cfg.with_(use_pallas=True), 1, 11440


def _custom_calls(hlo):
    return set(re.findall(r"%([A-Za-z_]+)\.\d+ = \S+ custom-call\(.*"
                          r"custom_call_target=\"tpu_custom_call\"", hlo))


def _adaln_tensors_in_vmem(hlo, tensor):
    """The adaLN calls' (B, N, D) operands and results that XLA placed
    in VMEM (memory space ``S(1)``) rather than HBM."""
    shape_of = dict(re.findall(r"^\s*(?:ROOT )?%(\S+) = (\S+) ", hlo, re.M))
    calls = re.findall(r"%(adaln_modulate\.\d+) = \S+ custom-call\(([^)]*)\)",
                       hlo)
    names = [c for c, _ in calls] + [a.strip().lstrip("%") for _, args in
                                     calls for a in args.split(",")]
    return [n for n in names if shape_of[n].startswith(tensor)
            and "S(1)" in shape_of[n]]


@pytest.mark.parametrize("which", ["pixart", "wan"])
def test_layer_programs_compile_at_served_width(one_chip, compiled_kernels,
                                                which):
    from repro.models import dit
    from repro.models.layers import split_params
    cfg, b, n = _served_config(which)
    params = jax.eval_shape(
        lambda: split_params(dit.init(jax.random.PRNGKey(0), cfg))[0])
    weights = sum(a.size * a.dtype.itemsize
                  for a in jax.tree.leaves(params))

    def on_chip(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    blocks = jax.tree.map(lambda a: on_chip(a.shape, a.dtype),
                          params["blocks"])
    layer = on_chip((), jnp.int32)
    d, heads, hd = cfg.d_model, cfg.num_heads, cfg.head_dim
    x, c = on_chip((b, n, d)), on_chip((b, d))
    mods, txt = on_chip((b, 6 * d)), on_chip((b, TEXT_LEN, d))
    qkv = on_chip((b, n, heads, hd))
    pre = dit._pre.lower(blocks, layer, x, c, cfg=cfg).compile()
    post = dit._post.lower(blocks, layer, x, mods, txt, qkv, qkv, qkv,
                           cfg=cfg).compile()
    assert _custom_calls(pre.as_text()) == {"adaln_modulate"}
    assert _custom_calls(post.as_text()) == {"adaln_modulate",
                                             "flash_attention"}
    tensor = f"f32[{b},{n},{d}]"
    for prog in (pre, post):
        # adaLN streams its tensors through HBM, the pass its roofline
        # counts, inside a program as on its own
        assert not _adaln_tensors_in_vmem(prog.as_text(), tensor)
        m = prog.memory_analysis()
        # the program's arguments count the stacked weights it reads a
        # second time, beside all the weights the device holds
        assert weights + m.argument_size_in_bytes + m.output_size_in_bytes \
            + m.temp_size_in_bytes < HBM_BYTES


# FLUX.1-dev's programs (models/flux.py) at the benchmarked widths and
# depth (4 double + 8 single blocks of 3072, 24 heads of 128, MLP 12288)
# at 1024 px: 4,096 image and 512 text tokens, B=1.  The trace names
# their modules jit__<program>, which the block readers match.
FLUX_PROGRAMS = {
    "_flux_head": {"adaln_modulate": False, "flash_attention": False},
    "_double_pre": {"adaln_modulate": True, "flash_attention": False},
    "_double_post": {"adaln_modulate": True, "flash_attention": True},
    "_single_pre": {"adaln_modulate": True, "flash_attention": False},
    "_single_post": {"adaln_modulate": True, "flash_attention": True},
    "_flux_tail": {"adaln_modulate": True, "flash_attention": False},
}


@pytest.fixture(scope="module")
def flux_served():
    from dataclasses import replace
    from repro.configs.dit_models import FLUX1_DEV
    from repro.models import flux
    from repro.models.layers import split_params
    cfg = FLUX1_DEV.with_(num_layers=4, use_pallas=True,
                          dit=replace(FLUX1_DEV.dit, num_single_layers=8))
    params = jax.eval_shape(
        lambda: split_params(flux.init(jax.random.PRNGKey(0), cfg))[0])
    return cfg, params


@pytest.mark.parametrize("program", sorted(FLUX_PROGRAMS))
def test_flux_programs_compile_at_served_width(one_chip, compiled_kernels,
                                               flux_served, program):
    from repro.models import flux
    cfg, params = flux_served
    weights = sum(a.size * a.dtype.itemsize
                  for a in jax.tree.leaves(params))

    def on_chip(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    p = jax.tree.map(lambda a: on_chip(a.shape, a.dtype), params)
    b, n, lt, d = 1, 4096, 512, cfg.d_model
    s, heads, hd = n + lt, cfg.num_heads, cfg.head_dim
    i, x, vec = on_chip((), jnp.int32), on_chip((b, s, d)), on_chip((b, d))
    cos, q = on_chip((b, s, hd // 2)), on_chip((b, s, heads, hd))
    k_txt, k_img = on_chip((b, lt, heads, hd)), on_chip((b, n, heads, hd))
    m6, m3 = on_chip((b, 6 * d)), on_chip((b, 3 * d))
    args = {
        "_flux_head": (p, on_chip((b, n, 64)), on_chip((b,)), on_chip((b,)),
                       on_chip((b, lt, cfg.dit.cond_dim)), i),
        "_double_pre": (p["double"], i, x, vec, cos, cos),
        "_double_post": (p["double"], i, x, m6, m6, q, k_txt, k_txt, k_img,
                         k_img),
        "_single_pre": (p["single"], i, x, vec, cos, cos),
        "_single_post": (p["single"], i, x, m3, q, on_chip((b, s, cfg.d_ff)),
                         k_txt, k_txt, k_img, k_img),
        "_flux_tail": (p, x, vec),
    }[program]
    static = {"grids": ((1, 64, 64),)} if program == "_flux_head" else {}
    compiled = getattr(flux, program).lower(*args, cfg=cfg,
                                            **static).compile()
    hlo = compiled.as_text()
    assert hlo.startswith(f"HloModule jit_{program},")
    assert _custom_calls(hlo) == {k for k, on in
                                  FLUX_PROGRAMS[program].items() if on}
    m = compiled.memory_analysis()
    assert weights + m.output_size_in_bytes + m.temp_size_in_bytes \
        < HBM_BYTES
