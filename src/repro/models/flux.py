"""FLUX.1 (Black Forest Labs): double-stream then single-stream blocks
over text and image tokens in one attention, as compiled layer programs.

After BFL's ``flux/model.py`` and ``flux/modules/layers.py``:

* conditioning ``vec = time_in(t) + guidance_in(g) + vector_in(pooled)``,
  each a two-layer SiLU MLP embedder; ``t`` and ``g`` through the 256-wide
  sincos embedding at 1000 x their value;
* positions: 3-axis RoPE over ids (frame, row, col) of the image tokens
  and (0, 0, 0) of the text tokens, rotating adjacent lanes
  (2i, 2i + 1) of q and k after their RMSNorm with a learned scale;
* a double-stream block: each stream (text, image) has its own 6-row
  modulation, fused q/k/v projection with bias, QK norm, output
  projection and GELU(tanh) MLP; the two meet in one attention over
  ``[text; image]``;
* a single-stream block over ``[text; image]``: one 3-row modulation,
  ``linear1`` giving q, k, v and the MLP input together, ``linear2`` on
  ``concat(attention, gelu(mlp))``, one gated residual;
* the final layer: shift and scale from ``vec``, a norm, a linear head.

The served step's state is one (B, L + N_local, D) array: the text
stream in its first ``text_len`` rows, this rank's image tokens after.
The text rows are the same on every rank, so under sequence parallelism
only the image shard's K/V are gathered, and every rank attends over
``[text K; gathered image K]``.  Each block kind runs as two programs
split at that gather (``_double_pre`` / ``_double_post``,
``_single_pre`` / ``_single_post``), built once per shape and serving
every layer of its kind, as ``dit``'s layer programs do (DESIGN.md §17,
§18).  The pooled CLIP vector is stood in for by a seeded projection
(``pool_proj``) of the mean text embedding.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.kernels import ops
from repro.models import layers as L
from repro.models.dit_parts import (Family, built, gated_residual, layer_of,
                                    mod_norm, timestep_embedding)
from repro.models.layers import pones, pspec, pzeros


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------

def _linear(key, n_in, n_out):
    """A weight (n_in, n_out) and a bias (n_out,), both drawn."""
    kw, kb = jax.random.split(key)
    return (pspec(kw, (n_in, n_out), (None, "embed")),
            pspec(kb, (n_out,), (None,)))


def _embedder_init(key, n_in, d):
    k1, k2 = jax.random.split(key)
    in_w, in_b = _linear(k1, n_in, d)
    out_w, out_b = _linear(k2, d, d)
    return {"in_w": in_w, "in_b": in_b, "out_w": out_w, "out_b": out_b}


def _stream_init(key, cfg: ModelConfig):
    """One stream of a double block (BFL's ``img_*`` or ``txt_*``)."""
    d, hd, f = cfg.d_model, cfg.head_dim, cfg.d_ff
    ks = jax.random.split(key, 4)
    qkv_w, qkv_b = _linear(ks[0], d, 3 * d)
    proj_w, proj_b = _linear(ks[1], d, d)
    w1, b1 = _linear(ks[2], d, f)
    w2, b2 = _linear(ks[3], f, d)
    return {"qkv_w": qkv_w, "qkv_b": qkv_b,
            "q_scale": pones((hd,), (None,)), "k_scale": pones((hd,), (None,)),
            "proj_w": proj_w, "proj_b": proj_b,
            "mlp_w1": w1, "mlp_b1": b1, "mlp_w2": w2, "mlp_b2": b2,
            "mod_w": pzeros((d, 6 * d), ("embed", "mlp")),
            "mod_b": pzeros((6 * d,), (None,))}


def _double_init(key, cfg: ModelConfig):
    ki, kt = jax.random.split(key)
    return {"img": _stream_init(ki, cfg), "txt": _stream_init(kt, cfg)}


def _single_init(key, cfg: ModelConfig):
    d, hd, f = cfg.d_model, cfg.head_dim, cfg.d_ff
    k1, k2 = jax.random.split(key)
    w1, b1 = _linear(k1, d, 3 * d + f)
    w2, b2 = _linear(k2, d + f, d)
    return {"linear1_w": w1, "linear1_b": b1,
            "q_scale": pones((hd,), (None,)), "k_scale": pones((hd,), (None,)),
            "linear2_w": w2, "linear2_b": b2,
            "mod_w": pzeros((d, 3 * d), ("embed", "mlp")),
            "mod_b": pzeros((3 * d,), (None,))}


def _stacked(init_one, key, n, cfg):
    """``n`` layers drawn from ``fold_in(key, i)``, stacked in place."""
    blocks = jax.vmap(lambda i: init_one(jax.random.fold_in(key, i), cfg))(
        jnp.arange(n))
    return jax.tree.map(lambda p: L.ParamSpec(p.value, ("layers",) + p.axes),
                        blocks, is_leaf=L.is_param_spec)


def init(key, cfg: ModelConfig):
    dc = cfg.dit
    d = cfg.d_model
    if sum(dc.rope_axes) != cfg.head_dim:
        raise ValueError(f"rope_axes {dc.rope_axes} do not sum to head_dim "
                         f"{cfg.head_dim}")
    patch_in = dc.patch_size * dc.patch_size * dc.in_channels
    ks = jax.random.split(key, 10)
    img_in_w, img_in_b = _linear(ks[0], patch_in, d)
    txt_in_w, txt_in_b = _linear(ks[1], dc.cond_dim, d)
    p = {"img_in_w": img_in_w, "img_in_b": img_in_b,
         "txt_in_w": txt_in_w, "txt_in_b": txt_in_b,
         "time_in": _embedder_init(ks[2], 256, d),
         "vector_in": _embedder_init(ks[3], dc.pooled_dim, d),
         "pool_proj": pspec(ks[4], (dc.cond_dim, dc.pooled_dim),
                            (None, None)),
         "double": _stacked(_double_init, ks[5], cfg.num_layers, cfg),
         "single": _stacked(_single_init, ks[6], dc.num_single_layers, cfg),
         "final_ada_w": pzeros((d, 2 * d), ("embed", "mlp")),
         "final_ada_b": pzeros((2 * d,), (None,)),
         "final_out": pzeros((d, patch_in), ("embed", None)),
         "final_out_b": pspec(ks[7], (patch_in,), (None,))}
    if dc.guidance_embeds:
        p["guidance_in"] = _embedder_init(ks[8], 256, d)
    return p


# ---------------------------------------------------------------------------
# pieces of the step
# ---------------------------------------------------------------------------

def _embed(p, x):
    """BFL's MLPEmbedder: in-linear, SiLU, out-linear."""
    return jax.nn.silu(x @ p["in_w"] + p["in_b"]) @ p["out_w"] + p["out_b"]


def _rope_freqs(cfg: ModelConfig):
    """(frequency, axis) of each rotated lane pair: axis ``a`` of width
    ``w`` gives ``w / 2`` pairs at 1 / theta^(2j / w)."""
    freqs, axis = [], []
    for a, w in enumerate(cfg.dit.rope_axes):
        freqs.append(1.0 / cfg.rope_theta ** (np.arange(0, w, 2) / w))
        axis += [a] * (w // 2)
    return (np.concatenate(freqs).astype(np.float32),
            np.asarray(axis, np.int32))


def _rope_tables(cfg: ModelConfig, grids, pos_offset, n_local):
    """cos and sin, (B, text_len + n_local, head_dim / 2), of each row's
    ``[text; image tokens pos_offset ..]``: ids (0, 0, 0) for text and
    (frame, row, col) on the row's grid = (frames, rows, cols) of
    patches for image tokens.  The rows of a pack may differ in grid
    at one token count (896 x 1152 and 1152 x 896)."""
    freqs, axis = _rope_freqs(cfg)
    idx = pos_offset + jnp.arange(n_local)
    angles = []
    for _, gh, gw in grids:
        ids = jnp.stack([idx // (gh * gw), (idx // gw) % gh, idx % gw],
                        axis=-1)
        ids = jnp.concatenate([jnp.zeros((cfg.dit.text_len, 3), ids.dtype),
                               ids])
        angles.append(ids[:, axis].astype(jnp.float32) * freqs)
    ang = jnp.stack(angles)
    return jnp.cos(ang), jnp.sin(ang)


def _rope(x, cos, sin):
    """Rotate adjacent lane pairs of x (B, S, H, hd) by the rows' angles
    (B, S, hd / 2), as BFL's ``apply_rope``."""
    x0, x1 = x[..., 0::2], x[..., 1::2]
    c, s = cos[:, :, None], sin[:, :, None]
    return jnp.stack([c * x0 - s * x1, s * x0 + c * x1],
                     axis=-1).reshape(x.shape)


def _qkv(y, p, cfg: ModelConfig, cos, sin):
    """q, k, v (B, S, H, hd) from a fused projection y (B, S, 3D) laid out
    (3, H, hd): q and k RMS-normed with their scales, then rotated."""
    b, s, _ = y.shape
    y = y.reshape(b, s, 3, cfg.num_heads, cfg.head_dim)
    q = _rope(L.rmsnorm(p["q_scale"], y[:, :, 0]), cos, sin)
    k = _rope(L.rmsnorm(p["k_scale"], y[:, :, 1]), cos, sin)
    return q, k, y[:, :, 2]


def _attend(q, k_txt, v_txt, k_img, v_img, cfg: ModelConfig):
    """This rank's rows over ``[text K; image K]``, heads merged."""
    k = jnp.concatenate([k_txt, k_img], axis=1)
    v = jnp.concatenate([v_txt, v_img], axis=1)
    if cfg.use_pallas:
        o = ops.attention(q, k, v, causal=False, use_pallas=True)
    else:
        o = L.sdpa(q, k, v, causal=False)
    return o.reshape(o.shape[0], o.shape[1], -1)


def _gelu(x):
    return jax.nn.gelu(x, approximate=True)


# ---------------------------------------------------------------------------
# the step's programs
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("cfg", "grids"))
def _flux_head(params, tok_shard, t, g, txt_embeds, pos_offset, *, cfg,
               grids):
    """State ``[txt_in(text); img_in(tokens)]``, ``vec`` and the RoPE
    tables of this rank's rows."""
    built()
    img = tok_shard @ params["img_in_w"] + params["img_in_b"]
    txt = txt_embeds @ params["txt_in_w"] + params["txt_in_b"]
    vec = _embed(params["time_in"], timestep_embedding(t, 256))
    if cfg.dit.guidance_embeds:
        vec = vec + _embed(params["guidance_in"],
                           timestep_embedding(g * 1000.0, 256))
    pooled = txt_embeds.mean(axis=1) @ params["pool_proj"]
    vec = vec + _embed(params["vector_in"], pooled)
    cos, sin = _rope_tables(cfg, grids, pos_offset, img.shape[1])
    return jnp.concatenate([txt, img], axis=1), vec, cos, sin


@functools.partial(jax.jit, static_argnames=("cfg",))
def _double_pre(blocks, i, x, vec, cos, sin, *, cfg):
    """Double block ``i`` up to the gather: each stream's modulation
    rows, modulated norm and q, k, v; the image K/V apart."""
    built()
    p = layer_of(blocks, i)
    lt = cfg.dit.text_len
    sv = jax.nn.silu(vec)
    out = []
    for name, rows in (("txt", slice(None, lt)), ("img", slice(lt, None))):
        sp = p[name]
        mods = sv @ sp["mod_w"] + sp["mod_b"]
        sh, sc = jnp.split(mods, 6, axis=-1)[:2]
        h = mod_norm(x[:, rows], sh, sc, up=cfg.use_pallas)
        out.append((mods,) + _qkv(h @ sp["qkv_w"] + sp["qkv_b"], sp, cfg,
                                  cos[:, rows], sin[:, rows]))
    (m_txt, q_txt, k_txt, v_txt), (m_img, q_img, k_img, v_img) = out
    q = jnp.concatenate([q_txt, q_img], axis=1)
    return m_txt, m_img, q, k_txt, v_txt, k_img, v_img


@functools.partial(jax.jit, static_argnames=("cfg",))
def _double_post(blocks, i, x, m_txt, m_img, q, k_txt, v_txt, k_img, v_img,
                 *, cfg):
    """Double block ``i`` from the gather on: joint attention, then each
    stream's projection, gated residual and MLP."""
    built()
    p = layer_of(blocks, i)
    lt, up = cfg.dit.text_len, cfg.use_pallas
    attn = _attend(q, k_txt, v_txt, k_img, v_img, cfg)
    out = []
    for name, rows, mods in (("txt", slice(None, lt), m_txt),
                             ("img", slice(lt, None), m_img)):
        sp = p[name]
        _, _, g1, sh2, sc2, g2 = jnp.split(mods, 6, axis=-1)
        h = gated_residual(x[:, rows], g1,
                                attn[:, rows] @ sp["proj_w"] + sp["proj_b"],
                                up=up)
        m = mod_norm(h, sh2, sc2, up=up)
        m = _gelu(m @ sp["mlp_w1"] + sp["mlp_b1"]) @ sp["mlp_w2"] \
            + sp["mlp_b2"]
        out.append(gated_residual(h, g2, m, up=up))
    return jnp.concatenate(out, axis=1)


@functools.partial(jax.jit, static_argnames=("cfg",))
def _single_pre(blocks, i, x, vec, cos, sin, *, cfg):
    """Single block ``i`` up to the gather: modulation rows, modulated
    norm, ``linear1`` split into q, k, v and the MLP input."""
    built()
    p = layer_of(blocks, i)
    lt, d3 = cfg.dit.text_len, 3 * cfg.d_model
    mods = jax.nn.silu(vec) @ p["mod_w"] + p["mod_b"]
    sh, sc, _ = jnp.split(mods, 3, axis=-1)
    h = mod_norm(x, sh, sc, up=cfg.use_pallas)
    y = h @ p["linear1_w"] + p["linear1_b"]
    q, k, v = _qkv(y[..., :d3], p, cfg, cos, sin)
    return mods, q, y[..., d3:], k[:, :lt], v[:, :lt], k[:, lt:], v[:, lt:]


@functools.partial(jax.jit, static_argnames=("cfg",))
def _single_post(blocks, i, x, mods, q, mlp, k_txt, v_txt, k_img, v_img, *,
                 cfg):
    """Single block ``i`` from the gather on: attention, ``linear2`` on
    ``concat(attention, gelu(mlp))``, the gated residual."""
    built()
    p = layer_of(blocks, i)
    attn = _attend(q, k_txt, v_txt, k_img, v_img, cfg)
    out = jnp.concatenate([attn, _gelu(mlp)], axis=-1) @ p["linear2_w"] \
        + p["linear2_b"]
    return gated_residual(x, jnp.split(mods, 3, axis=-1)[2], out,
                               up=cfg.use_pallas)


@functools.partial(jax.jit, static_argnames=("cfg",))
def _flux_tail(params, x, vec, *, cfg):
    """BFL's final layer on the image rows: shift and scale from ``vec``,
    the modulated norm, the linear head."""
    built()
    mods = jax.nn.silu(vec) @ params["final_ada_w"] + params["final_ada_b"]
    sh, sc = jnp.split(mods, 2, axis=-1)
    h = mod_norm(x[:, cfg.dit.text_len:], sh, sc, up=cfg.use_pallas)
    return h @ params["final_out"] + params["final_out_b"]


def _image_kv(kv):
    if isinstance(kv, ops.SplicedKV):
        raise NotImplementedError(
            "a §11 cache hit splices fresh K/V at the shard's offset in a "
            "stream with no text rows ahead of it; a two-stream model's "
            "keys are [text; image], and its cache-hit path is not built")
    return kv


def double_layer(blocks, i, x, ctx, kv_gather, layer, cfg):
    """Double block ``i``: pre, the image K/V gather, post."""
    vec, cos, sin = ctx
    m_txt, m_img, q, k_txt, v_txt, k_img, v_img = _double_pre(
        blocks, i, x, vec, cos, sin, cfg=cfg)
    k_img, v_img = _image_kv(kv_gather(k_img, v_img, layer))
    return _double_post(blocks, i, x, m_txt, m_img, q, k_txt, v_txt, k_img,
                        v_img, cfg=cfg)


def single_layer(blocks, i, x, ctx, kv_gather, layer, cfg):
    """Single block ``i``: pre, the image K/V gather, post."""
    vec, cos, sin = ctx
    mods, q, mlp, k_txt, v_txt, k_img, v_img = _single_pre(
        blocks, i, x, vec, cos, sin, cfg=cfg)
    k_img, v_img = _image_kv(kv_gather(k_img, v_img, layer))
    return _single_post(blocks, i, x, mods, q, mlp, k_txt, v_txt, k_img,
                        v_img, cfg=cfg)


def head(params, tok_shard, t, txt_embeds, cfg, *, pos_offset, n_total,
         guidance, grids):
    """The state and what every layer shares: (x, (vec, cos, sin))."""
    if grids is None or len(grids) != tok_shard.shape[0]:
        raise ValueError("a FLUX step needs each row's latent grid for its "
                         "positions")
    x, vec, cos, sin = _flux_head(
        params, tok_shard, t, guidance, txt_embeds, pos_offset, cfg=cfg,
        grids=tuple(tuple(g) for g in grids))
    return x, (vec, cos, sin)


FAMILY = Family(
    init=init, head=head,
    kinds=(("double", "double", double_layer),
           ("single", "single", single_layer)),
    counts=lambda cfg: (cfg.num_layers, cfg.dit.num_single_layers),
    tail=lambda params, x, ctx, cfg: _flux_tail(params, x, ctx[0], cfg=cfg),
    # in the order ``serving/cache_demo.liven`` draws them
    gate_leaves=(("double", "img", "mod_w"), ("double", "img", "mod_b"),
                 ("double", "txt", "mod_w"), ("double", "txt", "mod_b"),
                 ("single", "mod_w"), ("single", "mod_b"),
                 ("final_ada_w",), ("final_ada_b",), ("final_out",)),
    # a stale snapshot would be spliced at the shard's offset in a
    # stream with no text rows ahead of it (_image_kv)
    cache_hit=False)
