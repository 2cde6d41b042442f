"""Latent Diffusion Transformer (DiT, arXiv:2212.09748) — the paper's model.

adaLN-Zero blocks with self-attention over latent tokens + cross-attention
to text conditioning (PixArt-style), supporting image (F=1) and video
(F>1) latents.  The fused modulate op has a Pallas kernel in
``kernels/adaln.py``; ``dit_parts`` holds its jnp path / oracle.

Token layout: latents (B, F, H, W, C) -> patchify p x p spatial ->
(B, F*(H/p)*(W/p), p*p*C) -> linear embed -> N tokens.
"""
from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.kernels import ops
from repro.models import flux
from repro.models import layers as L
from repro.models.dit_parts import (Family, built, gated_residual, layer_of,
                                    mod_norm, timestep_embedding)
from repro.models.dit_parts import builds  # noqa: F401  (dit.builds())
from repro.models.layers import ParamSpec, pspec, pzeros, pones
from repro.sharding.ctx import constrain


# ---------------------------------------------------------------------------
# Embeddings
# ---------------------------------------------------------------------------

def pos_embedding(n_tokens: int, dim: int):
    """1D sincos position embedding over flattened latent tokens."""
    pos = jnp.arange(n_tokens, dtype=jnp.float32)
    half = dim // 2
    freqs = jnp.exp(-np.log(10000.0) * jnp.arange(half, dtype=jnp.float32)
                    / half)
    args = pos[:, None] * freqs[None]
    return jnp.concatenate([jnp.cos(args), jnp.sin(args)], axis=-1)


# ---------------------------------------------------------------------------
# DiT block
# ---------------------------------------------------------------------------

def dit_block_init(key, cfg: ModelConfig):
    d = cfg.d_model
    ks = jax.random.split(key, 4)
    return {
        "attn": L.attention_init(ks[0], cfg),
        "cross": L.attention_init(ks[1], cfg),
        "mlp": L.swiglu_init(ks[2], d, cfg.d_ff),
        # adaLN-Zero: 6*d modulation from conditioning; zero-init output
        "ada_w": pzeros((d, 6 * d), ("embed", "mlp")),
        "ada_b": pzeros((6 * d,), (None,)),
    }


def dit_block_apply(p, x, c, txt, cfg: ModelConfig, *, sp_axis=None):
    """x: (B, N, D) latent tokens; c: (B, D) adaLN cond; txt: (B, Lt, D)."""
    up = ops.use_pallas_enabled(cfg.use_pallas)
    mods = jnp.einsum("bd,dk->bk", jax.nn.silu(c),
                      p["ada_w"].astype(x.dtype)) + p["ada_b"].astype(x.dtype)
    sh_a, sc_a, g_a, sh_m, sc_m, g_m = jnp.split(mods, 6, axis=-1)

    h = mod_norm(x, sh_a, sc_a, up=up)
    attn, _ = L.attention_apply(p["attn"], h, cfg, causal=False,
                                use_rope=False)
    x = gated_residual(x, g_a, attn, up=up)

    # cross-attention to text conditioning (not modulated, PixArt-style)
    h = mod_norm(x, up=up)
    ca, _ = L.attention_apply(p["cross"], h, cfg, causal=False, kv_x=txt,
                              use_rope=False)
    x = x + ca

    h = mod_norm(x, sh_m, sc_m, up=up)
    x = gated_residual(x, g_m, L.swiglu_apply(p["mlp"], h), up=up)
    return x


# ---------------------------------------------------------------------------
# Full DiT
# ---------------------------------------------------------------------------

def _init(key, cfg: ModelConfig):
    dc = cfg.dit
    d = cfg.d_model
    patch_in = dc.patch_size * dc.patch_size * dc.in_channels
    ks = jax.random.split(key, 8)
    # one vmapped init builds the stacked layers in place: the same
    # values as initializing layer by layer and stacking, at half the
    # peak memory (the per-layer copies never coexist with the stack)
    blocks = jax.vmap(
        lambda i: dit_block_init(jax.random.fold_in(ks[0], i), cfg))(
        jnp.arange(cfg.num_layers))
    blocks = jax.tree.map(lambda p: ParamSpec(p.value, ("layers",) + p.axes),
                          blocks, is_leaf=L.is_param_spec)
    return {
        "x_embed": pspec(ks[1], (patch_in, d), (None, "embed")),
        "t_mlp1": pspec(ks[2], (256, d), (None, "embed")),
        "t_mlp2": pspec(ks[3], (d, d), ("embed", "embed")),
        "txt_proj": pspec(ks[4], (dc.cond_dim, d), (None, "embed")),
        "blocks": blocks,
        "final_ada_w": pzeros((d, 2 * d), ("embed", "mlp")),
        "final_ada_b": pzeros((2 * d,), (None,)),
        "final_out": pzeros((d, patch_in), ("embed", None)),
    }


def patchify(latents, patch: int):
    """(B, F, H, W, C) -> (B, F*(H/p)*(W/p), p*p*C)."""
    b, f, h, w, c = latents.shape
    x = latents.reshape(b, f, h // patch, patch, w // patch, patch, c)
    x = x.transpose(0, 1, 2, 4, 3, 5, 6)
    return x.reshape(b, f * (h // patch) * (w // patch),
                     patch * patch * c)


def unpatchify(tokens, shape, patch: int):
    b, f, h, w, c = shape
    x = tokens.reshape(b, f, h // patch, w // patch, patch, patch, c)
    x = x.transpose(0, 1, 2, 4, 3, 5, 6)
    return x.reshape(b, f, h, w, c)


def forward(params, latents, t, txt_embeds, cfg: ModelConfig, *,
            dtype=jnp.bfloat16, remat: str = "none"):
    """Denoiser forward: predicts velocity/noise for latent input.

    latents: (B, F, H, W, C); t: (B,) timesteps; txt_embeds: (B, Lt, cond_dim)
    """
    dc = cfg.dit
    shape = latents.shape
    x = patchify(latents, dc.patch_size).astype(dtype)
    x = jnp.einsum("bnp,pd->bnd", x, params["x_embed"].astype(dtype))
    x = x + pos_embedding(x.shape[1], cfg.d_model).astype(dtype)[None]

    t_emb = timestep_embedding(t, 256)
    c = jnp.einsum("bk,kd->bd", t_emb, params["t_mlp1"].astype(dtype))
    c = jnp.einsum("bd,de->be", jax.nn.silu(c),
                   params["t_mlp2"].astype(dtype))
    txt = jnp.einsum("blk,kd->bld", txt_embeds.astype(dtype),
                     params["txt_proj"].astype(dtype))
    # t_emb is fp32; keep the conditioning in compute dtype so the scan
    # carry dtype is stable under bf16 training
    c = (c + txt.mean(axis=1)).astype(dtype)

    def body(h, p_l):
        h = constrain(h, "act_batch", "act_seq", None)
        return dit_block_apply(p_l, h, c, txt, cfg), None
    fn = jax.checkpoint(body) if remat == "full" else body
    x, _ = jax.lax.scan(fn, x, params["blocks"],
                        unroll=True if cfg.scan_unroll else 1)

    mods = jnp.einsum("bd,dk->bk", jax.nn.silu(c),
                      params["final_ada_w"].astype(dtype)) \
        + params["final_ada_b"].astype(dtype)
    sh, sc = jnp.split(mods, 2, axis=-1)
    x = mod_norm(x, sh, sc, up=ops.use_pallas_enabled(cfg.use_pallas))
    x = jnp.einsum("bnd,dp->bnp", x, params["final_out"].astype(dtype))
    return unpatchify(x.astype(jnp.float32), shape, dc.patch_size)


def latent_shape(cfg: ModelConfig, height: int, width: int,
                 frames: int = 0) -> tuple[int, int, int, int]:
    """(F, H_lat, W_lat, C) for a pixel-space request (8x VAE downsample)."""
    dc = cfg.dit
    f = frames if frames else dc.latent_frames
    # video VAE: 4x temporal downsample (Wan-style), 8x spatial
    f_lat = max(1, (f + 3) // 4) if f > 1 else 1
    return (f_lat, height // 8, width // 8, dc.in_channels)


def token_count(cfg: ModelConfig, height: int, width: int,
                frames: int = 0) -> int:
    f, h, w, c = latent_shape(cfg, height, width, frames)
    p = cfg.dit.patch_size
    return f * (h // p) * (w // p)


# ---------------------------------------------------------------------------
# Sequence-parallel forward: compiled layer programs split at the K/V gather
# ---------------------------------------------------------------------------
#
# The served step runs as a handful of compiled programs: the head
# (embedding, positions, timestep and text conditioning), then per layer
# ``pre`` (modulation, norm, q/k/v projections) -> the K/V gather on the
# host -> ``post`` (self-attention on the gathered K/V, out-projection,
# gated residual, cross-attention, MLP), then the tail (final modulated
# norm, out-projection).  Each program is built once per shape and serves
# every layer: the stacked ``blocks`` weights and the layer index are
# arguments, and the layer is sliced inside the program.  The same
# programs run at every SP degree, packed or solo, so the degrees and the
# packs stay bitwise equal (DESIGN.md §17).

@functools.partial(jax.jit, static_argnames=("cfg", "n_total"))
def _head(params, tok_shard, t, txt_embeds, pos_offset, *, cfg, n_total):
    built()
    f32 = jnp.float32
    x = jnp.einsum("bnp,pd->bnd", tok_shard.astype(f32), params["x_embed"])
    pe = pos_embedding(n_total, cfg.d_model)
    x = x + jax.lax.dynamic_slice_in_dim(pe, pos_offset, x.shape[1])[None]

    t_emb = timestep_embedding(t, 256)
    c = jnp.einsum("bk,kd->bd", t_emb, params["t_mlp1"])
    c = jnp.einsum("bd,de->be", jax.nn.silu(c), params["t_mlp2"])
    txt = jnp.einsum("blk,kd->bld", txt_embeds.astype(f32),
                     params["txt_proj"])
    return x, c + txt.mean(axis=1), txt


@functools.partial(jax.jit, static_argnames=("cfg",))
def _pre(blocks, i, x, c, *, cfg):
    """Layer ``i`` up to the gather: its modulation rows and q, k, v."""
    built()
    p = layer_of(blocks, i)
    mods = jnp.einsum("bd,dk->bk", jax.nn.silu(c), p["ada_w"]) + p["ada_b"]
    sh_a, sc_a, _, _, _, _ = jnp.split(mods, 6, axis=-1)
    h = mod_norm(x, sh_a, sc_a, up=cfg.use_pallas)
    ap = p["attn"]
    q = jnp.einsum("bsd,dhk->bshk", h, ap["wq"])
    k = jnp.einsum("bsd,dhk->bshk", h, ap["wk"])
    v = jnp.einsum("bsd,dhk->bshk", h, ap["wv"])
    return mods, q, k, v


def _post_rest(p, x, mods, txt, attn, cfg: ModelConfig):
    """Layer ``p`` from its self-attention output on."""
    up = cfg.use_pallas
    _, _, g_a, sh_m, sc_m, g_m = jnp.split(mods, 6, axis=-1)
    attn = jnp.einsum("bshk,hkd->bsd", attn, p["attn"]["wo"])
    x = gated_residual(x, g_a, attn, up=up)

    h = mod_norm(x, up=up)
    ca, _ = L.attention_apply(p["cross"], h, cfg, causal=False, kv_x=txt,
                              use_rope=False)
    x = x + ca

    h = mod_norm(x, sh_m, sc_m, up=up)
    return gated_residual(x, g_m, L.swiglu_apply(p["mlp"], h), up=up)


@functools.partial(jax.jit, static_argnames=("cfg",))
def _post(blocks, i, x, mods, txt, q, k, v, *, cfg):
    """Layer ``i`` from the gather on: sharded queries over full K/V."""
    built()
    if cfg.use_pallas:
        attn = ops.attention(q, k, v, causal=False, use_pallas=True)
    else:
        attn = L.sdpa(q, k, v, causal=False)
    return _post_rest(layer_of(blocks, i), x, mods, txt, attn, cfg)


@functools.partial(jax.jit, static_argnames=("cfg", "offset"))
def _post_spliced(blocks, i, x, mods, txt, q, k_stale, v_stale, k_fresh,
                  v_fresh, *, cfg, offset):
    """``_post`` on a §11 cache hit: this rank's fresh K/V at ``offset``
    in the stale snapshot's stream (static: it lays out the kernel's
    blocks).  The splice kernel patches the stream tile by tile; the jnp
    path writes the fresh rows in and attends as ``_post`` does, so its
    hit steps equal materializing the splice first, bit for bit."""
    built()
    if cfg.use_pallas:
        attn = ops.splice_attention(q, k_stale, v_stale, k_fresh, v_fresh,
                                    offset=offset, use_pallas=True)
    else:
        k = jax.lax.dynamic_update_slice_in_dim(k_stale, k_fresh, offset,
                                                axis=1)
        v = jax.lax.dynamic_update_slice_in_dim(v_stale, v_fresh, offset,
                                                axis=1)
        attn = L.sdpa(q, k, v, causal=False)
    return _post_rest(layer_of(blocks, i), x, mods, txt, attn, cfg)


def _adaln_layer(blocks, i, x, ctx, kv_gather, layer, cfg):
    """adaLN block ``i``: pre, the K/V gather, post (or, on a §11 hit
    the gather hands over as a ``SplicedKV``, the spliced post)."""
    c, txt = ctx
    mods, q, k, v = _pre(blocks, i, x, c, cfg=cfg)
    kv = kv_gather(k, v, layer)                 # GFC all-gather (axis=1)
    if isinstance(kv, ops.SplicedKV):           # §11 hit
        return _post_spliced(blocks, i, x, mods, txt, q, kv.k_stale,
                             kv.v_stale, kv.k_fresh, kv.v_fresh, cfg=cfg,
                             offset=kv.offset)
    return _post(blocks, i, x, mods, txt, q, *kv, cfg=cfg)


@functools.partial(jax.jit, static_argnames=("cfg",))
def _tail(params, x, c, *, cfg):
    built()
    mods = jnp.einsum("bd,dk->bk", jax.nn.silu(c), params["final_ada_w"]) \
        + params["final_ada_b"]
    sh, sc = jnp.split(mods, 2, axis=-1)
    x = mod_norm(x, sh, sc, up=cfg.use_pallas)
    return jnp.einsum("bnd,dp->bnp", x, params["final_out"])


def _adaln_head(params, tok_shard, t, txt_embeds, cfg, *, pos_offset,
                n_total, guidance, grids):
    x, c, txt = _head(params, tok_shard, t, txt_embeds, pos_offset,
                      cfg=cfg, n_total=n_total)
    return x, (c, txt)


# the adaLN-Zero blocks of PixArt and Wan (one kind, cross-attention to
# fixed text); FLUX's double- and single-stream blocks in models/flux.py
FAMILIES = {
    "adaln": Family(
        init=_init, head=_adaln_head,
        kinds=(("adaln", "blocks", _adaln_layer),),
        counts=lambda cfg: (cfg.num_layers,),
        tail=lambda params, x, ctx, cfg: _tail(params, x, ctx[0], cfg=cfg),
        # in the order ``serving/cache_demo.liven`` draws them
        gate_leaves=(("blocks", "ada_w"), ("blocks", "ada_b"),
                     ("final_ada_w",), ("final_ada_b",), ("final_out",)),
        cache_hit=True),
    "flux": flux.FAMILY,
}


def family(cfg: ModelConfig) -> Family:
    """The config's block family (``cfg.dit.blocks``)."""
    return FAMILIES[cfg.dit.blocks]


def init(key, cfg: ModelConfig):
    return family(cfg).init(key, cfg)


def segments(cfg: ModelConfig) -> tuple:
    """The model's block kinds in order, each with its layer count."""
    fam = family(cfg)
    return tuple((name, n) for (name, _, _), n in zip(fam.kinds,
                                                      fam.counts(cfg)))


def forward_sp_tokens(params, tok_shard, t, txt_embeds, cfg: ModelConfig, *,
                      pos_offset: int, n_total: int, kv_gather,
                      guidance=None, grids=None):
    """Denoiser forward over a TOKEN SHARD under sequence parallelism,
    in float32.

    tok_shard: (B, N_local, patch_dim) — this rank's patchified tokens.
    kv_gather(k, v, layer) -> (K, V) gathers key/value over the token axis
    across the execution group (GFC all-gather in the thread runtime;
    identity at SP1).  Queries stay local, so compute is token-sharded
    while attention sees the full sequence — the paper's elastic SP
    layout.  The layer index (counted over every block kind) keys the
    cross-step feature cache (DESIGN.md §11): a cache-hit gather skips
    the collective and returns a :class:`ops.SplicedKV`, the stale K/V of
    THIS layer from the previous refresh step beside the fresh local
    shard.  The layer splices them: inside the attention kernel's K/V
    stream on the Pallas path, so the spliced tensors never materialize,
    or by an in-program update on the jnp path (DESIGN.md §12).

    Between gathers the step runs compiled: the family's head, ``pre``
    and ``post`` per layer of each of its block kinds (:data:`FAMILIES`),
    its tail; :func:`builds` counts their builds.  A FLUX model
    (DESIGN.md §18) also takes ``guidance`` (B,), the scale as an input,
    and ``grids``, each row's (frames, rows, cols) of patches, which its
    positions index; its text stream rides in the state, and only image
    K/V are gathered.

    Returns the velocity prediction for the local token shard
    (B, N_local, patch_dim).
    """
    up = ops.use_pallas_enabled(cfg.use_pallas)
    if cfg.use_pallas != up:
        cfg = cfg.with_(use_pallas=up)
    fam = family(cfg)
    x, ctx = fam.head(params, tok_shard, t, txt_embeds, cfg,
                      pos_offset=pos_offset, n_total=n_total,
                      guidance=guidance, grids=grids)
    layer = 0
    for (_, key, step), n in zip(fam.kinds, fam.counts(cfg)):
        for i in range(n):
            x = step(params[key], i, x, ctx, kv_gather, layer, cfg)
            layer += 1
    return fam.tail(params, x, ctx, cfg)
