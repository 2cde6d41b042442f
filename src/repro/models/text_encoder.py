"""Lightweight text conditioning encoder for the DiT pipeline.

The paper treats the text encoder as a lightweight, effectively single-rank
stage (Fig. 3a).  We build a real (small) bidirectional transformer rather
than stubbing it — it is the "encode" trajectory task.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L


def encoder_config(cond_dim: int, vocab: int = 32000) -> ModelConfig:
    return ModelConfig(
        name="text-encoder", family="dense", num_layers=4,
        d_model=cond_dim, num_heads=8, num_kv_heads=8,
        head_dim=cond_dim // 8, d_ff=cond_dim * 4, vocab_size=vocab)


def init(key, cfg: ModelConfig):
    ks = jax.random.split(key, 2)
    blocks = [
        {
            "ln_attn": L.rmsnorm_init(cfg.d_model),
            "attn": L.attention_init(jax.random.fold_in(ks[1], 2 * i), cfg),
            "ln_mlp": L.rmsnorm_init(cfg.d_model),
            "mlp": L.swiglu_init(jax.random.fold_in(ks[1], 2 * i + 1),
                                 cfg.d_model, cfg.d_ff),
        }
        for i in range(cfg.num_layers)
    ]
    return {
        "embed": L.embedding_init(ks[0], cfg),
        "blocks": L.stack_layer_params(blocks),
        "ln_final": L.rmsnorm_init(cfg.d_model),
    }


@functools.partial(jax.jit, static_argnames=("cfg", "dtype"))
def encode(params, tokens, cfg: ModelConfig, dtype=jnp.bfloat16):
    """tokens: (B, Lt) -> embeddings (B, Lt, cond_dim).  Jitted: the
    layer scan's body is a fresh closure per call, so run eagerly it
    would compile again for every request."""
    x = L.embed(params["embed"], tokens, cfg, dtype)
    positions = jnp.arange(x.shape[1])[None, :]

    def body(h, p_l):
        a = L.rmsnorm(p_l["ln_attn"], h, cfg.norm_eps)
        a, _ = L.attention_apply(p_l["attn"], a, cfg, causal=False,
                                 positions=positions)
        h = h + a
        m = L.rmsnorm(p_l["ln_mlp"], h, cfg.norm_eps)
        return h + L.swiglu_apply(p_l["mlp"], m), None

    x, _ = jax.lax.scan(body, x, params["blocks"])
    return L.rmsnorm(params["ln_final"], x, cfg.norm_eps)
