"""What every DiT block family's step shares: the table a family gives
``dit.forward_sp_tokens`` (:class:`Family`), the sincos timestep
embedding, the modulated norm and gated residual (one fused HBM pass
each on the Pallas path, ``kernels/adaln.py``), the slice of one layer
from stacked weights inside a program, and the count of program builds.

``dit`` (adaLN blocks) and ``flux`` (double- and single-stream blocks)
both build on this module, and ``dit`` looks a config's family up in
their tables (DESIGN.md §17, §18).
"""
from __future__ import annotations

import threading
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops


class Family(NamedTuple):
    """One block family, as the served step walks it.

    ``head(params, tok_shard, t, txt_embeds, cfg, *, pos_offset,
    n_total, guidance, grids)`` gives the state and what every layer
    shares, ``(x, ctx)``; ``kinds`` lists the block kinds in order, each
    ``(name, its stacked weights' key in the params, one layer's step)``
    with ``step(blocks, i, x, ctx, kv_gather, layer, cfg) -> x``;
    ``counts(cfg)`` gives each kind's layer count; ``tail(params, x,
    ctx, cfg)`` the velocity.  ``gate_leaves`` are the paths of the
    zero-initialized modulation and output leaves; ``cache_hit`` says
    whether its layers take the §11 cache-hit snapshot."""
    init: Callable
    head: Callable
    kinds: tuple
    counts: Callable
    tail: Callable
    gate_leaves: tuple
    cache_hit: bool


def timestep_embedding(t, dim: int, max_period: float = 10000.0):
    """Sinusoidal timestep embedding. t: (B,) float in [0, 1000]."""
    half = dim // 2
    freqs = jnp.exp(-np.log(max_period) * jnp.arange(half, dtype=jnp.float32)
                    / half)
    args = t.astype(jnp.float32)[:, None] * freqs[None]
    return jnp.concatenate([jnp.cos(args), jnp.sin(args)], axis=-1)


# ---------------------------------------------------------------------------
# adaLN modulate (jnp oracle; Pallas kernel in kernels/adaln.py)
# ---------------------------------------------------------------------------

def modulate(x, shift, scale):
    """x: (B, N, D); shift/scale: (B, D)."""
    return x * (1.0 + scale[:, None]) + shift[:, None]


def ln(x, eps: float = 1e-6):
    """Parameter-free LayerNorm (adaLN supplies scale/shift)."""
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return ((x - mu) * jax.lax.rsqrt(var + eps)).astype(dt)


def mod_norm(x, shift=None, scale=None, *, up: bool = False):
    """LN (+ shift/scale modulate) — ONE fused HBM pass on the Pallas
    fast path (DESIGN.md §12), the historic jnp sequence otherwise."""
    if up:
        return ops.fused_adaln(x, shift, scale, use_pallas=True)
    h = ln(x)
    return modulate(h, shift, scale) if shift is not None else h


def gated_residual(residual, gate, branch, *, up: bool = False):
    """residual + gate[:, None] * branch, fused on the Pallas path."""
    if up:
        return ops.fused_adaln(branch, gate=gate, residual=residual,
                               ln=False, use_pallas=True)
    return residual + gate[:, None] * branch


# ---------------------------------------------------------------------------
# layer programs
# ---------------------------------------------------------------------------

_builds = 0
_builds_lock = threading.Lock()


def built():
    """Count one build of a layer program; runs only while tracing."""
    global _builds
    with _builds_lock:
        _builds += 1


def builds() -> int:
    """How many times this process has built (traced) a program of the
    served step: once per segment and shape, then 0 more once warm."""
    return _builds


def layer_of(blocks, i):
    """Layer ``i`` of the stacked block weights, sliced in the program."""
    return jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(
        a, i, keepdims=False), blocks)
