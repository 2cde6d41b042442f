"""adaln-cross-swiglu: the program's DiT block, as both benchmarked
configurations run it.

Per block: adaLN-Zero modulation rows from the timestep and the pooled
text, self-attention over the latent tokens, cross-attention to the
text, a SwiGLU MLP; 1-D sincos positions over the flattened tokens;
a final modulated norm and a linear velocity head.  A guided step runs
two rows, ``[cond, uncond]``, merged as ``v_u + g * (v_c - v_u)``.

What the benchmark needs of an architecture, one function each:
``weights`` (the float32 DiT weights, drawn as the configuration file
states), ``velocity`` (one denoise step in plain ``jax.numpy``, computed
in ``dtype``), ``rows`` and ``merge`` (how a guided request becomes rows
and how they are merged), ``step_flops`` (the operations of one step)
and ``KERNELS`` (the operations and bytes of each kernel's calls in one
step, by the name the kernel's ops carry in a trace).  Counts are
algorithmic: a multiply-add is two operations, nothing recomputed, no
padding.  ``m`` is the ``model`` table of a configuration file.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from gfbench import reference as R
from gfbench.flops import F32, flash_bytes, flash_flops


# ---------------------------------------------------------------------------
# weights from the seed
# ---------------------------------------------------------------------------

def _dit_weights(key, m: dict):
    d, h, hd = m["d_model"], m["num_heads"], m["head_dim"]
    patch_in = m["patch_size"] ** 2 * m["in_channels"]
    ks = jax.random.split(key, 8)

    def block(i):
        k = jax.random.split(jax.random.fold_in(ks[0], i), 4)
        return {"attn": R._attn_w(k[0], d, h, hd),
                "cross": R._attn_w(k[1], d, h, hd),
                "mlp": R._mlp_w(k[2], d, m["d_ff"])}

    return {
        "x_embed": R._normal(ks[1], (patch_in, d), patch_in),
        "t_mlp1": R._normal(ks[2], (256, d), 256),
        "t_mlp2": R._normal(ks[3], (d, d), d),
        "txt_proj": R._normal(ks[4], (m["cond_dim"], d), m["cond_dim"]),
        "blocks": jax.vmap(block)(jnp.arange(m["num_layers"])),
    }


def _liven(w, key, m: dict, scale: float):
    """The adaLN gates and output head, drawn in the stated order."""
    d, n = m["d_model"], m["num_layers"]
    patch_in = m["patch_size"] ** 2 * m["in_channels"]
    shapes = (("ada_w", (n, d, 6 * d)), ("ada_b", (n, 6 * d)),
              ("final_ada_w", (d, 2 * d)), ("final_ada_b", (2 * d,)),
              ("final_out", (d, patch_in)))
    for name, shape in shapes:
        key, k = jax.random.split(key)
        val = scale * jax.random.normal(k, shape, jnp.float32)
        if name.startswith("ada"):
            w["blocks"][name] = val
        else:
            w[name] = val
    return w


def weights(key, liven_key, conf: dict):
    """The DiT's float32 weights: the program's initializer from the DiT
    key, then the livened adaLN gates and output head from the liven
    key (traced inside ``reference.make_weights``' one jitted call)."""
    m = conf["model"]
    return _liven(_dit_weights(key, m), liven_key, m, conf["liven_scale"])


# ---------------------------------------------------------------------------
# the forward pass
# ---------------------------------------------------------------------------

def _layer_norm(x, eps=1e-6):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps)


def _sincos(pos, dim, max_period=10000.0):
    half = dim // 2
    freqs = jnp.exp(-np.log(max_period)
                    * jnp.arange(half, dtype=jnp.float32) / half)
    args = pos.astype(jnp.float32)[:, None] * freqs[None]
    return jnp.concatenate([jnp.cos(args), jnp.sin(args)], axis=-1)


@functools.partial(jax.jit, static_argnames=("dtype",))
def _velocity(w, x, t, txt, dtype=jnp.float32):
    w = jax.tree.map(lambda a: a.astype(dtype), w)
    n, d = x.shape[1], w["t_mlp2"].shape[0]
    h = x.astype(dtype) @ w["x_embed"] + _sincos(jnp.arange(n), d
                                                ).astype(dtype)[None]
    c = _sincos(t, 256).astype(dtype) @ w["t_mlp1"]
    c = jax.nn.silu(c) @ w["t_mlp2"]
    tx = txt.astype(dtype) @ w["txt_proj"]
    c = c + tx.mean(axis=1)
    sc = jax.nn.silu(c)

    def layer(h, p):
        mods = sc @ p["ada_w"] + p["ada_b"]
        sh_a, sc_a, g_a, sh_m, sc_m, g_m = (
            m[:, None] for m in jnp.split(mods, 6, axis=-1))
        a = _layer_norm(h) * (1 + sc_a) + sh_a
        at = p["attn"]
        q, k, v = (jnp.einsum("bsd,dhk->bshk", a, at[n_])
                   for n_ in ("wq", "wk", "wv"))
        h = h + g_a * jnp.einsum("bshk,hkd->bsd", R.attend(q, k, v),
                                 at["wo"])
        a = _layer_norm(h)
        cr = p["cross"]
        q = jnp.einsum("bsd,dhk->bshk", a, cr["wq"])
        k = jnp.einsum("bsd,dhk->bshk", tx, cr["wk"])
        v = jnp.einsum("bsd,dhk->bshk", tx, cr["wv"])
        h = h + jnp.einsum("bshk,hkd->bsd", R.attend(q, k, v), cr["wo"])
        a = _layer_norm(h) * (1 + sc_m) + sh_m
        return h + g_m * R._swiglu(p["mlp"], a), None

    h, _ = jax.lax.scan(layer, h, w["blocks"])
    sh, scl = (m[:, None] for m in jnp.split(
        sc @ w["final_ada_w"] + w["final_ada_b"], 2, axis=-1))
    h = _layer_norm(h) * (1 + scl) + sh
    return (h @ w["final_out"]).astype(jnp.float32)


def velocity(w, x, t, txt, guidance, dtype=jnp.float32):
    """DiT velocity for full-sequence tokens x (B, N, patch_in) at
    timesteps t (B,) with text embeddings txt (B, Lt, cond).  The
    guidance scale is not an input of this block: it acts in ``merge``."""
    return _velocity(w, x, t, txt, dtype=dtype)


# ---------------------------------------------------------------------------
# guidance
# ---------------------------------------------------------------------------

def rows(guidance) -> tuple:
    """The rows one step of a request runs: batched classifier-free
    guidance runs the unconditional row beside the conditional one."""
    return ("cond",) if guidance is None else ("cond", "uncond")


def merge(v_rows, guidance):
    """Merge [cond, uncond] velocity rows; unguided rows pass through."""
    if guidance is None:
        return v_rows[0]
    return v_rows[1] + guidance * (v_rows[0] - v_rows[1])


# ---------------------------------------------------------------------------
# operations and bytes
# ---------------------------------------------------------------------------

def _inner(m: dict) -> int:
    return m["num_heads"] * m["head_dim"]


def step_flops(m: dict, n: int, b: int, text_len: int) -> float:
    """One denoise step of the whole model on ``b`` rows of ``n`` tokens
    (b = 2 for batched classifier-free guidance)."""
    d, dff, inner = m["d_model"], m["d_ff"], _inner(m)
    patch_in = m["patch_size"] ** 2 * m["in_channels"]
    cond = m["cond_dim"]
    head = 2 * b * (n * patch_in * d + 256 * d + d * d
                    + text_len * cond * d)
    per_layer = 2 * b * (
        d * 6 * d                                  # adaLN modulation
        + 4 * n * d * inner                        # self q, k, v, o
        + 2 * n * d * inner + 2 * text_len * d * inner   # cross q, o; k, v
        + 3 * n * d * dff)                         # SwiGLU
    per_layer += flash_flops(b, m["num_heads"], n, n, m["head_dim"])
    per_layer += flash_flops(b, m["num_heads"], n, text_len, m["head_dim"])
    tail = 2 * b * (d * 2 * d + n * d * patch_in)
    return float(head + m["num_layers"] * per_layer + tail)


def step_flash(m: dict, n: int, b: int, text_len: int) -> tuple[float, float]:
    """(flops, bytes) of every flash call in one step: self- and
    cross-attention in each layer."""
    h, hd = m["num_heads"], m["head_dim"]
    fl = flash_flops(b, h, n, n, hd) + flash_flops(b, h, n, text_len, hd)
    by = flash_bytes(b, h, n, n, hd) + flash_bytes(b, h, n, text_len, hd)
    return m["num_layers"] * fl, m["num_layers"] * by


def step_adaln(m: dict, n: int, b: int, text_len: int = 0
               ) -> tuple[float, float]:
    """(flops, bytes) of every fused adaLN call in one step.  Per layer:
    two modulated norms and one plain norm (read x, write out: 2 passes)
    and two gated residuals (read branch and residual, write out: 3);
    then the final modulated norm (2).  Modulation rows are B x D."""
    d = m["d_model"]
    tile = b * n * d
    passes = m["num_layers"] * (3 * 2 + 2 * 3) + 2
    rows = m["num_layers"] * (2 * 2 + 2 * 1) + 2
    by = F32 * (passes * tile + rows * b * d)
    # norm ~ 8 ops an element, modulate 2, gate-accumulate 2
    fl = m["num_layers"] * (2 * 10 + 8 + 2 * 2) * tile + 10 * tile
    return float(fl), float(by)


KERNELS = {"flash_attention": step_flash, "adaln_modulate": step_adaln}
