"""joint-guidance-embed: a block that exists only in the test fixture, to
show that a new architecture is one new file.

Per block: adaLN modulation rows (shift, scale, gate) from the timestep,
the guidance scale and the pooled text; one attention over the text
tokens and the modulated latent tokens together, whose latent rows feed
a gated residual; a GELU MLP.  The guidance scale is an input of the
model, so a guided step is one row, with no unconditional pass.  The
step calls the flash kernel and no adaLN kernel."""
import functools

import jax
import jax.numpy as jnp
import numpy as np

from gfbench import reference as R
from gfbench.flops import flash_bytes, flash_flops


def weights(key, liven_key, conf):
    m = conf["model"]
    d, h, hd, dff = m["d_model"], m["num_heads"], m["head_dim"], m["d_ff"]
    patch_in = m["patch_size"] ** 2 * m["in_channels"]
    ks = jax.random.split(key, 6)
    lk = jax.random.split(liven_key, 2)

    def block(i):
        k = jax.random.split(jax.random.fold_in(ks[0], i), 4)
        return {"attn": R._attn_w(k[0], d, h, hd),
                "w1": R._normal(k[1], (d, dff), d),
                "w2": R._normal(k[2], (dff, d), dff),
                "ada_w": conf["liven_scale"] * jax.random.normal(
                    k[3], (d, 3 * d))}

    return {"x_embed": R._normal(ks[1], (patch_in, d), patch_in),
            "t_mlp": R._normal(ks[2], (256, d), 256),
            "g_mlp": R._normal(ks[3], (256, d), 256),
            "txt_proj": R._normal(ks[4], (m["cond_dim"], d), m["cond_dim"]),
            "blocks": jax.vmap(block)(jnp.arange(m["num_layers"])),
            "final_out": conf["liven_scale"] * jax.random.normal(
                lk[0], (d, patch_in))}


def _layer_norm(x, eps=1e-6):
    mu = x.mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(((x - mu) ** 2).mean(-1, keepdims=True)
                                    + eps)


def _sincos(pos, dim):
    freqs = jnp.exp(-np.log(10000.0) * jnp.arange(dim // 2) / (dim // 2))
    args = pos.astype(jnp.float32)[:, None] * freqs[None]
    return jnp.concatenate([jnp.cos(args), jnp.sin(args)], axis=-1)


@functools.partial(jax.jit, static_argnames=("dtype",))
def _velocity(w, x, t, g, txt, dtype=jnp.float32):
    w = jax.tree.map(lambda a: a.astype(dtype), w)
    h = x.astype(dtype) @ w["x_embed"]
    c = (jax.nn.silu(_sincos(t, 256).astype(dtype) @ w["t_mlp"])
         + jax.nn.silu(_sincos(g * 1000.0, 256).astype(dtype) @ w["g_mlp"]))
    tx = txt.astype(dtype) @ w["txt_proj"]
    sc = jax.nn.silu(c + tx.mean(axis=1))
    lt = tx.shape[1]

    def layer(h, p):
        sh, scl, gate = (m[:, None] for m in jnp.split(sc @ p["ada_w"], 3,
                                                       axis=-1))
        a = jnp.concatenate([tx, _layer_norm(h) * (1 + scl) + sh], axis=1)
        at = p["attn"]
        q, k, v = (jnp.einsum("bsd,dhk->bshk", a, at[n])
                   for n in ("wq", "wk", "wv"))
        o = jnp.einsum("bshk,hkd->bsd", R.attend(q, k, v)[:, lt:], at["wo"])
        h = h + gate * o
        return h + jax.nn.gelu(_layer_norm(h) @ p["w1"]) @ p["w2"], None

    h, _ = jax.lax.scan(layer, h, w["blocks"])
    return (_layer_norm(h) @ w["final_out"]).astype(jnp.float32)


def velocity(w, x, t, txt, guidance, dtype=jnp.float32):
    g = jnp.full(t.shape, 0.0 if guidance is None else guidance, jnp.float32)
    return _velocity(w, x, t, g, txt, dtype=dtype)


def rows(guidance) -> tuple:
    return ("cond",)


def merge(v_rows, guidance):
    return v_rows[0]


def step_flops(m, n, b, text_len):
    d, dff, h, hd = m["d_model"], m["d_ff"], m["num_heads"], m["head_dim"]
    patch_in = m["patch_size"] ** 2 * m["in_channels"]
    s = n + text_len
    head = 2 * b * (n * patch_in * d + 2 * 256 * d
                    + text_len * m["cond_dim"] * d)
    per_layer = 2 * b * (d * 3 * d + 3 * s * d * h * hd + n * h * hd * d
                         + 2 * n * d * dff) + flash_flops(b, h, s, s, hd)
    return float(head + m["num_layers"] * per_layer + 2 * b * n * d * patch_in)


def step_flash(m, n, b, text_len):
    s, h, hd = n + text_len, m["num_heads"], m["head_dim"]
    return (m["num_layers"] * flash_flops(b, h, s, s, hd),
            m["num_layers"] * flash_bytes(b, h, s, s, hd))


KERNELS = {"flash_attention": step_flash}
