"""Plain float32 reference of the served DiT pipeline, kept with the benchmark.

It imports nothing of the program.  The weights are made here from the
run's seed by the derivation the configuration file states (the same
keys, shapes and scales as the served model's initializer, then the
livened adaLN gates and output head), so the reference never reads a
weight, a prompt or a table that the program made.  Sizes come from the
configuration file under ``bench/configs``.

What it computes, in straightforward ``jax.numpy``:

* the prompt tokens and the initial noisy latent of a request, derived
  from the request id;
* the text encoder (pre-norm RMSNorm transformer with RoPE);
* one denoise velocity: patch embed, sincos position and timestep
  embeddings, adaLN-Zero blocks with full self-attention, cross-attention
  to the text and a SwiGLU MLP, the final modulated norm and projection;
  classifier-free guidance merges ``v_u + g * (v_c - v_u)``;
Attention runs in query blocks, so that no full score matrix is ever
held.  ``dtype`` selects the compute type: float32, with every matmul at
``highest`` precision (which the caller sets), is the reference;
bfloat16 throughout is the control that ``correct`` must reject.
"""
from __future__ import annotations

import functools
import hashlib

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 512


# ---------------------------------------------------------------------------
# seeds and inputs
# ---------------------------------------------------------------------------

def weight_seeds(seed: int) -> tuple[int, int]:
    """(model seed, liven seed) for a run seed: both fit a PRNG key."""
    model = seed % (2 ** 31)
    return model, (model + 1) % (2 ** 31)


def request_key(request_id: str):
    return jax.random.PRNGKey(
        int(hashlib.sha1(request_id.encode()).hexdigest()[:8], 16))


def prompt_tokens(request_id: str, te: dict):
    """(1, Lt) int32 prompt tokens of a request."""
    return jax.random.randint(request_key(request_id), (1, te["prompt_len"]),
                              0, te["vocab"])


def flow_sigmas(steps: int, shift: float) -> np.ndarray:
    t = np.linspace(1.0, 1.0 / steps, steps)
    return (shift * t) / (1 + (shift - 1) * t)


def sigma_pair(steps: int, step: int, shift: float) -> tuple[float, float]:
    s = flow_sigmas(steps, shift)
    return float(s[step]), (float(s[step + 1]) if step + 1 < steps else 0.0)


def initial_latent(request_id: str, n_tok: int, patch_dim: int,
                   sigma0: float) -> np.ndarray:
    noise = jax.random.normal(jax.random.fold_in(request_key(request_id), 1),
                              (n_tok, patch_dim), jnp.float32)
    return np.asarray(noise) * sigma0


# ---------------------------------------------------------------------------
# weights from the seed
# ---------------------------------------------------------------------------

def _normal(key, shape, fan_in=None, scale=None):
    """Unit normals scaled by 1/sqrt(fan_in), where fan_in is the product
    of every dimension but the last."""
    if scale is None:
        scale = max(fan_in, 1) ** -0.5
    return scale * jax.random.normal(key, shape)


def _attn_w(key, d, h, hd):
    k = jax.random.split(key, 4)
    return {"wq": _normal(k[0], (d, h, hd), d * h),
            "wk": _normal(k[1], (d, h, hd), d * h),
            "wv": _normal(k[2], (d, h, hd), d * h),
            "wo": _normal(k[3], (h, hd, d), h * hd)}


def _mlp_w(key, d, dff):
    k = jax.random.split(key, 3)
    return {"w_gate": _normal(k[0], (d, dff), d),
            "w_up": _normal(k[1], (d, dff), d),
            "w_down": _normal(k[2], (dff, d), dff)}


def _dit_weights(key, m: dict):
    d, h, hd = m["d_model"], m["num_heads"], m["head_dim"]
    patch_in = m["patch_size"] ** 2 * m["in_channels"]
    ks = jax.random.split(key, 8)

    def block(i):
        k = jax.random.split(jax.random.fold_in(ks[0], i), 4)
        return {"attn": _attn_w(k[0], d, h, hd),
                "cross": _attn_w(k[1], d, h, hd),
                "mlp": _mlp_w(k[2], d, m["d_ff"])}

    return {
        "x_embed": _normal(ks[1], (patch_in, d), patch_in),
        "t_mlp1": _normal(ks[2], (256, d), 256),
        "t_mlp2": _normal(ks[3], (d, d), d),
        "txt_proj": _normal(ks[4], (m["cond_dim"], d), m["cond_dim"]),
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


def _text_weights(key, te: dict):
    d, h, hd = te["d_model"], te["num_heads"], te["head_dim"]
    ks = jax.random.split(key, 2)
    blocks = [{"attn": _attn_w(jax.random.fold_in(ks[1], 2 * i), d, h, hd),
               "mlp": _mlp_w(jax.random.fold_in(ks[1], 2 * i + 1), d,
                             te["d_ff"])}
              for i in range(te["num_layers"])]
    return {"tok": _normal(ks[0], (te["vocab"], d), scale=1.0),
            "blocks": jax.tree.map(lambda *x: jnp.stack(x), *blocks)}


def make_weights(conf: dict, seed: int):
    """(dit, text) float32 weights for a run seed, in one jitted call on
    the default device (the third key, the program's VAE, is not drawn:
    nothing decoded is compared)."""
    model_seed, liven_seed = weight_seeds(seed)
    m, te = conf["model"], conf["text_encoder"]

    @jax.jit
    def build(mk, lk):
        ks = jax.random.split(mk, 3)
        dit = _liven(_dit_weights(ks[0], m), lk, m, conf["liven_scale"])
        return dit, _text_weights(ks[1], te)

    return build(jax.random.PRNGKey(model_seed),
                 jax.random.PRNGKey(liven_seed))


# ---------------------------------------------------------------------------
# forward passes
# ---------------------------------------------------------------------------

def attend(q, k, v):
    """Softmax attention over (B, S, H, hd), in query blocks."""
    b, n, h, hd = q.shape
    blk = min(QUERY_BLOCK, n)
    nb = -(-n // blk)
    qp = jnp.pad(q, ((0, 0), (0, nb * blk - n), (0, 0), (0, 0)))
    qp = qp.reshape(b, nb, blk, h, hd).transpose(1, 0, 2, 3, 4)
    scale = jnp.asarray(hd ** -0.5, q.dtype)

    def one(qb):
        s = jnp.einsum("bqhd,bkhd->bhqk", qb, k) * scale
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)

    out = jax.lax.map(one, qp).transpose(1, 0, 2, 3, 4)
    return out.reshape(b, nb * blk, h, hd)[:, :n]


def _layer_norm(x, eps=1e-6):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps)


def _swiglu(p, x):
    g = x @ p["w_gate"]
    return (jax.nn.silu(g) * (x @ p["w_up"])) @ p["w_down"]


def _sincos(pos, dim, max_period=10000.0):
    half = dim // 2
    freqs = jnp.exp(-np.log(max_period)
                    * jnp.arange(half, dtype=jnp.float32) / half)
    args = pos.astype(jnp.float32)[:, None] * freqs[None]
    return jnp.concatenate([jnp.cos(args), jnp.sin(args)], axis=-1)


@functools.partial(jax.jit, static_argnames=("dtype",))
def velocity(w, x, t, txt, dtype=jnp.float32):
    """DiT velocity for full-sequence tokens x (B, N, patch_in) at
    timesteps t (B,) with text embeddings txt (B, Lt, cond)."""
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
        h = h + g_a * jnp.einsum("bshk,hkd->bsd", attend(q, k, v), at["wo"])
        a = _layer_norm(h)
        cr = p["cross"]
        q = jnp.einsum("bsd,dhk->bshk", a, cr["wq"])
        k = jnp.einsum("bsd,dhk->bshk", tx, cr["wk"])
        v = jnp.einsum("bsd,dhk->bshk", tx, cr["wv"])
        h = h + jnp.einsum("bshk,hkd->bsd", attend(q, k, v), cr["wo"])
        a = _layer_norm(h) * (1 + sc_m) + sh_m
        return h + g_m * _swiglu(p["mlp"], a), None

    h, _ = jax.lax.scan(layer, h, w["blocks"])
    sh, scl = (m[:, None] for m in jnp.split(
        sc @ w["final_ada_w"] + w["final_ada_b"], 2, axis=-1))
    h = _layer_norm(h) * (1 + scl) + sh
    return (h @ w["final_out"]).astype(jnp.float32)


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _rope(x, theta):
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                           axis=-1).astype(x.dtype)


@functools.partial(jax.jit, static_argnames=("theta", "eps", "dtype"))
def encode(w, tokens, theta: float, eps: float, dtype=jnp.float32):
    """Text encoder: tokens (B, Lt) -> embeddings (B, Lt, cond)."""
    w = jax.tree.map(lambda a: a.astype(dtype), w)
    h = w["tok"][tokens]

    def layer(h, p):
        a = _rms(h, eps)
        at = p["attn"]
        q = _rope(jnp.einsum("bsd,dhk->bshk", a, at["wq"]), theta)
        k = _rope(jnp.einsum("bsd,dhk->bshk", a, at["wk"]), theta)
        v = jnp.einsum("bsd,dhk->bshk", a, at["wv"])
        h = h + jnp.einsum("bshk,hkd->bsd", attend(q, k, v), at["wo"])
        return h + _swiglu(p["mlp"], _rms(h, eps)), None

    h, _ = jax.lax.scan(layer, h, w["blocks"])
    return _rms(h, eps).astype(jnp.float32)


def guided(v_rows, guidance):
    """Merge [cond, uncond] velocity rows; unguided rows pass through."""
    if guidance is None:
        return v_rows[0]
    return v_rows[1] + guidance * (v_rows[0] - v_rows[1])


def rel_l2(a, b) -> float:
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def timestep(sigma: float) -> float:
    return float(sigma) * 1000.0
