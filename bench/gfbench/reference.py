"""Plain float32 reference of the served DiT pipeline, kept with the benchmark.

It imports nothing of the program.  The weights are made here from the
run's seed by the derivation the configuration file states (the same
keys, shapes and scales as the served model's initializer), so the
reference never reads a weight, a prompt or a table that the program
made.  Sizes come from the configuration file under ``bench/configs``.

What it computes, in straightforward ``jax.numpy``, for every
architecture:

* the prompt tokens and the initial noisy latent of a request, derived
  from the request id, and the flow-matching sigmas;
* the text encoder (pre-norm RMSNorm transformer with RoPE and a SwiGLU
  MLP) and its weights;
* attention in query blocks, so that no full score matrix is ever held,
  and the weight draws (``_normal``, ``_attn_w``, ``_mlp_w``) that blocks
  share.

The DiT block itself, its weights, its velocity and how guidance makes
and merges rows, belongs to the configuration's architecture,
``bench/archs/<architecture>.py``.  ``dtype`` selects the compute type:
float32, with every matmul at ``highest`` precision (which the caller
sets), is the reference; bfloat16 throughout is the control that
``correct`` must reject.
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


def _text_weights(key, te: dict):
    d, h, hd = te["d_model"], te["num_heads"], te["head_dim"]
    ks = jax.random.split(key, 2)
    blocks = [{"attn": _attn_w(jax.random.fold_in(ks[1], 2 * i), d, h, hd),
               "mlp": _mlp_w(jax.random.fold_in(ks[1], 2 * i + 1), d,
                             te["d_ff"])}
              for i in range(te["num_layers"])]
    return {"tok": _normal(ks[0], (te["vocab"], d), scale=1.0),
            "blocks": jax.tree.map(lambda *x: jnp.stack(x), *blocks)}


def make_weights(conf: dict, seed: int, arch):
    """(dit, text) float32 weights for a run seed, in one jitted call on
    the default device: the DiT's by the cell's architecture
    (``arch.weights``), the text encoder's here (the third key, the
    program's VAE, is not drawn: nothing decoded is compared)."""
    model_seed, liven_seed = weight_seeds(seed)
    te = conf["text_encoder"]

    @jax.jit
    def build(mk, lk):
        ks = jax.random.split(mk, 3)
        return arch.weights(ks[0], lk, conf), _text_weights(ks[1], te)

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


def _swiglu(p, x):
    g = x @ p["w_gate"]
    return (jax.nn.silu(g) * (x @ p["w_up"])) @ p["w_down"]


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


def rel_l2(a, b) -> float:
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def timestep(sigma: float) -> float:
    return float(sigma) * 1000.0
