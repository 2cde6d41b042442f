"""flux-double-single: FLUX.1's transformer (Black Forest Labs,
``flux/model.py`` and ``flux/modules/layers.py``), as the program serves
it (``models/flux.py``), in plain ``jax.numpy``.

Per step: ``vec = time_in(t) + guidance_in(g) + vector_in(pooled)``, each
a two-layer SiLU MLP embedder of the 256-wide sincos embedding of
1000 x t (and of 1000 x g), or of the pooled text vector; ``num_layers``
double-stream blocks, where text and image tokens each have their own
modulation (6 rows), q/k/v projection with bias, QK RMSNorm with a
learned scale, output projection and GELU(tanh) MLP, and meet in one
attention over ``[text; image]``; ``num_single_layers`` single-stream
blocks over ``[text; image]`` (3 modulation rows, ``linear1`` to q, k, v
and the MLP input, ``linear2`` on ``concat(attention, gelu(mlp))``, one
gated residual); then the final layer (shift and scale from ``vec``, a
LayerNorm, a linear head) on the image tokens.  Positions: 3-axis RoPE
over ids (0, row, col) of the image tokens and (0, 0, 0) of the text,
rotating adjacent lane pairs of q and k.

The guidance scale is an input of the model: a guided step is one row,
with no unconditional pass (``rows``, ``merge``).  An unguided request
runs at scale 1.  The pooled CLIP vector is stood in for by a seeded
projection (``pool_proj``) of the mean text embedding, as in the
program.  What the benchmark needs of an architecture is described in
``adaln-cross-swiglu.py``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from gfbench import reference as R
from gfbench.flops import F32, flash_bytes, flash_flops


# ---------------------------------------------------------------------------
# weights from the seed
# ---------------------------------------------------------------------------

def _linear(key, n_in, n_out):
    kw, kb = jax.random.split(key)
    return (R._normal(kw, (n_in, n_out), n_in),
            R._normal(kb, (n_out,), n_out))


def _embedder(key, n_in, d):
    k1, k2 = jax.random.split(key)
    (in_w, in_b), (out_w, out_b) = _linear(k1, n_in, d), _linear(k2, d, d)
    return {"in_w": in_w, "in_b": in_b, "out_w": out_w, "out_b": out_b}


def _stream(key, m):
    d, hd, f = m["d_model"], m["head_dim"], m["d_ff"]
    ks = jax.random.split(key, 4)
    (qkv_w, qkv_b), (proj_w, proj_b) = (_linear(ks[0], d, 3 * d),
                                        _linear(ks[1], d, d))
    (w1, b1), (w2, b2) = _linear(ks[2], d, f), _linear(ks[3], f, d)
    return {"qkv_w": qkv_w, "qkv_b": qkv_b, "q_scale": jnp.ones((hd,)),
            "k_scale": jnp.ones((hd,)), "proj_w": proj_w, "proj_b": proj_b,
            "mlp_w1": w1, "mlp_b1": b1, "mlp_w2": w2, "mlp_b2": b2}


def _double(key, m):
    ki, kt = jax.random.split(key)
    return {"img": _stream(ki, m), "txt": _stream(kt, m)}


def _single(key, m):
    d, hd, f = m["d_model"], m["head_dim"], m["d_ff"]
    k1, k2 = jax.random.split(key)
    (w1, b1), (w2, b2) = _linear(k1, d, 3 * d + f), _linear(k2, d + f, d)
    return {"linear1_w": w1, "linear1_b": b1, "q_scale": jnp.ones((hd,)),
            "k_scale": jnp.ones((hd,)), "linear2_w": w2, "linear2_b": b2}


def _rope_buffer(conf: dict):
    """BFL's ``EmbedND`` as data: the frequency of each rotated lane pair,
    1 / theta^(2j / w) for axis width w, and the id axis it reads."""
    m = conf["model"]
    freqs, axis = [], []
    for a, w in enumerate(conf["rope_axes"]):
        freqs.append(1.0 / m["rope_theta"] ** (np.arange(0, w, 2) / w))
        axis += [a] * (w // 2)
    return {"freqs": jnp.asarray(np.concatenate(freqs), jnp.float32),
            "axis": jnp.asarray(axis, jnp.int32)}


def _dit_weights(key, m: dict):
    d = m["d_model"]
    patch_in = m["patch_size"] ** 2 * m["in_channels"]
    ks = jax.random.split(key, 10)
    (img_in_w, img_in_b), (txt_in_w, txt_in_b) = (
        _linear(ks[0], patch_in, d), _linear(ks[1], m["cond_dim"], d))
    w = {"img_in_w": img_in_w, "img_in_b": img_in_b,
         "txt_in_w": txt_in_w, "txt_in_b": txt_in_b,
         "time_in": _embedder(ks[2], 256, d),
         "vector_in": _embedder(ks[3], m["pooled_dim"], d),
         "pool_proj": R._normal(ks[4], (m["cond_dim"], m["pooled_dim"]),
                                m["cond_dim"]),
         "double": jax.vmap(lambda i: _double(jax.random.fold_in(ks[5], i),
                                              m))(
             jnp.arange(m["num_layers"])),
         "single": jax.vmap(lambda i: _single(jax.random.fold_in(ks[6], i),
                                              m))(
             jnp.arange(m["num_single_layers"])),
         "final_out_b": R._normal(ks[7], (patch_in,), patch_in)}
    if m["guidance_embeds"]:
        w["guidance_in"] = _embedder(ks[8], 256, d)
    return w


def _liven(w, key, m: dict, scale: float):
    """The modulation rows and the output head, drawn in the stated order
    (the program's initializer leaves them zero)."""
    d, nd, ns = m["d_model"], m["num_layers"], m["num_single_layers"]
    patch_in = m["patch_size"] ** 2 * m["in_channels"]
    leaves = ((w["double"]["img"], "mod_w", (nd, d, 6 * d)),
              (w["double"]["img"], "mod_b", (nd, 6 * d)),
              (w["double"]["txt"], "mod_w", (nd, d, 6 * d)),
              (w["double"]["txt"], "mod_b", (nd, 6 * d)),
              (w["single"], "mod_w", (ns, d, 3 * d)),
              (w["single"], "mod_b", (ns, 3 * d)),
              (w, "final_ada_w", (d, 2 * d)), (w, "final_ada_b", (2 * d,)),
              (w, "final_out", (d, patch_in)))
    for tree, name, shape in leaves:
        key, k = jax.random.split(key)
        tree[name] = scale * jax.random.normal(k, shape, jnp.float32)
    return w


def weights(key, liven_key, conf: dict):
    """The DiT's float32 weights: the program's initializer from the DiT
    key, the livened modulation rows and output head from the liven key,
    and the RoPE frequencies (``rope``, a buffer the program computes in
    its head)."""
    m = conf["model"]
    w = _liven(_dit_weights(key, m), liven_key, m, conf["liven_scale"])
    w["rope"] = _rope_buffer(conf)
    return w


# ---------------------------------------------------------------------------
# the forward pass
# ---------------------------------------------------------------------------

def _layer_norm(x, eps=1e-6):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps)


def _rms(x, scale, eps=1e-6):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def _sincos(t, dim, max_period=10000.0):
    half = dim // 2
    freqs = jnp.exp(-np.log(max_period)
                    * jnp.arange(half, dtype=jnp.float32) / half)
    args = t.astype(jnp.float32)[:, None] * freqs[None]
    return jnp.concatenate([jnp.cos(args), jnp.sin(args)], axis=-1)


def _embed(p, x):
    return jax.nn.silu(x @ p["in_w"] + p["in_b"]) @ p["out_w"] + p["out_b"]


def _rope_tables(rope, grid, lt):
    """cos and sin (lt + tokens, hd / 2): ids (0, 0, 0) for the text,
    (frame, row, col) for the image tokens in raster order."""
    f, gh, gw = grid
    fr, r, c = np.meshgrid(np.arange(f), np.arange(gh), np.arange(gw),
                           indexing="ij")
    ids = np.concatenate([np.zeros((lt, 3), np.int32),
                          np.stack([fr, r, c], -1).reshape(-1, 3)])
    ang = jnp.asarray(ids, jnp.float32)[:, rope["axis"]] * rope["freqs"]
    return jnp.cos(ang), jnp.sin(ang)


def _rotate(x, cos, sin):
    """Adjacent lane pairs (2i, 2i + 1) of x (B, S, H, hd) rotated."""
    x0, x1 = x[..., 0::2], x[..., 1::2]
    c, s = cos[None, :, None], sin[None, :, None]
    return jnp.stack([c * x0 - s * x1, s * x0 + c * x1],
                     axis=-1).reshape(x.shape)


def _qkv(y, p, heads, cos, sin):
    b, s, d3 = y.shape
    y = y.reshape(b, s, 3, heads, d3 // (3 * heads))
    return (_rotate(_rms(y[:, :, 0], p["q_scale"]), cos, sin),
            _rotate(_rms(y[:, :, 1], p["k_scale"]), cos, sin), y[:, :, 2])


def _gelu(x):
    return jax.nn.gelu(x, approximate=True)


@functools.partial(jax.jit, static_argnames=("grid", "dtype"))
def _velocity(w, x, t, g, txt, grid, dtype=jnp.float32):
    def cast(tree):
        return jax.tree.map(lambda a: a.astype(dtype), tree)

    top = cast({k: v for k, v in w.items()
                if k not in ("double", "single", "rope")})
    heads = w["double"]["img"]["qkv_w"].shape[-1] // (
        3 * w["double"]["img"]["q_scale"].shape[-1])
    lt = txt.shape[1]
    txt = txt.astype(dtype)
    img = x.astype(dtype) @ top["img_in_w"] + top["img_in_b"]
    tx = txt @ top["txt_in_w"] + top["txt_in_b"]
    vec = _embed(top["time_in"], _sincos(t, 256).astype(dtype))
    if "guidance_in" in top:
        vec = vec + _embed(top["guidance_in"],
                           _sincos(g * 1000.0, 256).astype(dtype))
    vec = vec + _embed(top["vector_in"], txt.mean(axis=1) @ top["pool_proj"])
    sv = jax.nn.silu(vec)
    cos, sin = (a.astype(dtype) for a in _rope_tables(w["rope"], grid, lt))

    def double(carry, p):
        p = cast(p)
        streams = []
        for name, h, rows in (("txt", carry[0], slice(None, lt)),
                              ("img", carry[1], slice(lt, None))):
            sp = p[name]
            mods = [m[:, None] for m in jnp.split(
                sv @ sp["mod_w"] + sp["mod_b"], 6, axis=-1)]
            a = _layer_norm(h) * (1 + mods[1]) + mods[0]
            streams.append((h, sp, mods) + _qkv(
                a @ sp["qkv_w"] + sp["qkv_b"], sp, heads, cos[rows],
                sin[rows]))
        q, k, v = (jnp.concatenate([s[i] for s in streams], axis=1)
                   for i in (3, 4, 5))
        o = R.attend(q, k, v)
        o = o.reshape(o.shape[0], o.shape[1], -1)
        out = []
        for (h, sp, mods, *_), rows in zip(streams, (slice(None, lt),
                                                     slice(lt, None))):
            h = h + mods[2] * (o[:, rows] @ sp["proj_w"] + sp["proj_b"])
            a = _layer_norm(h) * (1 + mods[4]) + mods[3]
            h = h + mods[5] * (_gelu(a @ sp["mlp_w1"] + sp["mlp_b1"])
                               @ sp["mlp_w2"] + sp["mlp_b2"])
            out.append(h)
        return tuple(out), None

    def single(h, p):
        p = cast(p)
        sh, sc, gate = (m[:, None] for m in jnp.split(
            sv @ p["mod_w"] + p["mod_b"], 3, axis=-1))
        y = (_layer_norm(h) * (1 + sc) + sh) @ p["linear1_w"] \
            + p["linear1_b"]
        d3 = 3 * h.shape[-1]
        q, k, v = _qkv(y[..., :d3], p, heads, cos, sin)
        o = R.attend(q, k, v)
        o = jnp.concatenate([o.reshape(o.shape[0], o.shape[1], -1),
                             _gelu(y[..., d3:])], axis=-1)
        return h + gate * (o @ p["linear2_w"] + p["linear2_b"]), None

    (tx, img), _ = jax.lax.scan(double, (tx, img), w["double"])
    h, _ = jax.lax.scan(single, jnp.concatenate([tx, img], axis=1),
                        w["single"])
    sh, sc = (m[:, None] for m in jnp.split(
        sv @ top["final_ada_w"] + top["final_ada_b"], 2, axis=-1))
    h = _layer_norm(h[:, lt:]) * (1 + sc) + sh
    return (h @ top["final_out"] + top["final_out_b"]).astype(jnp.float32)


def velocity(w, x, t, txt, guidance, dtype=jnp.float32):
    """DiT velocity for full-sequence tokens x (B, N, patch_in) at
    timesteps t (B,) with text embeddings txt (B, Lt, cond), at the
    guidance scale (1 for an unguided request).  The tokens are taken as
    one square image: a step's items carry no latent grid."""
    side = math.isqrt(x.shape[1])
    if side * side != x.shape[1]:
        raise ValueError(f"{x.shape[1]} tokens are not a square image")
    g = jnp.full(t.shape, 1.0 if guidance is None else guidance,
                 jnp.float32)
    return _velocity(w, x, t, g, txt, (1, side, side), dtype=dtype)


# ---------------------------------------------------------------------------
# guidance
# ---------------------------------------------------------------------------

def rows(guidance) -> tuple:
    """One row, guided or not: the scale is an input of the model."""
    return ("cond",)


def merge(v_rows, guidance):
    return v_rows[0]


# ---------------------------------------------------------------------------
# operations and bytes
# ---------------------------------------------------------------------------
#
# A matmul (rows, K, N) of every row does 2 K N + N (the bias) operations
# a row, reads its input, weight and bias and writes its output once, in
# float32.  Counted where the program runs it: a double block's two
# streams, text (text_len rows) and image (n rows); a single block's
# rows = text_len + n.

def _mm(b, rows, k, n):
    return (b * rows * (2.0 * k * n + n),
            F32 * (b * rows * (k + n) + k * n + n))


def _sum(parts):
    return tuple(float(sum(p[i] for p in parts)) for i in (0, 1))


def _double_mms(m, n, b, lt):
    d, f = m["d_model"], m["d_ff"]
    parts = []
    for r in (lt, n):
        parts += [_mm(b, 1, d, 6 * d), _mm(b, r, d, 3 * d), _mm(b, r, d, d),
                  _mm(b, r, d, f), _mm(b, r, f, d)]
    return parts


def _single_mms(m, n, b, lt):
    d, f = m["d_model"], m["d_ff"]
    s = n + lt
    return [_mm(b, 1, d, 3 * d), _mm(b, s, d, 3 * d + f),
            _mm(b, s, d + f, d)]


def _adaln(m, b, rows, passes, mod_rows, ops_per_elem):
    """adaLN kernel calls over (b, rows, d) tiles: ``passes`` reads and
    writes of a tile, ``mod_rows`` (b, d) modulation rows read, and
    ``ops_per_elem`` operations (norm 8, modulate 2, gate-accumulate 2)."""
    d = m["d_model"]
    return (float(ops_per_elem * b * rows * d),
            float(F32 * (passes * b * rows * d + mod_rows * b * d)))


# per layer: a double block's two modulated norms before the gather (2
# passes, 2 rows each) and, per stream after it, two gated residuals (3
# passes, 1 row) and one modulated norm (2, 2): 10 passes and 12 rows
# over text + image, 24 operations an element; a single block's
# modulated norm (2, 2) and gated residual (3, 1)
def _double_adaln(m, n, b, lt):
    return _adaln(m, b, n + lt, 10, 12, 2 * (10 + 2))


def _single_adaln(m, n, b, lt):
    return _adaln(m, b, n + lt, 5, 3, 10 + 2)


def _elementwise(m, b, rows, mlp_width):
    """QK RMSNorm and rotation of q and k (7 operations a lane each) and
    the MLP's GELU (8 an element), fused into the matmuls' outputs."""
    return (float(b * rows * (2 * 7 * m["d_model"] + 8 * mlp_width)), 0.0)


def step_double_block(m, n, b, text_len):
    """(flops, bytes) of the double blocks' work other than attention in
    one step: modulation, projections, QK norms, RoPE, MLPs, adaLN."""
    per = _sum(_double_mms(m, n, b, text_len)
               + [_double_adaln(m, n, b, text_len),
                  _elementwise(m, b, n + text_len, m["d_ff"])])
    return m["num_layers"] * per[0], m["num_layers"] * per[1]


def step_single_block(m, n, b, text_len):
    """(flops, bytes) of the single blocks' work other than attention in
    one step: modulation, ``linear1``, QK norms, RoPE, GELU, ``linear2``,
    adaLN."""
    per = _sum(_single_mms(m, n, b, text_len)
               + [_single_adaln(m, n, b, text_len),
                  _elementwise(m, b, n + text_len, m["d_ff"])])
    ns = m["num_single_layers"]
    return ns * per[0], ns * per[1]


def step_flash(m, n, b, text_len):
    """(flops, bytes) of every flash call in one step: one joint attention
    over text_len + n rows in each block."""
    s, h, hd = n + text_len, m["num_heads"], m["head_dim"]
    layers = m["num_layers"] + m["num_single_layers"]
    return (layers * flash_flops(b, h, s, s, hd),
            layers * flash_bytes(b, h, s, s, hd))


def step_adaln(m, n, b, text_len):
    """(flops, bytes) of every fused adaLN call in one step: the blocks'
    (above) and the final layer's modulated norm (2 passes, 2 rows)."""
    parts = [_adaln(m, b, n, 2, 2, 10)]
    parts += [_double_adaln(m, n, b, text_len)] * m["num_layers"]
    parts += [_single_adaln(m, n, b, text_len)] * m["num_single_layers"]
    return _sum(parts)


def step_flops(m: dict, n: int, b: int, text_len: int) -> float:
    """One denoise step of the whole model on ``b`` rows of ``n`` image
    tokens: every matmul and attention (elementwise work left out)."""
    d, cond, pooled = m["d_model"], m["cond_dim"], m["pooled_dim"]
    patch_in = m["patch_size"] ** 2 * m["in_channels"]
    embedders = 3 if m["guidance_embeds"] else 2
    head = [_mm(b, n, patch_in, d), _mm(b, text_len, cond, d),
            _mm(b, 1, cond, pooled), _mm(b, 1, pooled, d)]
    head += [_mm(b, 1, 256, d)] * (embedders - 1) + [_mm(b, 1, d, d)] \
        * embedders
    tail = [_mm(b, 1, d, 2 * d), _mm(b, n, d, patch_in)]
    per_double = _sum(_double_mms(m, n, b, text_len))[0]
    per_single = _sum(_single_mms(m, n, b, text_len))[0]
    return float(_sum(head + tail)[0]
                 + m["num_layers"] * per_double
                 + m["num_single_layers"] * per_single
                 + step_flash(m, n, b, text_len)[0])


KERNELS = {"flash_attention": step_flash, "adaln_modulate": step_adaln,
           "double_block": step_double_block,
           "single_block": step_single_block}
