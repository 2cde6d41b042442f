"""Property tests for the Pallas fast path (DESIGN.md §12).

Randomized shape/dtype/offset sweeps against the jnp oracles in
kernels/ref.py: odd sequence lengths (internal padding), GQA head
ratios, bf16/fp32, causal block-skipping, and §11 splice offsets.
Runs under hypothesis when installed (CI: requirements-dev.txt); a
seeded fallback sweep below keeps a subset exercised without it.

All kernels run interpret-mode on CPU; properties are shape/masking
properties, so a handful of examples per property suffices
(max_examples is kept small — each example is an interpreted kernel).
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

hyp = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

KEY = jax.random.PRNGKey(7)

SETTINGS = dict(max_examples=12, deadline=None)


def _tol(dtype):
    return 1e-5 if dtype == jnp.float32 else 2e-2


def _qkv(sq, sk, h, kv, d, dtype):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (1, sq, h, d), dtype)
    k = jax.random.normal(ks[1], (1, sk, kv, d), dtype)
    v = jax.random.normal(ks[2], (1, sk, kv, d), dtype)
    return q, k, v


@settings(**SETTINGS)
@given(sq=st.integers(1, 300), sk=st.integers(1, 300),
       heads=st.sampled_from([(2, 2), (4, 2), (6, 2), (8, 1)]),
       d=st.sampled_from([32, 48, 64, 96, 128]),
       dtype=st.sampled_from([jnp.float32, jnp.bfloat16]))
def test_attention_any_shape_matches_oracle(sq, sk, heads, d, dtype):
    h, kv = heads
    q, k, v = _qkv(sq, sk, h, kv, d, dtype)
    out = ops.attention(q, k, v, causal=False, use_pallas=True)
    want = ref.attention_ref(q, k, v, causal=False)
    assert out.shape == want.shape
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               atol=_tol(dtype), rtol=_tol(dtype))


@settings(**SETTINGS)
@given(n=st.integers(1, 300), d=st.sampled_from([32, 64]),
       dtype=st.sampled_from([jnp.float32, jnp.bfloat16]))
def test_causal_block_skipping_matches_oracle(n, d, dtype):
    # causal needs aligned q/k; block skipping means upper k-blocks are
    # never visited — the mask must still be exact at every length
    q, k, v = _qkv(n, n, 2, 2, d, dtype)
    out = ops.attention(q, k, v, causal=True, use_pallas=True)
    want = ref.attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               atol=_tol(dtype), rtol=_tol(dtype))


@settings(**SETTINGS)
@given(data=st.data(), n_total=st.integers(16, 260),
       d=st.sampled_from([48, 64]),
       heads=st.sampled_from([(2, 2), (6, 2)]))
def test_splice_any_offset_matches_oracle(data, n_total, d, heads):
    h, kv = heads
    local = data.draw(st.integers(1, n_total), label="local")
    offset = data.draw(st.integers(0, n_total - local), label="offset")
    ks = jax.random.split(KEY, 5)
    q = jax.random.normal(ks[0], (1, n_total, h, d))
    k_st = jax.random.normal(ks[1], (1, n_total, kv, d))
    v_st = jax.random.normal(ks[2], (1, n_total, kv, d))
    k_fr = jax.random.normal(ks[3], (1, local, kv, d))
    v_fr = jax.random.normal(ks[4], (1, local, kv, d))
    out = ops.splice_attention(q, k_st, v_st, k_fr, v_fr, offset=offset,
                               use_pallas=True)
    want = ref.splice_attention_ref(q, k_st, v_st, k_fr, v_fr,
                                    offset=offset)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


@settings(**SETTINGS)
@given(n=st.integers(1, 300), d=st.sampled_from([64, 96]),
       variant=st.sampled_from(["mod_norm", "gated", "full"]),
       dtype=st.sampled_from([jnp.float32, jnp.bfloat16]))
def test_adaln_any_length_matches_oracle(n, d, variant, dtype):
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (2, n, d), dtype)
    sh = (jax.random.normal(ks[1], (2, d)) * 0.2).astype(dtype)
    sc = (jax.random.normal(ks[2], (2, d)) * 0.2).astype(dtype)
    g = (jax.random.normal(ks[3], (2, d)) * 0.2).astype(dtype)
    res = jax.random.normal(ks[4], (2, n, d), dtype)
    if variant == "mod_norm":
        out = ops.fused_adaln(x, sh, sc, use_pallas=True)
        want = ref.adaln_ref(x, sh, sc)
    elif variant == "gated":
        out = ops.fused_adaln(x, gate=g, residual=res, ln=False,
                              use_pallas=True)
        want = ref.adaln_ref(x, gate=g, residual=res, ln=False)
    else:
        out = ops.fused_adaln(x, sh, sc, g, res, use_pallas=True)
        want = ref.adaln_ref(x, sh, sc, g, res)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol, rtol=tol)


def test_attention_pad_keys_carry_no_mass():
    """Masking, not luck: filling the region beyond ``kv_valid`` with
    huge values must not change the kernel's output one bit."""
    from repro.kernels.flash_attention import flash_attention
    q, k, v = _qkv(128, 256, 2, 2, 64, jnp.float32)
    # valid=100: rows 100-127 exercise the partial-block mask, rows
    # 128-255 exercise block skipping (that block is never visited)
    clean = flash_attention(q, k, v, kv_valid=100, block_k=128)
    kw = k.at[:, 100:].set(1e6)
    vw = v.at[:, 100:].set(1e6)
    dirty = flash_attention(q, kw, vw, kv_valid=100, block_k=128)
    np.testing.assert_array_equal(np.asarray(clean), np.asarray(dirty))
    want = ref.attention_ref(q, k[:, :100], v[:, :100], causal=False)
    np.testing.assert_allclose(np.asarray(clean), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


def test_sm_scale_uses_true_head_dim():
    """Internal head-dim padding must not change the softmax scale."""
    q, k, v = _qkv(64, 64, 2, 2, 48, jnp.float32)
    out = ops.attention(q, k, v, causal=False, use_pallas=True)
    # oracle with explicit 1/sqrt(48): ref scales by true d as well
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(48)
    want = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=1e-5, rtol=1e-5)
