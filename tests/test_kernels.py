"""Per-kernel shape/dtype sweeps against the pure-jnp oracles
(interpret=True on CPU; the kernels target TPU BlockSpecs)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.adaln import adaln_modulate
from repro.kernels import flash_attention as fa
from repro.kernels.flash_attention import flash_attention, flash_tiles
from repro.kernels.ssd import ssd_scan

KEY = jax.random.PRNGKey(42)


@pytest.mark.parametrize("b,sq,sk,h,kv,d", [
    (1, 128, 128, 2, 2, 64),
    (2, 256, 256, 4, 2, 64),
    (1, 384, 384, 2, 1, 32),
    (1, 128, 256, 2, 2, 128),
])
@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(b, sq, sk, h, kv, d, causal, dtype):
    if causal and sq != sk:
        pytest.skip("causal requires aligned q/k (decode uses masked path)")
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, sq, h, d), dtype)
    k = jax.random.normal(ks[1], (b, sk, kv, d), dtype)
    v = jax.random.normal(ks[2], (b, sk, kv, d), dtype)
    out = flash_attention(q, k, v, causal=causal)
    want = ref.attention_ref(q, k, v, causal=causal)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("sq,sk,tiles", [
    (4096, 4096, (1024, 1024)),     # image-batch-1c self-attention
    (4096, 128, (1024, 128)),       # image-batch-1c cross-attention
    (11520, 11520, (1280, 1280)),   # video-batch-1c, 11,440 rows padded
    (11520, 128, (1280, 128)),      # video-batch-1c cross-attention
    (4608, 4608, (1152, 1152)),     # flux-batch-1c joint attention
    (2176, 2176, (128, 128)),       # 17 x 128: no other divisor fits
    (128, 256, (128, 256)),
])
def test_flash_tiles(sq, sk, tiles):
    """The largest 128-multiple blocks dividing each padded length, up to
    the cap and within the VMEM budget; never a pad past 128."""
    block_q, block_k = flash_tiles(sq, sk, 128, 4)
    assert (block_q, block_k) == tiles
    assert sq % block_q == 0 and sk % block_k == 0
    assert fa._vmem_bytes(block_q, block_k, 128, 4) <= fa.VMEM_BUDGET


def test_flash_tiles_respect_vmem_budget():
    """A wide head shrinks the tile to what the budget holds."""
    block_q, block_k = flash_tiles(4608, 4608, 1024, 4)
    assert fa._vmem_bytes(block_q, block_k, 1024, 4) <= fa.VMEM_BUDGET
    assert block_q * block_k < 1152 * 1152
    assert fa._vmem_bytes(1152, 1152, 1024, 4) > fa.VMEM_BUDGET


@pytest.mark.parametrize("sq,sk,block_q,block_k,kv_valid,causal", [
    (768, 1280, 384, 640, 1230, False),     # pad keys in the last block only
    (768, 1280, 384, 640, 1280, False),     # no pad keys: no block masks
    (1280, 1280, 640, 256, 1000, False),    # pad keys from a middle block on
    (768, 768, 256, 384, 768, True),        # block_q < block_k
    (768, 768, 384, 128, 768, True),        # block_q > block_k
    (768, 768, 384, 256, 700, True),        # diagonal and pad keys
])
def test_flash_attention_tiles(sq, sk, block_q, block_k, kv_valid, causal):
    """Tiles that are not powers of two, or not square, give the oracle's
    attention over the first ``kv_valid`` keys; the keys past it are
    filled with huge values, so any mass they got would show."""
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (1, sq, 2, 64))
    k = jax.random.normal(ks[1], (1, sk, 2, 64)).at[:, kv_valid:].set(1e6)
    v = jax.random.normal(ks[2], (1, sk, 2, 64)).at[:, kv_valid:].set(1e6)
    out = flash_attention(q, k, v, causal=causal, block_q=block_q,
                          block_k=block_k, kv_valid=kv_valid)
    if causal:
        out, q = out[:, :kv_valid], q[:, :kv_valid]
    want = ref.attention_ref(q, k[:, :kv_valid], v[:, :kv_valid],
                             causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("b,n,d", [(1, 128, 64), (2, 256, 128),
                                   (3, 384, 96)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_adaln_sweep(b, n, d, dtype):
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (b, n, d), dtype)
    sh = (jax.random.normal(ks[1], (b, d)) * 0.2).astype(dtype)
    sc = (jax.random.normal(ks[2], (b, d)) * 0.2).astype(dtype)
    g = (jax.random.normal(ks[3], (b, d)) * 0.2).astype(dtype)
    res = jax.random.normal(ks[4], (b, n, d), dtype)
    out = adaln_modulate(x, sh, sc, g, res)
    want = ref.adaln_ref(x, sh, sc, g, res)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("b,l,h,p,n,chunk", [
    (1, 128, 2, 16, 16, 32),
    (2, 256, 4, 32, 16, 64),
    (1, 256, 2, 64, 32, 128),
])
def test_ssd_sweep(b, l, h, p, n, chunk):
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (b, l, h, p), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, l, h)))
    A = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
    B = jax.random.normal(ks[3], (b, l, n))
    C = jax.random.normal(ks[4], (b, l, n))
    y, st = ssd_scan(x, dt, A, B, C, chunk=chunk)
    yr, sr = ref.ssd_ref(x, dt, A, B, C)
    scale = float(np.abs(np.asarray(yr)).max()) + 1e-9
    assert np.abs(np.asarray(y) - np.asarray(yr)).max() / scale < 1e-4
    sscale = float(np.abs(np.asarray(sr)).max()) + 1e-9
    assert np.abs(np.asarray(st) - np.asarray(sr)).max() / sscale < 1e-4


def test_ops_dispatch_pads_odd_shapes():
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (1, 100, 2, 64))
    k = jax.random.normal(ks[1], (1, 100, 2, 64))
    v = jax.random.normal(ks[2], (1, 100, 2, 64))
    out = ops.attention(q, k, v, causal=True, use_pallas=True)
    want = ref.attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("b,sq,sk,h,kv,d", [
    (1, 100, 100, 2, 2, 64),        # odd seq, even head dim
    (1, 64, 200, 6, 2, 48),         # GQA 3:1, odd everything
    (2, 37, 91, 4, 4, 32),          # small odd shapes, short head dim
    (1, 130, 130, 2, 1, 96),        # just past one block, MQA
])
def test_ops_attention_internal_padding(b, sq, sk, h, kv, d):
    """Non-multiple-of-128 seq lengths AND head dims are padded inside
    ops.attention (mask-correct: pad keys get no probability mass)."""
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, sq, h, d))
    k = jax.random.normal(ks[1], (b, sk, kv, d))
    v = jax.random.normal(ks[2], (b, sk, kv, d))
    out = ops.attention(q, k, v, causal=False, use_pallas=True)
    want = ref.attention_ref(q, k, v, causal=False)
    assert out.shape == want.shape
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("offset,local", [(0, 32), (32, 32), (68, 60),
                                          (96, 32)])
@pytest.mark.parametrize("n_total", [128, 200])
def test_splice_attention_vs_oracle(offset, local, n_total):
    """§11 fused cache-splice vs materialize-then-attend oracle."""
    if offset + local > n_total:
        pytest.skip("fresh shard must fit inside the snapshot")
    ks = jax.random.split(KEY, 5)
    b, h, d = 2, 4, 64
    q = jax.random.normal(ks[0], (b, n_total, h, d))
    k_st = jax.random.normal(ks[1], (b, n_total, h, d))
    v_st = jax.random.normal(ks[2], (b, n_total, h, d))
    k_fr = jax.random.normal(ks[3], (b, local, h, d))
    v_fr = jax.random.normal(ks[4], (b, local, h, d))
    out = ops.splice_attention(q, k_st, v_st, k_fr, v_fr, offset=offset,
                               use_pallas=True)
    want = ref.splice_attention_ref(q, k_st, v_st, k_fr, v_fr,
                                    offset=offset)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


def test_splice_attention_gqa_odd_head_dim():
    ks = jax.random.split(KEY, 5)
    b, n, h, kv, d, local, off = 1, 150, 6, 2, 48, 50, 75
    q = jax.random.normal(ks[0], (b, n, h, d))
    k_st = jax.random.normal(ks[1], (b, n, kv, d))
    v_st = jax.random.normal(ks[2], (b, n, kv, d))
    k_fr = jax.random.normal(ks[3], (b, local, kv, d))
    v_fr = jax.random.normal(ks[4], (b, local, kv, d))
    out = ops.splice_attention(q, k_st, v_st, k_fr, v_fr, offset=off,
                               use_pallas=True)
    want = ref.splice_attention_ref(q, k_st, v_st, k_fr, v_fr, offset=off)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("n_total,offset,local", [
    (3072, 900, 300),       # 1,024-row blocks: the shard straddles two
    (3000, 2000, 800),      # padded to 3,072: pad keys after the shard
    (1024, 256, 256),       # one 1,024 x 1,024 tile, an aligned shard
])
def test_splice_attention_rule_tiles(n_total, offset, local):
    """§11 splice at the tiles ``flash_tiles`` picks, larger than 128."""
    padded = -(-n_total // 128) * 128
    assert min(flash_tiles(padded, padded, 64, 4)) > 128
    ks = jax.random.split(KEY, 5)
    b, h, d = 1, 2, 64
    q = jax.random.normal(ks[0], (b, n_total, h, d))
    k_st = jax.random.normal(ks[1], (b, n_total, h, d))
    v_st = jax.random.normal(ks[2], (b, n_total, h, d))
    k_fr = jax.random.normal(ks[3], (b, local, h, d))
    v_fr = jax.random.normal(ks[4], (b, local, h, d))
    out = ops.splice_attention(q, k_st, v_st, k_fr, v_fr, offset=offset,
                               use_pallas=True)
    want = ref.splice_attention_ref(q, k_st, v_st, k_fr, v_fr,
                                    offset=offset)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("variant", ["mod_norm", "gated", "full"])
@pytest.mark.parametrize("n", [128, 100])
def test_fused_adaln_variants(variant, n):
    """All three statically-selected fusion variants vs the oracle,
    at block-aligned and internally-padded lengths."""
    ks = jax.random.split(KEY, 5)
    b, d = 2, 64
    x = jax.random.normal(ks[0], (b, n, d))
    sh = jax.random.normal(ks[1], (b, d)) * 0.2
    sc = jax.random.normal(ks[2], (b, d)) * 0.2
    g = jax.random.normal(ks[3], (b, d)) * 0.2
    res = jax.random.normal(ks[4], (b, n, d))
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
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


def test_env_override_forces_path(monkeypatch):
    """REPRO_USE_PALLAS overrides the caller's flag in both directions."""
    monkeypatch.setenv("REPRO_USE_PALLAS", "0")
    assert not ops.use_pallas_enabled(True)
    monkeypatch.setenv("REPRO_USE_PALLAS", "1")
    assert ops.use_pallas_enabled(False)
    monkeypatch.delenv("REPRO_USE_PALLAS")
    assert ops.use_pallas_enabled(True)
    assert not ops.use_pallas_enabled(False)


def test_env_override_numerics(monkeypatch):
    """With the env var forcing the kernel on, a use_pallas=False call
    runs the kernel path — and still matches the oracle."""
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (1, 100, 2, 48))
    k = jax.random.normal(ks[1], (1, 100, 2, 48))
    v = jax.random.normal(ks[2], (1, 100, 2, 48))
    want = ref.attention_ref(q, k, v, causal=False)
    monkeypatch.setenv("REPRO_USE_PALLAS", "1")
    out = ops.attention(q, k, v, causal=False, use_pallas=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=1e-5, rtol=1e-5)
    monkeypatch.setenv("REPRO_USE_PALLAS", "0")
    out = ops.attention(q, k, v, causal=False, use_pallas=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))


def test_interpret_mode_follows_backend(monkeypatch):
    """Kernels compile on a TPU backend and interpret everywhere else."""
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    assert not ops.interpret_mode()
    monkeypatch.setattr(ops, "_on_tpu", lambda: False)
    assert ops.interpret_mode()


def test_kernel_matches_model_ssd_path():
    """Kernel vs the model's chunked-jnp SSD (two independent impls)."""
    from repro.models.ssm import ssd_chunked
    ks = jax.random.split(KEY, 5)
    b, l, h, p, n = 2, 128, 4, 16, 8
    x = jax.random.normal(ks[0], (b, l, h, p), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, l, h)))
    A = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
    B = jax.random.normal(ks[3], (b, l, n))
    C = jax.random.normal(ks[4], (b, l, n))
    y1, s1 = ssd_scan(x, dt, A, B, C, chunk=32)
    y2, s2 = ssd_chunked(x, dt, A, B, C, chunk=32)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               atol=1e-4, rtol=1e-4)
