"""Flash attention Pallas TPU kernel, with the §11 cache splice fused in.

Blockwise online-softmax attention over a (batch*head, q-block, k-block)
grid: the (block_q x d) query tile stays resident while (block_k x d)
key/value tiles stream through VMEM along the last grid axis, and the
running max / denominator / accumulator live in VMEM scratch across that
axis.  The tiles come from the call's shape (:func:`flash_tiles`): the
largest multiples of 128 that divide the padded lengths, up to 1,280
rows and within a VMEM budget, since the kernel's time follows its
number of grid programs.  VMEM use is one such tile's working set,
independent of sequence length.  head_dim is padded to 128 lanes by the
caller if needed (``sm_scale`` then carries the UNPADDED head dim's
softmax scale).

Supports causal masking (k-blocks above the diagonal are neither fetched
nor computed), GQA (q-head group -> kv-head mapping in the index maps),
and a static ``kv_valid`` key-validity bound so callers can zero-pad the
key axis to a multiple of 128 without the pad keys leaking probability
mass (k-blocks past ``kv_valid`` are neither fetched nor computed).  Only
the k-block holding ``kv_valid`` and, causal, the blocks crossing the
diagonal build a mask.

The §11 cache-hit path (DESIGN.md §11-§12) attends against the stale
snapshot with this rank's fresh shard at rows ``[offset, offset + L)``:
:func:`splice_attention` streams the fresh shard as a second pair of K/V
tiles, block-aligned to the stale stream, and selects fresh rows per
tile before the softmax update.  The spliced (N_total) tensor is never
written; only the L-row fresh shard is re-laid out to block alignment.

TARGET: TPU (pl.pallas_call + BlockSpec).  VALIDATED on CPU with
``interpret=True`` against ``ref.py``'s pure-jnp oracles.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANE = 128
# a call's time on a v5e fell with the number of grid programs up to
# tiles of about 1,024-1,280 rows; 1,536-row tiles ran 15% faster still
# but cost FLUX's first step some 5 s in every process (PERF.md §6)
MAX_BLOCK = 1280
# VMEM that one program's tiles may take, and the scoped limit the
# compiler is given: a 1,280 x 1,280 float32 tile fills the compiler's
# default of 16 MiB, and the splice's fresh K/V tiles come on top (a v5e
# has 128 MiB)
VMEM_BUDGET = 48 << 20
VMEM_LIMIT = 64 << 20


def _vmem_bytes(block_q: int, block_k: int, d: int, itemsize: int) -> int:
    """VMEM one program takes: the double-buffered q/o and k/v tiles,
    the float32 acc and lane-padded m/l scratch, and the float32 score
    tile with the temporaries of its softmax (s, its exp, p)."""
    tiles = 2 * (2 * block_q + 2 * block_k) * d * itemsize
    scratch = (block_q * d + 2 * block_q * LANE) * 4
    return tiles + scratch + 3 * block_q * block_k * 4


def _blocks(n: int, cap: int) -> list[int]:
    """Multiples of 128 that divide ``n``, up to ``cap``."""
    return [b for b in range(LANE, min(n, cap) + 1, LANE) if n % b == 0]


def flash_tiles(sq: int, sk: int, d: int, itemsize: int) -> tuple[int, int]:
    """(block_q, block_k) for a call at 128-padded lengths ``sq`` x ``sk``.

    The largest tile (fewest grid programs, then the larger ``block_q``,
    which streams K/V fewer times) whose blocks divide the lengths, stay
    under ``MAX_BLOCK`` and fit ``VMEM_BUDGET``.  A length is never
    padded past its multiple of 128: 128 always divides.
    """
    assert sq % LANE == 0 and sk % LANE == 0, (sq, sk)
    fits = [(bq, bk) for bq in _blocks(sq, MAX_BLOCK)
            for bk in _blocks(sk, MAX_BLOCK)
            if _vmem_bytes(bq, bk, d, itemsize) <= VMEM_BUDGET]
    return max(fits, key=lambda t: (t[0] * t[1], t[0]))


def _attn_kernel(*refs, block_q: int, block_k: int, causal: bool,
                 sm_scale: float, kv_valid: int,
                 fresh_rows: Optional[tuple[int, int]]):
    """One (batch*head, q-block, k-block) program.

    refs: q (block_q, d), k/v (block_k, d), [fresh k/v (block_k, d)],
    out (block_q, d), then scratch m/l (block_q, 1) and acc (block_q, d).
    """
    if fresh_rows is None:
        q_ref, k_ref, v_ref, o_ref, m_sc, l_sc, acc_sc = refs
    else:
        q_ref, k_ref, v_ref, kf_ref, vf_ref, o_ref, m_sc, l_sc, acc_sc = refs
    q_idx, k_idx = pl.program_id(1), pl.program_id(2)
    k_start = k_idx * block_k

    @pl.when(k_idx == 0)
    def _init():
        m_sc[...] = jnp.full(m_sc.shape, NEG_INF, jnp.float32)
        l_sc[...] = jnp.zeros(l_sc.shape, jnp.float32)
        acc_sc[...] = jnp.zeros(acc_sc.shape, jnp.float32)

    # block 0 always runs and always holds a valid key for every row, so
    # the running max is finite before any fully-masked block is seen
    run = k_start < kv_valid
    if causal:
        run = jnp.logical_and(run, k_start < (q_idx + 1) * block_q)

    def step(masked: bool):
        q = q_ref[...].astype(jnp.float32) * sm_scale
        k = k_ref[...].astype(jnp.float32)
        v = v_ref[...].astype(jnp.float32)
        if fresh_rows is not None:
            lo, hi = fresh_rows
            row = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                                     (block_k, 1), 0)
            fresh = jnp.logical_and(row >= lo, row < hi)
            k = jnp.where(fresh, kf_ref[...].astype(jnp.float32), k)
            v = jnp.where(fresh, vf_ref[...].astype(jnp.float32), v)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if masked:
            col = k_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            keep = col < kv_valid
            if causal:
                qrow = q_idx * block_q + jax.lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 0)
                keep = jnp.logical_and(keep, col <= qrow)
            s = jnp.where(keep, s, NEG_INF)
        m_prev = m_sc[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_sc[...] = l_sc[...] * alpha + p.sum(axis=1, keepdims=True)
        acc_sc[...] = acc_sc[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_sc[...] = m_new

    # only the block holding the kv_valid bound and, causal, the blocks
    # crossing the diagonal build the mask; every other block skips it
    edge = None
    if kv_valid % block_k:
        edge = k_start + block_k > kv_valid
    if causal:
        cross = k_start + block_k - 1 > q_idx * block_q
        edge = cross if edge is None else jnp.logical_or(edge, cross)
    if edge is None:
        pl.when(run)(lambda: step(masked=False))
    else:
        pl.when(jnp.logical_and(run, edge))(lambda: step(masked=True))
        pl.when(jnp.logical_and(run, jnp.logical_not(edge)))(
            lambda: step(masked=False))

    @pl.when(k_idx == pl.num_programs(2) - 1)
    def _finish():
        o_ref[...] = (acc_sc[...] / jnp.maximum(l_sc[...], 1e-30)
                      ).astype(o_ref.dtype)


def _tiles(q, k, block_q, block_k):
    """The blocks a call asked for, else :func:`flash_tiles`'s."""
    tq, tk = flash_tiles(q.shape[1], k.shape[1], q.shape[3],
                         q.dtype.itemsize)
    return block_q or tq, block_k or tk


def _fold_heads(x):
    """(B, S, H, d) -> (B*H, S, d)."""
    b, s, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, s, d)


def _flash_call(q, k, v, fresh, *, causal, block_q, block_k, sm_scale,
                kv_valid, fresh_rows, interpret):
    b, sq, h, d = q.shape
    _, sk, kv, _ = k.shape
    assert h % kv == 0, (h, kv)
    group = h // kv
    assert sq % block_q == 0 and sk % block_k == 0, (sq, sk)
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    if kv_valid is None:
        kv_valid = sk
    assert 0 < kv_valid <= sk, (kv_valid, sk)

    last_k = -(-kv_valid // block_k) - 1

    def kv_block(qb, kb):
        # skipped k-blocks repeat the last fetched index, so the pipeline
        # issues no DMA for them
        kb = jnp.minimum(kb, last_k)
        if causal:
            kb = jnp.minimum(kb, ((qb + 1) * block_q - 1) // block_k)
        return kb

    q_spec = pl.BlockSpec((None, block_q, d), lambda bh, qb, kb: (bh, qb, 0))
    kv_spec = pl.BlockSpec((None, block_k, d),
                           lambda bh, qb, kb: (bh // group,
                                               kv_block(qb, kb), 0))
    operands = [_fold_heads(q), _fold_heads(k), _fold_heads(v)]
    in_specs = [q_spec, kv_spec, kv_spec]
    if fresh is not None:
        first, n_win = fresh_rows[0] // block_k, fresh[0].shape[1] // block_k
        fresh_spec = pl.BlockSpec(
            (None, block_k, d),
            lambda bh, qb, kb: (bh // group,
                                jnp.clip(kb - first, 0, n_win - 1), 0))
        operands += list(fresh)
        in_specs += [fresh_spec, fresh_spec]

    out = pl.pallas_call(
        functools.partial(_attn_kernel, block_q=block_q, block_k=block_k,
                          causal=causal, sm_scale=sm_scale,
                          kv_valid=kv_valid, fresh_rows=fresh_rows),
        grid=(b * h, sq // block_q, sk // block_k),
        in_specs=in_specs,
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, 1), jnp.float32),
                        pltpu.VMEM((block_q, 1), jnp.float32),
                        pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        # the op's name in a profiler trace (flash_attention.N), for the
        # splice path too, whatever wrapper calls it
        name="flash_attention",
    )(*operands)
    return out.reshape(b, h, sq, d).transpose(0, 2, 1, 3)


@functools.partial(
    jax.jit, static_argnames=("causal", "block_q", "block_k", "sm_scale",
                              "kv_valid", "interpret"))
def flash_attention(q, k, v, *, causal: bool = False,
                    block_q: int | None = None, block_k: int | None = None,
                    sm_scale: float | None = None,
                    kv_valid: int | None = None, interpret: bool = True):
    """q: (B, Sq, H, d); k/v: (B, Sk, KV, d) with H % KV == 0.

    Returns (B, Sq, H, d).  Sq/Sk must be multiples of 128 and of the
    block sizes, which default to :func:`flash_tiles`'s (kernels/ops.py
    pads, passing ``kv_valid`` = the true key count so pad keys are
    masked out); d should be MXU-aligned (128) for peak throughput —
    zero-pad d and pass ``sm_scale`` for the original dim.
    """
    block_q, block_k = _tiles(q, k, block_q, block_k)
    return _flash_call(q, k, v, None, causal=causal, block_q=block_q,
                       block_k=block_k, sm_scale=sm_scale,
                       kv_valid=kv_valid, fresh_rows=None,
                       interpret=interpret)


@functools.partial(
    jax.jit, static_argnames=("offset", "block_q", "block_k", "sm_scale",
                              "kv_valid", "interpret"))
def splice_attention(q, k_stale, v_stale, k_fresh, v_fresh, *, offset: int,
                     block_q: int | None = None,
                     block_k: int | None = None,
                     sm_scale: float | None = None,
                     kv_valid: int | None = None, interpret: bool = True):
    """Attention over splice(stale, fresh @ offset), never materialized.

    q: (B, Sq, H, d); k_stale/v_stale: (B, Sk, KV, d);
    k_fresh/v_fresh: (B, L, KV, d) with offset + L <= kv_valid <= Sk.
    Non-causal (the DiT denoise path).  Sq/Sk must be multiples of 128
    and of the block sizes, which default to :func:`flash_tiles`'s
    (kernels/ops.py pads and passes ``kv_valid``).
    """
    sk = k_stale.shape[1]
    local_len = k_fresh.shape[1]
    kv_valid = sk if kv_valid is None else kv_valid
    block_q, block_k = _tiles(q, k_stale, block_q, block_k)
    assert 0 <= offset and offset + local_len <= kv_valid <= sk, \
        (offset, local_len, kv_valid, sk)
    # lay the fresh shard out on the stale stream's block grid: window
    # row j is global row (offset // block_k) * block_k + j
    lead = offset % block_k
    trail = (-(lead + local_len)) % block_k
    pad = ((0, 0), (lead, trail), (0, 0))
    fresh = (jnp.pad(_fold_heads(k_fresh), pad),
             jnp.pad(_fold_heads(v_fresh), pad))
    return _flash_call(q, k_stale, v_stale, fresh, causal=False,
                       block_q=block_q, block_k=block_k, sm_scale=sm_scale,
                       kv_valid=kv_valid,
                       fresh_rows=(offset, offset + local_len),
                       interpret=interpret)
