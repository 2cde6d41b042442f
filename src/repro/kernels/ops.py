"""jit'd public wrappers for the Pallas kernels.

``use_pallas`` selects between the kernel and the jnp reference path —
model code calls these so the kernel is a drop-in layer, not a fork of
the model.  Kernels are compiled when JAX's default backend is a TPU and
run in Pallas interpret mode on any other backend; nothing else selects
the mode.  Wrappers pad non-block-aligned sequence lengths AND head dims
internally (mask-correct via the kernels' ``kv_valid`` bound + an
unpadded ``sm_scale``; outputs are sliced back), so callers never
pre-pad.

``REPRO_USE_PALLAS=1|0`` forces the kernel path on/off regardless of
what the caller (usually ``ModelConfig.use_pallas``) requested
(DESIGN.md §12).
"""
from __future__ import annotations

import dataclasses
import math
import os
from typing import Any

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.adaln import adaln_modulate
from repro.kernels.flash_attention import (flash_attention,
                                           splice_attention as _splice_kernel)
from repro.kernels.ssd import ssd_scan

_TRUTHY = ("1", "true", "yes", "on")


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def use_pallas_enabled(flag: bool) -> bool:
    """Apply the ``REPRO_USE_PALLAS`` env override to a config flag."""
    v = os.environ.get("REPRO_USE_PALLAS")
    if v is None:
        return bool(flag)
    return v.strip().lower() in _TRUTHY


def interpret_mode() -> bool:
    """Pallas interpret mode exactly when the backend is not a TPU."""
    return not _on_tpu()


@dataclasses.dataclass
class SplicedKV:
    """A §11 hit-path KV stream: the stale snapshot plus this step's
    fresh local shard at ``offset``.  The model's layer splices it: the
    Pallas path through :func:`splice_attention`, so the spliced tensor
    is never materialized, the jnp path in its program (DESIGN.md §12)."""
    k_stale: Any                  # (B, N_total, KV, d)
    v_stale: Any
    k_fresh: Any                  # (B, N_local, KV, d)
    v_fresh: Any
    offset: int


def _pad_qkv(q, k, v):
    """Zero-pad (q, k, v) to 128-aligned seq and head dims.

    Returns the padded tensors plus (sq, sk, d) true extents; scores are
    unchanged by zero-padding the contraction dim, pad keys are masked
    via ``kv_valid``, and pad queries/lanes are sliced off the output.
    """
    b, sq, h, d = q.shape
    sk = k.shape[1]
    pq, pk, pd = (-sq) % 128, (-sk) % 128, (-d) % 128
    if pq or pd:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, pd)))
    if pk or pd:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, pd)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, pd)))
    return q, k, v, sq, sk, d


def attention(q, k, v, *, causal: bool = False,
              use_pallas: bool = False):
    """Dispatch: Pallas flash attention when requested/available, else ref.

    Handles DiT-realistic shapes directly: non-multiple-of-128 sequence
    lengths and head dims are padded internally (mask-correct — pad keys
    never receive probability mass) and the output is returned unpadded.
    """
    if not use_pallas_enabled(use_pallas):
        return ref.attention_ref(q, k, v, causal=causal)
    if causal:
        assert q.shape[1] == k.shape[1], \
            "causal kernel path requires aligned q/k lengths"
    qp, kp, vp, sq, sk, d = _pad_qkv(q, k, v)
    out = flash_attention(qp, kp, vp, causal=causal,
                          sm_scale=1.0 / math.sqrt(d), kv_valid=sk,
                          interpret=interpret_mode())
    return out[:, :sq, :, :d]


def splice_attention(q, k_stale, v_stale, k_fresh, v_fresh, *, offset: int,
                     use_pallas: bool = False):
    """§11 hit-path attention over splice(stale, fresh @ offset).

    The Pallas path streams the stale snapshot and the block-aligned
    fresh shard side by side and selects rows per tile
    (kernels/flash_attention.py) — the concatenated KV is never written;
    the ref path materializes it (the jnp oracle).
    """
    if not use_pallas_enabled(use_pallas):
        return ref.splice_attention_ref(q, k_stale, v_stale,
                                        k_fresh, v_fresh, offset=offset)
    qp, kp, vp, sq, sk, d = _pad_qkv(q, k_stale, v_stale)
    pd = (-d) % 128
    if pd:
        k_fresh = jnp.pad(k_fresh, ((0, 0), (0, 0), (0, 0), (0, pd)))
        v_fresh = jnp.pad(v_fresh, ((0, 0), (0, 0), (0, 0), (0, pd)))
    out = _splice_kernel(qp, kp, vp, k_fresh, v_fresh, offset=int(offset),
                         sm_scale=1.0 / math.sqrt(d), kv_valid=sk,
                         interpret=interpret_mode())
    return out[:, :sq, :, :d]


def fused_adaln(x, shift=None, scale=None, gate=None, residual=None, *,
                ln: bool = True, use_pallas: bool = False):
    """Fused (LN +) modulate (+ gated residual); kernels/adaln.py.

    Variants (all one HBM pass on the Pallas path):
      shift/scale only          -> LN(x)*(1+scale)+shift
      gate/residual, ln=False   -> residual + gate*x
      everything                -> residual + gate*(LN(x)*(1+scale)+shift)
    """
    if not use_pallas_enabled(use_pallas):
        return ref.adaln_ref(x, shift, scale, gate, residual, ln=ln)
    b, n, d = x.shape
    pad = (-n) % 128
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        if residual is not None:
            residual = jnp.pad(residual, ((0, 0), (0, pad), (0, 0)))
        out = adaln_modulate(x, shift, scale, gate, residual, ln=ln,
                             interpret=interpret_mode())
        return out[:, :n]
    return adaln_modulate(x, shift, scale, gate, residual, ln=ln,
                          interpret=interpret_mode())


def ssd(x, dt, A, B, C, *, chunk: int = 128, use_pallas: bool = False):
    if not use_pallas_enabled(use_pallas):
        return ref.ssd_ref(x, dt, A, B, C)
    l = x.shape[1]
    pad = (-l) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0)))
        y, state = ssd_scan(x, dt, A, B, C, chunk=chunk,
                            interpret=interpret_mode())
        return y[:, :l], state
    return ssd_scan(x, dt, A, B, C, chunk=chunk, interpret=interpret_mode())
