"""Fused adaLN-Zero modulate Pallas TPU kernel.

The paper's DiT blocks apply (LN -> scale/shift modulate -> gate ->
residual add) six tensor-wide passes per block per denoise step.  Unfused,
each pass round-trips the (B, N, D) activation through HBM; this kernel
fuses the elementwise stages into ONE pass: a (block_n, D) token tile is
loaded to VMEM once, normalized with an in-tile reduction, scaled, gated
and accumulated, saving the intermediate HBM round-trips.

Three statically-selected variants cover every modulation site in the DiT
block (DESIGN.md §12):

* ``shift/scale`` only              -> LN(x)*(1+scale)+shift
  (the pre-branch "modulated norm"; ``shift=scale=None`` degenerates to
  a bare fused LayerNorm, used before cross-attention)
* ``gate/residual`` with ``ln=False`` -> residual + gate*x
  (the post-branch gated residual accumulate)
* all operands                       -> residual + gate*(LN(x)*(1+scale)+shift)
  (the full fusion, when no op intervenes between norm and accumulate)

TARGET: TPU.  VALIDATED with interpret=True vs ref.adaln_ref.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _adaln_kernel(*refs, eps: float, ln: bool, has_mod: bool,
                  has_gate: bool):
    """One (batch, n-block) program.

    refs order: x, [shift, scale], [gate, residual], out.
    x/residual/out: (block_n, D) VMEM tiles; shift/scale/gate: (1, D)
    per-batch modulation rows.
    """
    it = iter(refs)
    x_ref = next(it)
    shift_ref = scale_ref = None
    if has_mod:
        shift_ref, scale_ref = next(it), next(it)
    gate_ref = res_ref = None
    if has_gate:
        gate_ref, res_ref = next(it), next(it)
    o_ref = next(it)

    x = x_ref[...].astype(jnp.float32)
    if ln:
        mu = x.mean(axis=1, keepdims=True)
        var = ((x - mu) ** 2).mean(axis=1, keepdims=True)
        x = (x - mu) * jax.lax.rsqrt(var + eps)
    if has_mod:
        x = x * (1.0 + scale_ref[...].astype(jnp.float32)) \
            + shift_ref[...].astype(jnp.float32)
    if has_gate:
        x = res_ref[...].astype(jnp.float32) \
            + gate_ref[...].astype(jnp.float32) * x
    o_ref[...] = x.astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("block_n", "eps", "ln", "interpret"))
def adaln_modulate(x, shift=None, scale=None, gate=None, residual=None, *,
                   block_n: int = 128, eps: float = 1e-6, ln: bool = True,
                   interpret: bool = True):
    """Fused (LN +) modulate (+ gate + residual); see module docstring.

    x/residual: (B, N, D); shift/scale/gate: (B, D).
    N must be a multiple of block_n (kernels/ops.py pads); shift/scale
    and gate/residual must be given (or omitted) together.
    """
    b, n, d = x.shape
    assert n % block_n == 0, (n, block_n)
    has_mod = shift is not None
    has_gate = gate is not None
    assert has_mod == (scale is not None), "shift/scale go together"
    assert has_gate == (residual is not None), "gate/residual go together"
    assert ln or has_mod or has_gate, "identity fusion requested"

    tile = pl.BlockSpec((None, block_n, d), lambda i, j: (i, j, 0))
    # modulation rows as (B, 1, D): the block's last two dims then equal
    # the array's, which the TPU lowering needs at any batch size
    row = pl.BlockSpec((None, 1, d), lambda i, j: (i, 0, 0))
    out_shape = jax.ShapeDtypeStruct((b, n, d), x.dtype)
    if not interpret:       # the interpreter takes no memory spaces
        # the (B, N, D) tensors stay in HBM: that is the one pass the
        # kernel fuses, and what its roofline counts; inside a larger
        # compiled program XLA would otherwise keep those that fit in
        # VMEM there
        x = pltpu.with_memory_space_constraint(x, pltpu.HBM)
        if has_gate:
            residual = pltpu.with_memory_space_constraint(residual,
                                                          pltpu.HBM)
        out_shape = pltpu.HBM(out_shape.shape, out_shape.dtype)
    operands, in_specs = [x], [tile]
    if has_mod:
        operands += [shift[:, None], scale[:, None]]
        in_specs += [row, row]
    if has_gate:
        operands += [gate[:, None], residual]
        in_specs += [row, tile]
    grid = (b, n // block_n)
    return pl.pallas_call(
        functools.partial(_adaln_kernel, eps=eps, ln=ln, has_mod=has_mod,
                          has_gate=has_gate),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((None, block_n, d), lambda i, j: (i, j, 0)),
        out_shape=out_shape,
        interpret=interpret,
        name="adaln_modulate",      # the op's name in a profiler trace
    )(*operands)
