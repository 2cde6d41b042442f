"""Operations and bytes of the served DiT step and its kernels, from shapes.

Counts are algorithmic: a multiply-add is two operations, nothing
recomputed, no padding.  The flash kernel pads head_dim 64 to 128 lanes
and the sequence to a multiple of 128; that padding is work the kernel
does but the algorithm does not need, so it shows as lost roofline share.
``m`` is the ``model`` table of a configuration file.
"""
from __future__ import annotations

F32 = 4


def _inner(m: dict) -> int:
    return m["num_heads"] * m["head_dim"]


def flash_flops(b: int, h: int, nq: int, nk: int, hd: int) -> float:
    """QK^T and PV of one attention call: 4 * B * H * Nq * Nk * hd."""
    return 4.0 * b * h * nq * nk * hd


def flash_bytes(b: int, h: int, nq: int, nk: int, hd: int,
                itemsize: int = F32) -> float:
    """q and o read/written once, k and v read once."""
    return float(itemsize * b * h * hd * (2 * nq + 2 * nk))


def padded_flash_flops(b: int, h: int, nq: int, nk: int, hd: int) -> float:
    """What the kernel executes: sequences and head_dim padded to 128."""
    def up(x):
        return -(-x // 128) * 128
    return flash_flops(b, h, up(nq), up(nk), up(hd))


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


def least_time(flops: float, nbytes: float, peak: dict) -> tuple[float, str]:
    """(seconds, bound) of the roofline: the larger of the two times."""
    tc = flops / peak["flops_per_s"]
    tm = nbytes / peak["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
