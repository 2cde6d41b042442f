"""Operations and bytes that any DiT step's counts are built from, and the
roofline's least time.

Counts are algorithmic: a multiply-add is two operations, nothing
recomputed, no padding.  The flash kernel pads head_dim 64 to 128 lanes
and the sequence to a multiple of 128; that padding is work the kernel
does but the algorithm does not need, so it shows as lost roofline share.
A whole step's counts, and each kernel's calls in a step, belong to the
configuration's architecture (``bench/archs/<architecture>.py``).
"""
from __future__ import annotations

F32 = 4


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


def least_time(flops: float, nbytes: float, peak: dict) -> tuple[float, str]:
    """(seconds, bound) of the roofline: the larger of the two times."""
    tc = flops / peak["flops_per_s"]
    tm = nbytes / peak["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
