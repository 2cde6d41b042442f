"""Window accounting: what one run did inside its measured window.

Times are plane seconds (the serving clock).  ``w0`` and ``w1`` are the
window's edges.  A step that straddles an edge counts by the fraction of
its dispatch-to-completion span inside the window.  A request is judged
when it falls due inside the window; one still unfinished when the run
stops waiting for it is neither failed nor dropped, only counted as
unfinished.
"""
from __future__ import annotations


def inside(start: float, end: float, w0: float, w1: float) -> float:
    """Fraction of [start, end] that lies in [w0, w1]."""
    if end <= start:
        return 1.0 if w0 <= end <= w1 else 0.0
    return max(0.0, min(end, w1) - max(start, w0)) / (end - start)


def denoise_steps(rec: dict) -> list:
    """Denoise completions of the window's requests, each with the share
    of it inside the window (``share``), excluding failed ones."""
    w0, w1 = rec["window"]["w0"], rec["window"]["w1"]
    out = []
    for s in rec["steps"]:
        if s["kind"] != "denoise" or s["failed"]:
            continue
        share = inside(s["start"], s["finish"], w0, w1)
        if share > 0:
            out.append(dict(s, share=share))
    return out


def judged(rec: dict) -> list:
    """Requests due inside the window."""
    w0, w1 = rec["window"]["w0"], rec["window"]["w1"]
    return [r for r in rec["requests"].values() if w0 <= r["due"] < w1]


def attempted(rec: dict) -> int:
    return len(judged(rec))


def failed(rec: dict) -> int:
    """Requests the plane failed, plus worker errors and collective
    timeouts: a request still in flight is never counted here."""
    return (sum(1 for r in judged(rec) if r["failed"])
            + rec["worker_errors"] + rec["collective_timeouts"])


def unfinished(rec: dict) -> int:
    """Judged requests not done when the run stopped waiting."""
    return sum(1 for r in judged(rec) if r["done"] is None
               and not r["failed"])


def ms_per_ktoken(rec: dict):
    """Host milliseconds of denoise completions (dispatch to last rank
    done) per thousand latent tokens they denoised, each step weighted by
    its share inside the window; a guided step counts its tokens once."""
    steps = denoise_steps(rec)
    ktok = sum(s["tokens"] * s["share"] for s in steps) / 1000.0
    if not ktok:
        return None
    return sum(s["duration"] * s["share"] for s in steps) * 1000.0 / ktok
