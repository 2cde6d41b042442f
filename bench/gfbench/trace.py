"""Reduce a profiler trace to the events the metrics read, and those to
numbers.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote and keeps
two lists, on the trace's own clock (nanoseconds):

* ``device``: every operation on the device's ``XLA Ops`` line
  (``[op, module, start, duration]``), where ``op`` is the HLO
  instruction's name (``fusion.3``, or ``flash_attention.1`` for the
  Pallas kernel's custom call) and ``module`` the compiled program it
  ran in, from the ``XLA Modules`` line (eager JAX runs each op, and
  each jitted kernel wrapper, as a program of its own: ``jit_silu``,
  ``jit_flash_attention``);
* ``host``: the benchmark's own ``bench.*`` spans
  (``[name, start, duration, metadata]``), among them
  ``bench.traced_window``, which bounds the traced window, and
  ``bench.exec.denoise`` with the step's ``tokens`` and ``rows``.

The rest works on that reduced form, which the tests feed from a small
recorded trace kept beside them.
"""
from __future__ import annotations

import bisect
import glob
import os
import sys

from gfbench import flops

DEVICE_PLANE = "/device:"
OPS, MODULES = "XLA Ops", "XLA Modules"
# what the host was doing, most telling first, when a device gap falls
# under several bench spans at once
HOST_ORDER = ("bench.exec.", "bench.all_gather", "bench.schedule_point",
              "bench.clock_wait")


def _op_name(text: str) -> str:
    """``%fusion.3 = f32[...] fusion(...)`` -> ``fusion.3``."""
    return text.split(" = ", 1)[0].lstrip("%")


def _with_modules(ops: list, modules: list) -> list:
    """Give each op the module whose run covers its start."""
    modules.sort(key=lambda m: m[1])
    starts = [m[1] for m in modules]
    out = []
    for name, start, dur in ops:
        i = bisect.bisect_right(starts, start) - 1
        mod = ""
        if i >= 0 and start <= modules[i][1] + modules[i][2]:
            mod = modules[i][0].split("(", 1)[0]
        out.append([name, mod, start, dur])
    return out


def load(trace_dir: str) -> dict:
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no trace under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    ops, modules, host = [], [], []
    for plane in data.planes:
        on_device = plane.name.startswith(DEVICE_PLANE)
        for line in plane.lines:
            events = list(line.events)
            if on_device and line.name == OPS:
                ops += [[_op_name(e.name), e.start_ns, e.duration_ns]
                        for e in events]
            elif on_device and line.name == MODULES:
                modules += [[e.name, e.start_ns, e.duration_ns]
                            for e in events]
            elif not on_device:
                for e in events:
                    if e.name.startswith("bench."):
                        host.append([e.name, e.start_ns, e.duration_ns,
                                     {k: v for k, v in e.stats
                                      if isinstance(v, (int, float))}])
    return {"device": _with_modules(ops, modules), "host": host}


def window(tr: dict) -> tuple[float, float]:
    """(start, end) of the traced window, from its host span."""
    spans = [h for h in tr["host"] if h[0] == "bench.traced_window"]
    if not spans:
        raise ValueError("trace holds no bench.traced_window span")
    _, start, dur, _ = spans[0]
    return start, start + dur


def _clip(events, t0, t1):
    for name, module, s, d in events:
        a, b = max(s, t0), min(s + d, t1)
        if b > a:
            yield name, module, a, b


def busy_intervals(tr: dict, t0: float, t1: float) -> list:
    """Union of device op intervals inside [t0, t1], sorted."""
    spans = sorted((a, b) for _, _, a, b in _clip(tr["device"], t0, t1))
    out: list = []
    for a, b in spans:
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def busy_ns(tr: dict, t0: float, t1: float) -> float:
    return float(sum(b - a for a, b in busy_intervals(tr, t0, t1)))


def top_ops(tr: dict, t0: float, t1: float, n: int = 10) -> list:
    """[[module:op, seconds]]: the device ops that took the most time in
    the window, summed by program and instruction name."""
    tot: dict = {}
    for name, module, a, b in _clip(tr["device"], t0, t1):
        key = f"{module}:{name}" if module else name
        tot[key] = tot.get(key, 0.0) + (b - a)
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v * 1e-9] for k, v in best]


def _host_at(tr: dict, t: float) -> str:
    """What the host was doing at t: of the bench spans covering it, the
    first in ``HOST_ORDER`` (a step being executed explains a gap better
    than the event loop waiting for it)."""
    names = {name for name, s, d, _ in tr["host"]
             if name != "bench.traced_window" and s <= t <= s + d}
    for prefix in HOST_ORDER:
        for name in sorted(names):
            if name.startswith(prefix):
                return name
    return "no bench span"


def idle_gaps(tr: dict, t0: float, t1: float, n: int = 10) -> list:
    """[[host span, seconds]]: the longest stretches with no device op,
    each named by what the host was doing at its middle."""
    gaps, at = [], t0
    for a, b in busy_intervals(tr, t0, t1) + [[t1, t1]]:
        if a > at:
            gaps.append((a - at, _host_at(tr, (at + a) / 2)))
        at = max(at, b)
    gaps.sort(key=lambda g: -g[0])
    return [[name, d * 1e-9] for d, name in gaps[:n]]


def denoise_spans(tr: dict, t0: float, t1: float) -> list:
    """Host spans of denoise steps wholly inside [t0, t1]:
    [(start, end, tokens, rows)]."""
    out = []
    for name, s, d, meta in tr["host"]:
        if name == "bench.exec.denoise" and s >= t0 and s + d <= t1:
            out.append((s, s + d, int(meta["tokens"]), int(meta["rows"])))
    return out


def kernel_ns(tr: dict, spans: list, match) -> float:
    """Device time of the ops ``match(module, name)`` selects, inside the
    given host spans."""
    total = 0.0
    for s, e, *_ in spans:
        for name, module, a, b in _clip(tr["device"], s, e):
            if match(module, name):
                total += b - a
    return total


def roofline_share(run: dict, per_step, match, name: str):
    """Percent of its roofline that a kernel reached in the traced
    window: the least time of its calls in the denoise steps wholly
    inside the window (``per_step(model, tokens, rows, text_len)`` gives
    a step's operations and bytes) over the device time of the ops
    ``match`` selects in those steps.  None where nothing was traced."""
    if run["trace"] is None:
        return None
    tr = run["trace"]["events"]
    a, b = run["trace"]["span"]
    spans = denoise_spans(tr, a, b)
    took = kernel_ns(tr, spans, match) * 1e-9
    if not spans or took <= 0:
        return None
    least, bounds = 0.0, set()
    for _, _, n, rows in spans:
        t, bound = flops.least_time(
            *per_step(run["model"], n, rows, run["text_len"]), run["peak"])
        least += t
        bounds.add(bound)
    print(f"{name}: {'/'.join(sorted(bounds))}-bound, {len(spans)} steps, "
          f"kernel {took:.6f} s, least {least:.6f} s", file=sys.stderr)
    return 100.0 * least / took
