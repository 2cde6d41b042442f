"""The program's own spans in a reduced trace, and what they say about
the device's idle time.

The program marks each boundary of a step's life with a ``gfdit.*``
region (``Telemetry.region``, DESIGN.md §15): a
``jax.profiler.TraceAnnotation`` on the clock of the device's ops.  In
the reduced form that ``trace.load`` gives they are host events
``[name, start, duration, stats]`` beside the device's ops.  Against the
device's busy intervals (``trace.busy_intervals``) they split the traced
window's idle time three ways, with nothing left over, since a step's
forward lies inside its task:

* under an open ``gfdit.step.forward``: the host issuing the model's
  ops behind the device;
* under no open ``gfdit.task.*``: between tasks, in the event loop, the
  control plane and dispatch;
* inside a task but outside its forward: the step's inputs, update and
  fetch, and encodes and decodes.

A region entered before the profiler started is not in the trace, so the
step in flight when the window opens has no task or forward span.  The
split and the clock check therefore cover the window from the first
task span that opened inside it.

Each function takes a run's records and returns None where the trace
holds none of the spans it reads, as from a program without them.
"""
from __future__ import annotations

import statistics
from typing import Optional

from gfbench import trace

TASK = "gfdit.task."
FORWARD = "gfdit.step.forward"
COMPLETE = "gfdit.plane.complete"
SCHEDULE = "gfdit.plane.schedule"


def _traced(run: dict):
    if run["trace"] is None:
        return None, 0, 0
    a, b = run["trace"]["span"]
    return run["trace"]["events"], a, b


def _from_first_task(run: dict):
    """The trace, and the window from the first task span opened in it
    to its end (None where no task opened in it)."""
    tr, a, b = _traced(run)
    if tr is None:
        return None, 0, 0
    first = min((s for name, s, _, _ in tr["host"]
                 if name.startswith(TASK) and a <= s < b), default=None)
    if first is None:
        return None, 0, 0
    return tr, first, b


def _named(name: str, want: str) -> bool:
    return name.startswith(want) if want.endswith(".") else name == want


def clipped(tr: dict, want: str, t0: float, t1: float) -> list:
    """Merged [start, end] intervals inside [t0, t1] in which a host span
    named ``want`` (a prefix where it ends in a dot) is open."""
    out: list = []
    for a, b in sorted((max(s, t0), min(s + d, t1))
                       for name, s, d, _ in tr["host"]
                       if _named(name, want)):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def whole(tr: dict, want: str, t0: float, t1: float) -> list:
    """(duration, stats) of the host spans named ``want`` that lie wholly
    inside [t0, t1]."""
    return [(d, stats) for name, s, d, stats in tr["host"]
            if name == want and t0 <= s and s + d <= t1]


def idle_intervals(tr: dict, t0: float, t1: float) -> list:
    """The stretches of [t0, t1] in which no op ran on the device."""
    out, at = [], t0
    for a, b in trace.busy_intervals(tr, t0, t1) + [[t1, t1]]:
        if a > at:
            out.append([at, a])
        at = max(at, b)
    return out


def overlap(xs: list, ys: list) -> float:
    """Length of the intersection of two sorted lists of disjoint
    intervals."""
    total, i, j = 0.0, 0, 0
    while i < len(xs) and j < len(ys):
        a, b = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if b > a:
            total += b - a
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return total


def idle_split(run: dict) -> Optional[dict]:
    """Percent of the window, from its first task, with no device op
    (``idle``), and its three parts: under a forward (``forward``), under
    no task (``between``) and the rest of task time (``in_task``)."""
    tr, a, b = _from_first_task(run)
    if tr is None:
        return None
    tasks = clipped(tr, TASK, a, b)
    idle = idle_intervals(tr, a, b)
    total = sum(e - s for s, e in idle)
    in_tasks = overlap(idle, tasks)
    forward = overlap(idle, clipped(tr, FORWARD, a, b))
    pct = 100.0 / (b - a)
    return {"idle": total * pct, "forward": forward * pct,
            "between": (total - in_tasks) * pct,
            "in_task": (in_tasks - forward) * pct}


def busy_outside_tasks_share(run: dict) -> Optional[float]:
    """Percent of the device's busy time in the window, from its first
    task, outside every ``gfdit.task.*`` span: near 0 when the program's
    spans and the device's ops lie on one clock."""
    tr, a, b = _from_first_task(run)
    if tr is None:
        return None
    tasks = clipped(tr, TASK, a, b)
    busy = trace.busy_intervals(tr, a, b)
    total = sum(e - s for s, e in busy)
    if not total:
        return None
    return 100.0 * (total - overlap(busy, tasks)) / total


def _median_ms(values: list, scale: float) -> Optional[float]:
    return statistics.median(values) * scale if values else None


def forward_dispatch_ms(run: dict) -> Optional[float]:
    """Median host milliseconds spent in one forward, issuing its ops
    and waiting wherever the device's queue holds the host back: the
    duration of the ``gfdit.step.forward`` spans wholly inside the
    window."""
    tr, a, b = _traced(run)
    if tr is None:
        return None
    return _median_ms([d for d, _ in whole(tr, FORWARD, a, b)], 1e-6)


def completion_wait_ms(run: dict) -> Optional[float]:
    """Median milliseconds a finished task waited before the control
    plane handled it: ``wait_us`` of the ``gfdit.plane.complete`` spans
    wholly inside the window."""
    tr, a, b = _traced(run)
    if tr is None:
        return None
    return _median_ms([st["wait_us"] for _, st in whole(tr, COMPLETE, a, b)],
                      1e-3)


def schedule_useful_share(run: dict) -> Optional[float]:
    """Percent of the schedule points wholly inside the window
    (``gfdit.plane.schedule``) that applied at least one action."""
    tr, a, b = _traced(run)
    if tr is None:
        return None
    points = whole(tr, SCHEDULE, a, b)
    if not points:
        return None
    return 100.0 * sum(1 for _, st in points if st["actions"] >= 1) \
        / len(points)
