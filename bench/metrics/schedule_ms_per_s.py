"""schedule_ms_per_s: host milliseconds spent inside the control plane's
``schedule_point`` (the benchmark's ``bench.schedule_point`` wrapper)
per second of the window."""
from gfbench import window


def read(run):
    w0, w1 = run["window"]["w0"], run["window"]["w1"]
    busy = sum(max(0.0, min(b, w1) - max(a, w0))
               for name, a, b in run["spans"]
               if name == "bench.schedule_point")
    return 1000.0 * busy / run["window"]["seconds"]
