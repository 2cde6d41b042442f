"""device_idle_share: percent of the traced window in which no operation
ran on the device (one minus the union of device op intervals over the
window's length)."""
from gfbench import trace


def read(run):
    if run["trace"] is None:
        return None
    a, b = run["trace"]["span"]
    return 100.0 * (1.0 - trace.busy_ns(run["trace"]["events"], a, b)
                    / (b - a))
