"""setup_s: seconds from the start of the process to the opening of the
window: imports, the engine and its weights, and the warm-up that loads
(or, on a checkout's first run, compiles) every program the cell uses."""


def read(run):
    return run["setup_s"]
