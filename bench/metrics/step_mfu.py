"""step_mfu: percent of the chip's peak that the window's denoise steps
reached: their operations (the cell's architecture's ``step_flops``, on
every row a step runs, nothing recomputed), each weighted by its share
inside the window, over the window's seconds times the peak FLOP/s of
``bench/peaks.json``."""
from gfbench import spec, window


def read(run):
    step_flops = spec.arch_of(run).step_flops
    done = sum(step_flops(run["model"], s["tokens"], s["rows"],
                          run["text_len"]) * s["share"]
               for s in window.denoise_steps(run))
    if not done:
        return None
    return 100.0 * done / (run["window"]["seconds"]
                           * run["peak"]["flops_per_s"])
