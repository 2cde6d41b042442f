"""step_mfu: percent of the chip's peak that the window's denoise steps
reached: their operations (``flops.step_flops``, both guidance rows,
nothing recomputed), each weighted by its share inside the window, over
the window's seconds times the peak FLOP/s of ``bench/peaks.json``."""
from gfbench import flops, window


def read(run):
    done = sum(flops.step_flops(run["model"], s["tokens"], s["rows"],
                                run["text_len"]) * s["share"]
               for s in window.denoise_steps(run))
    if not done:
        return None
    return 100.0 * done / (run["window"]["seconds"]
                           * run["peak"]["flops_per_s"])
