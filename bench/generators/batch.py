"""batch: N requests of the mix's one class, all due when the window
opens.  N is set so that even a denoise step at 100% of the chip's peak
could not finish the batch within the window, times ``margin``; a step
counts the architecture's operations on the rows a request of the mix
runs.  Every seed gets the same N requests."""
import math

from gfbench.traffic import Planned, tokens


def batch_size(mix: dict, model: dict, arch, peak: dict, seconds: float,
               text_len: int) -> int:
    (cls, _), = mix["mix"].items()
    c = mix["classes"][cls]
    rows = len(arch.rows(mix.get("guidance")))
    t_step = arch.step_flops(model, tokens(model, c), rows,
                             text_len) / peak["flops_per_s"]
    return max(1, math.ceil(mix["margin"] * seconds
                            / (mix["steps"] * t_step)))


def plan(mix: dict, model: dict, arch, peak: dict, seconds: float,
         seed: int, text_len: int) -> list:
    n = batch_size(mix, model, arch, peak, seconds, text_len)
    (cls, _), = mix["mix"].items()
    c = mix["classes"][cls]
    return [Planned(f"s{seed}-b{i:03d}", cls, c["height"], c["width"],
                    c["frames"], mix["steps"], mix.get("guidance"), 0.0)
            for i in range(n)]
