"""batch: N requests of the mix's one class, all due when the window
opens.  N is set so that even a denoise step at 100% of the chip's peak
could not finish the batch within the window, times ``margin``; every
seed gets the same N requests."""
import math

from gfbench import flops
from gfbench.traffic import Planned, tokens


def batch_size(mix: dict, model: dict, peak: dict, seconds: float,
               text_len: int) -> int:
    (cls, _), = mix["mix"].items()
    c = mix["classes"][cls]
    rows = 2 if mix.get("guidance") is not None else 1
    t_step = flops.step_flops(model, tokens(model, c), rows,
                              text_len) / peak["flops_per_s"]
    return max(1, math.ceil(mix["margin"] * seconds
                            / (mix["steps"] * t_step)))


def plan(mix: dict, model: dict, peak: dict, seconds: float, seed: int,
         text_len: int) -> list:
    n = batch_size(mix, model, peak, seconds, text_len)
    (cls, _), = mix["mix"].items()
    c = mix["classes"][cls]
    return [Planned(f"s{seed}-b{i:03d}", cls, c["height"], c["width"],
                    c["frames"], mix["steps"], mix.get("guidance"), 0.0)
            for i in range(n)]
