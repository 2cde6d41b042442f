"""staggered: a generator that exists only in the test fixture, to show
that a new arrival process is one new file.  ``count`` requests of the
mix's one class, due ``gap_s`` apart, in an order drawn from the seed."""
import numpy as np

from gfbench.traffic import Planned


def plan(mix, model, arch, peak, seconds, seed, text_len):
    (cls, _), = mix["mix"].items()
    c = mix["classes"][cls]
    due = np.arange(mix["count"]) * mix["gap_s"]
    np.random.Generator(np.random.PCG64([seed % 2 ** 63, 1])).shuffle(due)
    return [Planned(f"s{seed}-g{i:03d}", cls, c["height"], c["width"],
                    c["frames"], mix["steps"], mix.get("guidance"), float(t))
            for i, t in enumerate(due)]
