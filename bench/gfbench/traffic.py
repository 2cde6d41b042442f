"""The benchmark's traffic.  A mix file under ``bench/traffic`` names a
``kind`` and its parameters; the generator of that kind,
``bench/generators/<kind>.py``, turns it into planned requests from the
run's seed (``plan``), given the configuration's sizes and architecture
(its operation counts and the rows a guided request runs).  The program
receives only the requests.

A generator gives every seed the same work, in another order where
order matters, so that runs of different seeds differ by arrangement
and not by amount.  A new arrival process is a new generator file.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

from gfbench import spec


@dataclass(frozen=True)
class Planned:
    id: str
    cls: str
    height: int
    width: int
    frames: int
    steps: int
    guidance: Optional[float]
    due: float                          # seconds after the window opens


def tokens(model: dict, c: dict) -> int:
    """Latent tokens of a request class: 8x spatial and 4x temporal
    compression, then ``patch_size`` patches."""
    f = max(1, (c["frames"] + 3) // 4) if c["frames"] > 1 else 1
    p = model["patch_size"]
    return f * (c["height"] // 8 // p) * (c["width"] // 8 // p)


def generate(mix: dict, model: dict, arch, peak: dict, seconds: float,
             seed: int, text_len: int, root=spec.ROOT) -> list[Planned]:
    """The planned requests of one run, sorted by due time."""
    plan = spec.generator(mix["kind"], root)
    return sorted(plan(mix, model, arch, peak, seconds, seed, text_len),
                  key=lambda p: p.due)


def warm_set(mix: dict, seed: int) -> list[Planned]:
    """One short request of each class in the mix, served before the
    window so that every shape it uses is compiled."""
    return [Planned(f"warm{seed}-{cls}", cls, c["height"], c["width"],
                    c["frames"], mix["warm_steps"], mix.get("guidance"),
                    0.0)
            for cls, c in sorted(mix["classes"].items())
            if mix["mix"].get(cls)]
