"""denoise_tokens_per_s: latent tokens of every denoise step done in the
window, over the window's seconds.  A guided step counts its request's
tokens once; a step straddling an edge counts by the share of its
dispatch-to-completion span inside the window."""
from gfbench import window


def read(run):
    steps = window.denoise_steps(run)
    return sum(s["tokens"] * s["share"] for s in steps) \
        / run["window"]["seconds"]
