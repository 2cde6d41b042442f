"""denoise_ms_per_ktoken.tput: host milliseconds per thousand latent
tokens of the window's denoise steps (``window.ms_per_ktoken``), in the
batch cells, where it moves ``denoise_tokens_per_s``."""
from gfbench import window


def read(run):
    return window.ms_per_ktoken(run)
