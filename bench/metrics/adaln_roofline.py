"""adaln_roofline: percent of its roofline reached by the fused adaLN
kernel, from the device trace: bytes of its inputs and output over
bandwidth (``flops.step_adaln``; it is memory-bound), over the device
time of its Pallas custom call (the op ``adaln_modulate.N``) inside the
traced denoise steps."""
from gfbench import flops, trace


def _match(module, name):
    return name.startswith("adaln_modulate")


def read(run):
    return trace.roofline_share(run, flops.step_adaln, _match,
                                "adaln_roofline")
