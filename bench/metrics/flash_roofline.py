"""flash_roofline: percent of its roofline reached by the flash attention
kernel (self- and cross-attention), from the device trace: the larger
of unpadded FLOPs over peak and q/k/v/o bytes over bandwidth
(``flops.step_flash``), over the device time of its Pallas custom call
(the op ``flash_attention.N``) inside the traced denoise steps."""
from gfbench import flops, trace


def _match(module, name):
    return name.startswith("flash_attention")


def read(run):
    return trace.roofline_share(run, flops.step_flash, _match,
                                "flash_roofline")
