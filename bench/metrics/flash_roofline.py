"""flash_roofline: percent of its roofline reached by the flash attention
kernel, from the device trace: the larger of unpadded FLOPs over peak
and q/k/v/o bytes over bandwidth of its calls in a step (the cell's
architecture's ``KERNELS["flash_attention"]``), over the device time of
its Pallas custom call (the op ``flash_attention.N``) inside the traced
denoise steps.  None where the architecture's step calls no flash."""
from gfbench import spec, trace

KERNEL = "flash_attention"


def _match(module, name):
    return name.startswith(KERNEL)


def read(run):
    per_step = spec.arch_of(run).KERNELS.get(KERNEL)
    if per_step is None:
        return None
    return trace.roofline_share(run, per_step, _match, "flash_roofline")
