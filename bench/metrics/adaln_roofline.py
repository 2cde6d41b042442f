"""adaln_roofline: percent of its roofline reached by the fused adaLN
kernel, from the device trace: bytes of its inputs and output over
bandwidth (the cell's architecture's ``KERNELS["adaln_modulate"]``; it
is memory-bound), over the device time of its Pallas custom call (the
op ``adaln_modulate.N``) inside the traced denoise steps.  None where
the architecture's step calls no adaLN kernel."""
from gfbench import spec, trace

KERNEL = "adaln_modulate"


def _match(module, name):
    return name.startswith(KERNEL)


def read(run):
    per_step = spec.arch_of(run).KERNELS.get(KERNEL)
    if per_step is None:
        return None
    return trace.roofline_share(run, per_step, _match, "adaln_roofline")
