"""single_block_roofline: percent of its roofline reached by the
single-stream blocks' work other than attention, from the device trace:
the least time of its operations and bytes, weights included (the cell's
architecture's ``KERNELS["single_block"]``: modulation, projections,
QK norms, RoPE, MLP, adaLN), over the device time of the ops of the
``jit__single_pre`` and ``jit__single_post`` programs that are not
the flash kernel (``flash_attention.N``) inside the traced denoise
steps.  None where the architecture has no such blocks."""
from gfbench import spec, trace

KERNEL = "single_block"


def _match(module, name):
    return module.startswith("jit__single_") \
        and not name.startswith("flash_attention")


def read(run):
    per_step = spec.arch_of(run).KERNELS.get(KERNEL)
    if per_step is None:
        return None
    return trace.roofline_share(run, per_step, _match,
                                "single_block_roofline")
