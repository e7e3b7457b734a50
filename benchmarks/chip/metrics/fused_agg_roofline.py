"""fused_agg_roofline: the flush kernel's share of its roofline.

Least time of every ``kernels/fused_agg`` call in the traced window (bytes
and operations from shapes, ``chipbench.flops.fused_agg_work``) over the
summed device time of its events in the profiler's trace.
"""
from chipbench.roofline import kernel_share

# ops of the kernel are named after its jitted caller in kernels/
FUNCTION = "dequant_accumulate"


def read(ctx):
    return kernel_share(ctx, "fused_agg", FUNCTION)
