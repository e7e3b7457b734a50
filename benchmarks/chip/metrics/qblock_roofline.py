"""qblock_roofline: the upload-encode kernel's share of its roofline.

Least time of every ``kernels/qblock`` call in the traced window (bytes and
operations from shapes, ``chipbench.flops.qblock_work``) over the summed
device time of its events in the profiler's trace.
"""
from chipbench.roofline import kernel_share

# ops of the kernel are named after its jitted caller in kernels/
FUNCTION = "quantize"


def read(ctx):
    return kernel_share(ctx, "qblock", FUNCTION)
