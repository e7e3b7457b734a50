"""qblock_roofline: the upload-encode kernel's share of its roofline.

Least time of every ``kernels/qblock`` call in the traced window (bytes and
operations from shapes, ``chipbench.flops.qblock_work``, for the clients
one call covers) over the summed device time of its events in the
profiler's trace.
"""
from chipbench.roofline import kernel_share

# ops of the kernel are named after its jitted caller in kernels/
FUNCTION = "quantize"


def read(ctx):
    # the encode runs in each client's step: under the chunked executor one
    # call covers a chunk of the cohort, not the whole of it
    t = ctx.traffic
    clients = (min(t["chunk_size"], ctx.clients)
               if t.get("executor") == "chunked" else ctx.clients)
    return kernel_share(ctx, "qblock", FUNCTION, clients=clients)
