"""staging_idle_ms: device idle time a round while the host stages.

The idle gaps of the traced window whose midpoint lies in the program's
own ``repro.staging`` span or its ``repro.stage_batches`` child (the
system tracer's spans on the profiler's clock), over the rounds in the
window (``chipbench.scopes``): what overlapping staging with the device's
work would remove.
"""
from chipbench.scopes import idle_ms


def read(ctx):
    return idle_ms(ctx, "staging", "stage_batches")
