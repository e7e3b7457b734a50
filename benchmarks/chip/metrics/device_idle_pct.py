"""device_idle_pct: share of the traced window in which no operation ran.

1 - (union of the device's op intervals / window), from the profiler's
trace (``chipbench.xtrace``), averaged over the chips used.
"""


def read(ctx):
    r = ctx.reduced
    if not r.n_devices or r.window_s <= 0:
        return None
    return 100.0 * (1.0 - r.busy_s / r.window_s)
